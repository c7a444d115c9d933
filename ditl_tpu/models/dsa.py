"""The single pre-norm latent block (DeepSeek-V3's family): multi-head latent
attention, and an FFN that is dense in the first ``first_k_dense_replace``
layers and an expert layer with a shared expert in the rest. ``llama.forward``
hands its layers to ``stack`` when ``cfg.dsa_layer``. Three extras are each
optional (``ModelConfig``): a QUERY LATENT (``q_lora_rank > 0``; without one
``q = z Wq``, one matrix), YaRN on the rotary frequencies (``rope_yarn_factor
> 0``) and DeepSeek-V3.2's lightning INDEXER (``index_topk > 0``: DeepSeek
Sparse Attention; without one the ``index`` subtree, the index keys and their
pool do not exist and attention is dense). DeepSeek-V3.2 has all three,
Kanana-2 none.

With ``n`` an RMSNorm with its own scale, ``t`` a query position and
``s <= t`` a key position::

    h = x + Attn(n(x));   y = h + FFN(n(h))

    Attn(z): cq = n_q(z Wqa);  q = cq Wqb -> heads of [q_nope | q_rope]
             [ckv | kr] = z Wkva;  c = n_kv(ckv);  [k_nope | v] = c Wkvb
             qI = cq Wq_b -> index heads, rotary on the first qk_rope_head_dim
             kI = LayerNorm(z Wk), one a token, rotary likewise
             w  = z Wproj * index_n_heads ** -0.5 * index_head_dim ** -0.5
             I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])
             S_t = the min(index_topk, t + 1) positions s <= t of largest I
             scores = (q_nope . k_nope + rope(q_rope) . rope(kr)) * scale
             out = (softmax over S_t only) v Wo
    rotary:  neighbouring pairs (2i, 2i+1) at YaRN's frequencies
             (``yarn_inv_freq``);  scale = (nope + rope) ** -0.5 * m ** 2,
             m = 0.1 * mscale_all_dim * ln(factor) + 1
    FFN:     SwiGLU of ``intermediate_size`` (dense layers) or
             ``models/moe.py``'s share with sigmoid scores, group-limited
             choice and a shared expert.

A token's cache entry is the latent ``[c | rope(kr)]`` (``mla.latent_width``:
576 values stored at 640) AND its index key ``rope(kI)`` (``index_head_dim``
values), in two page pools under ONE page table.

Without an indexer a forward WITHOUT cache (the trainer, a reference check,
an evaluation) runs the DECOMPRESSED form (``_decompressed_attend``): ``k =
[k_nope | rope(kr) broadcast over the heads]`` (``nope + rope`` wide), ``v``
(``v_head_dim`` wide) for every position, through ``ops/attention.py`` and so
the flash kernels at two widths, under ``segment_ids``. A row of 8,192 tokens
in the absorbed form does 3 x the score and 4 x the value operations (a query
576 wide against ``c``, a value 512 wide) and has no kernel.

Everything else is the ABSORBED form (``Wkvb``'s key half folded into the
query, its value half behind the attention), because a query reads the few
entries it selected and not a decompressed copy of the context:

- a forward without cache and a prefill chunk (``_select_attend``): index
  scores of the whole chunk against the row (its context pages' entries, then
  the chunk's), ``top_indices`` a query, then the selected entries gathered
  and attended, each in blocks of ``Q_BLOCK`` queries;
- a paged decode step (``_decode_attend``): index scores over the row's pages
  of the index pool and the tick's tail, ``top_indices`` a row, the selected
  latent entries gathered out of the pool through the page table, the tail's
  entries beside them under the selection's mask. On the TPU the scores over
  the pages are ``ops/dsa_index.py``'s kernel ``dsa_index_scores``: a walk
  over each live row's pages where they lie, several pages a step, which
  writes only the positions the row still attends to (``_page_scores``;
  whatever else the array holds is selected away in front of
  ``top_indices``); off the TPU, and as the tests' oracle,
  ``paged_index_scores`` gathers the pages (three passes over the keys).

``top_indices`` sorts nothing (a sort of every row's 33,800 scores was the
cell's largest device operation): a threshold search finds the k-th largest
score, compares, sums and two small 0/1 matmuls turn the selected mask into
the list of positions, in position order. Every reader takes it as a set.

Where the buffer is no longer than ``index_topk`` (a static fact) everything
is selected: the indexer is skipped and attention is dense over the valid
entries (a decode step: ``ops/mla_attention.py``'s kernel). No path attends
to more than ``index_topk`` entries of a longer buffer.

Scopes (``ops/names.py`` ``DSA_SCOPES``): ``dsa_index`` (inside ``attn_qkv``
the indexer's projections, inside ``attn_core`` its scores: in decode the
kernel ``dsa_index_scores``, ``DSA_KERNELS``), ``dsa_select``
and ``dsa_gather`` inside ``attn_core``; ``mla_q`` / ``mla_kv`` / ``mla_attn``
as the double layer has them.

Serving only as far as the cache goes, like the double layer: paged pools,
plain ticks; the engine refuses the rest, and the block without an indexer
whole (infer/page_format.py). The multi-token-prediction module is not
implemented.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ditl_tpu.config import ModelConfig
from ditl_tpu.models.mla import latent_width, rope_interleaved

__all__ = ["init_dsa_params", "dsa_logical_axes", "stack", "yarn_inv_freq",
           "softmax_scale", "top_indices", "Q_BLOCK", "LANES", "TAP"]

Q_BLOCK = 32  # queries a block of the index scores and of the selected attention

# What the programs chose, for a check that must see it (benchmarks/
# dsa_check.py: the ENGINE's prefill and decode programs, not a pass of its
# own): while this is a function, every attention sublayer traced calls it on
# the host (``jax.debug.callback``) as ``TAP(what, layer, arrays)``, ``what``
# "chunk" (a forward without cache, a prefill chunk) or "step" (a paged decode
# step), ``arrays`` the indexer's normed input ``h`` (B, S, D), the queries'
# ``positions`` (B, S), ``real`` (B, S; None: all) false on a bucket's padding
# and on dead rows, and ``chosen``: None where everything was selected, else
# ``top_indices``' ``(idx, ok)`` into the buffer the scores were taken over (a
# chunk's: positions; a step's: the row's ``pages`` page positions in order,
# then the tail from ``starts``). None, as everywhere in serving, traces
# nothing.
TAP = None


def _tap(what: str, layer, **arrays):
    if TAP is not None:
        jax.debug.callback(functools.partial(TAP, what), layer, arrays)


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN. With ``f_i
    = theta ** (-2i / dim)``, ``corr(r) = dim * ln(L / (2 pi r)) / (2 ln
    theta)`` the dimension that makes ``r`` rotations over the original ``L``
    positions, ``low = floor(corr(beta_fast))``, ``high = ceil(corr(
    beta_slow))`` and ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
    frequency ``i`` is ``f_i * (1 - ramp_i) + f_i / factor * ramp_i``: fast
    dimensions keep their frequency, slow ones are interpolated. Plain
    ``f_i`` without a factor."""
    dim, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if cfg.rope_yarn_factor <= 0:
        return freq.astype(np.float32)

    def corr(rotations: float) -> float:
        return (dim * math.log(cfg.rope_yarn_original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (freq * (1 - ramp) + freq / cfg.rope_yarn_factor * ramp).astype(np.float32)


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn_factor > 0 and cfg.rope_yarn_mscale_all_dim:
        m = 0.1 * cfg.rope_yarn_mscale_all_dim * math.log(cfg.rope_yarn_factor) + 1.0
        scale *= m * m
    return scale


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _kinds(cfg: ModelConfig) -> dict[str, int]:
    """The two stacks of ``params["layers"]`` and their depths."""
    return {"dense": cfg.first_k_dense_replace,
            "sparse": cfg.num_layers - cfg.first_k_dense_replace}


def init_dsa_params(rng: jax.Array, cfg: ModelConfig) -> dict[str, Any]:
    """``{"dense": ..., "sparse": ...}``: the leading dense layers and the
    expert layers, each a stack of its own depth (a scan a stack). Leaves are
    drawn in ``param_dtype`` (``moe.lean_dense``)."""
    from ditl_tpu.models.moe import init_moe_params, lean_dense

    pd = jnp.dtype(cfg.param_dtype)
    d, f, nh = cfg.hidden_size, cfg.intermediate_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim

    def one(rng, n, dense_ffn):
        keys = iter(jax.random.split(rng, 16))

        def dense(shape, fan_in):
            return lean_dense(next(keys), (n,) + shape, fan_in, pd)

        if qr:
            query = {"w_qa": dense((d, qr), d), "q_norm": jnp.ones((n, qr), pd),
                     "w_qb": dense((qr, nh * (nope + rope)), qr)}
        else:  # no query latent: one matrix
            query = {"wq": dense((d, nh * (nope + rope)), d)}
        out = {
            "attn_norm": {"scale": jnp.ones((n, d), pd)},
            "attn": {
                **query,
                "w_kva": dense((d, kr + rope), d),
                "kv_norm": jnp.ones((n, kr), pd),
                "w_kvb": dense((kr, nh * (nope + vd)), kr),
                "wo": dense((nh * vd, d), nh * vd),
            },
            "mlp_norm": {"scale": jnp.ones((n, d), pd)},
        }
        if cfg.indexed:
            out["index"] = {
                "wq_b": dense((qr, hi * di), qr),
                "wk": dense((d, di), d),
                "k_norm": {"scale": jnp.ones((n, di), pd), "bias": jnp.zeros((n, di), pd)},
                "w_proj": dense((d, hi), d),
            }
        if dense_ffn:
            out["mlp"] = {"w_gate": dense((d, f), d), "w_up": dense((d, f), d),
                          "w_down": dense((f, d), f)}
        else:
            out["moe"] = init_moe_params(next(keys), cfg, n_layers=n)
        return out

    k_dense, k_sparse = jax.random.split(rng)
    kinds = _kinds(cfg)
    return {"dense": one(k_dense, kinds["dense"], True),
            "sparse": one(k_sparse, kinds["sparse"], False)}


def dsa_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    from ditl_tpu.models.moe import moe_logical_axes

    def one(dense_ffn):
        if cfg.q_lora_rank:
            query = {"w_qa": ("layers", "embed", None), "q_norm": ("layers", "norm"),
                     "w_qb": ("layers", None, "heads")}
        else:
            query = {"wq": ("layers", "embed", "heads")}
        out = {
            "attn_norm": {"scale": ("layers", "norm")},
            "attn": {
                **query,
                "w_kva": ("layers", "embed", None),
                "kv_norm": ("layers", "norm"),
                "w_kvb": ("layers", None, "heads"),
                "wo": ("layers", "heads", "embed"),
            },
            "mlp_norm": {"scale": ("layers", "norm")},
        }
        if cfg.indexed:
            out["index"] = {
                "wq_b": ("layers", None, "heads"),
                "wk": ("layers", "embed", None),
                "k_norm": {"scale": ("layers", "norm"), "bias": ("layers", "norm")},
                "w_proj": ("layers", "embed", None),
            }
        if dense_ffn:
            out["mlp"] = {"w_gate": ("layers", "embed", "mlp"),
                          "w_up": ("layers", "embed", "mlp"),
                          "w_down": ("layers", "mlp", "embed")}
        else:
            out["moe"] = moe_logical_axes(cfg)
        return out

    return {"dense": one(True), "sparse": one(False)}


# ---------------------------------------------------------------------------
# The indexer, the selection, the attention over what was selected
# ---------------------------------------------------------------------------


def _layer_norm(x, norm, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * norm["scale"].astype(jnp.float32)
            + norm["bias"].astype(jnp.float32)).astype(x.dtype)


def _rope_front(x, positions, inv_freq, rope: int):
    """Rotary on the first ``rope`` values of each head. x: (B, S, H, D)."""
    return jnp.concatenate(
        [rope_interleaved(x[..., :rope], positions, 0.0, inv_freq=inv_freq),
         x[..., rope:]], axis=-1)


def index_scores(qi: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``. qi: (B, S, Hi, Di),
    w: (B, S, Hi) float32, keys: (B, N, Di) -> (B, S, N) float32."""
    s = jnp.einsum("bqhd,bnd->bqhn", qi, keys.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bqhn,bqh->bqn", jax.nn.relu(s), w)


LANES = 128  # scores a chunk of the selection's two-level count


def _order_key(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' total order (-inf
    < negatives < -0.0 < 0.0 < positives), as ``jax.lax.top_k`` compares."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _kth_key(keys: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest of each row of ``keys`` (R, N) int32: its bits
    from the top down, a bit is set where at least ``k`` keys reach the
    prefix with it. 32 passes of a compare and a count, no sort."""
    low = jnp.int32(-2 ** 31)  # the sign flip between signed and unsigned order

    def bit(i, prefix):  # prefix: the unsigned pattern found so far
        cand = prefix | (jnp.int32(1) << (31 - i))
        reach = (keys >= (cand ^ low)[:, None]).sum(axis=1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, prefix)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:1], jnp.int32)) ^ low


def _compact(keys: jax.Array, thr: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The positions of the ``k`` selected keys of each row, ascending, made
    of compares, sums and two small matmuls on 0/1 values. keys: (R, nc,
    LANES) int32, thr: (R,) their ``k``-th largest. Selected: every key above
    ``thr`` and the first ``need = k - #above`` keys equal to it. Every count
    is a whole number under 2 ** 24 in float32, or at most 256 in bfloat16."""
    nc = keys.shape[1]
    lanes = jnp.arange(LANES, dtype=jnp.int32)
    above, equal = keys > thr[:, None, None], keys == thr[:, None, None]
    # inclusive counts inside a chunk: one upper-triangular 0/1 matmul
    upto = (lanes[:, None] <= lanes[None, :]).astype(jnp.bfloat16)
    inc_a, inc_e = jnp.einsum("xrcm,ml->xrcl", jnp.stack([above, equal]).astype(jnp.bfloat16),
                              upto, preferred_element_type=jnp.float32)
    # and the chunks before it: a cumulative sum over nc chunks, not N entries
    tot_a, tot_e = inc_a[..., -1], inc_e[..., -1]
    off_a, off_e = jnp.cumsum(tot_a, axis=1) - tot_a, jnp.cumsum(tot_e, axis=1) - tot_e
    need = (k - tot_a.sum(axis=1))[:, None]  # (R, 1)
    upto_e = off_e[..., None] + inc_e  # equal keys up to and with this one
    sel = above | (equal & (upto_e <= need[..., None]))
    rank = off_a[..., None] + inc_a + jnp.minimum(upto_e, need[..., None])  # 1-based, of a selected
    off = off_a + jnp.minimum(off_e, need)  # (R, nc): selected before the chunk
    # a chunk's selected lanes carry their rank mod 256 (a chunk holds at most
    # 128 of them, so each value once), negative where the entry is invalid
    pay = (rank - 1) % 256 + 1
    pay = jnp.where(sel, jnp.where(keys > _order_key(jnp.float32(-jnp.inf)), pay, -pay), 0)
    # slot j's chunk: the last whose offset is at most j (an empty chunk
    # shares its offset with the next one)
    j = jnp.arange(k, dtype=jnp.float32)
    chunk = (off[:, None, :] <= j[None, :, None]).sum(axis=-1, dtype=jnp.int32) - 1  # (R, k)
    mine = chunk[..., None] == jnp.arange(nc, dtype=jnp.int32)  # (R, k, nc), one-hot
    got = jnp.einsum("rkc,rcl->rkl", mine.astype(jnp.bfloat16), pay.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)  # (R, k, LANES): that chunk's row
    hit = jnp.abs(got) == (j % 256 + 1)[None, :, None]
    lane = (hit * lanes).sum(axis=-1)
    return chunk * LANES + lane, (hit & (got > 0)).any(axis=-1)


def top_indices(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The ``k`` largest of each row of ``scores`` (..., N) float32, invalid
    entries at -inf: ``(indices (..., k) int32, ok (..., k))``, ``ok`` false
    where the row had fewer than ``k`` valid entries and an invalid one (the
    lowest positions first) filled the place. Exactly ``jax.lax.top_k``'s SET
    (ties to the lower index), in ascending POSITION, not by score: attention
    over the entries, the counters and ``TAP``'s readers read sets.

    No sort: ``top_k`` of 2,048 sorts the whole row (device time on a v5e at
    32 rows of 33,800: 1,216 us, the largest operation of the cell; this: 157
    us, PERF.md section 6, PR 45). A bit-wise threshold search on the floats'
    ordered integer keys finds the k-th largest (``_kth_key``), which gives
    the set as a MASK; the list a gather needs is a compaction of that mask.
    Three compactions lost to the sort they replaced (PR 44: a sort of the
    masked iota, a cumulative sum over the row and a scatter, ``searchsorted``
    into that sum: 1.2 x, 4 x and 8 x ``top_k``'s time): each sorts the row
    again or moves scalars one at a time. ``_compact`` counts in two levels
    over chunks of ``LANES`` and fetches each output slot's chunk with a
    one-hot matmul. Search and compaction run a block of ``Q_BLOCK`` rows at
    a time: a block's keys (4 MB) stay in fast memory through the search's 32
    passes and its (rows, k, chunks) one-hot stays tens of megabytes."""
    *lead, n = scores.shape
    nc = -(-n // LANES)
    rows = scores.reshape(1, -1, n)
    if nc * LANES > n:  # -inf behind the row loses every tie by sitting last
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, nc * LANES - n)), constant_values=-jnp.inf)

    def block(keys):  # (1, rows of a block, nc * LANES)
        idx, ok = _compact(keys[0].reshape(-1, nc, LANES), _kth_key(keys[0], k), k)
        return idx[None], ok[None]

    idx, ok = _blocks(block, (_order_key(rows),), rows.shape[1])
    return idx.reshape(*lead, k), ok.reshape(*lead, k)


def _attend(q_full, entries, ok, *, scale: float, r: int, per_query: bool):
    """Absorbed attention of each query over its entries. q_full: (B, S, H,
    Dl); entries (B, S, K, Dl) (``per_query``: every query its own, gathered)
    or (B, N, Dl) (shared); ok: (B, S, K or N) -> (B, S, H, r), float32
    softmax. A query with no entry at all comes out as the mean of whatever
    it was handed: the caller discards such rows."""
    from ditl_tpu.ops.attention import NEG_INF

    eq = "bqhd,bqkd->bqhk" if per_query else "bqhd,bkd->bqhk"
    s = jnp.einsum(eq, q_full, entries, preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(entries.dtype)
    ev = "bqhk,bqkd->bqhd" if per_query else "bqhk,bkd->bqhd"
    return jnp.einsum(ev, p, entries[..., :r])


def _blocks(fn, xs: tuple, s: int):
    """``fn`` over blocks of ``Q_BLOCK`` queries (axis 1 of every array of
    ``xs``), one after the other; a ragged last block is padded with zeros
    (queries that see nothing) and cut off again. Each block is written into
    ONE output buffer (one for each array ``fn`` returns) the loop carries,
    so block i + 1 follows block i and only one block's temporaries are live
    at a time."""
    n = Q_BLOCK
    if s <= n:
        return fn(*xs)
    nb = -(-s // n)
    if s % n:
        xs = tuple(jnp.pad(x, ((0, 0), (0, nb * n - s)) + ((0, 0),) * (x.ndim - 2))
                   for x in xs)

    def block(i):
        return fn(*(jax.lax.dynamic_slice_in_dim(x, i * n, n, axis=1) for x in xs))

    first = jax.eval_shape(block, 0)
    out = jax.tree.map(lambda f: jnp.zeros((f.shape[0], nb * n, *f.shape[2:]), f.dtype), first)
    out = jax.lax.fori_loop(
        0, nb, lambda i, o: jax.tree.map(
            lambda whole, part: jax.lax.dynamic_update_slice_in_dim(whole, part, i * n, axis=1),
            o, block(i)), out)
    return jax.tree.map(lambda o: o[:, :s], out)


def _select_attend(q_full, qi, w, entries, keys, valid, *, cfg: ModelConfig):
    """Every query of a chunk over the row it sits in. entries: (B, N, Dl),
    keys: (B, N, Di), valid: (B, S, N) (causal, same document, written).
    -> (lat (B, S, H, r), selected tokens (), ``top_indices``' (idx, ok) or
    None where everything valid is selected)."""
    s, n = q_full.shape[1], entries.shape[1]
    k, r = cfg.index_topk, cfg.kv_lora_rank
    scale = softmax_scale(cfg)
    if n <= k:  # everything valid is selected: dense, and no indexer
        with jax.named_scope("mla_attn"):
            lat = _blocks(lambda q, v: _attend(q, entries, v, scale=scale, r=r,
                                               per_query=False), (q_full, valid), s)
        return lat, valid.sum(), None
    with jax.named_scope("dsa_index"):
        scores = _blocks(lambda q, ww: index_scores(q, ww, keys), (qi, w), s)
    with jax.named_scope("dsa_select"):
        idx, ok = top_indices(jnp.where(valid, scores, -jnp.inf), k)  # (B, S, k)

    def block(q, ix, good):
        with jax.named_scope("dsa_gather"):
            sel = jax.vmap(lambda e, i: e[i])(entries, ix)  # (B, n_q, k, Dl)
        with jax.named_scope("mla_attn"):
            return _attend(q, sel, good, scale=scale, r=r, per_query=True)

    lat = _blocks(block, (q_full, idx, ok), s)
    return lat, ok.sum(), (idx, ok)


def paged_index_scores(qi, w, ipool, table):
    """A decode step's index scores over each row's PAGES, in page-table
    order (which is position order): qi (B, Hi, Di), w (B, Hi) float32,
    ipool (P, ps, Di), table (B, maxp) -> (B, maxp * ps) float32. Positions
    no page of the row holds score against whatever the table names there:
    the caller masks by the row's length."""
    b, maxp = table.shape
    keys = ipool[table].reshape(b, maxp * ipool.shape[1], ipool.shape[-1])
    return index_scores(qi[:, None], w[:, None], keys)[:, 0]


def _page_scores(qi, w, ipool, paged):
    """``paged_index_scores`` of a decode step: on the TPU the kernel that
    scores each live row's pages where they lie (``ops/dsa_index.py``), which
    writes nothing at positions ``>= min(starts, lengths)``: the caller
    selects those away. Off the TPU the gather: the interpreted kernel
    carries the whole pool through its grid loop, as ``mla_paged_attention``
    does."""
    from ditl_tpu.ops.backend import interpret_default

    if interpret_default():
        return paged_index_scores(qi, w, ipool, paged["table"])
    from ditl_tpu.ops.dsa_index import dsa_index_scores

    return dsa_index_scores(qi, w, ipool, paged["table"], paged["lengths"], paged["starts"],
                            steps=paged.get("index_steps"))


def _decode_attend(q_full, qi, w, *, cfg: ModelConfig, pools, tails, paged):
    """One decode step, every row over its pages and the tick's tail.
    q_full: (B, H, Dl), qi: (B, Hi, Di), w: (B, Hi); pools: the flat latent
    and index pools (L * P, ps, .); tails: this layer's (B, T, .), this
    step's entry already written; ``paged["table"]`` names this layer's
    pages. -> (lat (B, H, r), selected tokens (), ``top_indices``' (idx, ok)
    or None where no row can hold more than ``index_topk`` tokens)."""
    cp, ip = pools["cp"], pools["ip"]
    tc, ti = tails
    table, lengths, starts = paged["table"], paged["lengths"], paged["starts"]
    b, maxp = table.shape
    ps, t = cp.shape[1], tc.shape[1]
    n = maxp * ps
    k, r = cfg.index_topk, cfg.kv_lora_rank
    scale = softmax_scale(cfg)
    if n + t <= k:  # no row can hold more than index_topk tokens: dense
        from ditl_tpu.ops.mla_attention import mla_paged_attention

        with jax.named_scope("mla_attn"):
            lat = mla_paged_attention(
                q_full, cp, table, lengths, tail=tc, starts=starts, value_width=r,
                scale=scale, steps=paged.get("steps"))
        return lat, lengths.sum(), None
    in_pages = jnp.arange(n, dtype=jnp.int32)[None, :] < jnp.minimum(starts, lengths)[:, None]
    in_tail = (starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]) < lengths[:, None]
    with jax.named_scope("dsa_index"):
        scores = jnp.concatenate(
            [_page_scores(qi, w, ip, paged),
             index_scores(qi[:, None], w[:, None], ti)[:, 0]], axis=1)  # (B, n + T)
    with jax.named_scope("dsa_select"):
        valid = jnp.concatenate([in_pages, in_tail], axis=1)
        idx, ok = top_indices(jnp.where(valid, scores, -jnp.inf), k)  # (B, k)
        at = jnp.minimum(idx, n - 1)
        # each selected position's page by a compare and a sum over the
        # row's table: a gather of k scalars a row takes 0.67 ms on a v5e
        mine = (at // ps)[:, :, None] == jnp.arange(maxp, dtype=jnp.int32)
        flat = (mine * table[:, None, :]).sum(axis=-1) * ps + at % ps
        ok_pages = ok & (idx < n)
        ok_tail = ((idx[:, :, None] == n + jnp.arange(t, dtype=jnp.int32))
                   & ok[:, :, None]).any(axis=1)  # (B, T)
    with jax.named_scope("dsa_gather"):
        sel = cp.reshape(-1, cp.shape[-1])[flat]  # (B, k, Dl)
    with jax.named_scope("mla_attn"):
        entries = jnp.concatenate([sel, tc.astype(sel.dtype)], axis=1)
        good = jnp.concatenate([ok_pages, ok_tail], axis=1)
        lat = _attend(q_full[:, None], entries[:, None], good[:, None], scale=scale,
                      r=r, per_query=True)[:, 0]
        lat = jnp.where(lengths[:, None, None] > 0, lat, 0).astype(q_full.dtype)
    return lat, ok.sum(), (idx, ok)


def _decompressed_attend(q, c, kr, w_kvb, *, cfg: ModelConfig, segment_ids, mesh, rules):
    """Dense attention of a forward without cache, keys and values decompressed
    for every position: q (B, S, H, nope + rope) rotated, c (B, S, r) normed,
    kr (B, S, rope) rotated, w_kvb (r, H, nope + vd) -> (B, S, H, vd). Through
    ``ops/attention.py``: the flash kernels at two widths where
    ``attention_impl`` says so, causal inside ``segment_ids``."""
    from ditl_tpu.ops.attention import dot_product_attention

    b, s, nh, _ = q.shape
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla_kv"):
        # two products, so that no [k_nope | v] of every position is held
        # beside the k and the v cut out of it (512 MB a row of 4 x 8,192)
        k_nope = jnp.einsum("bsr,rhn->bshn", c, w_kvb[..., :nope])
        v = jnp.einsum("bsr,rhv->bshv", c, w_kvb[..., nope:])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :], (b, s, nh, rope))], axis=-1)
    ratio = softmax_scale(cfg) / (nope + rope) ** -0.5  # YaRN's m ** 2, else 1
    if ratio != 1.0:  # the attention paths scale by the query's width alone
        q = (q * ratio).astype(q.dtype)
    with jax.named_scope("mla_attn"):
        return dot_product_attention(
            q, k, v, causal=True, segment_ids=segment_ids,
            impl=cfg.attention_impl, mesh=mesh, rules=rules,
            block_sizes=(cfg.flash_block_q, cfg.flash_block_kv,
                         cfg.flash_block_q_bwd, cfg.flash_block_kv_bwd))


def _attention(a, ix, h, *, cfg: ModelConfig, positions, allowed, cache, cache_index,
               paged, pools, cd, layer, token_mask, segment_ids=None, mesh=None, rules=None):
    """The attention sublayer of layer ``layer`` on the normed input ``h`` (B,
    S, D): ``(out (B, S, D) before the residual, new cache or None, selected
    tokens)``. ``ix``: the indexer's parameters, None without one. ``cache``:
    None; a prefill's row ``{"c": (B, Smax, Dl), "i": (B, Smax, Di)}`` (written
    at ``cache_index``, attended under ``allowed`` (B, S, Smax)); or, with
    ``pools``, this layer's tails ``{"tc": (B, T, Dl), "ti": (B, T, Di)}`` of a
    paged decode step. ``allowed`` None (no cache, no indexer): the
    decompressed form under ``segment_ids``."""
    from ditl_tpu.models.llama import rms_norm
    from ditl_tpu.ops.quant import is_quantized_leaf, weight_einsum

    b, s, _ = h.shape
    nh, eps = cfg.num_heads, cfg.rms_norm_eps
    r, hi, di = cfg.kv_lora_rank, cfg.index_n_heads, cfg.index_head_dim
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    w_kvb = a["w_kvb"].astype(cd).reshape(r, nh, nope + vd)
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    pad = latent_width(cfg) - r - rope
    decompressed = cache is None and ix is None

    with jax.named_scope("attn_qkv"):
        with jax.named_scope("mla_q"):
            if "wq" not in a:
                cq = rms_norm(weight_einsum("bsd,dr->bsr", h, a["w_qa"], compute_dtype=cd),
                              a["q_norm"], eps)
                q = weight_einsum("bsr,rf->bsf", cq, a["w_qb"], compute_dtype=cd)
                q = q.reshape(b, s, nh, nope + rope)
            elif is_quantized_leaf(a["wq"]):  # no query latent; a weight-only int8 leaf
                cq = None
                q = weight_einsum("bsd,df->bsf", h, a["wq"], compute_dtype=cd)
                q = q.reshape(b, s, nh, nope + rope)
            else:
                # the heads split in the WEIGHT: a (B, S, H x 192) product cut
                # into heads of 192 is a copy into 256-lane tiles, forward and back
                cq = None
                q = jnp.einsum("bsd,dhf->bshf", h,
                               a["wq"].astype(cd).reshape(-1, nh, nope + rope))
            q_rope = rope_interleaved(q[..., nope:], positions, 0.0, inv_freq=inv_freq)
            if decompressed:
                q_full = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            else:
                # Wkvb's key half folded into the query: kv_lora_rank wide, against c
                q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope], w_kvb[..., :nope])
                q_full = jnp.concatenate(
                    [q_lat, q_rope, jnp.zeros((b, s, nh, pad), q_lat.dtype)], axis=-1)
        with jax.named_scope("mla_kv"):
            ckr = weight_einsum("bsd,df->bsf", h, a["w_kva"], compute_dtype=cd)
            c = rms_norm(ckr[..., :r], a["kv_norm"], eps)
            kr = rope_interleaved(ckr[..., None, r:], positions, 0.0,
                                  inv_freq=inv_freq)[:, :, 0]
            if not decompressed:
                entry = jnp.concatenate(
                    [c, kr, jnp.zeros((b, s, pad), c.dtype)], axis=-1)  # (B, S, Dl)
        qi = ki = w = None
        if ix is not None:
            with jax.named_scope("dsa_index"):
                qi = weight_einsum("bsr,rf->bsf", cq, ix["wq_b"], compute_dtype=cd)
                qi = _rope_front(qi.reshape(b, s, hi, di), positions, inv_freq, rope)
                ki = _layer_norm(weight_einsum("bsd,df->bsf", h, ix["wk"], compute_dtype=cd),
                                 ix["k_norm"], eps)
                ki = _rope_front(ki[:, :, None], positions, inv_freq, rope)[:, :, 0]
                w = weight_einsum("bsd,dh->bsh", h, ix["w_proj"], compute_dtype=cd,
                                  preferred=jnp.float32) * (hi ** -0.5 * di ** -0.5)

    if decompressed:
        attn = _decompressed_attend(q_full, c, kr, w_kvb, cfg=cfg, segment_ids=segment_ids,
                                    mesh=mesh, rules=rules)
        with jax.named_scope("attn_out"):
            out = weight_einsum("bsf,fd->bsd", attn.reshape(b, s, nh * vd), a["wo"],
                                compute_dtype=cd)
        return out, None, jnp.zeros((), jnp.int32)  # nothing is selected: dense
    if ix is None:
        raise ValueError(
            "the latent block without an indexer (index_topk 0, models/dsa.py) has no "
            "cached forward: it is trained and evaluated, not served "
            "(infer/page_format.py refuses it)")

    with jax.named_scope("attn_core"):
        if pools is not None:
            with jax.named_scope("kv_write"):
                new_cache = {
                    "tc": jax.lax.dynamic_update_slice(
                        cache["tc"], entry.astype(cache["tc"].dtype), (0, paged["t"], 0)),
                    "ti": jax.lax.dynamic_update_slice(
                        cache["ti"], ki.astype(cache["ti"].dtype), (0, paged["t"], 0)),
                }
            lat, n_sel, chosen = _decode_attend(
                q_full[:, 0], qi[:, 0], w[:, 0], cfg=cfg, pools=pools,
                tails=(new_cache["tc"], new_cache["ti"]), paged=paged)
            lat = lat[:, None]
            _tap("step", layer, h=h, positions=positions, real=token_mask, chosen=chosen,
                 starts=paged["starts"],
                 pages=paged["table"].shape[1] * pools["cp"].shape[1])
        else:
            entries, keys, new_cache = entry, ki, None
            if cache is not None:  # a prefill's row: all of it is context
                new_cache = {
                    "c": jax.lax.dynamic_update_slice(
                        cache["c"], entry.astype(cache["c"].dtype), (0, cache_index, 0)),
                    "i": jax.lax.dynamic_update_slice(
                        cache["i"], ki.astype(cache["i"].dtype), (0, cache_index, 0)),
                }
                entries, keys = new_cache["c"].astype(cd), new_cache["i"].astype(cd)
            lat, n_sel, chosen = _select_attend(q_full, qi, w, entries, keys, allowed,
                                                cfg=cfg)
            _tap("chunk", layer, h=h, positions=positions, real=token_mask, chosen=chosen)
    with jax.named_scope("attn_out"):
        with jax.named_scope("mla_kv"):
            # Wkvb's value half, folded behind the attention
            attn = jnp.einsum("bshr,rhv->bshv", lat.astype(cd), w_kvb[..., nope:])
        out = weight_einsum("bsf,fd->bsd", attn.reshape(b, s, nh * vd), a["wo"],
                            compute_dtype=cd)
    return out, new_cache, n_sel


def _block(lp, x, *, cfg: ModelConfig, positions, allowed, mesh, rules, layer_cache,
           cache_index, paged, pools, token_mask, moe_stack, layer_index, layer,
           segment_ids=None):
    """Layer ``layer`` of the model, ``layer_index`` of its stack: ``(x, aux,
    new cache or None, expert counts (count_width,) or None (a dense layer),
    selected tokens ())``.
    ``layer_cache`` / the returned cache: a prefill's row ``{"c": (1,
    B, Smax, Dl), "i": (1, B, Smax, Di)}`` or a decode step's tails ``{"tc":
    (1, B, T, Dl), "ti": ...}`` (the axis of one: the double layer's two
    sublayers, which the engine's trees carry)."""
    from ditl_tpu.models.llama import _constrain, dense_mlp, rms_norm
    from ditl_tpu.models.moe import moe_block

    cd = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    cache = None if layer_cache is None else {k: v[0] for k, v in layer_cache.items()}
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lp["attn_norm"]["scale"], eps)
    out, new_cache, n_sel = _attention(
        lp["attn"], lp.get("index"), h, cfg=cfg, positions=positions, allowed=allowed,
        cache=cache, cache_index=cache_index, paged=paged, pools=pools, cd=cd, layer=layer,
        token_mask=token_mask, segment_ids=segment_ids, mesh=mesh, rules=rules)
    with jax.named_scope("attn_out"):
        x = _constrain(x + out, ("batch", "seq", "act_embed"), mesh, rules)
    with jax.named_scope("mlp"):
        u = rms_norm(x, lp["mlp_norm"]["scale"], eps)
        if "mlp" in lp:
            y = dense_mlp(lp["mlp"], u, cfg=cfg, mesh=mesh, rules=rules)
            aux, counts = jnp.zeros((), jnp.float32), None
        else:
            # a forward without cache of the block without an indexer may be
            # differentiated: a static number of buffers of held pairs, not a
            # loop whose trip count is data (an indexed block is served only,
            # and its passes keep the loop they had)
            y, aux, counts = moe_block(
                {**lp["moe"], **(moe_stack or {})}, u, cfg, token_mask=token_mask,
                mesh=mesh, layer=layer_index if moe_stack else None,
                static_buffers=layer_cache is None and not cfg.indexed)
        x = _constrain(x + y, ("batch", "seq", "act_embed"), mesh, rules)
    if new_cache is not None:
        new_cache = {k: v[None] for k, v in new_cache.items()}
    return x, aux, new_cache, counts, n_sel.astype(jnp.int32)


def stack(layers, x, *, cfg: ModelConfig, positions, segment_ids, mesh, rules,
          cache=None, cache_index=None, attn_mask=None, paged=None,
          prefill_causal=False, token_mask=None):
    """All layers: the leading dense stack, then the expert stack, each a
    scan. -> ``(x, layer_aux (L,), new cache or None, expert counts (expert
    layers, count_width), selected tokens (L,))``.

    ``cache`` as ``llama.forward`` gets it: a prefill's rows ``{"c": (L, 1,
    B, Smax, Dl), "i": (L, 1, B, Smax, Di)}``, or the page pools ``{"cp": (L,
    P, ps, Dl), "ip": (L, P, ps, Di)}`` beside the tick's tails ``{"tc": (L,
    1, B, T, Dl), "ti": (L, 1, B, T, Di)}`` (then only the tails come back).
    The pools stay whole and outside the scans, every layer's pages one axis,
    and each layer's page table is offset to its own (as ``llama.forward``
    does for every other pool)."""
    from ditl_tpu.models.llama import _apply_remat
    from ditl_tpu.models.moe import experts_in_place, grouped_rows

    if mesh is not None and mesh.shape.get("stage", 1) > 1:
        raise ValueError("pipeline parallelism does not carry DeepSeek-V3.2's "
                         "two stacks (models/dsa.py)")
    b, s, _ = x.shape
    pools = None
    if cache is not None and "cp" in cache:
        n_pages = cache["cp"].shape[1]
        pools = {k: cache[k].reshape(-1, *cache[k].shape[2:]) for k in ("cp", "ip")}
        cache = {k: cache[k] for k in ("tc", "ti")}
    if pools is not None or (cache is None and not cfg.indexed):
        allowed = None  # a decode step; the decompressed form, under segment_ids
    elif cache is not None and not prefill_causal:
        allowed = attn_mask  # (B, S, Smax), the engine's
    else:
        idx = jnp.arange(s)
        allowed = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (b, s, s))
        if segment_ids is not None:
            allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
        if cache is not None:
            # a prefill of an EMPTY row from offset 0: the chunk attends to
            # itself, wherever it sits in the row
            smax = cache["c"].shape[3]
            allowed = jax.lax.dynamic_update_slice(
                jnp.zeros((b, s, smax), bool), allowed, (0, 0, cache_index))

    outs, first = [], 0
    for kind, depth in _kinds(cfg).items():
        lp_stack, moe_stack = layers[kind], None
        if cache is not None and kind == "sparse" and experts_in_place(
                lp_stack["moe"], grouped_rows(cfg, b * s), mesh):
            # the scan slices only the router and the shared expert; the
            # kernel addresses the layer's experts inside the stack
            in_loop = ("router", "router_bias", "shared")
            moe_stack = {k: v for k, v in lp_stack["moe"].items() if k not in in_loop}
            lp_stack = {**lp_stack, "moe": {k: v for k, v in lp_stack["moe"].items()
                                            if k in in_loop}}

        def layer_fn(carry, xs, first=first, moe_stack=moe_stack):
            lp, layer_cache, i = xs
            layer_paged = paged
            if pools is not None:
                layer_paged = {**paged, "table": paged["table"] + (first + i) * n_pages}
            y, *ys = _block(
                lp, carry, cfg=cfg, positions=positions, allowed=allowed, mesh=mesh,
                rules=rules, layer_cache=layer_cache, cache_index=cache_index,
                paged=layer_paged, pools=pools, token_mask=token_mask,
                moe_stack=moe_stack, layer_index=i, layer=first + i,
                segment_ids=segment_ids)
            return y, tuple(ys)

        if cache is None:
            layer_fn = _apply_remat(layer_fn, cfg)
        part = None if cache is None else {
            k: v[first:first + depth] for k, v in cache.items()}
        with jax.named_scope("layer_scan"):
            x, ys = jax.lax.scan(
                layer_fn, x, (lp_stack, part, jnp.arange(depth, dtype=jnp.int32)))
        outs.append(ys)
        first += depth

    def joined(parts):  # the two stacks' outputs, one after the other
        parts = [p for p in parts if p is not None]
        return jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *parts) if parts else None

    aux, new_cache, counts, n_sel = (joined(parts) for parts in zip(*outs))
    return x, aux, new_cache, counts, n_sel
