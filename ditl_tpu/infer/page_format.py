"""What a cache entry is: the page formats of the paged engine.

``infer/paged_cache.py`` says WHICH page (host bookkeeping of page ids); this
module says what is IN one. The engine (``infer/continuous.py``) builds one
``PageFormat`` from its model's config and asks it for everything that depends
on a token's cache entry: the donated tree of pools and what a page costs, what
the format cannot carry (``refuse``), a prefill's row (``gather``, ``write``), a
decode tick's tails and their one write into the pools (``tails0``, ``split``,
``flush``), and the tick's counters with what derives from them.

Three page layouts, one component and one format without a page.
``StateSlots`` (a stack of retention layers, models/retention.py): STATE A
SLOT and nothing else; no pool, no page, no tail. ``WindowKVPages`` (a stack with window
attention layers, models/swa.py): TWO pools of keys and values, the full
layers' and the window layers', page ids of their own; a row keeps every page
of the first and, of the second, only those inside its window. ``KVPages``: keys and values a kv-head
(bf16, or int8 with a scale a position) and, for a stack with state-space
mixers (models/ssm.py), the mixers' STATE A SLOT beside them in the same
donated tree. ``LatentPages``: one latent vector an attention sublayer
(models/mla.py), and DeepSeek-V3.2's index keys (models/dsa.py) in a second
pool under the same page table.

The names the model code reads stay what they are: pools ``kp vp ks vs cp ip``,
tails ``tk tv tc ti``, the state ``ssm conv`` / ``ret retz``, a prefill row's
``k v c i``.
This module imports from ``models/`` and ``ops/``; nothing there imports from
``infer/``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ditl_tpu.config import ModelConfig
from ditl_tpu.infer.cache import _quantize
from ditl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

__all__ = ["KVPages", "LatentPages", "MODES", "PageFormat", "StateSlots", "WindowKVPages",
           "page_format", "tail_width"]


def tail_width(decode_chunk: int) -> int:
    """Columns of a paged decode tick's tail buffers: one a step of the
    program, and no fewer than the 8 sublanes Mosaic wants of the tail block
    (a 4-step program fills columns 0-3; ``pos - starts`` masks the rest in
    the attention kernels and in the flush)."""
    return max(decode_chunk, 8)


def _quantize_pages(chunk: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(L, n, K, ps, D) float -> int8 values + (L, n, K, 1, ps) f32 scales
    — one quantization recipe for both cache modes (infer/cache._quantize,
    symmetric per-position absmax over the last axis)."""
    q, scale = _quantize(chunk)
    return q, scale[:, :, :, None, :]


@jax.named_scope("kv_write")
def _flush_tail_into_pools(pools, tk, tv, starts, pos, table, mesh=None,
                           rules=None):
    """Write the tick's tail columns into their pages — ONE flush per tick
    (amortized over the chunk; per-token in-scan page writes cost ~7 ms/step
    on v5e), in place: the ``kv_flush`` kernel reads, merges and writes back
    only the tiles the committed columns land in (ops/kv_flush.py; an XLA
    scatter here cost a transpose of the whole pool there and back, or 70 ns
    a row, live or dead). Valid columns are j < pos - starts (exactly the
    tokens the tick committed; rejected speculative positions and dead rows
    fall outside) and nothing else is written. int8 pools: the tail is
    quantized HERE (tokens attend at full precision within their own tick,
    then round once); their small scale pools take a scatter of single
    scales, invalid columns aimed past the pool's end and dropped. Shared by
    the plain and speculative paged decode programs."""
    from ditl_tpu.ops.kv_flush import kv_flush

    out = dict(pools)
    if "ks" in pools:
        (tk, sk), (tv, sv) = _quantize(tk), _quantize(tv)
        L, n_pages, K, _, ps = pools["ks"].shape
        j = jnp.arange(tk.shape[3], dtype=jnp.int32)
        gpos = starts[:, None] + j[None, :]  # (B, tail_len)
        valid = j[None, :] < (pos - starts)[:, None]
        pidx = jnp.take_along_axis(
            table, jnp.clip(gpos // ps, 0, table.shape[1] - 1), axis=1
        )
        # index arrays broadcast to the scales' own (L, B, K, T)
        ix = (jnp.arange(L, dtype=jnp.int32)[:, None, None, None],
              jnp.where(valid, pidx, n_pages)[None, :, None, :],
              jnp.arange(K, dtype=jnp.int32)[None, None, :, None],
              0, (gpos % ps)[None, :, None, :])
        out["ks"] = pools["ks"].at[ix].set(sk, mode="drop")
        out["vs"] = pools["vs"].at[ix].set(sv, mode="drop")
    dt = pools["kp"].dtype
    out["kp"], out["vp"] = kv_flush(
        pools["kp"], pools["vp"], tk.astype(dt), tv.astype(dt), table, starts,
        pos, mesh=mesh, rules=rules,
    )
    return out


# A latent pool and the name of its entries in a tick's tails and in a
# prefill's transient row; DeepSeek-V3.2's index-key pool beside it
# (models/dsa.py) lives under the same page table and goes wherever it goes.
LATENT_POOLS = {"cp": ("tc", "c"), "ip": ("ti", "i")}


@jax.named_scope("kv_write")
def _flush_latent_tail(pools, tails, starts, pos, table):
    """``_flush_tail_into_pools`` for latent page pools (models/mla.py; with
    models/dsa.py's index keys, two of them): each tail (L, sublayers, B, T,
    D), one an attention sublayer, into its pool (sublayers x L, n_pages, ps,
    D) through the same ``kv_flush`` kernel, in place."""
    from ditl_tpu.ops.kv_flush import latent_flush

    out = {}
    for name, pool in pools.items():
        tail = tails[LATENT_POOLS[name][0]]
        tail = tail.reshape(-1, *tail.shape[2:]).astype(pool.dtype)
        out[name] = latent_flush(pool, tail, table, starts, pos)
    return out


# Everything an engine can be asked to do with its cache beyond plain paged
# ticks of one process on one chip. A format carries a mode or refuses it by
# name (``PageFormat.refuse``).
MODES = ("contiguous", "speculative", "int8", "host tier", "mesh", "adapters",
         "pod", "handoff", "registered prefix")

# How a refusal at construction names the option that asked.
_OPTION = {
    "contiguous": "the contiguous cache (cache_mode='contiguous')",
    "speculative": "speculative ticks (speculative=True)",
    "int8": "int8 page pools (kv_cache_dtype='int8')",
    "host tier": "the host tier (host_tier_mb)",
    "mesh": "a mesh",
    "adapters": "LoRA adapters",
}
_HANDOFF = ("the disaggregated KV handoff (export_kv / import_kv) cannot "
            "carry latent pages or a recurrent state yet")


def _state_stats(per_slot: int, n_slots: int, totals: dict, slots_seated: int) -> dict:
    """The ``/v1/stats`` keys of a format that keeps a state a slot."""
    return {"ssm_state_bytes_per_slot": per_slot,
            "ssm_state_bytes_resident": per_slot * n_slots,
            "ssm_slots_seated": slots_seated,
            "ssm_row_steps_total": totals["ssm_row_steps"]}


def _slot_state(pools, axes: dict[str, int], slot, offset) -> dict:
    """The slot's recurrent state as a prefill's transient row carries it: what
    a chunk before this one left, or zeros at a sequence's start (whatever the
    slot's last tenant left is never read). The bucket's padding leaves it at
    the last real token's."""
    row = {}
    for k, axis in axes.items():
        was = jax.lax.dynamic_slice_in_dim(pools[k], slot, 1, axis=axis)
        row[k] = jnp.where(offset > 0, was, jnp.zeros_like(was))
    return row


def _seat_state(pools, row, axes: dict[str, int], slot) -> dict:
    """The row's state seated at the slot, in place."""
    return {k: jax.lax.dynamic_update_slice_in_dim(pools[k], row[k], slot, axis=axis)
            for k, axis in axes.items()}


def _count_live(acc: dict, alive) -> dict:
    """The live rows: each read its state once a mixer (and, a state-space
    mixer's, wrote it)."""
    return {**acc, "ssm_row_steps": acc["ssm_row_steps"] + alive.sum(dtype=jnp.int32)}


class PageFormat:
    """What both layouts share: the sizes an engine was built with, the
    refusal, the counters' protocol. A layout adds ``page_bytes``, ``fresh()``,
    ``gather`` / ``write`` (a prefill's row), ``tails0`` / ``split`` / ``flush``
    (a decode tick), ``shardings()`` if it carries a mesh, and overrides the
    rest where it has something to say."""

    # each mode the format cannot carry -> the message that says so
    refused: dict[str, str] = {}
    # the tick's scalar counters ``count`` fills, by name: each is an
    # ``engine.tick`` span attribute of that name and a lifetime total
    counters: tuple[str, ...] = ()
    # the forward pass is told which tokens are real (a bucket's padding and a
    # tick's dead rows must not advance what the format keeps)
    masks_tokens = False
    # the content cache is fed and consulted (a page serves whoever matches it)
    publishes = True
    # a token's entry lies in a page of a pool (a format without one asks for
    # no page, lists no attention step and takes no ``n_pages``)
    pooled = True

    def __init__(self, cfg: ModelConfig, *, n_pages: int, page_size: int, n_slots: int,
                 decode_chunk: int, mesh=None, rules=None, window_pages: int = 0):
        if window_pages and not cfg.window_layer:
            raise ValueError(
                "window_pages sizes the window layers' page pool: this model has "
                "no window attention layer")
        self.cfg = cfg
        self.n_pages, self.page_size, self.n_slots = n_pages, page_size, n_slots
        self.decode_chunk, self.tail_len = decode_chunk, tail_width(decode_chunk)
        self.mesh, self.rules = mesh, rules
        self.dtype = jnp.dtype(cfg.dtype)

    @property
    def carries(self) -> frozenset[str]:
        return frozenset(MODES) - frozenset(self.refused)

    def pages_for(self, tokens: int) -> int:
        """Pages a row of ``tokens`` tokens holds."""
        return -(-tokens // self.page_size)

    def refuse(self, *asked: str, error: type[Exception] = ValueError) -> None:
        """Raise for the first of the modes ``asked`` this format cannot carry."""
        for mode in asked:
            if mode not in MODES:
                raise KeyError(f"unknown mode {mode!r}: one of {MODES}")
            if mode in self.refused:
                raise error(self.refused[mode])

    def stats(self, totals: dict, slots_seated: int) -> dict:
        """The ``/v1/stats`` keys this format adds: sizes, and what derives
        from the lifetime ``totals`` of its ``counters``."""
        return {}

    def span_attrs(self, tick: dict, decode_chunk: int) -> dict:
        """What an ``engine.tick`` span carries beside one ``tick``'s counters."""
        return {}

    def slot_operand(self, slot):
        """A prefill call's ``slot`` operand: None (no operand) where no state is."""
        return None

    def count(self, acc: dict, *, t, alive, lengths, starts, meta, counted) -> dict:
        """``acc`` (``counters``, by name) after decode step ``t`` of a tick: ``alive`` its
        live rows, ``lengths`` their contexts (0 for a dead row), ``meta`` what
        ``tick_meta`` gave, ``counted`` the forward pass's counts, by name."""
        return acc

    def tick_meta(self, starts, listed, table) -> dict:
        """What a decode step's ``paged`` metadata carries for this format beside
        the table and the kernels' work list; built once a program."""
        return {}

    def attn_pages_a_step(self, max_pages: int) -> int:
        """The pages a step of the decode attention kernel's work list takes
        (``ops/paged_attention.py`` ``pages_a_step``, read off the pools'
        shape): one, where the kernel is not the K/V one."""
        return 1

    def allocator(self, **kw):
        """The host's bookkeeping of this format's page ids."""
        from ditl_tpu.infer.paged_cache import PageAllocator

        return PageAllocator(self.n_pages, **kw)

    def device_table(self, table, spans):
        """The page table a decode program takes, from the host's ``table``
        (slots, pages a row) and the spans the rows hold (``hold``)."""
        return jnp.asarray(table)

    def prefill_tables(self, row, pids, d: int, ctx_pages: int):
        """A prefill program's ``table_row`` and ``write_pids`` operands, from
        the row's first ``ctx_pages`` page ids and the pages the chunk at
        position ``d`` writes."""
        return jnp.asarray(row), jnp.asarray(pids)


class KVPages(PageFormat):
    """Keys and values a kv-head in pools (L, P, K, ps, D), kv-heads before
    page slots so the Pallas kernel's per-head blocks keep (ps, D) trailing
    dims; int8 pools carry a scale a position (L, P, K, 1, ps). In a hybrid
    stack (models/ssm.py) they are its attention layers' alone, and every
    mixer's state (``ssm``, ``conv``: fixed size, rewritten every token) sits
    beside them."""

    def __init__(self, cfg: ModelConfig, **kw):
        super().__init__(cfg, **kw)
        self.quantized = cfg.kv_cache_dtype == "int8"
        self.state_axes: dict[str, int] = {}
        self.layers = cfg.layer_types.count("a") or cfg.num_layers
        # The width a page stores a head at. A hybrid stack's 64-wide heads
        # are stored in whole lanes of 128, the upper half zeros (stored, and
        # counted as stored): a pool whose last dimension is 64 lives on the
        # chip in another layout than the kernels read, and the compiler
        # copied both pools whole in front of every decode tick (2 x 2 GiB of
        # temporaries in the program compiled for a described v5e).
        self.head_dim = cfg.head_dim
        if "m" in cfg.layer_types:
            from ditl_tpu.models.ssm import SLOT_AXIS

            self.state_axes = SLOT_AXIS
            self.head_dim = -(-cfg.head_dim // 128) * 128
            self.counters = ("ssm_row_steps",)
            self.masks_tokens = True
            said = {**_OPTION, "mesh": "a mesh (mesh, and pod serving over it)",
                    "adapters": "LoRA adapters (lora_rank)"}
            self.refused = {
                mode: f"a state-space layer (layer_types={cfg.layer_types!r}) "
                      f"keeps a recurrent state a slot, which {option} cannot "
                      "carry yet: serve it with cache_mode='paged', plain ticks, "
                      "bfloat16 pages, no host tier, no mesh and no adapters"
                for mode, option in said.items()}
            self.refused.update({
                "pod": "pod serving cannot carry a recurrent state a slot yet (a "
                       "state-space layer is served by one process on one chip)",
                "handoff": _HANDOFF,
                "registered prefix":
                    "register_prefix cannot serve a state-space layer: a prefix's "
                    "pages are reusable only with the recurrent state at their "
                    "boundary, which nothing keeps yet"})
            # pages without the recurrent state at their boundary serve nobody
            self.publishes = False
        self.shape = (self.layers, self.n_pages, cfg.num_kv_heads, self.page_size,
                      self.head_dim)
        self.scale_shape = (*self.shape[:3], 1, self.page_size)
        per_val = math.prod(self.shape) // self.n_pages
        if self.quantized:
            self.page_bytes = 2 * per_val + 2 * (per_val // self.head_dim) * 4
        else:
            self.page_bytes = 2 * per_val * self.dtype.itemsize

    def attn_pages_a_step(self, max_pages: int) -> int:
        from ditl_tpu.ops.paged_attention import pages_a_step

        return pages_a_step(self.shape, jnp.int8 if self.quantized else self.dtype, max_pages)

    def fresh(self) -> dict[str, jax.Array]:
        if self.quantized:
            return {"kp": jnp.zeros(self.shape, jnp.int8),
                    "vp": jnp.zeros(self.shape, jnp.int8),
                    "ks": jnp.ones(self.scale_shape, jnp.float32),
                    "vs": jnp.ones(self.scale_shape, jnp.float32)}
        pools = {"kp": jnp.zeros(self.shape, self.dtype),
                 "vp": jnp.zeros(self.shape, self.dtype)}
        if self.state_axes:
            from ditl_tpu.models.ssm import init_state

            pools.update(init_state(self.cfg, self.n_slots))
        return pools

    def shardings(self):
        """The pools' shardings over the engine's mesh: kv-heads over the
        tensor axis (the kernels are shard_mapped the same way), everything
        else of a pool whole."""
        from ditl_tpu.ops.attention import _mesh_axes_size
        from ditl_tpu.parallel.sharding import DEFAULT_RULES, named_sharding_tree, seq_shards

        cfg, mesh = self.cfg, self.mesh
        if seq_shards(mesh, self.rules) > 1:
            # Deliberate: page pools shard kv-heads/tensor only and
            # REPLICATE over the sequence axis — paged capacity
            # does not scale with it. The sequence axis exists for
            # contexts that exceed one chip's HBM, where
            # concurrency is inherently tiny and paged's capacity
            # sharing buys nothing; use the contiguous cache there
            # (it context-shards over the axis).
            logger.warning(
                "cache_mode='paged' on a sequence-sharded mesh: "
                "page pools replicate over the sequence axis (no "
                "context-capacity scaling); long-context serving "
                "should use the contiguous cache"
            )
        r = self.rules if self.rules is not None else DEFAULT_RULES
        tp = _mesh_axes_size(mesh, r.get("act_kv_heads"))
        if tp > 1 and (cfg.num_kv_heads % tp or cfg.num_heads % tp):
            raise ValueError(
                f"paged cache with a mesh shards kv-heads over the "
                f"tensor axis: heads {cfg.num_heads}/"
                f"{cfg.num_kv_heads} must divide tp={tp}"
            )
        dp = _mesh_axes_size(mesh, r.get("batch"))
        if dp > 1 and self.n_slots % dp:
            # Fail at construction: the kernel would silently fall
            # back to the unsharded GSPMD path, resharding the whole
            # page pool every decode step (ADVICE r2).
            raise ValueError(
                f"paged cache with a mesh shards slots over the "
                f"data axes: n_slots={self.n_slots} must divide dp={dp}"
            )
        pool_axes = ("layers", None, "act_kv_heads", None, "head_dim")
        axes_tree = {"kp": pool_axes, "vp": pool_axes}
        if self.quantized:
            scale_axes = ("layers", None, "act_kv_heads", None, None)
            axes_tree.update({"ks": scale_axes, "vs": scale_axes})
        return named_sharding_tree(mesh, axes_tree, self.rules)

    def stats(self, totals, slots_seated):
        if not self.state_axes:
            return {}
        from ditl_tpu.models.ssm import state_bytes_per_slot

        return _state_stats(state_bytes_per_slot(self.cfg), self.n_slots, totals, slots_seated)

    def span_attrs(self, tick, decode_chunk):
        return {"ssm_steps": decode_chunk} if self.state_axes else {}

    def slot_operand(self, slot):
        return jnp.int32(slot) if self.state_axes else None

    def gather(self, pools, table_row, ctx_pages: int, s_bucket: int, *, offset, slot=None):
        """The transient row a prefill's forward pass attends over: ``ctx_pages``
        pages' entries, then room for the chunk's; the slot's state beside them."""
        L, _, K, ps, D = pools["kp"].shape
        cd = self.dtype

        def to_row(pool, scales=None):
            # (L, ctx_pages, K, ps, D) [+ scales] -> (L, 1, ctx*ps, K, D)
            if ctx_pages == 0:
                return jnp.zeros((L, 1, 0, K, D), cd)
            g = pool[:, table_row]
            if scales is not None:
                sc = scales[:, table_row][:, :, :, 0, :]  # (L, ctx_pages, K, ps)
                g = (g.astype(jnp.float32) * sc[..., None]).astype(cd)
            g = jnp.swapaxes(g, 2, 3)
            return g.reshape(L, 1, ctx_pages * ps, K, D)

        with jax.named_scope("kv_gather"):
            ctx_k = to_row(pools["kp"], pools.get("ks"))
            ctx_v = to_row(pools["vp"], pools.get("vs"))
        zeros = jnp.zeros((L, 1, s_bucket, K, D), ctx_k.dtype)
        row = {
            "k": jnp.concatenate([ctx_k, zeros], axis=2),
            "v": jnp.concatenate([ctx_v, zeros], axis=2),
        }
        # the slot's recurrent state rides the transient row
        row.update(_slot_state(pools, self.state_axes, slot, offset))
        return row

    @jax.named_scope("kv_write")
    def write(self, pools, row, offset, write_pids, *, slot=None):
        """The chunk's entries (the row's, from ``offset``, a page a ``write_pids``
        entry) into their pages, in place; the row's state seated at the slot."""
        L, _, K, ps, D = pools["kp"].shape
        n_wp = write_pids.shape[0]

        def to_pages(r):  # (L, 1, s_bucket, K, D) -> (L, n_wp, K, ps, D)
            chunk = jax.lax.dynamic_slice_in_dim(r, offset, n_wp * ps, axis=2)
            return jnp.swapaxes(chunk.reshape(L, n_wp, ps, K, D), 2, 3)

        chunks = {"kp": to_pages(row["k"]), "vp": to_pages(row["v"])}
        if self.quantized:
            chunks["kp"], chunks["ks"] = _quantize_pages(chunks["kp"])
            chunks["vp"], chunks["vs"] = _quantize_pages(chunks["vp"])
        out = dict(pools)
        for name, chunk in chunks.items():
            for j in range(n_wp):
                out[name] = jax.lax.dynamic_update_slice(
                    out[name], chunk[:, j:j + 1], (0, write_pids[j], 0, 0, 0))
        out.update(_seat_state(pools, row, self.state_axes, slot))
        return out

    def tails0(self, n_b: int, tail_len: int | None = None) -> dict[str, jax.Array]:
        shape = (self.layers, n_b, self.cfg.num_kv_heads, tail_len or self.tail_len,
                 self.head_dim)
        return {"tk": jnp.zeros(shape, self.dtype), "tv": jnp.zeros(shape, self.dtype)}

    def split(self, pools) -> tuple[dict, dict]:
        """(what a tick's scan reads and never writes: the page pools, whole;
        what it carries beside its tails: the slots' state, rewritten a step)."""
        carried = {k: pools[k] for k in self.state_axes}
        return {k: v for k, v in pools.items() if k not in carried}, carried

    def flush(self, pools, carried, starts, pos, table) -> dict:
        """The donated tree after a tick: ``pools`` (``split``'s first) with the
        tails' committed columns written, the state as the scan left it."""
        out = _flush_tail_into_pools(pools, carried["tk"], carried["tv"], starts, pos,
                                     table, self.mesh, self.rules)
        out.update({k: carried[k] for k in self.state_axes})
        return out

    def count(self, acc, *, t, alive, lengths, starts, meta, counted):
        return _count_live(acc, alive) if self.state_axes else acc


class StateSlots(PageFormat):
    """A stack of retention layers (models/retention.py): a sequence's cache
    entry is its STATE, of fixed size, seated at its slot, and nothing else.
    The donated tree holds the state alone; a request asks for no page
    (``pages_for`` is 0, so admission, holds and preemption go by slots), a
    row's length is bounded by the engine's ``max_cache_len`` (the positions
    of the rotation), a prefill's row is the slot's state (zeros at a
    sequence's start: what the slot's last tenant left is never read).

    A decode tick's tails are its HELD TOKENS (``retention.held_tokens``: each
    step's ``k``, ``v`` and ``log g`` a layer a row a kv head, zeros at the
    tick's start): a step reads a live row's state and the tick's LAST step
    alone writes it, the held tokens folded in (``ops/retention.py``), so
    there is nothing left to flush and ``flush`` drops them. A tick of one
    step holds nothing. ``ret_row_folds`` counts the rows whose state a tick
    wrote (those live at its last step) beside ``ssm_row_steps``, the rows
    whose state a step read. Nothing is published: state snapshots at
    boundaries, for prefix reuse and for resume, are not kept yet, so a
    preempted request runs again from its first token."""

    counters = ("ssm_row_steps", "ret_row_folds")
    masks_tokens = True
    publishes = False
    pooled = False
    page_bytes = 0

    def __init__(self, cfg: ModelConfig, **kw):
        from ditl_tpu.models.retention import SLOT_AXIS

        super().__init__(cfg, **kw)
        self.n_pages = 2  # the host allocator's sentinel and one id, which no row asks for
        self.state_axes = SLOT_AXIS
        said = {**_OPTION, "mesh": "a mesh (mesh, and pod serving over it)",
                "adapters": "LoRA adapters (lora_rank)", "pod": "pod serving",
                "handoff": "the disaggregated KV handoff (export_kv / import_kv)",
                "registered prefix": "register_prefix"}
        self.refused = {
            mode: f"a stack of retention layers (layer_types={cfg.layer_types[:4]!r}...) "
                  f"keeps a state a slot and no keys and values, which {option} cannot "
                  "carry yet: serve it with cache_mode='paged', plain ticks, no int8, "
                  "no host tier, no mesh and no adapters, one process on one chip"
            for mode, option in said.items()}

    def pages_for(self, tokens: int) -> int:
        return 0

    def attn_pages_a_step(self, max_pages: int) -> int:
        return 0  # no attention kernel walks anything

    def fresh(self) -> dict[str, jax.Array]:
        from ditl_tpu.models.retention import init_state

        return init_state(self.cfg, self.n_slots)

    def stats(self, totals, slots_seated):
        from ditl_tpu.models.retention import state_bytes_per_slot

        return {"pages_total": 0, "pages_free": 0,  # ids without a pool behind them
                **_state_stats(state_bytes_per_slot(self.cfg), self.n_slots, totals,
                               slots_seated),
                "ret_row_folds_total": totals["ret_row_folds"]}

    def span_attrs(self, tick, decode_chunk):
        return {"ssm_steps": decode_chunk}

    def slot_operand(self, slot):
        return jnp.int32(slot)

    def gather(self, pools, table_row, ctx_pages: int, s_bucket: int, *, offset, slot=None):
        return _slot_state(pools, self.state_axes, slot, offset)

    @jax.named_scope("kv_write")
    def write(self, pools, row, offset, write_pids, *, slot=None):
        return _seat_state(pools, row, self.state_axes, slot)

    def tails0(self, n_b: int, tail_len: int | None = None) -> dict[str, jax.Array]:
        from ditl_tpu.models.retention import held_tokens

        return held_tokens(self.cfg, n_b, self.decode_chunk) if self.decode_chunk > 1 else {}

    def split(self, pools) -> tuple[dict, dict]:
        return {}, dict(pools)

    def flush(self, pools, carried, starts, pos, table) -> dict:
        # the held tokens were folded in by the tick's last step
        return {k: carried[k] for k in self.state_axes}

    def count(self, acc, *, t, alive, lengths, starts, meta, counted):
        acc = _count_live(acc, alive)
        folds = jnp.where(t == self.decode_chunk - 1, alive.sum(dtype=jnp.int32), 0)
        return {**acc, "ret_row_folds": acc["ret_row_folds"] + folds}


class LatentPages(PageFormat):
    """ONE latent vector an attention sublayer a token (models/mla.py) in a
    pool (sublayers x L, P, ps, Dl); DeepSeek-V3.2 (models/dsa.py, one
    sublayer a layer) keeps its INDEX KEYS in a second pool beside it, same
    page ids: what cannot carry a latent page cannot carry its index keys
    either, so one set of refusals covers both."""

    def __init__(self, cfg: ModelConfig, **kw):
        from ditl_tpu.models.mla import SUBLAYERS, latent_width

        super().__init__(cfg, **kw)
        self.indexed = cfg.dsa_layer
        self.sublayers = 1 if self.indexed else SUBLAYERS
        shape = (cfg.num_layers * self.sublayers, self.n_pages, self.page_size,
                 latent_width(cfg))
        self.shapes = {"cp": shape}
        self.counters = ("decode_ctx_tokens",)
        if self.indexed:
            self.shapes["ip"] = (*shape[:3], cfg.index_head_dim)
            self.counters += ("dsa_selected_tokens", "dsa_index_pages")
        self.page_bytes = sum(
            math.prod(s) // self.n_pages * self.dtype.itemsize for s in self.shapes.values())
        self.refused = {
            mode: f"latent attention (kv_lora_rank={cfg.kv_lora_rank}) is served "
                  f"from a latent page pool, which {option} cannot carry yet: "
                  "serve it with cache_mode='paged', plain ticks, bfloat16 pages, "
                  "no host tier and no mesh"
            for mode, option in _OPTION.items()}
        self.refused.update(
            pod="pod serving cannot carry a latent page pool yet (latent "
                "attention is served by one process on one chip)",
            handoff=_HANDOFF)

    def fresh(self) -> dict[str, jax.Array]:
        return {name: jnp.zeros(shape, self.dtype) for name, shape in self.shapes.items()}

    def stats(self, totals, slots_seated):
        out = {"decode_ctx_tokens": totals["decode_ctx_tokens"]}
        if self.indexed:
            out.update(self.span_attrs(totals, 0),  # the same product of the totals
                       dsa_selected_tokens=totals["dsa_selected_tokens"],
                       index_pool_bytes=math.prod(self.shapes["ip"]) * self.dtype.itemsize)
        return out

    def span_attrs(self, tick, decode_chunk):
        if not self.indexed:
            return {}
        # what the indexer chose among, in every layer
        return {"dsa_ctx_tokens": tick["decode_ctx_tokens"] * self.cfg.num_layers}

    def gather(self, pools, table_row, ctx_pages: int, s_bucket: int, *, offset, slot=None):
        """Each pool (sublayers x L, P, ps, D) -> (L, sublayers, 1, ctx * ps +
        bucket, D): the context pages' entries, then room for the chunk's."""
        ps, buf = self.page_size, ctx_pages * self.page_size + s_bucket
        row = {}
        # Page by page, each a slice of the pool copied into its place in
        # the row: ONE gather of all the pages made the compiler copy the
        # whole pool in lane slices first (2 x 1.04 + 0.52 GiB of
        # temporaries at PR 44's cell, whatever the context: seen in
        # the buffer assignment compiled for a described v5e).
        for name, cp in pools.items():
            def put(j, r, cp=cp):
                page = jax.lax.dynamic_slice(
                    cp, (0, table_row[j], 0, 0), (cp.shape[0], 1, ps, cp.shape[-1]))
                return jax.lax.dynamic_update_slice(r, page, (0, 0, j * ps, 0))

            with jax.named_scope("kv_gather"):
                r = jax.lax.fori_loop(
                    0, ctx_pages, put,
                    jnp.zeros((cp.shape[0], 1, buf, cp.shape[-1]), cp.dtype))
            row[LATENT_POOLS[name][1]] = r.reshape(self.cfg.num_layers, -1, *r.shape[1:])
        return row

    @jax.named_scope("kv_write")
    def write(self, pools, row, offset, write_pids, *, slot=None):
        ps, n_wp = self.page_size, write_pids.shape[0]
        out = {}
        for name, cp in pools.items():
            c = row[LATENT_POOLS[name][1]]
            c = c.reshape(cp.shape[0], 1, c.shape[3], cp.shape[-1])
            chunk = jax.lax.dynamic_slice_in_dim(c, offset, n_wp * ps, axis=2)
            chunk = chunk.reshape(cp.shape[0], n_wp, ps, cp.shape[-1])
            for j in range(n_wp):
                cp = jax.lax.dynamic_update_slice(
                    cp, chunk[:, j:j + 1], (0, write_pids[j], 0, 0))
            out[name] = cp
        return out

    def tails0(self, n_b: int, tail_len: int | None = None) -> dict[str, jax.Array]:
        return {LATENT_POOLS[name][0]: jnp.zeros(
            (self.cfg.num_layers, self.sublayers, n_b, tail_len or self.tail_len, shape[-1]),
            self.dtype) for name, shape in self.shapes.items()}

    def split(self, pools) -> tuple[dict, dict]:
        return dict(pools), {}

    def flush(self, pools, carried, starts, pos, table) -> dict:
        return _flush_latent_tail(pools, carried, starts, pos, table)

    def tick_meta(self, starts, listed, table) -> dict:
        # the indexer scores a row's pages only where it has to choose
        max_pages = table.shape[1]
        if not self.indexed or (
                max_pages * self.page_size + self.tail_len <= self.cfg.index_topk):
            return {}
        from ditl_tpu.ops.dsa_index import index_steps

        # ``decode_steps``' list with a group of pages for a page, built
        # beside it and booked where it is
        with jax.named_scope("attn_core"), jax.named_scope("attn_steps"):
            return {"index_steps": index_steps(starts, listed, page_size=self.page_size,
                                               max_pages=max_pages)}

    def count(self, acc, *, t, alive, lengths, starts, meta, counted):
        # the context tokens this step's rows had (what the latent kernel had
        # to read, a sublayer)
        out = {**acc, "decode_ctx_tokens": acc["decode_ctx_tokens"] + lengths.sum()}
        if self.indexed:
            # the entries they selected (models/dsa.py), summed over the
            # layers; and the pages the index walk fetched: a live row's
            # flushed pages, in every layer
            live_pages = -(-jnp.minimum(starts, lengths) // self.page_size)
            walked = self.cfg.num_layers if "index_steps" in meta else 0
            out["dsa_selected_tokens"] = (
                acc["dsa_selected_tokens"] + counted["dsa_selected"].sum())
            out["dsa_index_pages"] = acc["dsa_index_pages"] + live_pages.sum() * walked
        return out


class WindowKVPages(PageFormat):
    """Keys and values of a stack with window attention layers (models/swa.py)
    in TWO pools: the full layers' ``kp`` / ``vp`` (full layers, P, K, ps, D)
    and the window layers' ``wkp`` / ``wvp`` (window layers, P_win, K, ps, D),
    page ids of their own (infer/paged_cache.py ``WindowedAllocator``: a
    window page is the companion of the full page that holds the same tokens).

    What the layout guarantees: (a) a live row holds at most ``ceil(window /
    page_size) + 1`` window pages of context plus those of the chunk or tick
    in flight; the pages behind go back as its position passes them, in
    chunked prefill and in decode alike; (b) a published prefix keeps its
    full pages whole and the window pages it had when it was published, and
    a hit is granted at the longest length whose last window both pools hold;
    (c) eviction, preemption and resume go through the same two calls
    (``hold``, ``match_prefix``), so both pools' counts move together; (d) a
    tick's tails are flushed into both pools through ``kv_flush``; (e) every
    mode of ``MODES`` is refused by name: the layout carries plain paged
    ticks of one process on one chip with the content cache, nothing else
    yet."""

    counters = ("window_pages_walked", "full_pages_walked")

    def __init__(self, cfg: ModelConfig, **kw):
        from ditl_tpu.models.swa import layer_kinds

        super().__init__(cfg, **kw)
        is_w, _ = layer_kinds(cfg)
        self.win_layers = [i for i, w in enumerate(is_w) if w]
        self.full_layers = [i for i, w in enumerate(is_w) if not w]
        self.window = cfg.sliding_window
        self.reach = -(-self.window // self.page_size)
        # a row's span, and room for a prefix a document to stay cached: a rule
        # where the server gives no size
        self.window_pages = kw.get("window_pages") or min(
            self.n_pages, self.n_slots * (self.reach + 6) + 1)
        k, ps, d = cfg.num_kv_heads, self.page_size, cfg.head_dim
        # a pool of no layers would have no page to name: one layer at least
        self.shape = (max(1, len(self.full_layers)), self.n_pages, k, ps, d)
        self.win_shape = (max(1, len(self.win_layers)), self.window_pages, k, ps, d)
        per_layer = 2 * k * ps * d * self.dtype.itemsize
        self.page_bytes = per_layer * self.shape[0]
        self.window_page_bytes = per_layer * self.win_shape[0]
        said = {**_OPTION, "pod": "pod serving", "handoff": "the disaggregated KV handoff",
                "registered prefix": "register_prefix"}
        self.refused = {
            mode: f"a stack with window attention layers (layer_types="
                  f"{cfg.layer_types!r}) keeps two page pools, which {option} cannot "
                  "carry yet: serve it with cache_mode='paged', plain ticks, bfloat16 "
                  "pages, no host tier, no mesh and no adapters, one process on one chip"
            for mode, option in said.items()}
        self.alloc = None
        self._seen = (0, 0)

    def attn_pages_a_step(self, max_pages: int) -> int:
        from ditl_tpu.ops.paged_attention import pages_a_step

        return pages_a_step(self.shape, self.dtype, max_pages)  # both pools' pages are one size

    def allocator(self, **kw):
        from ditl_tpu.infer.paged_cache import WindowedAllocator

        self.alloc = WindowedAllocator(self.n_pages, self.window_pages, window=self.window,
                                       page_size=self.page_size, **kw)
        return self.alloc

    def fresh(self) -> dict[str, jax.Array]:
        return {"kp": jnp.zeros(self.shape, self.dtype), "vp": jnp.zeros(self.shape, self.dtype),
                "wkp": jnp.zeros(self.win_shape, self.dtype),
                "wvp": jnp.zeros(self.win_shape, self.dtype)}

    def device_table(self, table, spans):
        import numpy as np

        return jnp.asarray(np.stack([table, self.alloc.window_table(table, spans)]))

    def prefill_tables(self, row, pids, d: int, ctx_pages: int):
        import numpy as np

        # the window layers' context: the last pages in front of the chunk
        wctx = min(ctx_pages, self.reach)
        first = d // self.page_size - wctx
        wrow = np.zeros((max(wctx, 1),), np.int32)
        for j in range(wctx):
            if 0 <= first + j < len(row):
                wrow[j] = self.alloc.companion[row[first + j]]
        return ((jnp.asarray(row), jnp.asarray(wrow)),
                (jnp.asarray(pids), jnp.asarray(self.alloc.companion[pids])))

    def gather(self, pools, table_row, ctx_pages: int, s_bucket: int, *, offset, slot=None):
        """The context a prefill chunk attends over: every cached page for the
        full layers, the last ``ceil(window / page_size)`` for the window
        layers (``prefill_tables``); the chunk's own entries stay the forward
        pass's."""
        def to_row(pool, ids, n):
            if n == 0:
                return jnp.zeros((pool.shape[0], 1, 0, *pool.shape[2:3], pool.shape[-1]),
                                 self.dtype)
            # page by page, each a slice of the pool copied into its place:
            # ONE gather of all the pages makes the compiler copy the whole
            # pool first (``LatentPages.gather``: 2 GiB of temporaries here)
            def put(j, g):
                page = jax.lax.dynamic_slice(
                    pool, (0, ids[j], 0, 0, 0), (pool.shape[0], 1, *pool.shape[2:]))
                return jax.lax.dynamic_update_slice(g, page, (0, j, 0, 0, 0))

            g = jax.lax.fori_loop(
                0, n, put, jnp.zeros((pool.shape[0], n, *pool.shape[2:]), pool.dtype))
            g = jnp.swapaxes(g, 2, 3)  # (L, n, ps, K, D)
            return g.reshape(pool.shape[0], 1, n * self.page_size, *g.shape[3:])

        row_f, row_w = table_row
        wctx = min(ctx_pages, self.reach)
        with jax.named_scope("kv_gather"):
            return {"k": to_row(pools["kp"], row_f, ctx_pages),
                    "v": to_row(pools["vp"], row_f, ctx_pages),
                    "wk": to_row(pools["wkp"], row_w, wctx),
                    "wv": to_row(pools["wvp"], row_w, wctx)}

    @jax.named_scope("kv_write")
    def write(self, pools, row, offset, write_pids, *, slot=None):
        """The chunk's keys and values, every layer's (``row``: (L, 1, S, K,
        D), what the forward pass returned), into the pages of both pools."""
        import numpy as np

        ps = self.page_size
        out = dict(pools)
        for names, layers, pids in ((("kp", "vp"), self.full_layers, write_pids[0]),
                                    (("wkp", "wvp"), self.win_layers, write_pids[1])):
            if not layers:
                continue
            n_wp = pids.shape[0]
            for name, r in zip(names, (row["k"], row["v"])):
                r = r[np.asarray(layers)][:, 0, :n_wp * ps]  # (layers, S, K, D)
                chunk = jnp.swapaxes(r.reshape(len(layers), n_wp, ps, *r.shape[2:]), 2, 3)
                for j in range(n_wp):
                    out[name] = jax.lax.dynamic_update_slice(
                        out[name], chunk[:, j:j + 1].astype(out[name].dtype),
                        (0, pids[j], 0, 0, 0))
        return out

    def tails0(self, n_b: int, tail_len: int | None = None) -> dict[str, jax.Array]:
        shape = (self.cfg.num_layers, n_b, self.cfg.num_kv_heads, tail_len or self.tail_len,
                 self.cfg.head_dim)
        return {"tk": jnp.zeros(shape, self.dtype), "tv": jnp.zeros(shape, self.dtype)}

    def split(self, pools) -> tuple[dict, dict]:
        return dict(pools), {}

    @jax.named_scope("kv_write")
    def flush(self, pools, carried, starts, pos, table) -> dict:
        import numpy as np

        from ditl_tpu.ops.kv_flush import kv_flush

        out = dict(pools)
        for (kn, vn), layers, tab in ((("kp", "vp"), self.full_layers, table[0]),
                                      (("wkp", "wvp"), self.win_layers, table[1])):
            if layers:
                at = np.asarray(layers)
                out[kn], out[vn] = kv_flush(
                    pools[kn], pools[vn], carried["tk"][at].astype(pools[kn].dtype),
                    carried["tv"][at].astype(pools[vn].dtype), tab, starts, pos)
        return out

    def tick_meta(self, starts, listed, table) -> dict:
        from ditl_tpu.ops.paged_attention import decode_steps, window_first_page

        ps, max_pages = self.page_size, table.shape[-1]
        with jax.named_scope("attn_core"), jax.named_scope("attn_steps"):
            wsteps = decode_steps(starts, listed, page_size=ps, max_pages=max_pages,
                                  window=self.window, group=self.attn_pages_a_step(max_pages))
            # each list's PAGES, from the rows' positions (a step of a list
            # may take several, and the tail step is neither's)
            full = -(-starts // ps)
            win = full - window_first_page(starts, self.window, ps)
            win, full = (jnp.where(listed, n, 0).sum(dtype=jnp.int32) for n in (win, full))
        return {"table": table[0], "wtable": table[1], "wsteps": wsteps, "pages": (win, full)}

    def count(self, acc, *, t, alive, lengths, starts, meta, counted):
        win, full = meta["pages"]  # a call of each kernel walked them
        return {**acc, "window_pages_walked": acc["window_pages_walked"] + win,
                "full_pages_walked": acc["full_pages_walked"] + full}

    def span_attrs(self, tick, decode_chunk):
        al = self.alloc
        was, self._seen = self._seen, (al.released, al.freed)
        return {"window_pages_released": al.released - was[0],
                "window_pages_freed": al.freed - was[1],
                "window_pages_live": al.window_pages - 1 - al.n_window_free,
                "window_pages_total": al.window_pages - 1}

    def stats(self, totals, slots_seated):
        al = self.alloc
        return {"window_pages_total": al.window_pages - 1,
                "window_pages_free": al.n_window_free,
                "window_pages_cached_evictable": al.n_window_cached,
                "window_pages_released_total": al.released,
                "window_pages_freed_total": al.freed,
                "window_pool_evictions": al.window_evictions,
                "window_kv_bytes_per_token": self.window_page_bytes // self.page_size,
                "prefix_hits_whole": al.hits_whole, "prefix_hits_short": al.hits_short,
                "prefix_hits_refused": al.hits_refused,
                "window_pages_walked_total": totals["window_pages_walked"],
                "full_pages_walked_total": totals["full_pages_walked"]}


def page_format(cfg: ModelConfig, **kw) -> PageFormat:
    """The format of ``cfg``'s cache entries (derived, never a setting): latent
    pages where attention is latent, two K/V pools where the stack has window
    layers, state slots where it keeps no keys and values, K/V pages otherwise."""
    if cfg.dsa_layer and not cfg.indexed:
        raise ValueError(
            "the single pre-norm latent block without an indexer (kv_lora_rank > 0, "
            "first_k_dense_replace > 0, index_topk 0: models/dsa.py) is trained, not "
            "served: its cached forward (the absorbed form over a latent pool with no "
            "index-key pool beside it) is not built; the trainer and an evaluation run it")
    if cfg.kv_lora_rank > 0:
        return LatentPages(cfg, **kw)
    if cfg.retention_layer:
        return StateSlots(cfg, **kw)
    return (WindowKVPages if cfg.window_layer else KVPages)(cfg, **kw)
