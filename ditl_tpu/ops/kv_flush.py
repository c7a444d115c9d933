"""The paged decode tick's flush: the tail's committed columns into the page
pools, in place, touching only the tiles they land in.

A tick accumulates its new K and V in a small tail buffer (``(L, B, K, T,
D)``, column ``j`` of slot ``b`` = position ``starts[b] + j``) and flushes
it once, after its scan (infer/page_format.py ``_flush_tail_into_pools``).
Valid columns are ``j < pos[b] - starts[b]``. Written as an XLA scatter the
flush costs what the compiler makes of the scatter's window, never what it
writes (25 MB a tick on the 7B serving cell): a window of ``(L, K, D)`` had
the WHOLE pool transposed to another layout and back, four pool-sized
copies a tick (55 ms on one v5e chip); a window of one ``D``-wide row is in
place but serial, 70 ns a row whether the slot is live or dead (6.9 ms for
98,304 rows; 23 ms for the 327,680 rows of a pool with 16 kv heads).

``kv_flush`` (Pallas/Mosaic) is a read-merge-write of the ``w``-row windows
the tail touches, ``w`` the rows of one tile of the pool's dtype (16 for
bf16): a pool's window ``(K, w, D)`` is a block whose index map reads the
page table, ``starts`` and ``pos`` from the scalar-prefetch channel, as the
read side does (ops/paged_attention.py), and the pools are aliased to the
outputs, so nothing but those blocks moves. Grid ``(B, L, n_w)``: ``T``
consecutive positions touch at most ``n_w = ceil((T - 1) / w) + 1`` aligned
windows. A window that holds no valid column (a dead slot, a row stopped
short, the window past ``pos``) is redirected to ONE sentinel block, layer
0's page 0, and written back as it was read: consecutive identical block
indices skip the fetch, so dead slots cost a grid step and no DMA. The
sentinel page is the read side's: never allocated, never read unmasked. No
two grid steps write the same live block: a slot only writes pages it alone
holds (shared prefix pages are full), and ``w`` divides the page size.

The tail's columns reach their window rows through a one-hot ``(w, T)``
matmul: a shift by ``starts % w`` on the MXU, exact (one product a sum),
where a sublane shift by a dynamic amount of a packed dtype has no Mosaic
lowering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default

__all__ = ["kv_flush", "latent_flush"]


def _kv_flush_kernel(tab, st, pos, *refs, w):
    """``refs``: the tails, the pools and the pools' outputs, as many of each
    (K and V: two; a latent pool: one)."""
    del tab  # the index maps' alone
    n_pools = len(refs) // 3
    tails, ins, outs = (refs[i * n_pools:(i + 1) * n_pools] for i in range(3))
    ib, iw = pl.program_id(0), pl.program_id(2)
    kv_heads, t_len = tails[0].shape[2], tails[0].shape[3]
    s = st[ib]
    n = pos[ib] - s
    # window row r holds position base + r, the tail's column base + r - s
    base = (s // w + iw) * w
    col = base - s + jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    valid = (col >= 0) & (col < jnp.minimum(n, t_len))
    # int8 and bf16 values are exact in bf16; float32 keeps its own width
    exact32 = tails[0].dtype == jnp.float32
    cdt = jnp.float32 if exact32 else jnp.bfloat16
    onehot = (col == jax.lax.broadcasted_iota(jnp.int32, (w, t_len), 1)).astype(cdt)
    precision = jax.lax.Precision.HIGHEST if exact32 else None

    for tail_ref, in_ref, out_ref in zip(tails, ins, outs):
        for k in range(kv_heads):
            new = jax.lax.dot(
                onehot, tail_ref[0, 0, k].astype(cdt),
                precision=precision,
                preferred_element_type=jnp.float32,
            )
            old = in_ref[0, 0, k].astype(jnp.float32)
            out_ref[0, 0, k] = jnp.where(valid, new, old).astype(out_ref.dtype)


def kv_flush(
    k_pool: jax.Array,  # (L, P, K, ps, D)
    v_pool: jax.Array,
    tail_k: jax.Array,  # (L, B, K, T, D), in the pools' dtype
    tail_v: jax.Array,
    page_table: jax.Array,  # (B, maxp) int32
    starts: jax.Array,  # (B,) the position of tail column 0
    pos: jax.Array,  # (B,) columns j < pos - starts are written
    *,
    interpret: bool | None = None,
    mesh=None,
    rules=None,
) -> tuple[jax.Array, jax.Array]:
    """The pools with every slot's valid tail columns written at
    ``page_table[b, p // ps]``, offset ``p % ps``, ``p = starts[b] + j``;
    every other row as it was (module docstring). The pools are aliased to
    the results: donate them.

    With a ``mesh`` the kernel is shard_mapped like ``paged_attention``:
    pools and tails split on kv-heads (``act_kv_heads``), the table,
    ``starts`` and ``pos`` replicated, the tails gathered over the batch
    axes (every replica of a pool shard writes every slot)."""
    if mesh is not None:
        from ditl_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec

        rules = rules if rules is not None else DEFAULT_RULES
        spec = logical_to_spec((None, None, "act_kv_heads", None, None), rules)
        rep = jax.sharding.PartitionSpec()
        return jax.shard_map(
            functools.partial(kv_flush, interpret=interpret),
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, rep, rep, rep),
            out_specs=(spec, spec),
            check_vma=False,
        )(k_pool, v_pool, tail_k, tail_v, page_table, starts, pos)
    return tuple(_flush((k_pool, v_pool), (tail_k, tail_v), page_table, starts, pos,
                        interpret))


def latent_flush(
    pool: jax.Array,  # (L, P, ps, D): one latent entry a token (models/mla.py)
    tail: jax.Array,  # (L, B, T, D), in the pool's dtype
    page_table: jax.Array,  # (B, maxp) int32
    starts: jax.Array,
    pos: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """``kv_flush`` for a latent page pool: the same kernel over ONE pool with
    one "head" (a bitcast of the pool and the tail: no copy)."""
    (out,) = _flush((pool[:, :, None],), (tail[:, :, None],), page_table, starts, pos,
                    interpret)
    return out[:, :, 0]


def _flush(pools, tails, page_table, starts, pos, interpret):
    if interpret is None:
        interpret = interpret_default()
    k_pool, tail_k = pools[0], tails[0]
    n = len(pools)
    n_layers, _, kv_heads, ps, d = k_pool.shape
    n_slots, t_len = tail_k.shape[1], tail_k.shape[3]
    maxp = page_table.shape[1]
    # one tile of the pool's dtype: 8 sublanes of 32 bits
    w = math.gcd(ps, 32 // k_pool.dtype.itemsize)
    n_w = -(-(t_len - 1) // w) + 1

    def window_map(ib, il, iw, tab, st, pos):
        base = (st[ib] // w + iw) * w
        touched = (base < pos[ib]) & (st[ib] < pos[ib])
        page = tab[ib, jnp.minimum(base // ps, maxp - 1)]
        return (jnp.where(touched, il, 0), jnp.where(touched, page, 0), 0,
                jnp.where(touched, base % ps // w, 0), 0)

    def tail_map(ib, il, iw, tab, st, pos):
        live = st[ib] < pos[ib]
        return (jnp.where(live, il, 0), jnp.where(live, ib, 0), 0, 0, 0)

    window = pl.BlockSpec((1, 1, kv_heads, w, d), window_map)
    tail = pl.BlockSpec((1, 1, kv_heads, t_len, d), tail_map)
    return pl.pallas_call(
        functools.partial(_kv_flush_kernel, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots, n_layers, n_w),
            in_specs=[tail] * n + [window] * n,
            out_specs=[window] * n,
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands count the scalar-prefetch ones: 3 + the tails + the pools
        input_output_aliases={3 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="kv_flush",
    )(page_table, starts, pos, *tails, *pools)
