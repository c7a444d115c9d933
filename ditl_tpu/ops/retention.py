"""Power retention of degree 2 with a gate ("Scaling Context Requires
Rethinking Attention", arXiv:2507.04239), three forms of one equation.

For a kv head with its group's ``G`` query heads, head width ``d``, gate
``g_t`` in (0, 1) and ``G_t = sum_{s<=t} log g_s``::

    attention form:   a_ts = exp(G_t - G_s) (q_t . k_s)^2    (s <= t)
                      y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
    recurrent form:   S_t = g_t S_(t-1) + v_t (outer) phi(k_t)      (P, D)
                      z_t = g_t z_(t-1) + phi(k_t)                  (D,)
                      y_t = S_t phi(q_t) / (phi(q_t) . z_t + eps)

with ``phi(u) . phi(w) == (u . w)^2`` (``q`` and ``k`` arrive already times
``d ** -0.25``, so that the products are ``((q . k) / sqrt(d))^2``). ``phi``
is the symmetric second power in BLOCKS: the head's ``d`` values in
``BLOCKS`` runs of ``t = d / BLOCKS``, one ``t x t`` tile of products for
each of the 36 pairs of runs ``I <= J``, an off-diagonal tile times sqrt(2):
``D = 36 t^2`` features, 9,216 = 72 lanes of 128 at d = 128 (the distinct
products alone are 8,256, which is no whole number of lanes; the full square,
16,384, is twice the state and is not built). Where ``d`` is no multiple of
``BLOCKS`` a run is one value and ``D = d (d + 1) / 2`` exactly.

``ret_scan`` runs one call's tokens a chunk of queries at a time: inside the
call the attention form (no ``phi``), across calls the state, read through
``phi(q)`` and grown through ``phi(k)``; it takes an initial state and
returns the final one.
``ret_step`` is one token of the recurrent form. State, gates, decays, powers
and every sum are float32 and every matmul of them runs at the highest
precision: a state is rewritten every token, and a product of two rounded
products is no feature map of anything.

A position with ``log g == 0`` and ``k == 0`` leaves the state EXACTLY as it
was and weighs nothing in any later sum: a bucket's padding, a chunk's tail
and a tick's dead rows are masked so, by their inputs alone.

The STORED layout of a row's state is the one every form here computes in:
``S`` (K, P, D) with the head's ``P`` values on the sublanes and the features
on the lanes, ``z`` (K, D). With the features on the lanes ``phi(q)`` and
``phi(k)`` are lane rows that broadcast over the sublanes for nothing, and
the read-out is a sum over lanes that is folded ONCE a block (the running
products stay (VB, 128) tiles until the last lane tile). With the features on
the sublanes each of a row's six ``phi`` vectors would have to be turned into
a column first, 72 tile transposes each.

``ret_step_rows`` is the decode step over the STACKED state of every layer
and slot, in place, a Pallas kernel on the chip (``ret_step``): the grid
walks ``ssd.live_rows``' list (live rows only, a traced count), a kv head
and a block of ``VALUES_A_BLOCK`` value rows a step; a step reads its block
of ``S`` once, decays and grows it, reads it out for the group's query heads
and writes it once. ``z`` and the denominators are a 130th of the bytes and
stay plain ``jax.numpy`` in front of the call.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default
from ditl_tpu.ops.ssd import live_rows

__all__ = ["features", "phi", "ret_scan", "ret_step", "ret_step_rows"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BLOCKS = 8
# value rows of a head's state a grid step of the kernel takes: 32 rows x
# 9,216 features x 4 B = 1.1 MiB a block, and its G running (32, 128)
# products stay in vector registers
VALUES_A_BLOCK = 32


def _run(head_dim: int) -> int:
    return head_dim // BLOCKS if head_dim % BLOCKS == 0 else 1


def features(head_dim: int) -> int:
    """``D``: the features ``phi`` makes of a head of ``head_dim`` values."""
    t = _run(head_dim)
    n = head_dim // t
    return n * (n + 1) // 2 * t * t


def phi(u):
    """(..., d) -> (..., D) float32 with ``phi(u) . phi(w) == (u . w)^2``.
    Feature ``f`` is ``u[a(f)] u[b(f)] w(f)``; the two selections are matmuls
    with 0/1 matrices at the highest precision (exact: a float32 value is
    the sum of the three bfloat16 parts the passes multiply), which leave
    the features on the lanes as they come. Written as tiles of products,
    ``(..., 36, t, t)`` flattened, the compiler turns every tile's two small
    minor dimensions into lanes by copies: 0.5 ms a layer a decode step at
    the published widths, a fifth of the step (PERF.md section 6, PR 56)."""
    d = u.shape[-1]
    t = _run(d)
    i, j = np.triu_indices(d // t)
    # feature (pair p, x, y) is value t i_p + x times value t j_p + y
    a = (t * i[:, None, None] + np.arange(t)[None, :, None] + np.zeros((1, 1, t), int)).ravel()
    b = (t * j[:, None, None] + np.zeros((1, t, 1), int) + np.arange(t)[None, None, :]).ravel()
    weight = np.repeat(np.where(i == j, 1.0, math.sqrt(2.0)), t * t).astype(np.float32)
    rows = jnp.arange(d, dtype=jnp.int32)[:, None]
    u = u.astype(F32)
    pick = lambda at: jnp.einsum(  # noqa: E731
        "...d,df->...f", u, (rows == jnp.asarray(at, jnp.int32)[None, :]).astype(F32),
        precision=HIGHEST)
    return pick(a) * pick(b) * weight


def ret_scan(q, k, v, log_g, *, chunk: int, eps: float, state=None):
    """q: (b, s, K, G, d) float32, k: (b, s, K, d) float32, both times
    ``d ** -0.25``; v: (b, s, K, P); log_g: (b, s, K) float32, <= 0; state:
    ``(S (b, K, P, D), z (b, K, D))`` float32 or None (zeros). A masked
    position has ``k == 0`` and ``log_g == 0``. Returns ``(y (b, s, K, G, P)
    float32, final state)``.

    A block of ``chunk`` queries at a time: against every key of THIS call in
    the attention form (no ``phi``: at 9,216 features a head the square of a
    call's length is the cheaper side up to ~5,000 tokens), against what the
    sequences carried in through ``phi(q)`` and the state, and that only
    where a state carries a key at all (a sequence's first call does not: the
    branch is the device's, taken from ``z``); the block's own keys join the
    final state through ``phi(k)``, decayed to the call's end."""
    b, s, n_kv, n_g, d = q.shape
    c = min(chunk, s)
    pad = -s % c
    if pad:  # k = 0, log g = 0: the tail neither decays nor adds
        q, k, v, log_g = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                          for t in (q, k, v, log_g))
    n = s + pad
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)

    def blocks(t):  # (b, n, ...) -> (n / c, b, c, ...)
        return jnp.moveaxis(t.reshape(b, n // c, c, *t.shape[2:]), 1, 0)

    if state is None:
        n_f = features(d)
        state = (jnp.zeros((b, n_kv, v.shape[-1], n_f), F32), jnp.zeros((b, n_kv, n_f), F32))
    big, z = (t.astype(F32) for t in state)
    cs = jnp.cumsum(log_g, axis=1)  # (b, n, K), inclusive: G_t from the call's start
    to_key = jnp.moveaxis(cs, 1, 2)[..., None, :]  # (b, K, 1, n)
    carried = jnp.any(z != 0.0)
    at = jnp.arange(n, dtype=jnp.int32)

    def one(acc, ins):
        qc, kc, vc, gc, first = ins
        seen = (first + at[:c])[:, None] >= at[None, :]  # (c, n): keys up to the query
        decay = jnp.exp(jnp.where(seen, jnp.moveaxis(gc, 1, 2)[..., None] - to_key, -jnp.inf))
        dots = jnp.einsum("bikgd,bjkd->bkgij", qc, k, precision=HIGHEST)
        a = dots * dots * decay[:, :, None]  # (b, K, G, c, n)
        num = jnp.einsum("bkgij,bjkp->bikgp", a, v, precision=HIGHEST)
        den = jnp.moveaxis(a.sum(axis=-1), 3, 1)  # (b, c, K, G)

        def with_state(num, den):  # decayed from the call's start
            pq = phi(qc)  # (b, c, K, G, D)
            since = jnp.exp(gc)[..., None]  # (b, c, K, 1)
            return (num + jnp.einsum("bikgd,bkpd->bikgp", pq, big, precision=HIGHEST)
                    * since[..., None],
                    den + jnp.einsum("bikgd,bkd->bikg", pq, z, precision=HIGHEST) * since)

        num, den = jax.lax.cond(carried, with_state, lambda *nd: nd, num, den)
        # the block's keys in the call's last position's state
        pk = phi(kc)  # (b, c, K, D)
        to_end = jnp.exp(cs[:, -1:] - gc)  # (b, c, K)
        acc = (acc[0] + jnp.einsum("bjkp,bjkd->bkpd", vc * to_end[..., None], pk,
                                   precision=HIGHEST),
               acc[1] + jnp.einsum("bjk,bjkd->bkd", to_end, pk, precision=HIGHEST))
        return acc, num / (den + eps)[..., None]

    keep = jnp.exp(cs[:, -1])  # (b, K): what is left of the incoming state
    state, y = jax.lax.scan(
        one, (big * keep[..., None, None], z * keep[..., None]),
        (blocks(q), blocks(k), blocks(v), blocks(cs), at[::c]))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n, n_kv, n_g, -1)
    return y[:, :s], state


def ret_step(big, z, pq, pk, v, g, *, eps: float):
    """One token. big: (b, K, P, D) float32; z: (b, K, D); pq: (b, K, G, D)
    ``phi(q)``; pk: (b, K, D) ``phi(k)`` (0 = this row adds nothing); v: (b,
    K, P); g: (b, K) float32 (1 = this row's state stays). Returns ``(y (b,
    K, G, P) float32, new S, new z)``."""
    big = big * g[..., None, None] + v.astype(F32)[..., :, None] * pk[..., None, :]
    z = z * g[..., None] + pk
    num = jnp.einsum("bkgd,bkpd->bkgp", pq, big, precision=HIGHEST)
    den = jnp.einsum("bkgd,bkd->bkg", pq, z, precision=HIGHEST)
    return num / (den + eps)[..., None], big, z


def _ret_step_kernel(layer, rows, count, g, pq_ref, pk_ref, vb_ref, s_ref, y_ref, o_ref):
    """One LIVE row, one kv head, ``VB`` value rows a grid step. g: (B, K)
    float32 in scalar memory; pq_ref: (1, 1, G, D); pk_ref: (1, 1, 1, D);
    vb_ref: (1, 1, VB, lanes), each value over a tile's lanes; s_ref / o_ref: (1,
    1, 1, VB, D), the same block of the aliased stack; y_ref: (1, 1, VB, lanes),
    query head ``h``'s numerators in lane ``h``. The features go by in tiles
    of 128 lanes: a tile of the state is decayed, grown by ``v phi(k)`` and
    written, and its products with each query head's ``phi(q)`` join that
    head's running tile; ONE sum over lanes a head ends the block."""
    del layer  # the index maps' alone
    n_g, n_f = pq_ref.shape[2:]
    lt = vb_ref.shape[-1]  # a lane tile: 128, or all the features where they are no multiple
    decay = g[rows[pl.program_id(0)], pl.program_id(1)]

    @pl.when(count[0] > 0)
    def _():
        vb = vb_ref[0, 0]
        acc = [jnp.zeros(vb.shape, F32) for _ in range(n_g)]
        for t in range(n_f // lt):
            at = slice(t * lt, (t + 1) * lt)
            new = s_ref[0, 0, 0, :, at] * decay + vb * pk_ref[0, 0, :, at]
            o_ref[0, 0, 0, :, at] = new
            for h in range(n_g):
                acc[h] = acc[h] + new * pq_ref[0, 0, h:h + 1, at]
        lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[2:], 1)
        y = jnp.zeros(y_ref.shape[2:], F32)
        for h in range(n_g):
            y = jnp.where(lane == h, jnp.sum(acc[h], axis=1, keepdims=True), y)
        y_ref[0, 0] = y

    # No live row at all: the walk's one step writes back what it read.
    @pl.when(count[0] == 0)
    def _():
        o_ref[...] = s_ref[...]


def ret_step_rows(stack, zstack, at, q, k, v, g, alive, *, eps: float,
                  interpret: bool | None = None):
    """One token of every slot, on layer ``at``'s entries of the stacked
    state ``stack`` (layers, B, K, P, D) and ``zstack`` (layers, B, K, D),
    float32, in place (donate them). q: (B, K, G, d), k: (B, K, d), float32,
    both times ``d ** -0.25``; v: (B, K, P); g: (B, K) float32; alive: (B,)
    bool, the rows whose state moves (a dead row's is neither read nor
    written). Returns ``(y (B, K, G, P) float32, 0 for a dead row; stack;
    zstack)``. Off the TPU the plain form runs."""
    n_b, n_kv, n_g, _ = q.shape
    _, _, _, p, n_f = stack.shape
    live = alive[:, None]
    pq = phi(q)
    pk = jnp.where(live[..., None], phi(k), 0.0)
    g = jnp.where(live, g, 1.0)
    z = jax.lax.dynamic_index_in_dim(zstack, at, keepdims=False)
    if interpret is None and interpret_default():
        y, new, z = ret_step(jax.lax.dynamic_index_in_dim(stack, at, keepdims=False),
                             z, pq, pk, v, g, eps=eps)
        return (jnp.where(live[..., None, None], y, 0.0),
                jax.lax.dynamic_update_index_in_dim(stack, new, at, 0),
                jax.lax.dynamic_update_index_in_dim(zstack, z, at, 0))
    z = z * g[..., None] + pk
    den = jnp.einsum("bkgd,bkd->bkg", pq, z, precision=HIGHEST)
    rows, count = live_rows(alive)
    vb = min(VALUES_A_BLOCK, p)
    lanes = 128 if n_f % 128 == 0 else n_f

    def head(i, h, j, layer, rows, *_):
        return (rows[i], h, 0, 0)

    def values(i, h, j, layer, rows, *_):
        return (rows[i], h, j, 0)

    def entry(i, h, j, layer, rows, *_):
        return (layer[0], rows[i], h, j, 0)

    state = pl.BlockSpec((1, 1, 1, vb, n_f), entry)
    num, stack = pl.pallas_call(
        _ret_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # the first axis is as long as the traced count of live rows
            grid=(jnp.maximum(count, 1), n_kv, p // vb),
            in_specs=[pl.BlockSpec((1, 1, n_g, n_f), head),
                      pl.BlockSpec((1, 1, 1, n_f), head),
                      pl.BlockSpec((1, 1, vb, lanes), values), state],
            out_specs=[pl.BlockSpec((1, 1, vb, lanes), values), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_b, n_kv, p, lanes), F32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # operands count the scalar-prefetch ones: the stack is the eighth
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=bool(interpret),
        name="ret_step",
    )(jnp.reshape(at, (1,)).astype(jnp.int32), rows, jnp.reshape(count, (1,)), g,
      pq, pk[:, :, None], jnp.broadcast_to(v.astype(F32)[..., None], (n_b, n_kv, p, lanes)),
      stack)
    y = jnp.swapaxes(num[..., :n_g], 2, 3) / (den + eps)[..., None]
    # a row that is not walked was never written: this is what defines it
    return (jnp.where(live[..., None, None], y, 0.0), stack,
            jax.lax.dynamic_update_index_in_dim(zstack, z, at, 0))
