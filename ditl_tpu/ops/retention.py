"""Power retention of degree 2 with a gate ("Scaling Context Requires
Rethinking Attention", arXiv:2507.04239), three forms of one equation, and
a decode step that mixes two of them.

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
``ret_step`` is one token of the recurrent form and ``ret_fold`` several
tokens into the state at once. State, gates, decays, powers and every sum
are float32 and every matmul of them runs at the highest precision: a state
lives for a sequence's whole length, and a product of two rounded products
is no feature map of anything.

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
and slot, in place. ``y_t`` needs all of ``S_(t-1)``, but an exact step need
not STORE ``S_t``: a decode tick of ``T`` steps (the serving loop's
``decode_chunk``) holds its tokens' ``k_j``, ``v_j`` and ``log g_j`` beside
the state (``HELD``: float32, zeros at the tick's start) and, with ``S0`` the
state as stored (as of the tick's start) and ``L_t`` the sum of the tick's
``log g`` up to and including step ``t``::

    numerator_t = exp(L_t) (S0 phi(q_t)) + sum_{j<=t} exp(L_t - L_j) (q_t . k_j)^2 v_j
    at t == T - 1, and there alone:
        S <- exp(L_t) S0 + sum_{j<=t} exp(L_t - L_j) v_j (outer) phi(k_j)

the recurrent form through the state the tick began with and the attention
form over the tick's own tokens: the same equation in another order of
float32 sums. A live row's state is READ once a step and WRITTEN once a tick.
Two Pallas kernels on the chip, chosen by a conditional on ``t``, whose grid
walks ``ssd.live_rows``' list (live rows only, a traced count), a kv head and
a block of ``VALUES_A_BLOCK`` value rows a step: ``ret_step_read`` reads its
block of ``S0`` once, reads it out for the group's query heads and writes
nothing back; ``ret_step``, the tick's last step, decays the block, grows it
by every held token's ``v (outer) phi(k)``, reads it out and writes it once.
The rows folded are those live at the LAST step: a row only ever ends inside
a tick, and the state of one that ended is never read again (a slot is
reseated from zeros). A step with no held tokens (``held=None``, a tick of
one step) is the last step of its own tick. ``z``, the denominators and the
held tokens' own sums are a 130th of the bytes and stay plain ``jax.numpy``
around the call.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default
from ditl_tpu.ops.names import RET_KERNELS
from ditl_tpu.ops.ssd import live_rows

__all__ = ["features", "phi", "ret_scan", "ret_fold", "ret_step", "ret_step_rows", "HELD"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BLOCKS = 8
# a tick's held tokens, a layer a row a kv head: ``k`` (after norm, rotation
# and ``d ** -0.25``), ``v`` and ``log g``, float32
HELD = ("hk", "hv", "hl")
# the kernels of ``ret_step_rows``: a tick's last step, and every other
FOLD_KERNEL, READ_KERNEL = RET_KERNELS
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


def ret_fold(big, pks, vc, decay):
    """Tokens folded into the state together. big: (b, K, P, D) float32, the
    state before the first of them; pks: (T, b, K, D), each token's
    ``phi(k)``; vc: (T, b, K, P), each token's ``v`` times its decay from its
    own position to the last one's; decay: (b, K), the state's over all of
    them. Returns the state after the last one."""
    return big * decay[..., None, None] + jnp.einsum(
        "tbkp,tbkd->bkpd", vc.astype(F32), pks, precision=HIGHEST)


def ret_step(big, z, pq, pk, v, g, *, eps: float):
    """One token. big: (b, K, P, D) float32; z: (b, K, D); pq: (b, K, G, D)
    ``phi(q)``; pk: (b, K, D) ``phi(k)`` (0 = this row adds nothing); v: (b,
    K, P); g: (b, K) float32 (1 = this row's state stays). Returns ``(y (b,
    K, G, P) float32, new S, new z)``."""
    big = ret_fold(big, pk[None], v[None], g)
    z = z * g[..., None] + pk
    num = jnp.einsum("bkgd,bkpd->bkgp", pq, big, precision=HIGHEST)
    den = jnp.einsum("bkgd,bkd->bkg", pq, z, precision=HIGHEST)
    return num / (den + eps)[..., None], big, z


# The kernels' blocks leave their leading 1s out (``None`` in a BlockSpec),
# every access in their loops is slices alone, and the loop over the lane
# tiles is a ``fori_loop`` of ``TILES_A_TURN`` tiles a turn, not 72 copies of
# its body: what a process pays to TRACE and lower the decode program it
# pays at every start, whatever the compile cache holds, and inside the
# server that was 15-20 s for two kernels written out tile by tile with an
# integer index (each made an array where it is written: a host-to-device
# transfer of a scalar) in every access (PERF.md section 6, PR 57).
TILES_A_TURN = 8


def _read_out(tile_at, n_f, pq_ref, y_ref):
    """``tile_at(at)``: the (VB, lanes) tile of the state at lane slice
    ``at``. The features go by in tiles of ``lanes``: a tile's products with
    each query head's ``phi(q)`` (``pq_ref`` (G, D)) join that head's running
    tile; ONE sum over lanes a head ends the block, head ``h``'s numerators
    in lane ``h`` of ``y_ref`` (VB, lanes)."""
    n_g = pq_ref.shape[0]
    lt = y_ref.shape[-1]  # a lane tile: 128, or all the features where they are no multiple

    tiles = n_f // lt
    a_turn = TILES_A_TURN if tiles % TILES_A_TURN == 0 else tiles

    def turn(i, acc):
        for u in range(a_turn):  # (Mosaic's own unrolling is all of a loop or none)
            at = pl.ds(pl.multiple_of((i * a_turn + u) * lt, lt), lt)
            tile = tile_at(at)
            acc = tuple(a + tile * pq_ref[h:h + 1, at] for h, a in enumerate(acc))
        return acc

    acc = jax.lax.fori_loop(0, tiles // a_turn, turn, (jnp.zeros(y_ref.shape, F32),) * n_g)
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
    y = jnp.zeros(y_ref.shape, F32)
    for h in range(n_g):
        y = jnp.where(lane == h, jnp.sum(acc[h], axis=1, keepdims=True), y)
    y_ref[...] = y


def _ret_read_kernel(layer, rows, count, pq_ref, s_ref, y_ref):
    """A step that is not its tick's last: one LIVE row, one kv head, ``VB``
    value rows a grid step, READ and nothing else. pq_ref: (G, D); s_ref:
    (VB, D), the block of the stack as the tick's start left it; y_ref: (VB,
    lanes), ``S0 phi(q)``. The stack is no output: no decay, no grow, no
    store. (No live row at all: the walk's one step reads a block and writes
    a ``y`` that nobody keeps.)"""
    del layer, rows, count  # the index maps' alone
    _read_out(lambda at: s_ref[:, at], s_ref.shape[-1], pq_ref, y_ref)


def _ret_step_kernel(layer, rows, count, g, pq_ref, pk_ref, vb_ref, s_ref, y_ref, o_ref):
    """A tick's last step: one LIVE row, one kv head, ``VB`` value rows a grid
    step, the tick's ``T`` held tokens folded in and the result read out in
    ONE pass. g: (B, K) float32 in scalar memory, the state's decay over the
    tick; pq_ref: (G, D); pk_ref: (T, D), each held token's ``phi(k)``;
    vb_ref: (T, VB, lanes), each held token's values times their decay to the
    tick's end, over a tile's lanes; s_ref / o_ref: (VB, D), the same block
    of the aliased stack; y_ref: (VB, lanes), query head ``h``'s numerators
    in lane ``h``. A tile of the state is decayed, grown by every ``v
    phi(k)`` and written, and read out (``_read_out``)."""
    del layer  # the index maps' alone
    n_t = pk_ref.shape[0]
    decay = g[rows[pl.program_id(0)], pl.program_id(1)]

    @pl.when(count[0] > 0)
    def _():
        vbs = [vb_ref[j] for j in range(n_t)]

        def tile_at(at):
            new = s_ref[:, at] * decay
            for j in range(n_t):
                new = new + vbs[j] * pk_ref[j:j + 1, at]
            o_ref[:, at] = new
            return new

        _read_out(tile_at, s_ref.shape[-1], pq_ref, y_ref)

    # No live row at all: the walk's one step writes back what it read.
    @pl.when(count[0] == 0)
    def _():
        o_ref[...] = s_ref[...]


def _walk(stack, at, alive, pq, fold, *, interpret: bool):
    """A kernel over ``ssd.live_rows``' list of ``alive``, a kv head and
    ``VALUES_A_BLOCK`` value rows of layer ``at``'s entry of ``stack`` a grid
    step. ``fold`` None: ``ret_step_read``, which writes no state. Else
    ``(decay (B, K), pks (T, B, K, D), vc (T, B, K, P))`` as ``ret_fold``
    takes them: ``ret_step``, the stack aliased in place. Returns ``(S phi(q)
    (B, K, G, P), stack)``."""
    n_b, n_kv, n_g, n_f = pq.shape
    p = stack.shape[3]
    rows, count = live_rows(alive)
    vb = min(VALUES_A_BLOCK, p)
    lanes = 128 if n_f % 128 == 0 else n_f

    def head(i, h, j, layer, rows, *_):
        return (rows[i], h, 0, 0)

    def values(i, h, j, layer, rows, *_):
        return (rows[i], h, j, 0)

    def held_values(i, h, j, layer, rows, *_):
        return (rows[i], h, 0, j, 0)

    def entry(i, h, j, layer, rows, *_):
        return (layer[0], rows[i], h, j, 0)

    state = pl.BlockSpec((None, None, None, vb, n_f), entry)
    y_spec = pl.BlockSpec((None, None, vb, lanes), values)
    y_shape = jax.ShapeDtypeStruct((n_b, n_kv, p, lanes), F32)
    scalars = [jnp.reshape(at, (1,)).astype(jnp.int32), rows, jnp.reshape(count, (1,))]
    operands, in_specs = [pq], [pl.BlockSpec((None, None, n_g, n_f), head)]
    if fold is not None:
        decay, pks, vc = fold
        n_t = len(pks)
        scalars.append(decay)
        # a row's and a kv head's tokens together; each value over a tile's lanes
        operands += [jnp.moveaxis(pks, 0, 2), jnp.broadcast_to(
            jnp.moveaxis(vc, 0, 2)[..., None], (n_b, n_kv, n_t, p, lanes))]
        in_specs += [pl.BlockSpec((None, None, n_t, n_f), head),
                     pl.BlockSpec((None, None, n_t, vb, lanes), held_values)]
    out = pl.pallas_call(
        _ret_read_kernel if fold is None else _ret_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            # the first axis is as long as the traced count of live rows
            grid=(jnp.maximum(count, 1), n_kv, p // vb),
            in_specs=[*in_specs, state],
            out_specs=y_spec if fold is None else [y_spec, state],
        ),
        out_shape=y_shape if fold is None else [
            y_shape, jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # operands count the scalar-prefetch ones: the stack is the last
        input_output_aliases={} if fold is None else {len(scalars) + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=READ_KERNEL if fold is None else FOLD_KERNEL,
    )(*scalars, *operands, stack)
    num, stack = (out, stack) if fold is None else out
    return jnp.swapaxes(num[..., :n_g], 2, 3), stack


def ret_step_rows(stack, zstack, at, q, k, v, log_g, alive, *, eps: float, held=None,
                  t=None, interpret: bool | None = None):
    """One token of every slot, on layer ``at``'s entries of the stacked
    state ``stack`` (layers, B, K, P, D) and ``zstack`` (layers, B, K, D),
    float32, in place (donate them). q: (B, K, G, d), k: (B, K, d), float32,
    both times ``d ** -0.25``; v: (B, K, P); log_g: (B, K) float32, <= 0;
    alive: (B,) bool, the rows whose state moves (a dead row's is neither
    read nor written). ``held``: the tick's tokens beside the state (module
    docstring), ``{"hk": (layers, T, B, K, d), "hv": (layers, T, B, K, P),
    "hl": (layers, T, B, K)}`` float32, zeros at the tick's start, and ``t``
    this step's index in its tick: the token is held, the state is read, and
    at ``t == T - 1`` the rows live THEN are folded and written. None or
    empty: a tick of one step, every step folds. Returns ``(y (B, K, G, P) float32, 0
    for a dead row; stack; zstack; held)``. Off the TPU the plain forms run."""
    live = alive[:, None]
    q = q.astype(F32)
    k = jnp.where(live[..., None], k.astype(F32), 0.0)
    log_g = jnp.where(live, log_g, 0.0)
    pq, pk = phi(q), phi(k)
    z = (jax.lax.dynamic_index_in_dim(zstack, at, keepdims=False)
         * jnp.exp(log_g)[..., None] + pk)
    den = jnp.einsum("bkgd,bkd->bkg", pq, z, precision=HIGHEST)
    now = {"hk": k, "hv": v.astype(F32), "hl": log_g}
    if not held:
        ks, vs, lgs = (now[name][None] for name in HELD)
    else:
        held = {name: jax.lax.dynamic_update_slice(
            held[name], now[name][None, None], (at, t) + (0,) * now[name].ndim)
            for name in HELD}
        ks, vs, lgs = (jax.lax.dynamic_index_in_dim(held[name], at, keepdims=False)
                       for name in HELD)
    # L_j of the held tokens; a step still to come holds zeros: L_j = L_t there
    upto = jnp.cumsum(lgs, axis=0)
    total = upto[-1]  # (B, K): L_t
    since = jnp.exp(total - upto)  # (T, B, K): exp(L_t - L_j)
    plain = interpret is None and interpret_default()

    def read(stack):  # S0 through phi(q), the held tokens in the attention form
        if plain:
            was = jnp.einsum("bkgd,bkpd->bkgp", pq, jax.lax.dynamic_index_in_dim(
                stack, at, keepdims=False), precision=HIGHEST)
        else:
            was, _ = _walk(stack, at, alive, pq, None, interpret=bool(interpret))
        dots = jnp.einsum("bkgd,tbkd->bkgt", q, ks, precision=HIGHEST)
        a = dots * dots * jnp.moveaxis(since, 0, -1)[:, :, None]
        return (was * jnp.exp(total)[..., None, None]
                + jnp.einsum("bkgt,tbkp->bkgp", a, vs, precision=HIGHEST)), stack

    def fold(stack):  # the held tokens into S, and S through phi(q), in one pass
        pks, vc = phi(ks), vs * since[..., None]
        if plain:
            was = jax.lax.dynamic_index_in_dim(stack, at, keepdims=False)
            new = jnp.where(live[..., None, None], ret_fold(was, pks, vc, jnp.exp(total)), was)
            return (jnp.einsum("bkgd,bkpd->bkgp", pq, new, precision=HIGHEST),
                    jax.lax.dynamic_update_index_in_dim(stack, new, at, 0))
        return _walk(stack, at, alive, pq, (jnp.exp(total), pks, vc), interpret=bool(interpret))

    if len(ks) == 1:
        num, stack = fold(stack)
    else:
        num, stack = jax.lax.cond(t == len(ks) - 1, fold, read, stack)
    # a row that is not walked was never written: this is what defines it
    return (jnp.where(live[..., None, None], num / (den + eps)[..., None], 0.0), stack,
            jax.lax.dynamic_update_index_in_dim(zstack, z, at, 0), held)
