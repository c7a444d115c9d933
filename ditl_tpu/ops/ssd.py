"""The Mamba-2 recurrence (state-space duality), two forms of one equation.

For a head with scalar decay rate ``a < 0``, step size ``dt_t > 0``, input
``x_t`` (P,), and the group's ``B_t``, ``C_t`` (N,)::

    S_t = exp(dt_t a) S_(t-1) + dt_t x_t (outer) B_t        S: (P, N)
    y_t = S_t C_t

``ssd_scan`` runs a whole sequence in chunks (inside a chunk the recurrence
unrolls into a masked (Q, Q) matmul, between chunks the state is carried by
a ``lax.scan``), takes an initial state and returns the final one.
``ssd_step`` is one token of a decode step. Both keep the state, the step
sizes, the decays and every sum in float32: a state is rewritten every token,
so its rounding accumulates where a page's does not.

A position with ``dt == 0`` leaves the state EXACTLY as it was (decay
``exp(0) = 1``, update 0) and a state is never read at such a position by a
later real one other than through that identity: a prefill bucket's padding,
a chunk's tail and a decode tick's dead rows are masked by their step size
alone, with no ``where`` over the state.

``doc`` (a whole sequence only): a per-position document count that does not
fall; a position reads state and inputs only from positions with ITS count,
so a packed row's documents each start from ``S = 0`` (``doc`` starts at 0,
the count the initial state belongs to).

``ssd_step_rows`` is the decode step over the STACKED state of every mixer
and slot, in place, and is a Pallas kernel on the chip. Written plainly (a
slice of the stack, ``ssd_step``, a ``dynamic_update_slice``) the compiler
makes two fusions of it, the read-out ``S C`` and the write back, each
reading all 64 slots' state whether a slot is live or not: two reads and a
write of 128 MiB a mixer a step, 14.5 GB a step over 36 mixers beside 6.4 GB
of weights (seen in the program compiled for a described v5e). The kernel
(``ssd_step``) reads a LIVE row's state once, updates it, reads it out and
writes it once.

The STORED layout of a row's state, which only this module and
``models/ssm.py`` know (``to_stored`` / ``from_stored``; ``ssd_scan`` and
``ssd_step`` keep ``(b, H, P, N)``): ``(H / hp, N, hp P)``, the state columns
``N`` on the sublanes and ``hp = 128 // P`` heads' ``P`` side by side on the
lanes (``heads_a_tile``, read off the shapes: 2 at P = 64; 1 where ``P`` does
not divide 128 or ``H`` is no multiple of it). In such a tile a head's ``dt
x`` and decay are LANE rows that broadcast over the sublanes for nothing,
``x`` goes in and ``y`` comes out as the row's flattened ``(H P)`` vector (no
transpose on either side of the call), the group's ``B`` and ``C`` become a
value a sublane ONCE a row, and the read-out ``S C`` is a sum over sublanes
(vreg adds and one fold). With the state columns on the lanes instead, every
head pays a lane broadcast and a lane reduction of its own and the step runs
at half the pace of its bytes (PERF.md section 6, PR 55).

The walk: the grid's ONE axis is as long as the traced count of live rows
(one step where there is none, which writes back what it read), over
``live_rows``' compacted list of their indices in scalar memory, as
``ops/paged_attention.py``'s walk over ``decode_steps``. A dead row is not
visited: its state is not moved and its ``y`` is memory nobody wrote, which
the ``where`` behind the call defines as 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ditl_tpu.ops.backend import interpret_default

__all__ = ["ssd_scan", "ssd_step", "ssd_step_rows", "causal_conv", "conv_step"]

F32 = jnp.float32


def ssd_scan(x, dt, a, bmat, cmat, *, chunk: int, state=None, doc=None):
    """x: (b, s, H, P); dt: (b, s, H) float32; a: (H,) float32, negative;
    bmat, cmat: (b, s, N); state: (b, H, P, N) float32 or None (zeros);
    doc: (b, s) int32 or None. Returns ``(y (b, s, H, P) float32, final
    state (b, H, P, N) float32)``."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    pad = -s % q
    if doc is None:
        doc = jnp.zeros((b, s), jnp.int32)
    if pad:  # dt = 0: the tail neither decays nor updates
        x, dt, bmat, cmat = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                             for t in (x, dt, bmat, cmat))
        doc = jnp.pad(doc, [(0, 0), (0, pad)], mode="edge")
    nc = (s + pad) // q

    def chunks(t):  # (b, nc * q, ...) -> (nc, b, q, ...)
        return jnp.moveaxis(t.reshape(b, nc, q, *t.shape[2:]), 1, 0)

    if state is None:
        state = jnp.zeros((b, h, p, n), F32)
    tril = jnp.tril(jnp.ones((q, q), bool))

    def one(carry, ins):
        st, d_in = carry  # (b, H, P, N), (b,): the count the state belongs to
        xc, dtc, bc, cc, dc = ins
        xc, bc, cc = xc.astype(F32), bc.astype(F32), cc.astype(F32)
        cs = jnp.cumsum(dtc * a, axis=1)  # (b, q, H), inclusive log-decay
        same = tril & (dc[:, :, None] == dc[:, None, :])  # (b, q, q)
        seg = cs[:, :, None, :] - cs[:, None, :, :]  # (b, q, q, H): i after j
        decay = jnp.exp(jnp.where(same[..., None], seg, -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", cc, bc)
        xdt = xc * dtc[..., None]  # (b, q, H, P)
        y = jnp.einsum("bijh,bjhp->bihp", decay * cb[..., None], xdt)
        # what the incoming state adds, for the positions of its document
        cont = (dc == d_in[:, None])[..., None]  # (b, q, 1)
        y = y + jnp.einsum("bin,bhpn->bihp", cc, st) * (
            jnp.exp(cs) * cont)[..., None]
        # the chunk's last position's state
        tail = (dc == dc[:, -1:])[..., None]  # (b, q, 1)
        to_end = jnp.exp(cs[:, -1:] - cs) * tail  # (b, q, H)
        new = jnp.einsum("bjhp,bjn->bhpn", xdt * to_end[..., None], bc)
        keep = jnp.exp(cs[:, -1]) * (dc[:, -1] == d_in)[:, None]  # (b, H)
        return (new + st * keep[..., None, None], dc[:, -1]), y

    (state, _), y = jax.lax.scan(
        one, (state.astype(F32), jnp.zeros((b,), jnp.int32)),
        tuple(chunks(t) for t in (x, dt, bmat, cmat, doc)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * q, h, p)
    return y[:, :s], state


def ssd_step(state, x, dt, a, bvec, cvec):
    """One token. state: (b, H, P, N) float32; x: (b, H, P); dt: (b, H)
    float32 (0 = this row's state stays); a: (H,); bvec, cvec: (b, N).
    Returns ``(y (b, H, P) float32, new state)``."""
    decay = jnp.exp(dt * a)[..., None, None]
    upd = (dt[..., None] * x.astype(F32))[..., None] * bvec.astype(F32)[:, None, None, :]
    new = state * decay + upd
    return jnp.einsum("bhpn,bn->bhp", new, cvec.astype(F32)), new


def heads_a_tile(heads: int, head_dim: int) -> int:
    """Heads whose ``P`` columns lie side by side on a stored tile's lanes:
    as many as fill 128 lanes (2 at P = 64), read off the shapes alone; one
    (a head a tile) where ``P`` does not divide 128 or ``heads`` is no
    multiple of that many."""
    hp = max(1, 128 // head_dim)
    return hp if 128 % head_dim == 0 and heads % hp == 0 else 1


def stored_shape(heads: int, head_dim: int, n: int) -> tuple[int, int, int]:
    """A row's state as it is STORED (module docstring): ``(H / hp, N, hp P)``."""
    hp = heads_a_tile(heads, head_dim)
    return heads // hp, n, hp * head_dim


def to_stored(state):
    """(..., H, P, N) -> (..., H / hp, N, hp P): a tile's heads side by side
    on the lanes, the state columns on the sublanes."""
    *lead, h, p, n = state.shape
    hp = heads_a_tile(h, p)
    tiles = state.reshape(*lead, h // hp, hp, p, n)
    return jnp.moveaxis(tiles, -1, -3).reshape(*lead, h // hp, n, hp * p)


def from_stored(stored, heads: int):
    """``to_stored``'s inverse; ``heads`` is H (the stored shape holds only
    H / hp and hp P)."""
    *lead, tiles, n, lanes = stored.shape
    hp = heads // tiles
    split = stored.reshape(*lead, tiles, n, hp, lanes // hp)
    return jnp.moveaxis(split, -3, -1).reshape(*lead, heads, lanes // hp, n)


def _ssd_step_kernel(layer, rows, count, dt, dec, x_ref, b_ref, c_ref, s_ref,
                     y_ref, o_ref):
    """One LIVE row a grid step: row ``rows[i]``. dt, dec: (B, H) float32 in
    scalar memory, a head's step size and decay; x_ref / y_ref: (1, H / hp,
    hp P), the row's flattened ``(H P)`` vector a tile a sublane row; b_ref /
    c_ref: (1, 1, N); s_ref / o_ref: (1, 1, H / hp, N, hp P), the same block of
    the aliased stack. In a tile everything a head owns is a lane row that
    broadcasts over the sublanes, the group's ``B`` and ``C`` are a value a
    sublane (made once a row) and the read-out is a sum over sublanes."""
    del layer  # the index maps' alone
    r = rows[pl.program_id(0)]
    _, _, tiles, n, lanes = s_ref.shape
    hp = dt.shape[1] // tiles
    p = lanes // hp

    @pl.when(count[0] > 0)
    def _():
        # (1, N) on the lanes -> (N, lanes), N on the sublanes
        bb = jnp.broadcast_to(b_ref[0], (lanes, n)).T
        cc = jnp.broadcast_to(c_ref[0], (lanes, n)).T
        head = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // p

        def of_lane(scalars, j):  # the tile's hp scalars, each over its P lanes
            row = jnp.full((1, lanes), scalars[r, j * hp], F32)
            for k in range(1, hp):
                row = jnp.where(head == k, scalars[r, j * hp + k], row)
            return row

        for j in range(tiles):
            dtx = of_lane(dt, j) * x_ref[0, j:j + 1, :]
            new = s_ref[0, 0, j] * of_lane(dec, j) + bb * dtx
            o_ref[0, 0, j] = new
            y_ref[0, j:j + 1, :] = jnp.sum(new * cc, axis=0, keepdims=True)

    # No live row at all: the walk's one step writes back what it read.
    @pl.when(count[0] == 0)
    def _():
        o_ref[...] = s_ref[...]


def live_rows(alive):
    """The walk's list: ``(rows (B,) int32, the live rows' indices in order,
    then B - 1 repeated; count () int32)``. One cumulative sum and one
    comparison a (step, row), as ``paged_attention.decode_steps``."""
    b = alive.shape[0]
    ends = jnp.cumsum(alive.astype(jnp.int32))  # live rows up to and with each row
    i = jnp.arange(b, dtype=jnp.int32)
    rows = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), b - 1)
    return rows.astype(jnp.int32), ends[-1]


def ssd_step_rows(stack, at, x, dt, a, bvec, cvec, alive, *,
                  interpret: bool | None = None):
    """One token of every slot, on mixer ``at``'s entry of the stacked state
    ``stack`` (n_mixers, B, H / hp, N, hp P) float32 in the STORED layout
    (module docstring), in place (donate it). x: (B, H P), as it leaves the
    convolution; dt: (B, H) float32; alive: (B,) bool, the rows whose state
    moves (a dead row's is neither read nor written; its dt has to be 0); a:
    (H,); bvec, cvec: (B, N). Returns ``(y (B, H P) float32, 0 for a dead
    row; the stack)``. Off the TPU the plain form runs."""
    n_b, h = dt.shape
    _, _, tiles, n, lanes = stack.shape
    if interpret is None and interpret_default():
        y, new = ssd_step(
            from_stored(jax.lax.dynamic_index_in_dim(stack, at, keepdims=False), h),
            x.reshape(n_b, h, -1), dt, a, bvec, cvec)
        return y.reshape(n_b, -1), jax.lax.dynamic_update_index_in_dim(
            stack, to_stored(new), at, 0)
    rows, count = live_rows(alive)

    def row(i, layer, rows, *_):
        return (rows[i], 0, 0)

    def entry(i, layer, rows, *_):
        return (layer[0], rows[i], 0, 0, 0)

    per_head = pl.BlockSpec((1, tiles, lanes), row)
    group = pl.BlockSpec((1, 1, n), row)
    state = pl.BlockSpec((1, 1, tiles, n, lanes), entry)
    y, stack = pl.pallas_call(
        _ssd_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # ONE axis whose length is the traced count of live rows
            grid=(jnp.maximum(count, 1),),
            in_specs=[per_head, group, group, state],
            out_specs=[per_head, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_b, tiles, lanes), F32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # operands count the scalar-prefetch ones: the stack is the ninth
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        name="ssd_step",
    )(jnp.reshape(at, (1,)).astype(jnp.int32), rows, jnp.reshape(count, (1,)),
      dt, jnp.exp(dt * a), x.astype(F32).reshape(n_b, tiles, lanes),
      bvec.astype(F32)[:, None], cvec.astype(F32)[:, None], stack)
    # a row that is not walked was never written: this is what defines it
    return jnp.where(alive[:, None], y.reshape(n_b, -1), 0.0), stack


def causal_conv(u, w, bias, *, conv=None, doc=None, lengths=None):
    """Causal depthwise convolution over time, then the bias. u: (b, s, C)
    pre-activation columns; w: (K, C) (tap K - 1 weighs the token itself);
    conv: (b, K - 1, C), the columns before position 0, or None (zeros);
    doc as ``ssd_scan``'s; lengths: (b,) real tokens a row, or None (s).
    Returns ``(out (b, s, C) float32, the last K - 1 columns up to each
    row's last real token (b, K - 1, C) in u's dtype)``."""
    b, s, c = u.shape
    k = w.shape[0]
    if conv is None:
        conv = jnp.zeros((b, k - 1, c), u.dtype)
    ext = jnp.concatenate([conv.astype(u.dtype), u], axis=1)  # (b, s + K - 1, C)
    out = jnp.zeros((b, s, c), F32) + bias.astype(F32)
    if doc is not None:
        doc_ext = jnp.concatenate([jnp.zeros((b, k - 1), doc.dtype), doc], axis=1)
    for j in range(k):  # tap j reads the column K - 1 - j tokens back
        term = ext[:, j:j + s].astype(F32) * w[j].astype(F32)
        if doc is not None:
            term = jnp.where((doc_ext[:, j:j + s] == doc)[..., None], term, 0.0)
        out = out + term
    if lengths is None:
        return out, ext[:, s:]
    last = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, k - 1, axis=0))(
        ext, lengths)
    return out, last


def conv_step(conv, u, w, bias):
    """One token. conv: (K - 1, b, C), the taps OUTERMOST (a stack whose
    last two dimensions are (3, C) is tiled with its 3 rows padded and in
    another order than its users read: the compiler copied the whole stack of
    windows around every use, 56 copies of 80 MB a decode step); u: (b, C);
    w: (K, C). Returns ``(out (b, C) float32, the window shifted by one
    column (K - 1, b, C))``."""
    window = jnp.concatenate([conv, u[None].astype(conv.dtype)], axis=0)
    # four products and a sum, elementwise: as an einsum the compiler made a
    # batched dot of it, (C, b) out and a transpose back, three programs for one
    out = bias.astype(F32) + sum(
        window[k].astype(F32) * w[k].astype(F32) for k in range(w.shape[0]))
    return out, window[1:]
