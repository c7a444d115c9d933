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
(``ssd_step``, grid over the slots) reads a LIVE row's state once, updates
it, reads it out and writes it once; a dead row's grid step is redirected to
the block of the live row before it, and consecutive identical block indices
move nothing (``ops/kv_flush.py`` has the same trick with a sentinel).
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


def _ssd_step_kernel(layer, rows, alive, dec, dtx_ref, b_ref, c_ref, s_ref,
                     y_ref, o_ref):
    """One slot a grid step. dec: (B, H) float32 in scalar memory, a head's
    decay a scalar; dtx_ref: (1, P, H), the row's ``dt x`` with the heads on
    the lanes, so that a head's column broadcasts over its (P, N) tile; b_ref
    / c_ref: (1, 1, N); s_ref / o_ref: (1, 1, H, P, N), the same block of the
    aliased stack; y_ref: (1, P, H)."""
    del layer, rows  # the index maps' alone
    i = pl.program_id(0)
    heads = s_ref.shape[2]

    @pl.when(alive[i] != 0)
    def _():
        bvec, cvec = b_ref[0], c_ref[0]  # (1, N)
        dtx = dtx_ref[0]  # (P, H)
        lane = jax.lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
        y = jnp.zeros(dtx.shape, F32)
        for h in range(heads):
            new = s_ref[0, 0, h] * dec[i, h] + dtx[:, h:h + 1] * bvec
            o_ref[0, 0, h] = new
            y = jnp.where(lane == h, jnp.sum(new * cvec, axis=1, keepdims=True), y)
        y_ref[0] = y

    # Grid step 0 of a dead row 0 is its blocks' first visit, and if no row
    # is live at all their only one: what is written back has to be what was
    # read. (Any later dead step sits on a block a live step has written.)
    @pl.when((alive[i] == 0) & (i == 0))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssd_step_rows(stack, at, x, dt, a, bvec, cvec, alive, *,
                  interpret: bool | None = None):
    """One token of every slot, on mixer ``at``'s entry of the stacked state
    ``stack`` (n_mixers, B, H, P, N) float32, in place (donate it). x: (B, H,
    P); dt: (B, H) float32; alive: (B,) bool, the rows whose state moves (a
    dead row's is neither read nor written; its dt has to be 0); a: (H,);
    bvec, cvec: (B, N). Returns ``(y (B, H, P) float32, 0 for a dead row; the
    stack)``. Off the TPU the plain form runs."""
    if interpret is None and interpret_default():
        y, new = ssd_step(jax.lax.dynamic_index_in_dim(stack, at, keepdims=False),
                          x, dt, a, bvec, cvec)
        return y, jax.lax.dynamic_update_index_in_dim(stack, new, at, 0)
    _, n_b, h, p, n = stack.shape
    idx = jnp.arange(n_b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(alive, idx, -1))  # the last live row so far
    rows = jnp.where(before >= 0, before, jnp.argmax(alive).astype(jnp.int32))
    dtx = jnp.swapaxes(dt[..., None] * x.astype(F32), 1, 2)  # (B, P, H)

    def row(i, layer, rows, alive, dec):
        return (rows[i], 0, 0)

    def entry(i, layer, rows, alive, dec):
        return (layer[0], rows[i], 0, 0, 0)

    per_head = pl.BlockSpec((1, p, h), row)
    group = pl.BlockSpec((1, 1, n), row)
    state = pl.BlockSpec((1, 1, h, p, n), entry)
    y, stack = pl.pallas_call(
        _ssd_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_b,),
            in_specs=[per_head, group, group, state],
            out_specs=[per_head, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_b, p, h), F32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # operands count the scalar-prefetch ones: the stack is the eighth
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        name="ssd_step",
    )(jnp.reshape(at, (1,)).astype(jnp.int32), rows, alive.astype(jnp.int32),
      jnp.exp(dt * a), dtx, bvec.astype(F32)[:, None], cvec.astype(F32)[:, None], stack)
    return jnp.where(alive[:, None, None], jnp.swapaxes(y, 1, 2), 0.0), stack


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
