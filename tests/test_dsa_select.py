"""``models/dsa.py::top_indices`` against ``jax.lax.top_k`` as SETS (ISSUE 45):
the selection sorts nothing, returns positions in ascending order, and has to
choose exactly what the sort chose, ties and fills included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.models.dsa import LANES, Q_BLOCK, top_indices


def _random(rng, shape):
    return rng.standard_normal(shape).astype(np.float32)


def _quarters(rng, shape):  # a few dozen distinct values: every row's k-th score is tied
    return (np.round(rng.standard_normal(shape) * 4) / 4).astype(np.float32)


def _cut(rng, shape):  # each row valid up to a random length, some under k
    x = _random(rng, shape)
    lengths = rng.integers(0, shape[-1] + 1, shape[:-1])
    x[np.arange(shape[-1]) >= lengths[..., None]] = -np.inf
    return x


def _holes(rng, shape):  # invalid entries anywhere, as a packed row's other documents
    x = _quarters(rng, shape)
    x[rng.random(shape) < 0.8] = -np.inf
    return x


def _all_invalid(rng, shape):
    x = _random(rng, shape)
    x[0] = -np.inf
    return x


def _zeros(rng, shape):  # -0.0 sorts under 0.0, as top_k has it; negative scores
    x = -np.abs(_quarters(rng, shape))
    x[..., ::3] = -0.0
    x[..., 1::3] = 0.0
    return x


def _huge(rng, shape):  # the ends of the floats' range, both signs
    x = _random(rng, shape) * np.float32(1e30)
    x[..., ::5] = np.float32(3.4e38)
    x[..., 1::5] = np.float32(-3.4e38)
    x[..., 2::5] = np.float32(1e-45)  # a denormal
    return x


CASES = {
    "random": (_random, (4, 1000), 128),
    "heavy-ties": (_quarters, (4, 1000), 128),
    "cut-to-a-length": (_cut, (16, 1000), 128),
    "invalid-anywhere": (_holes, (8, 1000), 128),
    "a-row-of-nothing": (_all_invalid, (3, 1000), 128),
    "signed-zeros-and-negatives": (_zeros, (4, 700), 300),
    "range-ends": (_huge, (2, 640), 130),
    "n-not-a-multiple-of-the-chunk": (_quarters, (5, 3 * LANES + 1), 64),
    "n-is-k-plus-1": (_random, (3, 129), 128),
    "n-is-k": (_quarters, (3, 2 * LANES), 2 * LANES),
    "k-of-1": (_quarters, (4, 300), 1),
    "one-row": (_random, (1000,), 100),
    "queries-under-a-block": (_cut, (2, Q_BLOCK - 5, 400), 50),
    "queries-over-a-block-ragged": (_cut, (1, 2 * Q_BLOCK + 7, 400), 50),
    "two-rows-of-whole-blocks": (_quarters, (2, 2 * Q_BLOCK, 300), 40),
    "the-cells-decode-step": (_cut, (32, 33800), 2048),
}


@pytest.mark.parametrize("case", CASES)
def test_top_indices_is_top_ks_set_in_position_order(case):
    make, shape, k = CASES[case]
    scores = make(np.random.default_rng(sorted(CASES).index(case)), shape)
    idx, ok = jax.jit(lambda x: top_indices(x, k))(jnp.asarray(scores))
    assert idx.shape == ok.shape == (*shape[:-1], k)
    assert idx.dtype == jnp.int32 and ok.dtype == jnp.bool_
    idx, ok = np.asarray(idx), np.asarray(ok)
    want = np.sort(np.asarray(jax.lax.top_k(jnp.asarray(scores), k)[1]), axis=-1)
    np.testing.assert_array_equal(idx, want)  # the same set, position-ascending
    chosen = np.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_array_equal(ok, chosen > -np.inf)  # false exactly on the fill
    # the fill is the lowest invalid positions, and only where valid ones ran out
    invalid = scores == -np.inf
    short = (~invalid).sum(axis=-1) < k
    assert (ok.all(axis=-1) == ~short).all()
    fills = (~ok).sum(axis=-1)
    first_invalid = np.sort(np.where(invalid, np.arange(shape[-1]), shape[-1]), axis=-1)[..., :k]
    for row in np.ndindex(*shape[:-1]):
        np.testing.assert_array_equal(idx[row][~ok[row]], first_invalid[row][:fills[row]])
