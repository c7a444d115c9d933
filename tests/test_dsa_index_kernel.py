"""The lightning indexer's decode kernel (``ops/dsa_index.py``
``dsa_index_scores``) in interpret mode against the gather it replaces on the
TPU (``models/dsa.py`` ``paged_index_scores``), at small sizes: the scores of
every position a row still attends to, and the set ``top_indices`` makes of
them. What the kernel leaves unwritten (dead and ended rows, the columns past
a row's pages) is compared nowhere: the caller selects it away, as here."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ditl_tpu.models import dsa
from ditl_tpu.ops import dsa_index, names

PS, HI, DI, POOL = 16, 4, 128, 96
TOL = 1e-5  # of the largest score: float32 sums in another order


def _case(name):
    """(maxp, starts, lengths, listed or None, table's dead positions name
    page 0, page size). A step holds at most 4 pages here: ``maxp`` 12 gives
    G = 4, ``maxp`` 11 (a prime) G = 4 and a ragged last group of 3, ``maxp``
    9 G = 3. Pages of 256 make a group whole (8, 128) tiles, which the kernel
    writes as (chunks, 128); the smaller pages' groups are one row."""
    full = 12 * PS
    cases = {
        "all-rows-live": (12, [full, 150, 40, 100], [full + 1, 152, 41, 104], None, False),
        "dead-rows-in-the-middle-and-at-the-end":
            (12, [full, 70, 150, 90, 33], [full + 2, 0, 151, 91, 0],
             [True, False, True, True, False], False),
        "a-row-ended-inside-the-program":
            (12, [full, 70, 150], [full + 1, 0, 153], [True, True, True], False),
        "starts-zero": (12, [0, 100, 0], [1, 101, 3], None, False),
        "a-partial-last-page": (12, [PS * 5 + 3, PS * 8 + 15, 1], [PS * 5 + 4, PS * 9, 2],
                                None, False),
        "a-last-group-with-fewer-pages": (12, [PS * 5, PS * 9 + 1, PS * 2],
                                          [PS * 5 + 1, PS * 9 + 2, PS * 2 + 4], None, False),
        "maxp-not-a-multiple-of-the-group": (11, [11 * PS, 10 * PS + 5, 8 * PS, 30],
                                             [11 * PS + 1, 10 * PS + 6, 8 * PS + 2, 31],
                                             None, False),
        "a-smaller-group-that-divides": (9, [9 * PS, 7 * PS + 2, 3 * PS],
                                         [9 * PS + 1, 7 * PS + 3, 3 * PS + 1], None, False),
        "an-empty-list": (12, [full, 70], [0, 0], [False, False], False),
        "dead-positions-name-page-zero": (12, [PS * 5 + 3, full, 20], [PS * 5 + 4, full + 1, 0],
                                          [True, True, False], True),
        "groups-of-whole-output-tiles": (8, [8 * 256, 5 * 256 + 7, 300, 0],
                                         [8 * 256 + 2, 5 * 256 + 8, 0, 1], None, False, 256),
    }
    return (*cases[name], PS)[:6]


CASES = ["all-rows-live", "dead-rows-in-the-middle-and-at-the-end",
         "a-row-ended-inside-the-program", "starts-zero", "a-partial-last-page",
         "a-last-group-with-fewer-pages", "maxp-not-a-multiple-of-the-group",
         "a-smaller-group-that-divides", "an-empty-list", "dead-positions-name-page-zero",
         "groups-of-whole-output-tiles"]


@pytest.mark.parametrize("name", CASES)
def test_the_interpreted_kernel_equals_the_gather(name, monkeypatch):
    maxp, starts, lengths, listed, zero_dead, ps = _case(name)
    monkeypatch.setattr(dsa_index, "GROUP_TOKENS", 4 * ps)
    b = len(starts)
    rng = np.random.default_rng(len(name))
    qi = jnp.asarray(rng.standard_normal((b, HI, DI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((b, HI)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((POOL, ps, DI)), jnp.bfloat16)
    starts, lengths = np.asarray(starts, np.int32), np.asarray(lengths, np.int32)
    table = rng.integers(1, POOL, (b, maxp)).astype(np.int32)
    if zero_dead:  # as the engine's table: positions past a row's pages name page 0
        table[np.arange(maxp)[None, :] >= -(-starts[:, None] // ps)] = 0
    steps = None
    if listed is not None:
        steps = dsa_index.index_steps(jnp.asarray(starts), jnp.asarray(listed), page_size=ps,
                                      max_pages=maxp)
    got = jax.jit(lambda *a: dsa_index.dsa_index_scores(*a, steps=steps, interpret=True))(
        qi, w, pool, jnp.asarray(table), jnp.asarray(lengths), jnp.asarray(starts))
    want = dsa.paged_index_scores(qi, w, pool, jnp.asarray(table))
    assert got.shape == want.shape == (b, maxp * ps) and got.dtype == jnp.float32
    valid = np.arange(maxp * ps)[None, :] < np.minimum(starts, lengths)[:, None]
    got, want = np.where(valid, np.asarray(got), -np.inf), np.where(valid, np.asarray(want), -np.inf)
    tol = TOL * max(1.0, float(np.abs(want[valid]).max())) if valid.any() else 0.0
    assert np.allclose(got[valid], want[valid], rtol=0, atol=tol)
    # and the selection the caller makes of them, where no tie within the
    # tolerance decides the k-th place
    k = 8
    (idx_g, ok_g), (idx_w, ok_w) = (map(np.asarray, dsa.top_indices(jnp.asarray(x), k))
                                    for x in (got, want))
    for row in range(b):
        ordered = np.sort(want[row][valid[row]])[::-1]
        if len(ordered) > k and ordered[k - 1] - ordered[k] <= 2 * tol:
            continue
        assert set(idx_g[row][ok_g[row]]) == set(idx_w[row][ok_w[row]])
        assert ok_g[row].sum() == min(k, valid[row].sum())


def test_the_kernels_name_is_the_tables():
    assert names.DSA_KERNELS == ("dsa_index_scores",)
    z = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(lambda: dsa_index.dsa_index_scores(
        jnp.zeros((2, HI, DI), jnp.bfloat16), jnp.zeros((2, HI)),
        jnp.zeros((POOL, PS, DI), jnp.bfloat16), z(2, 12), z(2), z(2), interpret=True))()
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == list(names.DSA_KERNELS)


@pytest.mark.parametrize("maxp, ps, want", [(132, 256, 12), (12, 16, 12), (11, 16, 11),
                                            (131, 256, 12), (1024, 256, 8), (7, 2048, 1),
                                            (130, 256, 10), (2112, 16, 192)])
def test_pages_a_step(maxp, ps, want):
    """The largest divisor of ``maxp`` inside the budget, or the budget with
    a ragged last group where the divisors are small."""
    assert dsa_index.pages_a_step(maxp, ps) == want
