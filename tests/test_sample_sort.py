"""System behaviour of the PGX.D sample sort (virtual-processor form) +
hypothesis property tests on its invariants."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    SortConfig,
    SortLibrary,
    investigator_bounds,
    load_imbalance,
    naive_bounds,
    regular_sample,
    sample_sort_sim,
    select_splitters,
)
from repro.core import topk as topk_lib

CFG = SortConfig(tile=256, capacity_factor=1.5)
LIB = SortLibrary(CFG)


def _run_and_flatten(x):
    r = LIB.sort(x)
    assert not bool(r.overflowed)
    parts = [np.asarray(r.values[i][: int(r.counts[i])]) for i in range(x.shape[0])]
    return np.concatenate(parts), r


DISTS = {
    "uniform": lambda rng, p, n: rng.uniform(0, 1, (p, n)).astype(np.float32),
    "normal": lambda rng, p, n: rng.normal(0, 1, (p, n)).astype(np.float32),
    "right_skewed": lambda rng, p, n: (rng.uniform(0, 1, (p, n)) ** 6 * 50).astype(np.int32),
    "exponential": lambda rng, p, n: np.floor(rng.exponential(1.0, (p, n)) * 4).astype(np.float32),
    "all_equal": lambda rng, p, n: np.full((p, n), 3, np.int32),
}


@pytest.mark.parametrize("dist", list(DISTS))
def test_sorts_correctly_all_distributions(dist):
    rng = np.random.default_rng(7)
    x = jnp.asarray(DISTS[dist](rng, 8, 4096))
    got, r = _run_and_flatten(x)
    np.testing.assert_array_equal(got, np.sort(np.asarray(x).reshape(-1)))


@pytest.mark.parametrize("dist", list(DISTS))
def test_load_balance_table2(dist):
    """Paper Table II: balanced shards for every distribution, including
    heavy duplication. Tolerance reflects splitter sampling noise at this
    small size (4k keys/proc; the paper runs 100M/proc — benchmarks at
    131k/proc land 1.001-1.009)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(DISTS[dist](rng, 8, 4096))
    _, r = _run_and_flatten(x)
    assert float(load_imbalance(r.counts)) < 1.06


def test_investigator_beats_naive_on_duplicates():
    """Paper Fig. 3b vs 3c: naive binary search starves processors under
    duplication; the investigator divides tied ranges equally."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 5, (8, 4096)), jnp.int32)
    inv = SortLibrary(CFG).sort(x)
    naive = SortLibrary(dataclasses.replace(CFG, capacity_factor=16.0),
                        investigator=False).sort(x)
    assert float(load_imbalance(inv.counts)) < 1.01
    assert float(load_imbalance(naive.counts)) > 1.3
    assert int(naive.counts.min()) == 0  # starved processors (Fig. 3b)


def test_regular_sample_strides_past_int32():
    """A 2^23-key shard with 2048 samples: the stride products pass 2^31.
    Wrapped indices sampled the wrong keys, so splitters went bad and a
    2^26-key sort ran ~4.5x imbalanced through two capacity retries."""
    n, s = 1 << 23, 2048
    got = np.asarray(regular_sample(jnp.arange(n, dtype=jnp.int32), s))
    want = ((2 * np.arange(s, dtype=np.int64) + 1) * n) // (2 * s)
    np.testing.assert_array_equal(got, want)


def test_order_across_processors():
    """Smaller data on smaller processor id (paper Table III)."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.uniform(0, 100, (4, 1024)), jnp.float32)
    r = LIB.sort(x)
    maxes = [float(r.values[i][int(r.counts[i]) - 1]) for i in range(4)]
    mins = [float(r.values[i][0]) for i in range(4)]
    for i in range(3):
        assert maxes[i] <= mins[i + 1]


def test_overflow_detected_not_silent():
    cfg = dataclasses.replace(CFG, capacity_factor=0.01)
    # adversarial: all data identical on one processor's range but capacity
    # tiny -> must flag, not drop silently
    x = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (8, 4096)), jnp.float32)
    r = sample_sort_sim(x, cfg)
    assert bool(r.overflowed)


def test_provenance_permutation_and_key_match():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, 6, (4, 512)), jnp.int32)
    r = LIB.sort_with_provenance(x)
    assert not bool(r.overflowed)
    flat = np.asarray(x).reshape(-1)
    ks = np.concatenate([np.asarray(r.keys[i][: int(r.counts[i])]) for i in range(4)])
    vs = np.concatenate([np.asarray(r.values[i][: int(r.counts[i])]) for i in range(4)])
    np.testing.assert_array_equal(ks, np.sort(flat))
    np.testing.assert_array_equal(np.sort(vs), np.arange(flat.size))
    np.testing.assert_array_equal(flat[vs], ks)


def test_sort_many():
    rng = np.random.default_rng(1)
    arrays = [jnp.asarray(rng.uniform(0, 1, (4, 256)), jnp.float32) for _ in range(3)]
    rs = LIB.sort_many(arrays)
    for a, r in zip(arrays, rs):
        got = np.concatenate(
            [np.asarray(r.values[i][: int(r.counts[i])]) for i in range(4)]
        )
        np.testing.assert_array_equal(got, np.sort(np.asarray(a).reshape(-1)))


def test_searchsorted_api():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.uniform(0, 1, (4, 1024)), jnp.float32)
    r = LIB.sort(x)
    q = jnp.asarray([0.0, 0.5, 0.999], jnp.float32)
    proc, loc = LIB.searchsorted(r, q)
    flat = np.sort(np.asarray(x).reshape(-1))
    ranks = np.searchsorted(flat, np.asarray(q))
    starts = np.concatenate([[0], np.cumsum(np.asarray(r.counts))[:-1]])
    np.testing.assert_array_equal(np.asarray(proc), np.searchsorted(
        np.cumsum(np.asarray(r.counts)), ranks, side="right").clip(0, 3))
    np.testing.assert_array_equal(np.asarray(loc), ranks - starts[np.asarray(proc)])


def test_topk():
    rng = np.random.default_rng(4)
    x = rng.uniform(0, 1, 4096).astype(np.float32)
    v, i = topk_lib.local_topk(jnp.asarray(x), 10)
    np.testing.assert_allclose(np.asarray(v), np.sort(x)[-10:][::-1])


# ------------------------------------------------------- hypothesis props


@settings(max_examples=25, deadline=None)
@given(
    p=st.sampled_from([2, 4, 8]),
    n=st.integers(64, 512),
    n_distinct=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_sort_invariants(p, n, n_distinct, seed):
    """For arbitrary duplication levels: output is the sorted multiset,
    shards are ordered, and counts sum to the input size."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, n_distinct, (p, n)), jnp.int32)
    r = sample_sort_sim(x, dataclasses.replace(CFG, capacity_factor=2.5))
    assert not bool(r.overflowed)
    counts = np.asarray(r.counts)
    assert counts.sum() == p * n
    got = np.concatenate([np.asarray(r.values[i][: counts[i]]) for i in range(p)])
    np.testing.assert_array_equal(got, np.sort(np.asarray(x).reshape(-1)))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(32, 512),
    m=st.integers(1, 15),
    n_distinct=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_investigator_bounds(n, m, n_distinct, seed):
    """Bounds are monotone, in range, and respect key order: every element
    strictly below a splitter lands strictly before its boundary."""
    rng = np.random.default_rng(seed)
    xs = jnp.sort(jnp.asarray(rng.integers(0, n_distinct, n), jnp.int32))
    spl = jnp.sort(jnp.asarray(rng.integers(0, n_distinct, m), jnp.int32))
    b = np.asarray(investigator_bounds(xs, spl))
    assert b[0] == 0 and b[-1] == n
    assert (np.diff(b) >= 0).all()
    xs_np = np.asarray(xs)
    for j in range(m):
        L = np.searchsorted(xs_np, int(spl[j]), side="left")
        R = np.searchsorted(xs_np, int(spl[j]), side="right")
        assert L <= b[j + 1] <= R


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_balance_under_any_duplication(seed):
    rng = np.random.default_rng(seed)
    n_distinct = int(rng.integers(1, 6))
    x = jnp.asarray(rng.integers(0, n_distinct, (8, 2048)), jnp.int32)
    r = sample_sort_sim(x, CFG)
    assert not bool(r.overflowed)
    assert float(load_imbalance(r.counts)) < 1.1
