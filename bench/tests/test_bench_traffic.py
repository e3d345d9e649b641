"""The generators the benchmark owns, at small sizes on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness

ONE = SingleDeviceSharding(jax.devices()[0])


def gen(name):
    return harness.load_module("traffic", name)


def make(name, params, n, seed):
    out = gen(name).build(params, n=n, sharding=ONE)(harness.seed_key(seed))
    return {k: np.asarray(v) for k, v in out.items()}


def test_right_skewed_is_64_values_skewed_to_zero():
    out = make("paper_fig4", {"distribution": "right_skewed"}, 1 << 16, 11)
    k, v = out["keys"], out["values"]
    assert k.dtype == np.int32 and v.dtype == np.int32
    assert np.array_equal(v, np.arange(1 << 16))
    assert k.min() == 0 and k.max() == 63 and np.unique(k).size == 64
    # P(floor(u^6 * 64) = 0) = P(u < 1/2)
    assert abs(np.mean(k == 0) - 0.5) < 0.01
    assert np.mean(k < 8) > 0.7


@pytest.mark.parametrize("dist", ["uniform", "exponential"])
def test_other_fig4_distributions(dist):
    k = make("paper_fig4", {"distribution": dist}, 1 << 14, 5)["keys"]
    assert k.dtype == np.int32 and k.max() < np.iinfo(np.int32).max
    if dist == "uniform":
        assert np.unique(k).size > (1 << 14) - 4 and k.min() < 0 < k.max()
    else:
        assert k.min() == 0 and 20 < np.unique(k).size < 300


def test_unknown_distribution_fails_at_build():
    with pytest.raises(KeyError):
        gen("paper_fig4").build({"distribution": "zipf"}, n=8, sharding=ONE)


def test_same_seed_same_input_and_large_seeds():
    p = {"distribution": "right_skewed"}
    a = make("paper_fig4", p, 4096, 2**31 + 12345)["keys"]
    b = make("paper_fig4", p, 4096, 2**31 + 12345)["keys"]
    c = make("paper_fig4", p, 4096, 12345)["keys"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)


KRON = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def test_kronecker_quadrants_match_a_b_c_d():
    kr = gen("kronecker")
    ii, jj = kr.level_bits(jax.random.key(3), 1 << 18, 4, 0.57, 0.19, 0.19)
    q = np.bincount(np.asarray(ii * 2 + jj), minlength=4) / (1 << 18)
    assert np.allclose(q, [0.57, 0.19, 0.19, 0.05], atol=0.004)


def test_kronecker_edges_are_in_range_and_skewed():
    out = make("kronecker", KRON, 16 << 10, 7)
    src, dst = out["src"], out["dst"]
    assert src.dtype == np.int32 and src.shape == (16 << 10,)
    assert src.min() >= 0 and src.max() < 1 << 10
    assert dst.min() >= 0 and dst.max() < 1 << 10
    deg = np.bincount(src, minlength=1 << 10)
    assert deg.max() > 10 * deg.mean()      # power-law: heavy vertices
    assert (deg == 0).sum() > 100           # and many without out-edges


def test_kronecker_labels_are_permuted():
    kr = gen("kronecker")
    v = jnp.arange(1 << 12, dtype=jnp.uint32)
    c0, c1 = jnp.uint32(0x1234567), jnp.uint32(0x89ABCDE)
    s = np.asarray(kr.scramble(v, 12, c0, c1))
    assert np.array_equal(np.sort(s), np.arange(1 << 12))   # a bijection
    assert np.mean(s == np.arange(1 << 12)) < 0.01           # that moves labels
    # the heaviest vertex is not vertex 0, as it is before scrambling
    src = make("kronecker", KRON, 16 << 10, 7)["src"]
    assert np.argmax(np.bincount(src)) != 0


def test_kronecker_checks_its_sizes():
    with pytest.raises(ValueError):
        gen("kronecker").build(KRON, n=12345, sharding=ONE)
