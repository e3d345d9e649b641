"""Per-kernel correctness sweeps: every Pallas kernel against the pure-jnp
oracle in repro.kernels.ref, across shapes and dtypes (interpret=True on
CPU executes the kernel bodies)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import merge as merge_lib
from repro.kernels import merge_path, ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    if dtype in (jnp.int32, jnp.uint32):
        hi = 1000 if dtype == jnp.int32 else 2**20
        return jnp.asarray(RNG.integers(0, hi, shape), dtype)
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("rows,n", [(1, 8), (4, 128), (8, 555), (16, 1024), (3, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.uint32, jnp.bfloat16])
def test_sort_rows_matches_ref(rows, n, dtype):
    k = _rand((rows, n), dtype)
    np.testing.assert_array_equal(
        np.asarray(ops.sort_rows(k)), np.asarray(ref.sort_rows_ref(k))
    )


@pytest.mark.parametrize("rows,n", [(2, 64), (8, 300), (4, 1024)])
@pytest.mark.parametrize("kdtype", [jnp.float32, jnp.int32])
def test_sort_rows_kv_stable(rows, n, kdtype):
    # few distinct keys -> heavy duplication; values = index -> stability
    keys = _rand((rows, n), jnp.int32) % 7
    keys = keys.astype(kdtype)
    vals = jnp.tile(jnp.arange(n, dtype=jnp.int32), (rows, 1))
    ok, ov = ops.sort_rows_kv(keys, vals, stable=True)
    rk, rv = ref.sort_rows_kv_ref(keys, vals, stable=True)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))


@pytest.mark.parametrize("rows,n", [(1, 64), (4, 256), (2, 1000)])
def test_merge_rows_matches_ref(rows, n):
    a = jnp.sort(_rand((rows, n), jnp.float32), axis=-1)
    b = jnp.sort(_rand((rows, n), jnp.float32), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(ops.merge_rows(a, b)), np.asarray(ref.merge_rows_ref(a, b))
    )


@pytest.mark.parametrize("n", [64, 500, 4096])
def test_merge_rows_kv_keys(n):
    ak = jnp.sort(_rand((3, n), jnp.int32) % 50, axis=-1)
    bk = jnp.sort(_rand((3, n), jnp.int32) % 50, axis=-1)
    av = _rand((3, n), jnp.int32)
    bv = _rand((3, n), jnp.int32)
    ok, _ = ops.merge_rows_kv(ak, av, bk, bv)
    rk, _ = ref.merge_rows_kv_ref(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))


@pytest.mark.parametrize("n,tile", [(100, 64), (5000, 512), (8192, 1024), (3, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_tile_sort_flat(n, tile, dtype):
    x = _rand((n,), dtype)
    np.testing.assert_array_equal(np.asarray(ops.tile_sort(x, tile=tile)),
                                  np.asarray(jnp.sort(x)))


@pytest.mark.parametrize("n,tile", [(1000, 128), (40000, 2048)])
def test_tile_sort_kv_stable_flat(n, tile):
    keys = _rand((n,), jnp.int32) % 16
    vals = jnp.arange(n, dtype=jnp.int32)
    sk, sv = ops.tile_sort_kv(keys, vals, tile=tile)
    rk, rv = ref.sort_rows_kv_ref(keys[None], vals[None], stable=True)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(rk[0]))
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv[0]))


@pytest.mark.parametrize("op", ["sort", "sort_kv", "merge"])
def test_partial_last_row_block(op):
    """12 rows = one full 8-row block + a partial one: rows 8-11 must be
    written too (the grid used to round the block count down)."""
    k = _rand((12, 16), jnp.int32)
    v = _rand((12, 16), jnp.int32)
    if op == "sort":
        got, want = ops.sort_rows(k), ref.sort_rows_ref(k)
    elif op == "sort_kv":
        got = ops.sort_rows_kv(k, jnp.tile(jnp.arange(16, dtype=jnp.int32), (12, 1)))
        want = ref.sort_rows_kv_ref(k, jnp.tile(jnp.arange(16, dtype=jnp.int32), (12, 1)))
    else:
        a, b = jnp.sort(k, axis=-1), jnp.sort(v, axis=-1)
        got, want = ops.merge_rows(a, b), ref.merge_rows_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_interpret_only_on_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


def test_kernel_path_names_64bit_fallback():
    assert ops.kernel_path(jnp.int32, jnp.bfloat16).startswith("Pallas")
    assert "(int64)" in ops.kernel_path(np.dtype("int64"))
    assert "(float64)" in ops.kernel_path(jnp.float32, np.dtype("float64"))
    import repro

    reasons = repro.plan(np.arange(8, dtype=np.int32)).reasons
    assert any("Pallas bitonic kernels" in r for r in reasons)
    kv = repro.plan(np.arange(8, dtype=np.int32), np.arange(8, dtype=np.int32)).reasons
    assert any("merge-path" in r for r in kv)


def test_lax_fallback_path_equivalence():
    x = _rand((6000,), jnp.float32)
    a = ops.tile_sort(x, tile=512, use_pallas=True)
    b = ops.tile_sort(x, tile=512, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sentinels():
    assert np.isposinf(float(ops.sentinel_for(jnp.float32)))
    assert int(ops.sentinel_for(jnp.int32)) == np.iinfo(np.int32).max


def _skewed_runs(procs, runs, width, dtype):
    """Sorted runs of the paper's right_skewed input (floor(u^6 * 64): 64
    distinct keys, about half of them 0) with all-sentinel tails on the
    first and last run, and random int32 payloads (not in run order, so
    only a stable merge reproduces them)."""
    keys = np.floor(RNG.random((procs, runs, width)) ** 6 * 64)
    keys = np.sort(keys - 8 if dtype == np.float32 else keys, axis=-1).astype(dtype)
    fill = np.asarray(ops.sentinel_for(jnp.dtype(dtype)))
    keys[:, 0, width // 2:] = fill
    keys[:, -1, width // 3:] = fill
    vals = RNG.integers(-2**31, 2**31 - 1, (procs, runs, width)).astype(np.int32)
    return jnp.asarray(keys), jnp.asarray(vals)


# runs wider than one bitonic merge row, at widths that are no multiple of
# the kernel's tile; 5 runs are padded to 8 with all-sentinel runs
@pytest.mark.parametrize("runs,width", [(2, 5000), (5, 4500), (8, 4097)])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_merge_path_kv_bit_identical_to_xla_sort(runs, width, dtype):
    keys, vals = _skewed_runs(2, runs, width, dtype)
    assert width % merge_path.TILE
    assert ops.merge_path_engages(8 if runs == 5 else runs, width, keys.dtype,
                                  vals.dtype, use_pallas=True)
    # per processor under vmap, as the sim merges: the batch folds into
    # the kernel's processor axis
    got_k, got_v = jax.vmap(merge_lib.merge_padded_runs_kv)(keys, vals)
    pad_k = jax.vmap(merge_lib._pad_runs_pow2, (0, None))(keys, ops.sentinel_for(keys.dtype))
    pad_v = jax.vmap(merge_lib._pad_runs_pow2, (0, None))(vals, ops.sentinel_for(vals.dtype))
    want_k, want_v = jax.vmap(ops._xla_sort_kv)(pad_k.reshape(2, -1), pad_v.reshape(2, -1))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))

