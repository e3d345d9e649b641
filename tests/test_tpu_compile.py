"""Compile the sort kernels and the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler lowers the programs for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (unsupported vector layouts, unlowered primitives,
too much VMEM). Interpret-mode tests cannot see those failures.

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and test collection
must not depend on it.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import sample_sort, sim
from repro.core.splitters import SortConfig
from repro.kernels import bitonic, ops
from repro.obs.tracing import phase_of


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    # code that asks jax.default_backend() sees the CPU here; steer it to
    # the Mosaic path the chip takes
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    # traces made with the patch hold Mosaic calls: drop them before a
    # later test of this process traces the same shapes for the CPU
    jax.clear_caches()
    sample_sort._mesh_program.cache_clear()


def _lower(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _unscoped_kernels_and_sorts(text: str) -> list:
    """Pallas calls and XLA sorts of a compiled program that sit under no
    phase scope (``obs.tracing.PHASES``) in their ``op_name`` metadata."""
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line or " sort(" in line:
            m = re.search(r'op_name="([^"]*)"', line)
            if m is None or phase_of(m.group(1)) is None:
                out.append(line.strip()[:160])
    return out


# (rows, width) the main path hands each kernel: the 1024-wide tile sort,
# a 128-lane row padded up from a small request's 64-element shard, and
# the widest bitonic merge round (two 4096 runs -> one 8192 row). The
# argument kinds: k = keys of the tested dtype, v = int32 payload.
_CASES = {
    "sort": ((64, 1024), "k", lambda k: bitonic.bitonic_sort_rows(k, interpret=False)),
    "sort_narrow": ((12, 128), "k", lambda k: bitonic.bitonic_sort_rows(k, interpret=False)),
    "sort_kv": ((64, 1024), "kv", lambda k, v: bitonic.bitonic_sort_rows_kv(
        k, v, interpret=False)),
    "merge": ((32, 4096), "kk", lambda a, b: bitonic.bitonic_merge_rows(
        a, b, interpret=False)),
    "merge_narrow": ((8, 128), "kk", lambda a, b: bitonic.bitonic_merge_rows(
        a, b, interpret=False)),
    "merge_kv": ((4, 4096), "kvkv", lambda ak, av, bk, bv: bitonic.bitonic_merge_rows_kv(
        ak, av, bk, bv, interpret=False)),
}


@pytest.mark.parametrize("dtype", ["int32", "uint32", "float32"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_lowers_for_v5e(one_chip, case, dtype):
    shape, kinds, fn = _CASES[case]
    spec = {
        "k": jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip),
        "v": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip),
    }
    compiled = _lower(fn, *(spec[c] for c in kinds))
    assert "tpu_custom_call" in compiled.as_text()


def test_sim_main_path_lowers_for_v5e(one_chip, compiled_kernels):
    """The default planner program below stream_threshold: 8 virtual
    processors over 2^19 keys each, Pallas on (SortConfig defaults)."""
    spec = jax.ShapeDtypeStruct((8, 1 << 19), jnp.int32, sharding=one_chip)
    compiled = _lower(lambda x: sim.sample_sort_sim(x, SortConfig()), spec)
    assert "tpu_custom_call" in compiled.as_text()
    assert _unscoped_kernels_and_sorts(compiled.as_text()) == []


def test_mesh_sort_lowers_on_four_described_chips(topo, compiled_kernels):
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    f = sample_sort._mesh_program(mesh, "data", SortConfig(), True, False)
    spec = jax.ShapeDtypeStruct((4, 1 << 16), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = f.lower(spec).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
    assert _unscoped_kernels_and_sorts(text) == []
    assert np.prod(mesh.devices.shape) == 4
