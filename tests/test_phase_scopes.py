"""Phase scopes inside the fused sort programs.

Every paper step of the in-core programs runs under a
``jax.named_scope`` named in ``repro.obs.tracing.PHASES``; a device
profile finds the scope in each op's ``op_name`` metadata (its
``tf_op``). These tests compile the programs on the CPU, the mesh ones on
four virtual devices in a subprocess (the main pytest process keeps one
device), and read that metadata from the compiled HLO.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs.tracing import PHASES, phase_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SORT_PHASES = {"local_sort", "splitter", "exchange", "merge"}
EXPECTED = {
    "sim": SORT_PHASES,
    "sim_kv": SORT_PHASES,
    "sim_flat": SORT_PHASES | {"decode"},
    "decode_grid": {"decode"},
    "mesh": SORT_PHASES,
    "mesh_kv": SORT_PHASES,
}

_COMPILE = """
    import json, re
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import keyenc, sample_sort, sim
    from repro.core.splitters import SortConfig

    cfg = SortConfig(use_pallas=False)
    grid = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    counts = jax.ShapeDtypeStruct((4,), jnp.int32)
    mesh = jax.make_mesh((4,), ("data",))
    sharded = jax.ShapeDtypeStruct((4, 1024), jnp.int32,
                                   sharding=NamedSharding(mesh, P("data")))
    lowered = {
        "sim": sim.sample_sort_sim.lower(grid, config=cfg),
        "sim_kv": sim.sample_sort_sim_kv.lower(grid, grid, config=cfg),
        "sim_flat": sim.sample_sort_sim_flat.lower(grid, config=cfg),
        "decode_grid": keyenc.decode_grid.lower(grid, counts, grid, m=4096,
                                                want_order=True),
        "mesh": sample_sort._mesh_program(mesh, "data", cfg, True, False)
                .lower(sharded),
        "mesh_kv": sample_sort._mesh_program(mesh, "data", cfg, True, True)
                   .lower(sharded, sharded),
    }
    out = {}
    for name, low in lowered.items():
        ops = []
        for line in low.compile().as_text().splitlines():
            m = re.search(r'op_name="([^"]*)"', line)
            if " = " not in line or m is None:
                continue
            kind = ("pallas" if 'custom_call_target="tpu_custom_call"' in line
                    else "sort" if " sort(" in line else "other")
            ops.append([kind, m.group(1)])
        out[name] = ops
    print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled_ops():
    """{program: [[kind, op_name], ...]} of each compiled program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_COMPILE)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_every_phase_scoped_and_every_sort_under_one(compiled_ops, program):
    ops = compiled_ops[program]
    found = {phase_of(name) for _, name in ops} - {None}
    assert found == EXPECTED[program]
    heavy = [(kind, name) for kind, name in ops if kind != "other"]
    assert any(kind == "sort" for kind, _ in heavy)
    unscoped = [name for _, name in heavy if phase_of(name) is None]
    assert not unscoped, f"sort / Pallas ops under no phase: {unscoped}"


def test_the_programs_cover_every_phase():
    assert set().union(*EXPECTED.values()) == set(PHASES)


@pytest.mark.parametrize("op_name,phase", [
    ("jit(sample_sort_sim_kv)/jit(sample_sort_sim_kv)/vmap(local_sort)/sort",
     "local_sort"),
    ("jit(wrapped)/shard_map/splitter/jit(sort)/sort", "splitter"),
    ("jit(sample_sort_sim)/exchange/vmap(jit(_where))/select_n", "exchange"),
    ("jit(sample_sort_sim_kv)/vmap(vmap(merge))/sort:", "merge"),
    ("jit(decode_grid)/decode/dynamic_update_slice:", "decode"),
    ("jit(sample_sort_sim_flat)/decode/vmap(merge)/sort", "merge"),
    ("jit(sample_sort_sim_kv)/vmap(jit(tile_sort_kv))/gather:", None),
    ("jit(local_sort)/sort", None),
    ("lt_to", None),
])
def test_phase_of_reads_the_name_stack(op_name, phase):
    assert phase_of(op_name) == phase

