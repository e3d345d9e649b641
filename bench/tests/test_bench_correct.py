"""``correct``: the plain reference, the control and the planted faults.

A run is driven end to end at a small size on the CPU, past the harness's
look for a chip: data from the seed, warm-up, window, comparison. The
program passes; the control (the sort on keys narrowed to int16) and each
fault a cell can have (nothing done, half the elements left out, the
exchange between chips left out, one answer altered) come out not
correct. The staged four-chip cell runs on four virtual CPU devices in a
child process, since the device count is fixed when JAX starts.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import faults, harness

ROOT = Path(__file__).resolve().parents[2]
kv_pairs = harness.load_module("references", "kv_pairs")
ZERO = {"length_diff": 0, "keys_out_of_order": 0, "pairs_mismatch": 0}


def test_reference_accepts_any_order_among_equal_keys():
    k = np.array([3, -2, 3, 7, -2, 3], np.int32)
    v = np.arange(6, dtype=np.int32)
    ks, vs = np.array([-2, -2, 3, 3, 3, 7]), np.array([4, 1, 5, 0, 2, 3])
    assert kv_pairs.compare(k, v, ks, vs, workers=3) == ZERO


@pytest.mark.parametrize("ks,vs,bad", [
    ([-2, -2, 3, 3, 7, 3], [1, 4, 0, 2, 3, 5], "keys_out_of_order"),   # unsorted
    ([-2, -2, 3, 3, 3, 7], [1, 4, 0, 2, 3, 3], "pairs_mismatch"),      # payload lost
    ([-2, -2, 3, 3, 3, 7], [1, 0, 4, 2, 5, 3], "pairs_mismatch"),      # payload moved to another key
    ([-2, -2, 3, 3, 3, 3], [1, 4, 0, 2, 5, 3], "pairs_mismatch"),      # key changed
    ([-2, -2, 3, 3, 3], [1, 4, 0, 2, 5], "length_diff"),               # element dropped
])
def test_reference_catches(ks, vs, bad):
    k = np.array([3, -2, 3, 7, -2, 3], np.int32)
    v = np.arange(6, dtype=np.int32)
    got = kv_pairs.compare(k, v, np.array(ks, np.int32), np.array(vs, np.int32), workers=2)
    assert got[bad] > 0


def test_reference_on_many_duplicates_and_workers():
    rng = np.random.default_rng(0)
    k = np.floor(rng.random(50_000) ** 6 * 64).astype(np.int32)
    v = rng.integers(-2**31, 2**31 - 1, k.size).astype(np.int32)
    order = np.argsort(k, kind="stable")
    ks, vs = k[order], v[order]
    for w in (1, 7, 32):
        assert kv_pairs.compare(k, v, ks, vs, workers=w) == ZERO
    vs2 = vs.copy()
    i = np.searchsorted(ks, 5)
    vs2[i], vs2[0] = vs2[0], vs2[i]                 # swap payloads across keys 0 and 5
    assert kv_pairs.compare(k, v, ks, vs2, workers=7)["pairs_mismatch"] > 0


@pytest.fixture(scope="module")
def small_cell():
    cell = harness.load_cell("pgxd_kv32.right_skewed")
    cell.config["n"] = 4096
    return cell


def run(cell, wrap=None, seed=2**31 + 99):
    return harness.execute(cell, seed, 0.2, False, time.perf_counter(),
                           jax.devices()[:1], wrap)


def test_program_run_is_correct(small_cell):
    res = run(small_cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert {k: c["value"] for k, c in res["checks"].items()} == ZERO
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"sorted_gb_per_s", "setup_s"}  # no HBM counter on a CPU
    assert res["window_compiles"] == {"traces": 0, "compiles": 0}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_comes_out_not_correct(small_cell, fault):
    res = run(small_cell, faults.FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_a_failing_call_is_counted(small_cell):
    def broken(call):
        def run_(arrays):
            raise RuntimeError("planted")
        return run_
    with pytest.raises(RuntimeError):
        run(small_cell, broken)   # the warm-up sort fails: no result at all


# The four-chip Graph500 cell is staged under bench/ but not yet named in
# BENCHMARK.json (it has not run on four chips), so it is built from its files.
FOUR = r"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import faults, harness
bench = Path(sys.argv[1]) / "bench"
workload = json.loads((bench / "workloads" / "graph500_kernel1.csr.json").read_text())
config = json.loads((bench / "configs" / "graph500_kernel1.json").read_text())
e2e = harness.load_cell("pgxd_kv32.right_skewed").end_to_end
cell = harness.Cell("graph500_kernel1.csr", 4, workload, config, e2e, [])
cell.config["n"] = 16 << 8
cell.config["data"]["scale"] = 8
for name in [None] + sorted(faults.FAULTS):
    res = harness.execute(cell, 2**31 + 5, 0.2, False, time.perf_counter(),
                          jax.devices()[:4], faults.FAULTS.get(name))
    print(json.dumps({"fault": name, "correct": res["correct"],
                      "count": res["device"]["count"], "checks": res["checks"]}))
"""


def test_four_chip_cell_and_its_faults_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR, str(ROOT)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    assert [r["fault"] for r in rows] == [None] + sorted(faults.FAULTS)
    assert rows[0]["correct"] and rows[0]["count"] == 4
    for r in rows[1:]:
        assert not r["correct"], r
