"""The peaks table and the refusal to run anywhere but on a TPU."""
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import harness, peaks

ROOT = Path(__file__).resolve().parents[2]


def test_v5e_peak_has_a_source():
    p = peaks.peak_for("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9 and p.hbm_bytes == 16e9
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for(kind)


def test_guard_refuses_a_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(harness.NoChip, match="needs a TPU"):
        harness.guard_devices(1)


def test_run_exits_nonzero_without_a_result_off_the_chip():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pgxd_kv32.right_skewed",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT), "PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr
