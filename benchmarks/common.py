"""Shared benchmark utilities: timing, the paper's four input
distributions (Fig. 4), CSV emission.

The paper sorts 1B 4-byte keys on 8..52 machines x 32 threads. This
container is one CPU, so the benchmarks run the same *algorithm* at
2^20..2^22 keys over virtual processors and report derived quantities
(imbalance, speedup ratios, step shares) that are scale-free; EXPERIMENTS
§Benchmarks records the scale-down factor next to every paper number.
"""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.tune import COST_MODEL_VERSION


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else in the fixed ``.jax_cache/`` at the repo root (the path is
    part of the cache key, so it must not move between runs)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parent.parent
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def timeit(fn, *args, warmup=2, iters=5):
    """Median wall time (us) of a jitted callable (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def gate_us(fn, *args, warmup=3, iters=9):
    """Median-of-N wall time (us) after warmup — the estimator for GATED
    assertions against an absolute bound. A single timing (or a small
    min-of-N on one side only) flakes when CI neighbors steal CPU
    mid-run; the median of N post-warmup runs is robust to load spikes
    in either direction. Same loop as ``timeit``, with deeper defaults
    because a gate failure aborts the suite."""
    return timeit(fn, *args, warmup=warmup, iters=iters)


def gate_ratio(fn_a, fn_b, *, warmup=2, iters=9):
    """Paired estimator for gated A-vs-B comparisons: INTERLEAVE the A
    and B timings so a load spike degrades both sides instead of biasing
    whichever happened to be running, then compare medians. Returns
    ``(us_a, us_b)``. This is what every timing gate (planner-overhead,
    serve-throughput) compares on."""
    for _ in range(warmup):
        jax.block_until_ready(fn_a())
        jax.block_until_ready(fn_b())
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        tb.append(time.perf_counter() - t0)
    return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6)


def distribution(name: str, rng, p: int, n: int, dtype=np.float32):
    """The paper's Fig. 4 inputs. right_skewed / exponential are quantized
    so they contain heavy duplication (the investigator's regime)."""
    if name == "uniform":
        x = rng.uniform(0, 1, (p, n))
    elif name == "normal":
        x = rng.normal(0, 1, (p, n))
    elif name == "right_skewed":
        x = np.floor((rng.uniform(0, 1, (p, n)) ** 6) * 64)
    elif name == "exponential":
        x = np.floor(rng.exponential(1.0, (p, n)) * 8)
    else:
        raise KeyError(name)
    return jnp.asarray(x.astype(dtype))


DISTRIBUTIONS = ("uniform", "normal", "right_skewed", "exponential")


_RECORDS: list[dict] = []


def emit(name: str, us: float, derived: str = "", *, size=None, dtype=None,
         backend=None, balance=None, ladder_retries=None, **extra):
    """Print the CSV line AND append a machine-readable record; ``run.py``
    drains the records into BENCH_<suite>.json so the perf trajectory is
    tracked across PRs. Every record carries ``balance`` (the run's
    max/mean processor-count imbalance, paper Table II) and
    ``ladder_retries`` (capacity-ladder steps the run took) — null when
    the benchmark has no sort to measure them on — so load-balance and
    overflow regressions are visible in the same trajectory as timing."""
    print(f"{name},{us:.1f},{derived}")
    # every record is stamped with the active cost-model version so a
    # calibration store (run.py --calibrate) can reject stale history
    # after a tune-schema bump instead of silently mixing regimes
    rec = {"op": name, "us_per_call": round(float(us), 2), "derived": derived,
           "cost_model": COST_MODEL_VERSION}
    for k, v in (("size", size), ("dtype", dtype), ("backend", backend)):
        if v is not None:
            rec[k] = v
    rec["balance"] = None if balance is None else float(balance)
    rec["ladder_retries"] = (None if ladder_retries is None
                             else int(ladder_retries))
    rec.update(extra)
    _RECORDS.append(rec)


def drain_records() -> list[dict]:
    out = list(_RECORDS)
    _RECORDS.clear()
    return out
