"""Unified-API benchmarks: planner dispatch overhead, the device-decode
materialization gate, the multi-key packing gate, and the backend
matrix.

``planner_overhead`` is the acceptance gate of the front-end redesign:
``repro.sort`` (plan -> dispatch -> SortOutput) must cost <5% over
calling the backend directly. ``decode_materialization`` is the
device-decode gate: materializing a 2^22-element descending kv sort
must be >=1.5x faster with the fused device decode than with the legacy
host decode (``REPRO_API_SMOKE=1`` = CI correctness-only mode, tiny
input, no wall-clock assert). ``multikey_pack`` is the packing gate: a
2^20-element three-narrow-key sort must run >=2x faster fused into one
packed int32 pass than as LSD stable passes (same smoke convention).
``x64_pack`` is the same gate one word up: under scoped x64 mode an
(int64 timestamp, int32 shard) tuple — over the 31-bit budget, inside
63 — must run >=2x faster fused into ONE packed int64 pass than as LSD
stable passes. ``api_matrix`` records wall time and achieved balance of
planner-dispatched sorts per backend/size/dtype for the cross-PR JSON
trajectory. ``tune_dispatch`` is the cost-model gate: a calibrated
``repro.tune`` store must never steer the planner to a backend >1.25x
slower than the measured-fastest, and a cold store must leave plans
bit-identical to the static rule.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, gate_ratio, timeit
import repro
from repro.core import sample_sort_sim

SMOKE = os.environ.get("REPRO_API_SMOKE", "") == "1"
CFG = repro.SortConfig(use_pallas=False)


def planner_overhead():
    """repro.sort (planner dispatch) vs direct sample_sort_sim on the same
    device-resident (p, n) input — both sides block on the sorted values,
    so the delta is pure front-end cost (plan + SortOutput wrapping).
    Gated on ``common.gate_ratio`` (interleaved median-of-N with warmup),
    so one CI load spike cannot fail the assert."""
    rng = np.random.default_rng(0)
    p, n = 8, 1 << 16
    x = jnp.asarray(rng.normal(0, 1, (p, n)).astype(np.float32))

    us_via, us_direct = gate_ratio(
        lambda: repro.sort(x, where="sim", config=CFG).raw.values,
        lambda: sample_sort_sim(x, CFG).values,
        warmup=3, iters=9,
    )
    overhead = us_via / us_direct - 1.0
    emit("api_dispatch_direct", us_direct, backend="sim", size=p * n,
         dtype="float32")
    emit("api_dispatch_planner", us_via,
         f"overhead_pct={100 * overhead:.2f}", backend="sim", size=p * n,
         dtype="float32", overhead_pct=round(100 * overhead, 2))
    assert overhead < 0.05, (
        f"planner dispatch overhead {100 * overhead:.2f}% >= 5%"
    )


def decode_materialization():
    """Device-decode gate: a 2^22-element descending kv sort's
    materialization — the step that BLOCKS the caller at first
    ``.keys``/``.values`` access — must be >=1.5x faster under the
    fused device decode than under the PR 3 host-decode path.

    Both sides sort ONCE (the device result grids stay resident).
    The device side's decode program is dispatched eagerly at sort
    time and overlaps the pipeline, so its caller-visible cost is the
    D2H conversion of the decoded buffers; to keep the gate honest
    (jax caches ``np.asarray`` of an Array, which would reduce
    repeated timings to a no-op), every timed call converts a FRESHLY
    decoded output pair, pre-dispatched and blocked outside the timed
    region. The decode program's own (overlapped) execution time is
    recorded as ``api_decode_program_exec`` so a regression there
    still shows in the BENCH trajectory. ``gate_ratio`` interleaves
    the two sides so a CI-neighbor load spike degrades both estimates
    instead of biasing the ratio. REPRO_API_SMOKE=1 shrinks the input
    and gates correctness only (shared runners cannot promise
    wall-clock ratios) — both paths must still match the numpy oracle
    bit for bit."""
    from repro.core import keyenc
    from repro.kernels.ops import _next_pow2

    n = (1 << 14) if SMOKE else (1 << 22)
    rng = np.random.default_rng(7)
    x = rng.normal(0, 1, n).astype(np.float32)
    v = np.arange(n, dtype=np.int32)

    def run(decode):
        return repro.sort(
            x, v, order="desc", config=CFG,
            limits=repro.SortLimits(decode=decode, stream_threshold=None),
        )

    out_dev, out_host = run("device"), run("host")
    mat_host = out_host._materialize

    # correctness first: keys np-exact, payload a valid rider
    # permutation (want="values" payload order is deliberately NOT
    # stable under duplicate keys — the investigator splits tied
    # ranges), decode paths bit-identical
    kd, vd = out_dev._materialize()
    kh, vh = mat_host()
    np.testing.assert_array_equal(kd, np.sort(x)[::-1])
    np.testing.assert_array_equal(x[vd], kd)
    np.testing.assert_array_equal(np.sort(vd), v)
    np.testing.assert_array_equal(kd, kh)
    np.testing.assert_array_equal(vd, vh)

    res = out_dev.raw  # device-resident SortKVResult grids
    m_prog = _next_pow2(n)

    def fresh_decode():
        dk, dv = keyenc.decode_grid(res.keys, res.counts, res.values,
                                    m=m_prog, descending=True)
        jax.block_until_ready(dk)
        jax.block_until_ready(dv)
        return dk, dv

    warm, iters = 1, (3 if SMOKE else 7)
    pool = [fresh_decode() for _ in range(warm + iters + 1)]

    def mat_dev_fresh():
        dk, dv = pool.pop()
        return np.asarray(dk)[:n], np.asarray(dv)[:n]

    us_dev, us_host = gate_ratio(lambda: mat_dev_fresh()[0],
                                 lambda: mat_host()[0],
                                 warmup=warm, iters=iters)
    us_decode = timeit(lambda: fresh_decode()[0], warmup=1,
                       iters=2 if SMOKE else 5)
    speedup = us_host / us_dev
    emit("api_materialize_host_decode", us_host, backend="sim", size=n,
         dtype="float32", smoke=SMOKE)
    emit("api_materialize_device_decode", us_dev,
         f"speedup={speedup:.2f}x_vs_host_decode", backend="sim", size=n,
         dtype="float32", speedup=round(speedup, 2), smoke=SMOKE)
    emit("api_decode_program_exec", us_decode,
         "overlapped_with_sort_pipeline", backend="sim", size=n,
         dtype="float32", smoke=SMOKE)
    if not SMOKE:
        assert speedup >= 1.5, (
            f"device decode materialization speedup {speedup:.2f}x < 1.5x"
        )


def multikey_pack():
    """Multi-key packing gate: one fused packed int32 pass must beat the
    LSD stable passes by >=2x on a 2^20 three-narrow-key sort.

    The LSD construction runs one stable argsort per key (device kv
    sort + host gathers + permutation composition); the packed path is
    one host pack, ONE keys-only device sort, and the fused device
    unpack — the traffic the paper's duplicate-heavy regime is made of
    (enum/bucket/timestamp-delta tuples). Both sides materialize their
    key columns, so the gate times what a caller actually waits for.
    ``gate_ratio`` interleaves the sides (median-of-N) so a CI-neighbor
    load spike degrades both estimates instead of biasing the ratio;
    REPRO_API_SMOKE=1 shrinks the input and gates correctness only —
    both strategies must still match the np.lexsort oracle bit for bit.
    """
    n = (1 << 12) if SMOKE else (1 << 20)
    rng = np.random.default_rng(21)
    keys = (
        rng.integers(0, 16, n).astype(np.int8),      # 4 bits
        rng.integers(0, 256, n).astype(np.int16),    # 8 bits
        rng.integers(0, 1024, n).astype(np.uint32),  # 10 bits
    )
    lim_packed = repro.SortLimits(multikey="packed", stream_threshold=None)
    lim_lsd = repro.SortLimits(multikey="lsd", stream_threshold=None)

    # correctness first: both strategies == np.lexsort, bit for bit
    expect = np.lexsort((keys[2], keys[1], keys[0]))
    out_p = repro.sort(keys, config=CFG, limits=lim_packed)
    out_l = repro.sort(keys, config=CFG, limits=lim_lsd)
    assert out_p.meta.multikey == "packed" and out_l.meta.multikey == "lsd"
    for a, b, k in zip(out_p.keys, out_l.keys, keys):
        np.testing.assert_array_equal(a, k[expect])
        np.testing.assert_array_equal(a, b)

    def run(limits):
        o = repro.sort(keys, config=CFG, limits=limits)
        return jax.block_until_ready([np.asarray(c) for c in o.keys])

    iters = 3 if SMOKE else 7
    us_packed, us_lsd = gate_ratio(lambda: run(lim_packed),
                                   lambda: run(lim_lsd),
                                   warmup=2, iters=iters)
    speedup = us_lsd / us_packed
    emit("api_multikey_lsd", us_lsd, backend="sim", size=n,
         dtype="int8+int16+uint32", smoke=SMOKE)
    emit("api_multikey_packed", us_packed,
         f"speedup={speedup:.2f}x_vs_lsd", backend="sim", size=n,
         dtype="int8+int16+uint32", speedup=round(speedup, 2), smoke=SMOKE)
    if not SMOKE:
        assert speedup >= 2.0, (
            f"packed multi-key speedup {speedup:.2f}x < 2x over LSD"
        )


def x64_pack():
    """x64 packing gate: under x64 mode, an (int64 timestamp, int32
    shard) tuple must run >=2x faster fused into ONE packed int64 pass
    than as LSD stable passes on a 2^20 sort.

    The tuple's 42 measured bits (a ~2^34 timestamp spread + an 8-bit
    shard id) exceed the default 31-bit budget — in 32-bit mode this
    workload is rejected at the door — but fit the 63-bit x64 budget,
    so the planner packs it into a single non-negative int64 word. The
    mode is entered with the SCOPED ``repro.x64_mode()`` (thread-local
    jax trace context, restored on exit), so the rest of the suite
    keeps running the 32-bit contract; ``SortLimits(x64=True)`` would
    flip jax's global flag for the whole process. Smoke convention as
    above: REPRO_API_SMOKE=1 gates correctness only, both strategies
    against the np.lexsort oracle bit for bit.
    """
    n = (1 << 12) if SMOKE else (1 << 20)
    rng = np.random.default_rng(23)
    with repro.x64_mode():
        keys = (
            np.int64(1_700_000_000) + rng.integers(0, 1 << 34, n),  # 34 bits
            rng.integers(0, 200, n).astype(np.int32),               # 8 bits
        )
        lim_packed = repro.SortLimits(multikey="packed",
                                      stream_threshold=None)
        lim_lsd = repro.SortLimits(multikey="lsd", stream_threshold=None)

        # correctness first: the plan packs into an int64 word, and both
        # strategies == np.lexsort, bit for bit
        plan = repro.plan(keys, config=CFG, limits=lim_packed)
        assert np.dtype(plan.packspec.pack_dtype) == np.dtype(np.int64)
        assert plan.key_width == 64
        expect = np.lexsort((keys[1], keys[0]))
        out_p = repro.sort(keys, config=CFG, limits=lim_packed)
        out_l = repro.sort(keys, config=CFG, limits=lim_lsd)
        assert out_p.meta.multikey == "packed"
        assert out_l.meta.multikey == "lsd"
        for a, b, k in zip(out_p.keys, out_l.keys, keys):
            np.testing.assert_array_equal(a, k[expect])
            np.testing.assert_array_equal(a, b)

        def run(limits):
            o = repro.sort(keys, config=CFG, limits=limits)
            return jax.block_until_ready([np.asarray(c) for c in o.keys])

        iters = 3 if SMOKE else 7
        us_packed, us_lsd = gate_ratio(lambda: run(lim_packed),
                                       lambda: run(lim_lsd),
                                       warmup=2, iters=iters)
    speedup = us_lsd / us_packed
    emit("api_x64_multikey_lsd", us_lsd, backend="sim", size=n,
         dtype="int64+int32", smoke=SMOKE)
    emit("api_x64_multikey_packed", us_packed,
         f"speedup={speedup:.2f}x_vs_lsd", backend="sim", size=n,
         dtype="int64+int32", speedup=round(speedup, 2), smoke=SMOKE)
    if not SMOKE:
        assert speedup >= 2.0, (
            f"x64 packed multi-key speedup {speedup:.2f}x < 2x over LSD"
        )


def trace_overhead():
    """Observability gates.

    (a) Cost: with tracing OFF (the default ``SortLimits``), the
    observability layer's residue — ``current_trace()`` checks, metric
    counter bumps, null-span context managers — must add <2% to a 2^20
    planner sort versus the same sort with the whole obs subsystem
    disabled (``obs.disabled()``). Both sides run the identical
    planner path, so the delta isolates instrumentation cost; the
    planner's own front-end overhead is gated separately by
    ``planner_overhead``. Interleaved median-of-N (``gate_ratio``).

    (b) Fidelity: a ``trace=True`` 2^20 sim sort's spans must cover
    >=95% of the traced wall window — phase-level attribution that
    misses 5% of the sort is not an account of where the time went.
    Phase names are asserted in both modes; REPRO_API_SMOKE=1 shrinks
    the input and keeps the coverage + phase-presence asserts (they are
    correctness-of-accounting, not wall-clock gates) while dropping the
    <2% timing assert."""
    from repro import obs

    n = (1 << 14) if SMOKE else (1 << 20)
    rng = np.random.default_rng(11)
    x = rng.normal(0, 1, n).astype(np.float32)
    limits = repro.SortLimits(stream_threshold=None)

    def run():
        o = repro.sort(x, where="sim", limits=limits, config=CFG)
        return jax.block_until_ready(np.asarray(o.keys))

    def run_obs_off():
        with obs.disabled():
            return run()

    iters = 3 if SMOKE else 9
    us_on, us_off = gate_ratio(run, run_obs_off, warmup=2, iters=iters)
    overhead = us_on / us_off - 1.0
    emit("api_trace_off_overhead", us_on,
         f"overhead_pct={100 * overhead:.2f}_vs_obs_disabled",
         backend="sim", size=n, dtype="float32",
         overhead_pct=round(100 * overhead, 2), smoke=SMOKE)
    if not SMOKE:
        assert overhead < 0.02, (
            f"untraced obs residue {100 * overhead:.2f}% >= 2%"
        )

    out = repro.sort(x, where="sim",
                     limits=repro.SortLimits(stream_threshold=None,
                                             trace=True), config=CFG)
    jax.block_until_ready(np.asarray(out.keys))
    tr = out.meta.trace
    assert tr is not None and tr.frozen, "trace=True sort must attach a trace"
    names = {s.name for s in tr.spans}
    for phase in ("plan", "encode", "stage", "sort", "dispatch",
                  "overflow_check", "decode", "d2h"):
        assert phase in names, f"missing phase span: {phase}"
    cov = tr.coverage()
    emit("api_trace_coverage", tr.duration() * 1e6,
         f"coverage={cov:.3f};spans={len(tr.spans)}",
         backend="sim", size=n, dtype="float32",
         coverage=round(cov, 4), smoke=SMOKE)
    assert cov >= 0.95, f"span coverage {cov:.3f} < 0.95 of traced window"


def tune_dispatch():
    """Cost-model dispatch gate (the ``repro.tune`` acceptance criteria).

    (a) Cold start is bit-identical: with an EMPTY tune store ambient,
    the planner must produce the same plan — backend, reason strings,
    chunk sizing — as with no tuner at all, and keep
    ``cost_source == "static"``.

    (b) Calibrated dispatch is never badly wrong: sim and stream are
    measured directly (pinned ``where=``) at probe sizes, the
    measurements seed a fresh ``TuneStore``, and the planner — now
    consulting the model (``cost_source == "model"``) — must pick a
    backend whose measured time is <= 1.25x the measured-fastest at the
    probed size. The probe records are emitted with ``tune_op="sort"``
    so ``run.py --calibrate`` folds this run's measurements back into
    the on-disk store.

    ``REPRO_API_SMOKE=1`` / ``REPRO_TUNE_SMOKE=1`` shrink the probes and
    keep the plan-shape asserts (cold identity, model consultation,
    correctness) while dropping the 1.25x wall-clock assert — shared
    runners cannot promise stable ratios at tiny sizes."""
    from repro import tune

    smoke = SMOKE or os.environ.get("REPRO_TUNE_SMOKE", "") == "1"
    sizes = ((1 << 12, 1 << 13, 1 << 14) if smoke
             else (1 << 14, 1 << 16, 1 << 18))
    n_gate = sizes[1]
    limits = repro.SortLimits(chunk_elems=1 << 14, n_procs=8,
                              stream_threshold=sizes[-1])
    rng = np.random.default_rng(17)
    data = {n: rng.normal(0, 1, n).astype(np.float32) for n in sizes}

    def run(n, backend):
        o = repro.sort(data[n], where=backend, limits=limits, config=CFG)
        return jax.block_until_ready(np.asarray(o.keys))

    # (a) cold bit-identity: empty store => the static plan, untouched
    plan_bare = repro.sort(data[n_gate], limits=limits, config=CFG).meta.plan
    with tune.active(tune.TuneStore()):
        plan_cold = repro.sort(data[n_gate], limits=limits,
                               config=CFG).meta.plan
    assert plan_cold.backend == plan_bare.backend
    assert plan_cold.reasons == plan_bare.reasons
    assert plan_cold.chunk_elems == plan_bare.chunk_elems
    assert plan_cold.cost_source == "static" and not plan_cold.cost_predicted

    # (b) measure both backends at the probes, seed a fresh store
    store = tune.TuneStore()
    measured = {}
    for n in sizes:
        for backend in ("sim", "stream"):
            us = timeit(lambda n=n, b=backend: run(n, b),
                        warmup=1, iters=2 if smoke else 5)
            measured[(backend, n)] = us
            # weight 2: three probe bins x2 reaches the model's
            # full-confidence count (FULL_COUNT=6) per backend curve
            store.observe("sort", backend, "float32", n, us, weight=2.0)
            emit(f"tune_probe_{backend}_{n}", us, backend=backend, size=n,
                 dtype="float32", tune_op="sort", smoke=smoke)

    with tune.active(store):
        out = repro.sort(data[n_gate], limits=limits, config=CFG)
        keys = np.asarray(out.keys)
    np.testing.assert_array_equal(keys, np.sort(data[n_gate]))
    plan = out.meta.plan
    assert plan.cost_source == "model", (
        f"calibrated store did not reach the planner: {plan.reasons}"
    )
    chosen = plan.backend
    fastest = min(measured[(b, n_gate)] for b in ("sim", "stream"))
    ratio = measured[(chosen, n_gate)] / fastest
    emit("tune_dispatch_gate", measured[(chosen, n_gate)],
         f"chosen={chosen};vs_fastest={ratio:.2f}x", backend=chosen,
         size=n_gate, dtype="float32", ratio=round(ratio, 3), smoke=smoke)
    if not smoke:
        assert ratio <= 1.25, (
            f"cost model chose {chosen}: {ratio:.2f}x slower than the "
            f"measured-fastest backend at n={n_gate}"
        )


def api_matrix():
    """Planner-dispatched repro.sort across backends / sizes / dtypes,
    recording wall time and achieved balance."""
    rng = np.random.default_rng(1)
    cases = [
        ("sim", 1 << 18, np.float32),
        ("sim", 1 << 18, np.int32),
        ("stream", 1 << 18, np.float32),
    ]
    limits = repro.SortLimits(chunk_elems=1 << 15, n_procs=8)
    for backend, size, dtype in cases:
        if np.issubdtype(dtype, np.floating):
            x = rng.normal(0, 1, size).astype(dtype)
        else:
            x = rng.integers(0, 50, size).astype(dtype)  # duplicate-heavy
        out = repro.sort(x, where=backend, limits=limits, config=CFG)
        _ = out.keys  # warm compile + materialize; counts reused below
        def run():
            o = repro.sort(x, where=backend, limits=limits, config=CFG)
            return jax.block_until_ready(np.asarray(o.keys))
        us = timeit(run)
        balance = round(out.imbalance(), 4) if out.counts is not None else None
        emit(f"api_sort_{backend}_{np.dtype(dtype).name}_{size}", us,
             f"elems_per_s={size / (us / 1e6):.0f}",
             backend=backend, size=size, dtype=np.dtype(dtype).name,
             balance=balance, ladder_retries=out.meta.retries)
