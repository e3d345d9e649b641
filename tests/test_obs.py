"""Observability subsystem (repro.obs): metrics registry semantics,
prometheus exposition, phase-level tracing across all three backends,
trace lifecycle (freeze-on-materialize, immutability), Chrome export,
and the obs kill switch."""
import json
import threading

import numpy as np
import pytest

import repro
from repro import obs
from repro.core.splitters import SortConfig
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing

CFG = SortConfig(use_pallas=False)


# ------------------------------------------------------------- registry


def test_counter_gauge_histogram_semantics():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("t_total", "help", labels=("op",))
    c.labels(op="a").inc()
    c.labels(op="a").inc(2)
    c.labels(op="b").inc()
    assert c.labels(op="a").value == 3
    assert c.labels(op="b").value == 1
    with pytest.raises(ValueError):
        c.labels(op="a").inc(-1)  # counters only go up

    g = reg.gauge("t_gauge", "help")
    g.set(5)
    g.set(2.5)
    assert g.value == 2.5

    h = reg.histogram("t_ms", "help", buckets=(1.0, 10.0, float("inf")))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.render()
    assert 't_ms_bucket{le="1"} 1' in text
    assert 't_ms_bucket{le="10"} 2' in text
    assert 't_ms_bucket{le="+Inf"} 3' in text
    assert "t_ms_sum 105.5" in text
    assert "t_ms_count 3" in text


def test_registry_idempotent_and_conflicts():
    reg = obs_metrics.MetricsRegistry()
    a = reg.counter("same_total", "help")
    b = reg.counter("same_total", "other help text is fine")
    assert a is b  # re-registration returns the existing metric
    with pytest.raises(ValueError):
        reg.gauge("same_total", "help")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("same_total", "help", labels=("x",))  # label mismatch


def test_exposition_parses_and_escapes():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("esc_total", "help", labels=("path",))
    c.labels(path='a"b\\c\nd').inc()
    text = reg.render()
    line = [l for l in text.splitlines() if l.startswith("esc_total{")][0]
    assert line == 'esc_total{path="a\\"b\\\\c\\nd"} 1'
    # every non-comment line is `name[{labels}] value`
    for l in text.splitlines():
        if l.startswith("#"):
            continue
        float(l.rpartition(" ")[2])


def test_describe_is_stable_schema():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a_total", "h", labels=("x", "y"))
    reg.histogram("b_ms", "h")
    desc = reg.describe()
    assert {"name": "a_total", "type": "counter", "labels": ["x", "y"]} in desc
    assert {"name": "b_ms", "type": "histogram", "labels": []} in desc
    assert desc == sorted(desc, key=lambda d: d["name"])


def test_metric_mutation_thread_safety():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("race_total", "h")

    def worker():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000


# -------------------------------------------------------------- tracing


def _traced_sort(x, **limit_kw):
    limit_kw.setdefault("stream_threshold", None)
    out = repro.sort(x, limits=repro.SortLimits(trace=True, **limit_kw),
                     config=CFG)
    np.testing.assert_array_equal(np.asarray(out.keys), np.sort(x))
    return out


# host spans of an in-core sort: the fused program is one fenced "sort"
# span holding "dispatch" and "overflow_check"; its per-phase device split
# is in a profiler capture (tests/test_phase_scopes.py)
IN_CORE_SPANS = ("plan", "encode", "stage", "sort", "dispatch",
                 "overflow_check", "decode", "d2h")


def _sort_span(tr):
    (sp,) = [s for s in tr.spans if s.name == "sort"]
    assert sp.attrs["phases"] == "local_sort+splitter+exchange+merge"
    inner = [s for s in tr.spans if s.name in ("dispatch", "overflow_check")]
    assert len(inner) == 2
    assert all(sp.t0 <= s.t0 <= s.t1 <= sp.t1 for s in inner)
    return sp


def test_sim_trace_phases_and_counts():
    # 2^16: a traced sort runs the cached production program, and at 2^12
    # (4 ms warm on the CPU) scheduler jitter in the host glue between
    # spans alone moves coverage across 0.95
    x = np.random.default_rng(0).normal(0, 1, 1 << 16).astype(np.float32)
    out = _traced_sort(x, n_procs=4)
    tr = out.meta.trace
    assert tr is not None and tr.frozen
    names = [s.name for s in tr.spans]
    for phase in IN_CORE_SPANS:
        assert phase in names
    sort = _sort_span(tr)
    assert len(sort.attrs["per_proc"]) == 4
    assert sum(sort.attrs["per_proc"]) == x.size
    assert sort.attrs["imbalance"] >= 1.0
    assert tr.coverage() >= 0.95
    assert tr.phase_totals()["sort"] > 0


def test_stream_trace_phases_and_counts():
    x = np.random.default_rng(1).normal(0, 1, 6000).astype(np.float32)
    out = repro.sort(
        x, where="stream", config=CFG,
        limits=repro.SortLimits(trace=True, n_procs=4, chunk_elems=2048),
    )
    np.testing.assert_array_equal(out.keys, np.sort(x))
    tr = out.meta.trace
    names = [s.name for s in tr.spans]
    for phase in ("plan", "encode", "local_sort", "splitter", "merge"):
        assert phase in names
    local = next(s for s in tr.spans if s.name == "local_sort")
    assert sum(local.attrs["per_proc"]) == x.size  # per-run sizes
    split = next(s for s in tr.spans if s.name == "splitter")
    assert sum(split.attrs["per_proc"]) == x.size  # per-bucket sizes
    merges = [s for s in tr.spans if s.name == "merge"]
    assert len(merges) == len(split.attrs["per_proc"])  # one per bucket


def test_mesh_trace_phases_and_counts():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = np.random.default_rng(2).integers(0, 1 << 16, 1 << 12).astype(np.int32)
    out = repro.sort(x, where=(mesh, "data"),
                     limits=repro.SortLimits(trace=True), config=CFG)
    np.testing.assert_array_equal(np.asarray(out.keys), np.sort(x))
    tr = out.meta.trace
    names = [s.name for s in tr.spans]
    for phase in IN_CORE_SPANS:
        assert phase in names
    sort = _sort_span(tr)
    assert sum(sort.attrs["per_proc"]) == x.size
    assert sort.attrs["imbalance"] == 1.0


def test_untraced_sort_has_no_trace():
    x = np.random.default_rng(3).normal(0, 1, 1 << 10).astype(np.float32)
    out = repro.sort(x, config=CFG,
                     limits=repro.SortLimits(stream_threshold=None))
    np.asarray(out.keys)
    assert out.meta.trace is None


def test_trace_frozen_after_materialization():
    x = np.random.default_rng(4).normal(0, 1, 1 << 10).astype(np.float32)
    out = _traced_sort(x, n_procs=4)
    tr = out.meta.trace
    assert tr.frozen
    n_spans = len(tr.spans)
    with pytest.raises(RuntimeError):
        with tr.span("late"):
            pass
    # maybe_span degrades to a no-op on frozen traces (late .keys access
    # must not blow up), and records nothing
    with obs_tracing.maybe_span(tr, "late") as sp:
        sp.set(ignored=1)
    assert len(tr.spans) == n_spans


def test_ambient_trace_context():
    x = np.random.default_rng(5).normal(0, 1, 1 << 10).astype(np.float32)
    with obs.trace(job="ambient") as tr:
        out = repro.sort(x, config=CFG,
                         limits=repro.SortLimits(stream_threshold=None))
        np.asarray(out.keys)
        assert out.meta.trace is tr
        assert not tr.frozen  # ambient traces freeze at context exit
    assert tr.frozen
    assert tr.labels["job"] == "ambient"
    _sort_span(tr)
    assert obs_tracing.current_trace() is None


def test_chrome_export(tmp_path):
    x = np.random.default_rng(6).normal(0, 1, 1 << 10).astype(np.float32)
    out = _traced_sort(x, n_procs=4)
    path = tmp_path / "trace.json"
    out.meta.trace.to_chrome_file(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} >= set(IN_CORE_SPANS)
    for e in complete:
        assert e["dur"] >= 0 and e["ts"] >= 0


def test_phase_histogram_published():
    x = np.random.default_rng(7).normal(0, 1, 1 << 10).astype(np.float32)
    fam = obs_metrics.REGISTRY.histogram(
        "repro_sort_phase_seconds", "", labels=("backend", "phase"))
    child = fam.labels(backend="sim", phase="sort")
    before = child._count
    _traced_sort(x, n_procs=4)
    assert child._count == before + 1
    assert child._sum > 0


@pytest.mark.parametrize("where,kv", [("sim", False), ("sim", True),
                                      ("mesh", False), ("mesh", True)])
def test_traced_sort_runs_the_untraced_program(where, kv):
    """A traced sort compiles nothing new after an untraced one of the same
    shape: both run the one fused program (and the same decode)."""
    import jax
    import jax.monitoring
    from jax.sharding import Mesh

    if where == "mesh":
        where = (Mesh(np.array(jax.devices()[:1]), ("data",)), "data")
    rng = np.random.default_rng(9)
    x = rng.integers(0, 1 << 12, 1 << 12).astype(np.int32)
    vals = np.arange(x.size, dtype=np.int32) if kv else None

    def sort(trace):
        out = repro.sort(x, vals, where=where, config=CFG,
                         limits=repro.SortLimits(trace=trace, n_procs=4,
                                                 stream_threshold=None))
        np.testing.assert_array_equal(out.keys, np.sort(x))
        return out

    sort(False)
    events = []

    def on(event, _secs, **_kw):
        if event in ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/backend_compile_duration"):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        out = sort(True)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert out.meta.trace is not None
    _sort_span(out.meta.trace)
    assert events == []


def test_disabled_suppresses_everything():
    x = np.random.default_rng(8).normal(0, 1, 1 << 10).astype(np.float32)
    c = obs_metrics.counter("repro_test_disabled_total", "h")
    with obs.disabled():
        out = repro.sort(x, config=CFG,
                         limits=repro.SortLimits(trace=True,
                                                 stream_threshold=None))
        np.asarray(out.keys)
        assert out.meta.trace is None  # kill switch beats trace=True
        c.inc()
        assert obs_tracing.current_trace() is None
    assert c.value == 0  # mutation was a no-op while disabled
    c.inc()
    assert c.value == 1
