"""Observability tour: phase tracing, Chrome export, metrics scrape,
request-scoped flight recording, and SLO burn rates.

A traced sort prints its phase table (wall time of each host phase, and
the per-processor counts and imbalance of the sort program — the paper's
Table II lens). A traced sort runs the same compiled program as an
untraced one; the per-phase device split of that program (local sort,
splitter, exchange, merge, decode) is in a ``jax.profiler`` capture,
where each step is a ``jax.named_scope`` and each host span a
``repro.<span>`` annotation on the same clock. An ambient
trace collects a whole block of sorts, the trace exports to a
chrome://tracing / Perfetto JSON file, and a short burst against the
async SortServer is scraped through the Prometheus text exposition.
The serve burst then shows the request-scoped layer: every request's
``trace_id``, the ``flush_id`` linking coalesced members to their ONE
vmapped flush, the flight recorder's ring snapshot, and the SLO's
burn-rate verdict. Everything here is also reachable operationally via
``python -m repro.obsctl`` (scrape/diff/slow/export/bench-diff).

    PYTHONPATH=src python examples/sort_observe.py
"""
import numpy as np

import repro
from repro import obs
from repro.obs import flight
from repro.obs.slo import SLOConfig
from repro.serve import SortServer


def print_phase_table(tr):
    total = tr.duration()
    print(f"  {'phase':<12}{'ms':>9}{'share':>8}  counts / imbalance")
    for span in tr.spans:
        ms = span.duration * 1e3
        share = span.duration / total if total else 0.0
        extra = ""
        if "per_proc" in span.attrs:
            counts = span.attrs["per_proc"]
            shown = counts if len(counts) <= 8 else counts[:8] + ["..."]
            extra = f"{shown}  imb={span.attrs['imbalance']:.3f}"
        print(f"  {span.name:<12}{ms:9.2f}{share:8.1%}  {extra}")
    print(f"  span coverage of traced window: {tr.coverage():.1%}")


def main():
    cfg = repro.SortConfig(use_pallas=False)
    rng = np.random.default_rng(0)

    # -- one traced sort: SortLimits(trace=True) attaches the phase
    #    breakdown to out.meta.trace; it freezes at materialization. The
    #    "sort" span is the fused program (dispatch + overflow_check
    #    inside it), fenced, with per-processor counts
    x = rng.normal(0, 1, 1 << 18).astype(np.float32)
    out = repro.sort(x, config=cfg,
                     limits=repro.SortLimits(trace=True,
                                             stream_threshold=None))
    assert np.array_equal(out.keys, np.sort(x))  # materializes + freezes
    tr = out.meta.trace
    print(f"traced sort of 2^18 float32 ({tr.duration() * 1e3:.1f}ms):")
    print_phase_table(tr)

    # -- Chrome/Perfetto export: load trace_sort.json in chrome://tracing
    tr.to_chrome_file("trace_sort.json")
    print("wrote trace_sort.json (chrome://tracing, ui.perfetto.dev)\n")

    # -- ambient trace: every sort in the block lands in one trace
    with obs.trace(job="observe-demo") as amb:
        for n in (1 << 14, 1 << 15):
            o = repro.sort(rng.normal(0, 1, n).astype(np.float32),
                           config=cfg,
                           limits=repro.SortLimits(stream_threshold=None))
            o.keys
    totals = amb.phase_totals()
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:3]
    print("ambient trace over 2 sorts, top phases by total time:")
    for name, secs in top:
        print(f"  {name:<12}{secs * 1e3:9.2f}ms")
    print()

    # -- serve a burst under a declared SLO, then scrape the registry.
    #    Every submit mints a trace_id; coalesced requests share the
    #    flush_id of the one vmapped program that served them.
    flight.RECORDER.reset()  # demo hygiene: only this burst in the rings
    slo = SLOConfig(name="demo_p99", threshold_ms=250.0, error_budget=0.05)
    with SortServer(max_batch=16, max_delay_ms=5.0, config=cfg,
                    limits=repro.SortLimits(n_procs=8), slo=slo) as server:
        futs = [server.submit(rng.normal(0, 1, 2048).astype(np.float32))
                for _ in range(24)]
        outs = [f.result(120) for f in futs]
        s = server.stats()
        print(f"served 24 requests: queue-wait p50 "
              f"{s['queue_wait_ms_p50']:.1f}ms, execute p50 "
              f"{s['execute_ms_p50']:.1f}ms, total p99 "
              f"{s['latency_ms_p99']:.1f}ms")
        print(f"SLO {s['slo']['name']}: {s['slo']['breaches']} breaches "
              f"in {s['slo']['observed']} observed, burn rate "
              f"{s['slo']['burn_rate']:.2f}x budget")

    # -- request-scoped identity: trace_id -> flush_id linkage, and the
    #    flight recorder's view of the same burst. Incident snapshots
    #    (terminal overflow, deadline misses, rejection bursts) dump the
    #    same structure to $REPRO_FLIGHT_DIR automatically; inspect with
    #    `python -m repro.obsctl slow/export <snapshot>`
    o = outs[0]
    print(f"\nfirst request: trace_id={o.meta.trace_id} "
          f"flush_id={o.meta.flush_id} "
          f"(coalesced with {o.meta.coalesced - 1} others)")
    snap = flight.RECORDER.snapshot()
    fl = next(f for f in snap["flushes"] if f["flush_id"] == o.meta.flush_id)
    phases = ", ".join(f"{k}={v:.2f}" for k, v in fl["phases"].items())
    print(f"its flush: batch={fl['batch']} ({phases})")
    slowest = max(snap["requests"], key=lambda r: r["total_ms"] or 0.0)
    print(f"slowest request {slowest['trace_id']}: "
          f"queue {slowest['queue_wait_ms']:.2f}ms + "
          f"execute {slowest['execute_ms']:.2f}ms "
          f"= {slowest['total_ms']:.2f}ms\n")

    text = obs.render_prometheus()
    wanted = ("sortd_requests_total", "sortd_queue_depth",
              "sortd_flush_trigger_total", "repro_sorts_total",
              "repro_program_cache_hits_total",
              "repro_overflow_ladder_retries_total", "repro_slo_burn_rate",
              "repro_flush_coalesce_size_count")
    print("prometheus exposition (selected families):")
    for line in text.splitlines():
        if line.startswith(wanted):
            print(f"  {line}")


if __name__ == "__main__":
    main()
