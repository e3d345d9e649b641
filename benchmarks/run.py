"""Benchmark harness: one function per paper table/figure + beyond-paper
studies. Prints ``name,us_per_call,derived`` CSV and writes a
machine-readable ``BENCH_<suite>.json`` per suite (op, size, dtype,
backend, wall-time, achieved balance) so the perf trajectory is tracked
across PRs.

    PYTHONPATH=src python -m benchmarks.run [--suite paper|external|api|serve|all]
                                            [--only fig5,...] [--out-dir .]
                                            [--calibrate] [--tune-store PATH]
                                            [--check-regression]
                                            [--baseline-dir D] [--tolerance T]

``--check-regression`` compares each suite's fresh records against the
baseline ``BENCH_<suite>.json`` in ``--baseline-dir`` (loaded before the
fresh file can clobber it) via ``repro.obsctl.compare_bench`` and exits
nonzero when a gated op slowed beyond its tolerance — the perf analog of
the tier-1 test gate. ``python -m repro.obsctl bench-diff A B`` runs the
same comparison standalone between any two BENCH files.

The serve suite honors REPRO_SERVE_SMOKE=1 and the api suite
REPRO_API_SMOKE=1 (tiny sizes, correctness-only gates — the CI profile;
see benchmarks/serve_bench.py / api_bench.py); REPRO_TUNE_SMOKE=1 puts
the two repro.tune gates (``tune_dispatch``, ``serve_adaptive``) in the
same correctness-only mode. ``--calibrate`` folds the run's per-sort
records into the ``repro.tune`` store (``--tune-store`` overrides the
path) so the cost-model planner starts warm on this machine. The api decode gate
(``decode_gate``) asserts the fused device-decode materialization is
>=1.5x faster than the host-decode baseline for a 2^22 descending kv
sort; the ``multikey`` gate asserts the packed multi-key path is >=2x
faster than the LSD stable passes for a 2^20 three-narrow-key sort;
``serve_pad_retries`` asserts zero overflow-ladder retries for
coalesced non-pow2 request sizes; ``trace_overhead`` asserts the
observability layer costs <2% when tracing is off and that a traced
sort's phase spans cover >=95% of its wall window.
"""
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--suite", default="paper",
                    choices=("paper", "external", "api", "serve", "all"),
                    help="paper = in-core tables/figures; external = "
                         "out-of-core + sort-service benchmarks; api = "
                         "unified-front-end dispatch overhead + matrix; "
                         "serve = async sort-server throughput/latency")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<suite>.json files land")
    ap.add_argument("--calibrate", action="store_true",
                    help="fold this run's per-sort records (tune_op / "
                         "api_sort_* matrix entries) into the repro.tune "
                         "store, so the cost-model planner starts warm")
    ap.add_argument("--tune-store", default=None,
                    help="tune-store path for --calibrate (default: "
                         "repro.tune.DEFAULT_STORE_PATH)")
    ap.add_argument("--check-regression", action="store_true",
                    help="after writing BENCH_<suite>.json, compare each "
                         "suite's gated ops against the baseline file in "
                         "--baseline-dir (repro.obsctl.compare_bench); "
                         "exit nonzero on regressions beyond tolerance")
    ap.add_argument("--baseline-dir", default=".",
                    help="where baseline BENCH_<suite>.json files live "
                         "(typically the repo root's committed copies)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override every regression gate's tolerance "
                         "(default: per-op repro.obsctl.REGRESSION_GATES)")
    args = ap.parse_args()

    from benchmarks import (api_bench, common, external_sort, ours,
                            paper_figs, serve_bench)

    common.use_compile_cache()

    suites = {
        "paper": {
            "fig5": paper_figs.fig5_distributions,
            "fig6": paper_figs.fig6_scaling,
            "fig7": paper_figs.fig7_step_breakdown,
            "table2": paper_figs.table2_balance,
            "fig9": paper_figs.fig9_10_11_sample_size,
            "fig12": paper_figs.fig12_memory,
            "moe": ours.moe_dispatch,
            "investigator": ours.investigator_ablation,
            "sort_colls": ours.sort_collective_schedule,
            "kernels": ours.kernel_paths,
        },
        "external": {
            "external_sort": external_sort.external_vs_incore,
            "sort_service": external_sort.service_batching,
        },
        "api": {
            "planner_overhead": api_bench.planner_overhead,
            "decode_gate": api_bench.decode_materialization,
            "multikey": api_bench.multikey_pack,
            "trace_overhead": api_bench.trace_overhead,
            "api_matrix": api_bench.api_matrix,
            "tune_dispatch": api_bench.tune_dispatch,
            # LAST in the suite: enters scoped x64 mode — nothing after
            # it should depend on a freshly 32-bit jit cache
            "x64_pack": api_bench.x64_pack,
        },
        "serve": {
            "serve_throughput": serve_bench.serve_throughput,
            "serve_latency": serve_bench.serve_latency,
            "serve_pad_retries": serve_bench.serve_pad_retries,
            "serve_adaptive": serve_bench.serve_adaptive,
            "serve_flight": serve_bench.serve_flight,
            "serve_fairness": serve_bench.serve_fairness,
        },
    }
    selected = list(suites) if args.suite == "all" else [args.suite]
    only = set(args.only.split(",")) if args.only else None

    # snapshot baselines up front: --out-dir may equal --baseline-dir,
    # in which case writing the fresh file below would clobber them
    baselines = {}
    if args.check_regression:
        for suite_name in selected:
            base_path = f"{args.baseline_dir}/BENCH_{suite_name}.json"
            try:
                with open(base_path) as f:
                    baselines[suite_name] = json.load(f)["records"]
            except (OSError, ValueError, KeyError):
                print(f"no baseline at {base_path}; skipping regression "
                      f"check for suite {suite_name!r}", file=sys.stderr)

    print("name,us_per_call,derived")
    failed = []
    calibration = []
    regressed = []
    for suite_name in selected:
        common.drain_records()
        for name, fn in suites[suite_name].items():
            if only is not None and name not in only:
                continue
            try:
                fn()
            except Exception:
                failed.append(name)
                traceback.print_exc()
        records = common.drain_records()
        calibration.extend(records)
        if records:
            path = f"{args.out_dir}/BENCH_{suite_name}.json"
            with open(path, "w") as f:
                json.dump({"suite": suite_name, "records": records}, f, indent=1)
            print(f"wrote {path} ({len(records)} records)", file=sys.stderr)
        if suite_name in baselines:
            from repro.obsctl import REGRESSION_GATES, compare_bench

            gates = REGRESSION_GATES
            if args.tolerance is not None:
                gates = {op: args.tolerance for op in gates}
            lines, regs = compare_bench(baselines[suite_name], records,
                                        gates=gates)
            print(f"--- regression check: {suite_name} ---", file=sys.stderr)
            print("\n".join(lines), file=sys.stderr)
            regressed.extend(regs)
    if args.calibrate:
        from repro import tune

        store_path = args.tune_store or tune.DEFAULT_STORE_PATH
        store, reason = tune.TuneStore.load_or_cold(store_path)
        if reason != "loaded":
            print(f"calibrating a fresh store ({reason})", file=sys.stderr)
        n = store.ingest_bench(calibration)
        store.save(store_path)
        print(f"calibrated {store_path}: +{n} records, "
              f"{store.total_count} observations total", file=sys.stderr)
    if regressed:
        print(f"REGRESSED: {[r['op'] for r in regressed]}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
    if failed or regressed:
        sys.exit(1)


if __name__ == '__main__':
    main()
