"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one cell is found by name:

  BENCHMARK.json                  the cell's chips and the metrics it reports
  bench/workloads/<cell>.json     its configuration, traffic and check sizes
  bench/configs/<config>.json     sizes, guarantees, the library call, the reference
  bench/traffic/<generator>.py    ``build(params, n=, sharding=) -> key -> arrays``
  bench/references/<ref>.py       ``compare(inputs, keys, values) -> {name: number}``
  bench/metrics/<metric>.py       ``read(run) -> number | None``

so a later cell, generator, reference or metric is a new file, and this
module stays as it is.

A run: set-up (device guard, compile cache, inputs made on the device from
the seed, one warm-up call of every shape the window uses), then either
the measured window (``--trace 0``: back-to-back calls for ``--seconds``)
or a short profiled window (``--trace 1``: the cell's ``trace_sorts``
calls), then the comparison with the plain reference of a seeded sample
of the window's outputs, then one JSON line on stdout.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------- data

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict
    config: dict
    end_to_end: list
    per_layer: list

    @property
    def n(self) -> int:
        return int(self.config["n"])


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_of_cell: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_of_cell is None or metric["moves"] in e2e_of_cell


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    workload = _json(root / "bench" / "workloads" / f"{name}.json")
    config = _json(root / "bench" / "configs" / f"{entry['config']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(entry["chips"]), workload, config, e2e, per_layer)


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted: str):
    """``"repro.SortLimits"`` -> the object."""
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def build_value(spec, env: dict):
    """A call argument from the configuration's JSON: ``"$name"`` is an
    object the harness made (``$mesh_axis``: ``(mesh, axis)``), a dict
    with ``make`` is ``resolve(make)(**kwargs)``, anything else is taken
    as it is."""
    if isinstance(spec, str) and spec.startswith("$"):
        return env[spec[1:]]
    if isinstance(spec, dict) and "make" in spec:
        kw = {k: build_value(v, env) for k, v in spec.get("kwargs", {}).items()}
        return resolve(spec["make"])(**kw)
    return spec


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds above 32 bits included)."""
    import jax

    s = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.key(s & 0x7FFFFFFF), s >> 31)


# ------------------------------------------------------------- records

@dataclasses.dataclass
class SortRecord:
    t_call: float       # host clock: the library call starts
    t_returned: float   # the call returned a SortOutput
    t_done: float       # keys and values are on the host
    retries: int
    counts: Any


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers from it."""
    cell: Cell
    setup_s: float
    sorts: list
    bytes_per_sort: int
    chips: int
    peak_bytes: int | None
    peak: Any = None            # bench.peaks.Peak of the device kind
    trace: Any = None           # bench.trace_reduce.Reduced, --trace 1 only

    @property
    def window_s(self) -> float:
        return self.sorts[-1].t_done - self.sorts[0].t_call if self.sorts else 0.0


# ------------------------------------------------------------- device

def guard_devices(chips: int):
    """The cell's devices, or ``NoChip``. No CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.jax_cache`` (its path is part of the key).
    Every program is cached, however short its compile, so that a second
    run's set-up compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------- the call

def make_call(cell: Cell, env: dict) -> Callable[[dict], Any]:
    spec = cell.config["call"]
    fn = resolve(spec["fn"])
    kwargs = {k: build_value(v, env) for k, v in spec.get("kwargs", {}).items()}
    names = tuple(spec["args"])

    def call(arrays):
        return fn(*(arrays[a] for a in names), **kwargs)

    call.arg_names = names
    return call


def sharding_for(cell: Cell, devices):
    """Where the inputs live: one device, or split over the cell's mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    mesh_spec = cell.config.get("mesh")
    if mesh_spec is None:
        return SingleDeviceSharding(devices[0]), {}
    mesh = jax.make_mesh(tuple(mesh_spec["shape"]), tuple(mesh_spec["axes"]),
                         devices=devices)
    axis = mesh_spec["axes"][0]
    return (NamedSharding(mesh, PartitionSpec(axis)),
            {"mesh": mesh, "mesh_axis": (mesh, axis)})


def one_sort(call, arrays, span=None) -> tuple[SortRecord, np.ndarray, np.ndarray]:
    """One closed-loop sort: the call, then the caller's host copies."""
    span = span or (lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()
    with span("bench.call"):
        out = call(arrays)
    t1 = time.perf_counter()
    with span("bench.materialize"):
        ks, vs = out.keys, out.values
    t2 = time.perf_counter()
    rec = SortRecord(t0, t1, t2, int(out.meta.retries),
                     None if out.counts is None else np.asarray(out.counts))
    return rec, ks, vs


class Sample:
    """A seeded reservoir of the window's outputs, for the reference.
    The host copies a caller gets hold no device buffer, so keeping them
    costs host memory only."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.seen = 0
        self.kept: list = []

    def offer(self, ks, vs) -> None:
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept.append((ks, vs))
            return
        j = int(self.rng.integers(self.seen))
        if j < self.size:
            self.kept[j] = (ks, vs)


def window(call, arrays, seconds: float, sample: Sample, span=None):
    """Back-to-back sorts until ``seconds`` have passed since the first
    call; the window ends with the last sort's host copy."""
    sorts, failed = [], 0
    t_start = time.perf_counter()
    while not sorts or time.perf_counter() - t_start < seconds:
        try:
            rec, ks, vs = one_sort(call, arrays, span)
        except Exception as e:  # a failed sort is counted, and the run is not correct
            print(f"sort {len(sorts) + failed} failed: {e!r}", file=sys.stderr)
            failed += 1
            if failed > 3 and not sorts:
                break
            continue
        sample.offer(ks, vs)
        sorts.append(rec)
        del ks, vs
    return sorts, failed


def traced_window(call, arrays, n_sorts: int, sample: Sample, tracedir: str):
    """``n_sorts`` sorts under the profiler, each call and host copy in a
    named host span on the trace's clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    span = jax.profiler.TraceAnnotation
    sorts, failed = [], 0
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        for _ in range(n_sorts):
            try:
                rec, ks, vs = one_sort(call, arrays, span)
            except Exception as e:
                print(f"traced sort failed: {e!r}", file=sys.stderr)
                failed += 1
                continue
            sample.offer(ks, vs)
            sorts.append(rec)
            del ks, vs
    finally:
        jax.profiler.stop_trace()
    return sorts, failed


# ------------------------------------------------------------- the run

def compare(cell: Cell, inputs_host: dict, sample: Sample) -> dict:
    """Worst reading of each compared number over the sampled outputs."""
    ref = load_module("references", cell.config["reference"])
    names = cell.config["call"]["args"]
    worst: dict = {}
    for ks, vs in sample.kept:
        got = ref.compare(inputs_host[names[0]], inputs_host[names[1]], ks, vs)
        for k, v in got.items():
            worst[k] = max(worst.get(k, v), v)
    return worst


def report_metrics(metrics: list, run: Run) -> dict:
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
            devices, call_wrapper=None) -> dict:
    """Set-up, window, check: the result's fields, in the contract's order."""
    import jax

    from bench import peaks

    kind = devices[0].device_kind
    peak = peaks.peak_for(kind) if devices[0].platform == "tpu" else None
    counter = CompileCounter()
    sharding, env = sharding_for(cell, devices)
    call = make_call(cell, env)
    if call_wrapper is not None:
        call = call_wrapper(call)
    gen = load_module("traffic", cell.workload["generator"])
    params = {**cell.config.get("data", {}), **cell.workload.get("traffic", {})}
    make = gen.build(params, n=cell.n, sharding=sharding)
    arrays = jax.block_until_ready(make(seed_key(seed)))
    bytes_per_sort = sum(int(a.nbytes) for a in arrays.values())

    one_sort(call, arrays)  # compiles, or loads from the cache, every program the window runs
    sample = Sample(int(cell.workload["check_sorts"]), seed)
    counter.active = True
    reduced = None
    if trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
            setup_s = time.perf_counter() - t_process
            sorts, failed = traced_window(call, arrays, int(cell.workload["trace_sorts"]),
                                          sample, d)
            counter.active = False
            from bench import trace_reduce
            reduced = trace_reduce.reduce_dir(d, [dev.id for dev in devices])
    else:
        setup_s = time.perf_counter() - t_process
        sorts, failed = window(call, arrays, seconds, sample)
        counter.active = False
    peak_b = peak_bytes(devices)

    run = Run(cell, setup_s, sorts, bytes_per_sort, len(devices), peak_b, peak, reduced)
    metrics = report_metrics(cell.per_layer if trace else cell.end_to_end, run)

    inputs_host = {k: np.asarray(v) for k, v in arrays.items()}
    del arrays
    checks = compare(cell, inputs_host, sample)
    limits = cell.config["limits"]
    ok = (failed == 0 and bool(sample.kept)
          and all(checks.get(k, np.inf) <= lim for k, lim in limits.items()))
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_b}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    result = {"correct": bool(ok), "attempted": len(sorts) + failed, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["window_compiles"] = counter.counts
    result["sort_walls"] = [[s.t_returned - s.t_call, s.t_done - s.t_returned] for s in sorts]
    result["checks"] = {k: {"value": checks.get(k), "limit": lim}
                        for k, lim in limits.items()}
    return result


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        devices = guard_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), t_process, devices)
    print(f"sorts: {result.pop('sort_walls')}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
