"""Phase-level wall-time spans for the sort pipeline.

The paper's headline claims (balanced workloads, hidden communication
latency) are *measurement* claims, so the repro needs the same
figure-level breakdown: one ``Span`` per host phase — plan, key
encode/pack, staging, the sort program (local sort, splitter selection,
exchange and merge in one program), decode, D2H — with per-processor
element counts and the measured imbalance attached where a phase has a
processor axis.

A ``Trace`` is created either explicitly::

    with obs.trace() as tr:
        out = repro.sort(x)
        out.keys  # materialize
    tr.to_chrome_file("sort.trace.json")

or implicitly via ``SortLimits(trace=True)``, in which case the planner
builds one and attaches it as ``SortOutput.meta.trace``. Spans are
appended under a lock and may nest (``sort`` holds ``dispatch`` and
``overflow_check``); ``coverage()`` reports the
fraction of the trace's wall window covered by at least one span — the
``trace_overhead`` benchmark gate asserts >= 0.95 for a sim sort.

Once the owning ``SortOutput`` materializes, the trace is *frozen*:
its spans are published to the shared metrics registry
(``repro_sort_phase_seconds{backend,phase}``) and further ``span()``
calls raise — trace objects are immutable after materialization so a
scraper can never see a half-built breakdown. Ambient traces (the
``obs.trace()`` context manager) stay open across multiple sorts and
freeze when the context exits.

JAX dispatch is asynchronous, so a span that should account for device
work must *fence*: ``sp.fence(arrays)`` calls ``jax.block_until_ready``
inside the span so the measured interval includes the program it
launched. Unfenced spans measure dispatch only — which is itself the
paper-relevant number for overlap phases (the stream pass-1 H2D, e.g.).

One clock for host and device: ``maybe_span`` also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span>`` (recorded only
while a profiler captures), and the in-core sort programs wrap each
paper step in ``jax.named_scope(<phase>)`` with the names in ``PHASES``.
A profile therefore shows the library's host phases and the device time
of each phase of the one fused program users run; a ``Trace`` of an
in-core sort holds one fenced ``sort`` span for that program (with
``dispatch`` and ``overflow_check`` inside it), not a span per phase.
"""
from __future__ import annotations

import contextlib
import functools
import json
import threading
import time
from typing import Any, Iterator

from repro.obs import metrics as _metrics

_state = threading.local()

_enabled = True

# the paper's steps as device scopes (``jax.named_scope``) inside the
# in-core sort programs, and as span names of the stream backend's passes
PHASES = ("local_sort", "splitter", "exchange", "merge", "decode")


def phase_of(op_name: str) -> str | None:
    """The ``PHASES`` scope an op sits under, from its name-stack path
    (an HLO op's ``op_name`` metadata, a profile's ``tf_op``), e.g.
    ``jit(sample_sort_sim_kv)/vmap(local_sort)/sort`` -> ``local_sort``.
    A scope entered under ``vmap`` shows as ``vmap(<scope>)``; a jitted
    function of the same name (``jit(<name>)``) is not a scope. The
    innermost scope wins; None when the op is under none."""
    found = None
    for part in op_name.split("/"):
        part = part.partition(":")[0]
        while part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        if part in PHASES:
            found = part
    return found


# per-phase wall time, published at trace freeze — the registry-side
# view of the same breakdown the Trace object holds
_PHASE_SECONDS = _metrics.histogram(
    "repro_sort_phase_seconds",
    "Wall time per sort pipeline phase.",
    labels=("backend", "phase"),
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
             2.5, 5.0, 10.0, 30.0, float("inf")),
)


def set_enabled(flag: bool) -> None:
    """Kill switch: while disabled, ``current_trace()`` returns None so
    every instrumentation site in the pipeline short-circuits."""
    global _enabled
    _enabled = bool(flag)


def enabled() -> bool:
    return _enabled


class Span:
    """One closed phase interval. ``t0``/``t1`` are perf_counter seconds;
    ``attrs`` carries phase payload (per_proc counts, imbalance, retries,
    ...). Immutable once its ``span()`` context exits."""

    __slots__ = ("name", "t0", "t1", "attrs")

    def __init__(self, name: str, t0: float, t1: float, attrs: dict):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Span({self.name!r}, {self.duration * 1e3:.3f}ms, {self.attrs})"


class _OpenSpan:
    """Handle yielded by ``Trace.span`` while the interval is open."""

    __slots__ = ("_trace", "name", "attrs")

    def __init__(self, trace: "Trace", name: str):
        self._trace = trace
        self.name = name
        self.attrs: dict[str, Any] = {}

    def set(self, **kv) -> "_OpenSpan":
        self.attrs.update(kv)
        return self

    def counts(self, per_proc) -> "_OpenSpan":
        """Attach per-processor element counts; derives the paper's
        imbalance metric (max/mean) for this phase."""
        c = [int(x) for x in per_proc]
        self.attrs["per_proc"] = c
        mean = sum(c) / len(c) if c else 0.0
        self.attrs["imbalance"] = (max(c) / mean) if mean > 0 else 1.0
        return self

    def fence(self, value) -> Any:
        """Block until ``value``'s device computations finish, inside the
        span — charges the async program to this phase. Lazy jax import
        keeps the obs package importable without jax."""
        import jax

        return jax.block_until_ready(value)


class Trace:
    """An append-only, lockable collection of phase spans.

    ``labels`` (notably ``backend``) flow into the registry histogram at
    freeze time and into the Chrome export's process name.
    """

    def __init__(self, labels: dict | None = None, *, ambient: bool = False):
        self.labels = dict(labels or {})
        self.spans: list[Span] = []
        self._lock = threading.Lock()
        self._frozen = False
        self._published = 0  # spans[:_published] already sent to registry
        self._ambient = ambient

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[_OpenSpan]:
        if self._frozen:
            raise RuntimeError(
                f"trace is frozen (materialized); cannot open span {name!r}"
            )
        sp = _OpenSpan(self, name)
        sp.attrs.update(attrs)
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            self._record(name, t0, time.perf_counter(), sp.attrs)

    def _record(self, name: str, t0: float, t1: float, attrs: dict) -> None:
        with self._lock:
            if not self._frozen:
                self.spans.append(Span(name, t0, t1, attrs))

    # ---- derived views -------------------------------------------------

    def duration(self) -> float:
        """Wall window spanned by the trace: max end - min start."""
        with self._lock:
            if not self.spans:
                return 0.0
            return max(s.t1 for s in self.spans) - min(s.t0 for s in self.spans)

    def coverage(self) -> float:
        """Fraction of the wall window covered by >= 1 span (union of
        intervals / window). 1.0 means every measured moment is
        attributed to a phase."""
        with self._lock:
            ivals = sorted((s.t0, s.t1) for s in self.spans)
        if not ivals:
            return 0.0
        lo = ivals[0][0]
        hi = max(t1 for _, t1 in ivals)
        window = hi - lo
        if window <= 0:
            return 1.0
        covered = 0.0
        cur_lo, cur_hi = ivals[0]
        for t0, t1 in ivals[1:]:
            if t0 > cur_hi:
                covered += cur_hi - cur_lo
                cur_lo, cur_hi = t0, t1
            else:
                cur_hi = max(cur_hi, t1)
        covered += cur_hi - cur_lo
        return covered / window

    def phase_totals(self) -> dict[str, float]:
        """Summed seconds per phase name, in first-seen order."""
        out: dict[str, float] = {}
        with self._lock:
            for s in self.spans:
                out[s.name] = out.get(s.name, 0.0) + s.duration
        return out

    # ---- lifecycle -----------------------------------------------------

    def _publish_locked(self) -> None:
        backend = str(self.labels.get("backend", "unknown"))
        for s in self.spans[self._published:]:
            _PHASE_SECONDS.labels(backend=backend, phase=s.name).observe(
                s.duration
            )
        self._published = len(self.spans)

    def freeze(self) -> "Trace":
        """Publish unpublished spans to the registry and make the trace
        immutable. Idempotent."""
        with self._lock:
            self._publish_locked()
            self._frozen = True
        return self

    @property
    def frozen(self) -> bool:
        return self._frozen

    def materialized(self) -> None:
        """Called by ``SortOutput`` when its result materializes. A
        per-sort trace (``SortLimits(trace=True)``) freezes here; an
        ambient trace (``obs.trace()``) only publishes — it may span
        several sorts and freezes when its context exits."""
        if self._ambient:
            with self._lock:
                self._publish_locked()
        else:
            self.freeze()

    # ---- export --------------------------------------------------------

    def to_chrome(self) -> list[dict]:
        """Chrome/Perfetto trace-event JSON objects (``ph: "X"`` complete
        events, microsecond timestamps relative to the trace start)."""
        with self._lock:
            spans = list(self.spans)
        if not spans:
            return []
        t_base = min(s.t0 for s in spans)
        name = ",".join(f"{k}={v}" for k, v in sorted(self.labels.items()))
        events: list[dict] = [{
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "args": {"name": f"repro.sort({name})" if name else "repro.sort"},
        }]
        for s in spans:
            events.append({
                "name": s.name,
                "ph": "X",
                "pid": 1,
                "tid": 1,
                "ts": round((s.t0 - t_base) * 1e6, 3),
                "dur": round(s.duration * 1e6, 3),
                "args": {k: v for k, v in s.attrs.items()},
            })
        return events

    def to_chrome_file(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump({"traceEvents": self.to_chrome()}, f)
        return path


class _NullSpan:
    """No-op span handle so instrumentation sites can be unconditional."""

    __slots__ = ()

    def set(self, **kv):
        return self

    def counts(self, per_proc):
        return self

    def fence(self, value):
        return value


_NULL_SPAN = _NullSpan()


@functools.cache
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use: the obs
    package itself imports without jax."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class _Phase:
    """The context manager ``maybe_span`` returns. A class, not a
    generator: the span's clock reads are the last thing on entry and the
    first on exit, so back-to-back phases leave next to no gap between
    their spans."""

    __slots__ = ("_trace", "_handle", "_annotation", "_t0")

    def __init__(self, trace: "Trace | None", name: str, attrs: dict):
        self._annotation = _annotation()(f"repro.{name}")
        self._trace = None if trace is None or trace.frozen else trace
        if self._trace is None:
            self._handle = _NULL_SPAN
        else:
            self._handle = _OpenSpan(trace, name)
            self._handle.attrs.update(attrs)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self._handle

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        if self._trace is not None:
            h = self._handle
            self._trace._record(h.name, self._t0, t1, h.attrs)
        return False


def maybe_span(trace: "Trace | None", name: str, **attrs):
    """The library's one instrumentation site. Always a
    ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, which records
    only while a profiler captures; also a span on ``trace`` when a trace
    is active, whose handle (``set``/``counts``/``fence``) the block gets,
    else a no-op handle, so pipeline code instruments unconditionally
    with near-zero cost on the untraced path. A frozen trace also
    degrades to the no-op handle: late materialization (``.keys`` read
    after an ambient ``obs.trace()`` block exited) must not blow up, it
    just goes unattributed. ``obs.disabled()`` drops both."""
    if not _enabled:
        return contextlib.nullcontext(_NULL_SPAN)
    return _Phase(trace, name, attrs)


def current_trace() -> Trace | None:
    """The thread's ambient trace, or None (also None while disabled)."""
    if not _enabled:
        return None
    return getattr(_state, "trace", None)


@contextlib.contextmanager
def trace(labels: dict | None = None, **labelkw) -> Iterator[Trace]:
    """Install an ambient trace for the current thread. Every
    ``repro.sort`` issued inside the block records its phases here; the
    trace freezes when the block exits. Labels come as a dict, keywords,
    or both (``obs.trace(job="nightly")``)."""
    tr = Trace({**(labels or {}), **labelkw}, ambient=True)
    prev = getattr(_state, "trace", None)
    _state.trace = tr
    try:
        yield tr
    finally:
        _state.trace = prev
        tr.freeze()
