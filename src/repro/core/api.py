"""Unified sort API — reference.

One entry point, planner-driven backend dispatch, one result type::

    import repro
    out = repro.sort(keys)                 # -> SortOutput
    out.keys                               # flat sorted host array (lazy)

Entry points
------------
``repro.sort(keys, values=None, *, order="asc", want="values",
where=None, limits=None, config=None, investigator=True)``
    keys:   flat array (np/jnp), a (p, n_local) global-view array, an
            iterator of arrays (out-of-core), or a tuple of equal-length
            arrays (lexicographic multi-key).
    values: optional payload that rides the sort (provenance, ids).
    order:  "asc" | "desc", or a tuple with one flag per key.
    want:   "values" (sorted keys [+payload]) | "order" (the stable
            sorting permutation — argsort).
    where:  backend override: "sim" | "stream" | "mesh", a
            ``jax.sharding.Mesh``, or (mesh, axis_name). Default: the
            planner decides (see ``repro.plan``).
    limits: ``SortLimits`` resource hints (n_procs, chunk_elems,
            stream_threshold, overflow ladder, serving size caps,
            multi-key strategy + declared key bit widths).
    config: ``SortConfig`` tuning knobs (paper defaults).

Multi-key strategy (``plan.multikey``)
--------------------------------------
A key tuple runs as ONE fused sort whenever it can: the planner
measures each key's effective bit width (the bits of its monotone
unsigned rank range — sign-xor for ints, the IEEE total-order bit trick
for floats) plus the per-key order flips, and when the widths sum to
<= 31 it packs the tuple into a single non-negative int32 key
(``keyenc.pack_keys``) sorted ascending in one pass — the decision rule
is ``plan.multikey == "packed"``, surfaced with its widths by
``repro.explain``. Anything unpackable — total width over the budget
(e.g. any full-range uint32/int32 column, a float column whose values
cross zero), an unpackable dtype (bfloat16), NaN floats — falls back to
``"lsd"``: one stable argsort pass per key, with the fallback cause in
the plan reasons. ``SortLimits.multikey`` forces either strategy
("packed" raises when the tuple cannot pack); ``SortLimits.key_bits``
declares per-key widths (values promised in ``[0, 2**bits)``, validated
at pack time) so the pack recipe — and therefore the async server's
coalescing bucket — stays identical across requests instead of being
re-measured per dataset. The 31-bit budget is a hard consequence of the
default 32-bit mode below: the packed key must stay a non-negative
int32. Opting into x64 mode widens the budget to 63 bits (a
non-negative int64 word — see the x64 section); the narrow word is
still used whenever the tuple fits 31 bits, so plans and programs are
identical across modes for narrow tuples. Packed PAYLOAD sorts have
one representability edge: a tuple saturating a full 31-bit pack (63
under x64) lands on the pack word's padding sentinel — int32 max, or
int64 max (9223372036854775807) for a wide pack — and raises a
``ValueError`` naming the packed value and its source columns
(narrower packs cannot collide; packed keys-only sorts are
unrestricted).

x64 mode (opt-in 64-bit keys and payloads)
------------------------------------------
The library defaults to jax's 32-bit mode: 64-bit dtypes are rejected
at the door (below) because without ``jax_enable_x64`` they would be
silently truncated on device. The x64 opt-in lifts that contract end
to end, mirroring the ``jax_enable_x64`` config pattern
(``repro.core.x64``):

* ``REPRO_X64=1`` in the environment (read lazily, before the first
  sort);
* ``repro.enable_x64()`` process-wide (also flips jax's own flag —
  required for 64-bit device arrays, and the only switch a
  ``SortServer`` flush thread sees); ``repro.x64_mode()`` is the
  scoped context-manager variant for tests/benchmarks;
* ``SortLimits(x64=True)`` per request — and ``SortLimits(x64=False)``
  pins a request to the 32-bit contract even when the ambient mode is
  on (the differential escape hatch).

With the mode on, int64/uint64/float64 keys and values are admitted on
every backend (sentinels and staging are dtype-driven, so the widening
is automatic), ``plan.key_width`` records the admitted lane width, and
the multi-key pack budget becomes 63 bits: an (int64 timestamp, int32
shard id) tuple — ~34 measured bits + 8 — fuses into ONE int64 sort
instead of per-key LSD passes (the ``x64_pack`` bench gate holds the
speedup). Caveats: a float64 column whose values cross zero measures a
~64-bit rank range and will not pack (LSD fallback, same rule as
float32 in narrow mode — packing needs a narrow exponent band or
declared ``key_bits``); a tuple saturating exactly 63 bits reaches the
int64 padding sentinel (payload-sort ``ValueError`` above). The
default mode is UNCHANGED: with the mode off, 32-bit plans, programs,
and outputs are bit-identical to previous releases, and 32/64-bit
serve requests never share a coalescing bucket or cached program.

Documented limitations
----------------------
* In the default 32-bit mode, 64-bit key and value dtypes are rejected
  at input checking with a ``TypeError`` (for iterator/stream inputs,
  at each staged chunk — the earliest point their dtype is knowable)
  rather than silently truncated on device; the error names the x64
  opt-in and the nearest 32-bit dtype to cast to. Note numpy defaults
  Python ints to int64 (``np.arange(n)`` included).
* sorts that carry a payload (``values`` or ``want="order"``) cannot
  contain the key that collides with the padding sentinel — the dtype
  MAXIMUM (int max / inf) when ascending, the dtype MINIMUM (int min /
  -inf) when descending (the order-flip encoding maps it onto the
  sentinel): the exchange's in-program pads would leak sentinel payload
  into the output, so the planner raises a ``ValueError`` naming the
  offending value at input checking — always, not only when the front
  end pads (``keyenc.check_payload_keys``); NaN keys are rejected for
  payload sorts for the same reason (they order past the sentinel).
  Keys-only sorts of NaN-free keys have no restriction in either
  direction; NaN keys are unsupported throughout (seed-era limitation).

Materialization decode
----------------------
Every plan records ``plan.decode``. The default ``"device"`` fuses the
output decode — compaction gather out of the padded result grid, the
inverse order-flip, the ``want="order"`` stability tie fix and the value
gather — into one jitted device program per backend
(``keyenc.decode_grid``; the stream backend decodes per output chunk,
which also lets descending keys-only stream results use
``SortOutput.chunks()``). Materializing ``.keys``/``.values`` is then a
single device->host transfer (zero-copy where the backend allows it, so
the returned arrays may be READ-ONLY views — ``.copy()`` them to
mutate). ``SortLimits(decode="host")`` selects the legacy numpy decode
— writable owned arrays — kept for differential testing and as the
``--suite api`` decode-gate baseline.

``repro.plan(...)`` / ``repro.explain(...)``
    Same signature; returns the ``SortPlan`` (backend + reasons) the
    planner would execute / its human-readable rendering.

Serving (``repro.serve``)
-------------------------
``SortServer`` is the async front end: ``submit(...)`` takes
``repro.sort``'s keyword surface, returns a ``SortFuture`` immediately,
and a background flush loop coalesces same-shape keys-only requests
into ONE vmapped program per bucket (everything else dispatches
individually on a worker pool). Three layers sit on top:

Tenants & priorities: ``submit(..., tenant="analytics", priority=0)``
tags each request; dispatch is start-time weighted fair queuing over
per-tenant virtual clocks (``SortServer(tenants={name: weight})`` or
``set_tenant``; undeclared tenants get weight 1.0). Each flush takes
the ``max_batch`` best requests by ``(priority, virtual finish tag,
arrival)`` — lower priority values first — so one flooding tenant owns
at most its weighted share of every flush and a light tenant's traffic
overtakes the flood's backlog instead of queuing behind it (the
paper's balanced-workload argument applied to the request plane).
``stats()["tenants"]`` reports per-tenant state; the
``repro_tenant_*`` metrics track it process-wide.

Admission control: the queue is depth-bounded (``max_queue``), and
with an ambient ``repro.tune`` model also COST-bounded
(``max_queue_cost_us``): each submit is priced by the cost model and
rejected when the queued work's predicted microseconds would blow the
budget. Rejections (``QueueFullError``) carry ``retry_after_ms`` —
model-derived (predicted drain of queued work + the request's own
price, monotone in request size) when the model is warm, the static
next-deadline guess when cold. ``sortd_admission_total{verdict}``
counts admitted/queue_depth/queue_cost verdicts.

Sort-adjacent request types: ``submit_topk(keys, k)``,
``submit_searchsorted(keys, queries)`` and
``submit_percentile(keys, q)`` serve cheaper-than-sort answers. All
three plan as ordinary keys-only sorts, so they coalesce into the same
flush buckets as plain sort traffic (``meta.coalesced`` proves it) and
resolve to a ``SortOutput`` whose ``.keys`` is the answer — computed
by the same ``core.topk`` helpers behind ``SortOutput.topk`` /
``.searchsorted``, hence bit-identical to sort-then-slice. For
out-of-core results, ``submit(..., where="stream",
stream_chunks=True)`` resolves to a lazy output whose ``.chunks()``
yields sorted chunks in bounded memory. Runnable tour:
``examples/sort_tenants.py``.

Observability (``repro.obs``)
-----------------------------
Phase-level tracing: ``repro.sort(x, limits=SortLimits(trace=True))``
attaches a ``Trace`` to ``out.meta.trace`` recording one wall-time span
per host phase — ``plan``, ``encode`` (key encode / multi-key pack),
``stage`` (H2D), ``sort`` (the fused program: ``dispatch`` enqueues it,
``overflow_check`` waits for its overflow flag), ``decode``, ``d2h`` —
with ``jax.block_until_ready`` fencing so device work is charged to the
phase that dispatched it. A traced sim/mesh sort runs the same compiled
program as an untraced one; the ``sort`` span carries its per-processor
counts and their max/mean ``imbalance`` (paper Table II). The stream
backend, whose passes are separate programs, records ``local_sort``,
``splitter`` and ``merge`` spans per pass. The device split of the
fused program is in a ``jax.profiler`` capture: each paper step runs
under a ``jax.named_scope`` (``local_sort``, ``splitter``,
``exchange``, ``merge``, ``decode``; ``obs.tracing.PHASES``) and every
span is also a ``repro.<span>`` ``TraceAnnotation``, so host phases and
device phases share the profile's clock. The trace freezes — becomes
immutable and publishes its spans to the ``repro_sort_phase_seconds``
histogram — when the output materializes.
``with obs.trace(job="nightly") as tr:`` installs an ambient trace that
collects every sort in the block instead. ``tr.phase_totals()``,
``tr.coverage()``, and ``tr.to_chrome_file(path)`` (Chrome/Perfetto
``chrome://tracing`` JSON) digest it.

Metrics: one process-wide registry aggregates the serve tier
(``sortd_*`` request outcomes, queue depth, queue-wait/execute/total
latency histograms), the shared program cache
(``repro_program_cache_{hits,builds}_total``), the overflow ladder
(``repro_overflow_ladder_retries_total``), per-backend sort counts
(``repro_sorts_total``) and published phase timings.
``obs.render_prometheus()`` renders the Prometheus text exposition;
``tests/metrics_schema.json`` pins the metric names/label sets in CI.
``obs.disabled()`` / ``obs.set_enabled(False)`` turn the whole
subsystem off (the ``trace_overhead`` gate holds its residue under 2%).
Runnable tour: ``examples/sort_observe.py``.

Request tracing + flight recorder (``repro.obs.flight``): every
serve-tier request is minted a ``trace_id`` at ``SortServer.submit()``
(surfaced on ``out.meta.trace_id``); coalesced requests additionally
carry the ``flush_id`` of the ONE vmapped flush that served them, and
the flush record links back to all member trace_ids with a shared
stage/sort/d2h phase split — so "where did this request's 38 ms go"
decomposes into queue-wait + its flush's phases after the fact. The
process-wide recorder (``obs.flight.RECORDER``) keeps bounded rings of
request/flush summaries, rate-sampled full phase traces (every Nth
direct dispatch runs traced), queue-depth history, and cost-model
predicted-vs-actual pairs — always on, O(1) leaf-lock appends, held
under the same <2% ``trace_overhead`` budget (``serve_flight`` gate).
Anomalies — terminal overflow, a deadline miss beyond
``deadline_miss_factor * max_delay_ms``, a ``QueueFullError`` burst, or
the adaptive controller pinned at an operator bound — freeze the rings
into ``incident_<kind>_<seq>.json`` under ``$REPRO_FLIGHT_DIR``
(rate-limited per kind; shape pinned by ``tests/flight_schema.json``).

SLOs (``repro.obs.slo``): ``SortServer(slo=SLOConfig(...))`` judges
every end-to-end latency against a declared threshold/error-budget
objective; ``stats()["slo"]`` and the ``repro_slo_*`` gauges report the
rolling violation ratio and burn rate (>1 = budget exhausting faster
than provisioned). An adaptive server with no explicit SLO derives one
from the SAME ``AdaptConfig.target_p99_ms`` the controller steers on.

``python -m repro.obsctl`` is the operator CLI over all of it:
``scrape`` (Prometheus exposition + flight snapshot), ``diff`` (two
scrapes), ``slow`` (top-N slow requests with the queue/execute split
and flush linkage), ``export`` (snapshot -> linked Chrome/Perfetto
trace, one row per request and per flush), and ``bench-diff`` — the
same ``compare_bench`` that ``benchmarks/run.py --check-regression``
uses to fail CI when a gated BENCH op slows beyond tolerance.

Empirical tuning (``repro.tune``)
---------------------------------
The planner's size rules and overflow ladder are static heuristics; the
``repro.tune`` control plane replaces them with measurements when you
opt in — and is bit-identical to the static library when you don't (no
tuner installed, or a cold/low-confidence store).

``tune.configure(path=tune.DEFAULT_STORE_PATH, bench=(...))`` installs
the ambient ``Tuner`` from a persisted ``TuneStore`` — per-(op, backend,
dtype) cost observations binned by log2(size), fed from
``BENCH_*.json`` history (``bench=`` paths, or
``benchmarks.run --calibrate`` which writes the store directly) and
online from every completed sort's dispatch->materialize wall time.
``with tune.active(store):`` scopes a tuner instead. Once warm:

* **dispatch** — ``_make_plan`` asks the log-log interpolated
  ``CostModel`` to price each candidate backend at the request's size;
  a confident prediction picks the predicted-fastest
  (``plan.cost_source == "model"``) and sizes stream chunks by modeled
  chunk-sort throughput. ``repro.explain`` prints the per-candidate
  predictions and which one won; the ``tune_dispatch`` bench gate
  asserts a calibrated model is never >1.25x off the measured-fastest.
* **overflow** — the capacity ladder's first retry jumps straight to
  the capacity the failed attempt's own ``send_counts`` measured
  (``overflow.measured_capacity_need``) instead of walking geometric
  doublings: splitters don't depend on capacity, so the re-run traffic
  is identical and the jump is exact (clamped to the ladder ceiling).
* **serving** — ``SortServer(adapt=tune.AdaptConfig(...))`` runs a
  feedback controller that walks ``max_delay_ms``/``max_batch`` toward
  a p99 latency objective within hard bounds (deadband + patience
  hysteresis); ``stats()`` reports the live knobs and an
  ``adaptations`` count.

Decisions are observable: ``repro_tune_plans_total{source}`` counts
static- vs model-sourced plans, ``repro_tune_observations_total{op}``
the samples collected, and ``repro_tune_serve_*`` the controller's knob
positions. The store file format is a persistence contract pinned by
``tests/tune_schema.json``; incompatible files reject at load and
recalibrate from cold. Runnable tour: ``examples/sort_autotune.py``.

``SortOutput`` fields & methods
    .keys .values .counts .overflowed .send_counts .raw .meta
    .order() .provenance() .imbalance() .searchsorted(q) .topk(k)
    .chunks()  (stream backend: bounded-memory sorted chunk iterator)

Deprecation table (old ``SortLibrary`` facade -> unified front end)
-------------------------------------------------------------------
    lib.sort(x)                  -> repro.sort(x).raw / repro.sort(x)
    lib.sort_kv(k, v)            -> repro.sort(k, v)
    lib.sort_with_provenance(x)  -> repro.sort(x, want="order")
    lib.sort_with_retry(x)       -> repro.sort(x)  (overflow ladder is
                                    the default policy; see SortLimits)
    lib.sort_many(arrays)        -> repro.sort per array (same-shape
                                    arrays share one vmapped program)
    lib.sort_external(x)         -> repro.sort(x, where="stream").keys
    lib.sort_external_kv(k, v)   -> repro.sort(k, v, where="stream")
    lib.sort_stream(x)           -> repro.sort(x, where="stream").chunks()
    lib.distributed_sort(x, m)   -> repro.sort(x, where=m)
    lib.searchsorted(r, q)       -> repro.sort(x).searchsorted(q)

The shims below keep every legacy method working (returning the legacy
result types via ``SortOutput.raw``) and warn exactly once per method.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keyenc, planner, sim, topk
from repro.core.overflow import OverflowPolicy, SortOverflowError
from repro.core.planner import SortLimits, SortPlan
from repro.core.result import SortMeta, SortOutput
from repro.core.splitters import SortConfig


def sort(keys, values=None, *, order="asc", want="values", where=None,
         limits: SortLimits | None = None, config: SortConfig | None = None,
         investigator: bool = True) -> SortOutput:
    """Sort ``keys`` (see module docstring for the full reference)."""
    return planner.execute(
        keys, values, order=order, want=want, where=where,
        limits=limits, config=config, investigator=investigator,
    )


def plan(keys, values=None, *, order="asc", want="values", where=None,
         limits: SortLimits | None = None, config: SortConfig | None = None,
         investigator: bool = True) -> SortPlan:
    """The backend the planner will use for this request, and why."""
    return planner.make_plan(
        keys, values, order=order, want=want, where=where,
        limits=limits, config=config, investigator=investigator,
    )


def explain(keys, values=None, **kwargs) -> str:
    """Human-readable rendering of ``repro.plan(...)``."""
    return plan(keys, values, **kwargs).explain()


# ---------------------------------------------------------- provenance


def encode_provenance(p: int, n_local: int) -> jnp.ndarray:
    """(p, n) index payload: global position = proc * n_local + local index.

    Unique and increasing in (proc, idx) — makes every kv sort exactly
    stable and lets users recover ``(previous processor, location)`` the way
    the paper's library does. int32 bounds the sortable volume at 2^31
    elements; past that the payload widens to int64, which requires x64
    mode (``repro.enable_x64()``) — without it this raises rather than
    silently overflowing the index (``keyenc.provenance_dtype``).
    """
    from repro.core.x64 import x64_enabled

    dt = keyenc.provenance_dtype(p * n_local, x64=x64_enabled())
    return (jnp.arange(p * n_local, dtype=dt)).reshape(p, n_local)


def decode_provenance(payload: jnp.ndarray, n_local: int):
    return payload // n_local, payload % n_local


def load_imbalance(counts: jnp.ndarray) -> jnp.ndarray:
    """max/mean shard size — 1.0 is perfect balance (paper Table II)."""
    return counts.max() / jnp.maximum(counts.mean(), 1)


# ------------------------------------------------------ legacy facade


_DEPRECATION_SEEN: set[str] = set()


def _warn_deprecated(name: str, instead: str) -> None:
    if name in _DEPRECATION_SEEN:
        return
    _DEPRECATION_SEEN.add(name)
    warnings.warn(
        f"SortLibrary.{name} is deprecated; use {instead}",
        DeprecationWarning, stacklevel=3,
    )


def _reset_deprecation_registry() -> None:
    """Test hook: make every shim warn again."""
    _DEPRECATION_SEEN.clear()


@dataclasses.dataclass(frozen=True)
class SortLibrary:
    """Deprecated facade over the unified front end (kept so seed-era
    callers run unchanged). Every method routes through ``repro.sort``'s
    planner with an explicit backend pin and returns the legacy result
    type from ``SortOutput.raw``; each warns once per process."""

    config: SortConfig = SortConfig()
    investigator: bool = True

    def _limits(self, **kw) -> SortLimits:
        return SortLimits(**kw)

    # ---- virtual-processor (single device) paths ----
    def sort(self, x: jnp.ndarray) -> sim.SortResult:
        """x: (p, n_local) — sort across virtual processors."""
        _warn_deprecated("sort", "repro.sort(x)")
        out = sort(x, where="sim", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=0, raise_on_overflow=False))
        return out.raw

    def sort_with_provenance(self, x: jnp.ndarray) -> sim.SortKVResult:
        _warn_deprecated("sort_with_provenance", 'repro.sort(x, want="order")')
        out = sort(x, want="order", where="sim", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=0, raise_on_overflow=False))
        return out.raw

    def sort_kv(self, keys: jnp.ndarray, values: jnp.ndarray) -> sim.SortKVResult:
        _warn_deprecated("sort_kv", "repro.sort(keys, values)")
        out = sort(keys, values, where="sim", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=0, raise_on_overflow=False))
        return out.raw

    def sort_many(self, arrays: Sequence[jnp.ndarray]):
        """Sort several independent datasets simultaneously (paper §IV end).
        Same-shape arrays are stacked and run as ONE vmapped program
        (shape-bucketed compiled-program cache, shared with the stream
        SortService)."""
        _warn_deprecated("sort_many", "repro.sort per array")
        return _sort_many_vmapped(arrays, self.config, self.investigator)

    def sort_with_retry(self, x: jnp.ndarray, max_doublings: int = 3):
        """On (detected, never silent) bucket overflow, retry with the
        unified capacity ladder (``overflow.OverflowPolicy``)."""
        _warn_deprecated("sort_with_retry", "repro.sort(x) (retries by default)")
        out = sort(x, where="sim", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=max_doublings))
        return out.raw, out.meta.config

    def searchsorted(self, result: sim.SortResult, queries: jnp.ndarray):
        _warn_deprecated("searchsorted", "SortOutput.searchsorted(queries)")
        return topk.searchsorted_in_result(result.values, result.counts, queries)

    # ---- out-of-core paths (repro.stream) ----
    def sort_external(self, data, *, chunk_elems: int = 1 << 16, n_procs: int = 8):
        """Sort a host-side dataset larger than one device program."""
        _warn_deprecated("sort_external", 'repro.sort(data, where="stream").keys')
        out = sort(data, where="stream", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(chunk_elems=chunk_elems, n_procs=n_procs))
        return out.keys

    def sort_external_kv(self, keys, values, *, chunk_elems: int = 1 << 16,
                         n_procs: int = 8):
        """Out-of-core key/value sort; the payload rides every pass."""
        _warn_deprecated("sort_external_kv",
                         'repro.sort(keys, values, where="stream")')
        out = sort(keys, values, where="stream", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(chunk_elems=chunk_elems, n_procs=n_procs))
        return out.keys, out.values

    def sort_stream(self, data, *, chunk_elems: int = 1 << 16, n_procs: int = 8):
        """Like ``sort_external`` but yields sorted chunks in bounded
        memory — the dataset is never host-materialized at once."""
        _warn_deprecated("sort_stream", 'repro.sort(data, where="stream").chunks()')
        out = sort(data, where="stream", config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(chunk_elems=chunk_elems, n_procs=n_procs))
        return out.chunks()

    # ---- real-mesh paths ----
    @staticmethod
    def _check_divisible(n: int, mesh, axis_name) -> None:
        """Legacy contract: the facade never padded, so uneven inputs must
        keep failing loudly (``repro.sort`` pads + unpads automatically —
        but ``.raw`` counts would include the sentinels)."""
        axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        p = 1
        for a in axes:
            p *= mesh.shape[a]
        if n % p:
            raise ValueError(
                f"input length {n} does not divide the {p}-way sort axis; "
                f"use repro.sort(x, where=mesh) for automatic padding"
            )

    def distributed_sort(self, x, mesh, axis_name="data"):
        _warn_deprecated("distributed_sort", "repro.sort(x, where=mesh)")
        self._check_divisible(int(np.size(x)), mesh, axis_name)
        out = sort(x, where=(mesh, axis_name), config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=0, raise_on_overflow=False))
        return out.raw

    def distributed_sort_kv(self, keys, values, mesh, axis_name="data"):
        _warn_deprecated("distributed_sort_kv", "repro.sort(keys, values, where=mesh)")
        self._check_divisible(int(np.size(keys)), mesh, axis_name)
        out = sort(keys, values, where=(mesh, axis_name), config=self.config,
                   investigator=self.investigator,
                   limits=self._limits(max_doublings=0, raise_on_overflow=False))
        return out.raw


# ------------------------------------------------- vmapped sort_many


_SORT_MANY_CACHE = None


def sort_many_cache():
    """Shape-bucketed compiled-program cache behind SortLibrary.sort_many
    (the SortService cache class, reused — one jit program per shape)."""
    global _SORT_MANY_CACHE
    if _SORT_MANY_CACHE is None:
        from repro.stream.service import ProgramCache

        _SORT_MANY_CACHE = ProgramCache()
    return _SORT_MANY_CACHE


def _sort_many_vmapped(arrays, config: SortConfig, investigator: bool):
    """Group same-(shape, dtype) arrays, stack each group, and execute it
    as one vmapped sample-sort program."""
    cache = sort_many_cache()
    groups: dict[tuple, list[int]] = {}
    arrays = [jnp.asarray(a) for a in arrays]
    for i, a in enumerate(arrays):
        groups.setdefault((a.shape, str(a.dtype)), []).append(i)
    results: list = [None] * len(arrays)
    for idxs in groups.values():
        stacked = jnp.stack([arrays[i] for i in idxs])
        fn = cache.get(len(idxs), stacked.shape[1], stacked.shape[2],
                       stacked.dtype, config, investigator)
        res = fn(stacked)
        for slot, i in enumerate(idxs):
            results[i] = sim.SortResult(
                res.values[slot], res.counts[slot],
                res.overflowed[slot], res.send_counts[slot],
            )
    return results
