"""Sort-service front end: shape-bucketed program cache + micro-batching.

The serving analogue of ``serve/batching.py`` for the sort library:
concurrent sort requests of arbitrary length are padded up to power-of-two
*shape buckets*, same-bucket requests are stacked and executed as ONE
vmapped sample-sort program, and compiled executables are cached per
(batch, shape, dtype, config) so a steady-state request mix runs with
zero recompiles. The device decode is fused into the vmapped program
(``sim.sample_sort_sim_flat``): compaction — and the order-flip for
descending buckets — happens before the D2H copy, so a flush transfers
the (batch, p*per) decoded output rather than the padded exchange grid
and per-request materialization is a host slice. Request staging spreads
real elements evenly across the grid rows (``planner.pad_grid``), so
far-from-pow2 request sizes no longer pile their pad sentinels into the
top key range and pay a per-request capacity-ladder retry on every
flush — steady-state retries are zero for any request size. Per-request overflow is detected from the vmapped
overflow flags and retried individually through the library's unified
capacity ladder (``core.overflow.OverflowPolicy`` — the same policy
``repro.sort`` applies), paid only by the requests that actually
overflowed, never by the whole batch. A request that still overflows
after the ladder fails alone: the rest of the flush completes first, and
the ``SortServiceError`` raised at the end carries the completed results
(``.results``) alongside the failures (``.errors``), so survivors are
never lost.

``SortService`` here is the SYNCHRONOUS front end: ``submit`` enqueues
and ``flush`` blocks the caller until the whole queue has executed. The
asynchronous, latency-targeted front end — futures, a background flush
loop with ``max_batch``/``max_delay_ms`` targets, admission control and
backpressure — lives in ``repro.serve.sortd.SortServer``; new serving
code should start there. Both share the ``FlushEngine`` below, so sync
and async flushes cannot diverge in padding, program caching, or
overflow-ladder behavior.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keyenc, sim
from repro.core.overflow import OverflowPolicy, SortOverflowError, retry_overflowed
from repro.core.splitters import SortConfig
from repro.kernels import ops as kops
from repro.kernels.ops import _next_pow2
from repro.obs import flight as obs_flight
from repro.obs import metrics as obs_metrics
from repro.obs.tracing import maybe_span as _span
from repro.stream.runs import _pad_chunk

# Registry mirrors of the per-instance ``stats`` dicts: process-wide
# compile/reuse accounting for every ProgramCache in the process, scraped
# through ``obs.render_prometheus()`` alongside the serve-tier metrics.
_M_CACHE_BUILDS = obs_metrics.counter(
    "repro_program_cache_builds_total",
    "Vmapped sort programs compiled into a ProgramCache (cache misses).",
)
_M_CACHE_HITS = obs_metrics.counter(
    "repro_program_cache_hits_total",
    "ProgramCache lookups served by an already-compiled program.",
)
# batching efficiency (the PR 3 design premise) as a scrape surface:
# how many requests actually shared each vmapped flush, per program
# kind — plain ascending, descending (fused flip decode), or packed
# multi-key (fused unpack). A mass at bucket 1 means the coalescing
# window is not capturing concurrency.
_M_COALESCE_SIZE = obs_metrics.histogram(
    "repro_flush_coalesce_size",
    "Requests coalesced into one vmapped flush program, by program kind.",
    labels=("kind",),  # plain|descending|packed
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, float("inf")),
)


class ProgramCache:
    """Compiled vmapped sample-sort programs, keyed by
    (batch, p, per, dtype, key_width, config, investigator, flat,
    descending, packspec) — the explicit key WIDTH rides in the key so
    32- and 64-bit (x64-mode) requests can never coalesce into one
    program even if a dtype ever aliases across widths. Shared between
    the SortService flush path and
    ``SortLibrary.sort_many``. ``flat=True`` programs fuse the device
    decode (``sim.sample_sort_sim_flat``): the compaction gather — and,
    for descending buckets, the order-flip encode/decode — runs inside
    the vmapped program, so the flush's D2H copy is the (batch, p*per)
    decoded output instead of the ~p-times-larger padded exchange
    grid. ``packspec`` programs (packed multi-key serving buckets)
    additionally fuse the bit-field unpack, so the D2H output is the
    tuple of decoded key columns."""

    def __init__(self, stats: dict | None = None):
        self.programs: dict = {}
        self.stats = stats if stats is not None else {"programs": 0, "hits": 0}
        self.stats.setdefault("programs", 0)
        self.stats.setdefault("hits", 0)

    def get(self, batch: int, p: int, per: int, dtype,
            config: SortConfig, investigator: bool, *,
            flat: bool = False, descending: bool = False, packspec=None):
        dt = np.dtype(dtype)
        key = (batch, p, per, dt.str, 8 * dt.itemsize, config, investigator,
               flat, descending, packspec)
        fn = self.programs.get(key)
        if fn is None:
            if flat:
                body = functools.partial(
                    sim.sample_sort_sim_flat, config=config,
                    investigator=investigator, descending=descending,
                    packspec=packspec,
                )
            else:
                body = functools.partial(
                    sim.sample_sort_sim, config=config,
                    investigator=investigator,
                )
            fn = jax.jit(jax.vmap(body))
            self.programs[key] = fn
            self.stats["programs"] += 1
            _M_CACHE_BUILDS.inc()
        else:
            self.stats["hits"] += 1
            _M_CACHE_HITS.inc()
        return fn


@dataclasses.dataclass
class SortRequest:
    rid: int
    data: np.ndarray  # flat, any supported key dtype
    # request-scoped identity, minted at submit (obs.flight): links this
    # request to the flush that served it in the flight recorder
    trace_id: str | None = None


class FlushEngine:
    """The shared flush core of the sync ``SortService`` and the async
    ``repro.serve.sortd.SortServer``.

    Owns the shape-bucketed ``ProgramCache`` and the per-request overflow
    ladder; callers own queueing, admission and error policy.
    ``run_group`` executes one shape bucket's requests (slicing into
    ``max_batch``-sized vmapped programs) and returns, per request,
    ``(sorted array | terminal SortOverflowError, ladder_steps)`` —
    callers decide whether to raise, collect, or fail a future with the
    error, and surface the ladder accounting on their result meta."""

    def __init__(self, *, config: SortConfig = SortConfig(), n_procs: int = 8,
                 investigator: bool = True, max_doublings: int = 3,
                 growth: float = 2.0, max_batch: int = 64,
                 stats: dict | None = None, stats_lock=None):
        self.config = config
        self.n_procs = n_procs
        self.investigator = investigator
        self.max_doublings = max_doublings
        self.growth = growth
        self.max_batch = max_batch
        self.stats = stats if stats is not None else {}
        # "retries" may have a second writer (the async server's direct-
        # dispatch workers add stream/mesh ladder steps to the same dict
        # under its own lock), so a shared lock must guard the
        # read-modify-write; single-threaded callers pass nothing
        self._stats_lock = (stats_lock if stats_lock is not None
                            else contextlib.nullcontext())
        for k in ("programs", "hits", "batches", "retries"):
            self.stats.setdefault(k, 0)
        self.cache = ProgramCache(self.stats)

    @property
    def policy(self) -> OverflowPolicy:
        return OverflowPolicy(max_doublings=self.max_doublings,
                              growth=self.growth)

    def bucket_elems(self, n: int) -> int:
        """Pad target: next power of two, at least one element per proc."""
        return _next_pow2(max(n, self.n_procs))

    def bucket_key(self, data: np.ndarray) -> tuple:
        """Requests with equal bucket keys may share one vmapped program.

        The key width is explicit so 32- and 64-bit (x64-mode) traffic
        buckets apart — an int64 request must never be stacked into an
        int32 program's flush, whatever the dtype string says."""
        return (self.bucket_elems(data.shape[0]), data.dtype.str,
                8 * data.dtype.itemsize)

    def _fill(self, dtype, descending: bool):
        """Staging sentinel: pads must sort to the tail of the ENCODED
        space, so descending buckets stage the flipped sentinel (dtype
        min / -inf) that the in-program flip maps back onto it."""
        fill = np.asarray(kops.sentinel_for(jnp.dtype(dtype)))
        return keyenc.flip_np(fill) if descending else fill

    def run_group(self, datas: list[np.ndarray], *,
                  descending: bool = False, packspec=None,
                  ctxs: list | None = None) -> list[tuple]:
        """Execute one shape bucket's flat arrays; per entry,
        ``(sorted array | terminal exception, ladder_steps)``.
        ``descending`` buckets run the same fused program with the
        order-flip encode/decode inside it — requests arrive raw.
        ``packspec`` buckets (packed multi-key serving) arrive as the
        packed ascending int32 arrays; the fused program unpacks the
        columns, and each result entry is the TUPLE of column arrays.

        ``ctxs`` (optional, parallel to ``datas``) are the requests'
        ``obs.flight.RequestContext``s: each flush links its member
        trace_ids, stamps its coarse phase breakdown (stage / sort /
        d2h) onto every member context, and records ONE flush summary
        in the flight recorder — the "one flush span, N request spans"
        linkage the trace export reconstructs.

        Ordering contract: entries run in LIST ORDER, sliced into
        ``max_batch``-sized vmapped programs front to back. Any
        scheduling policy (e.g. ``serve.sortd``'s weighted-fair tenant
        queues) must therefore order ``datas`` BEFORE calling — the
        engine itself is policy-free."""
        elems = self.bucket_elems(datas[0].shape[0])
        out: list = []
        for i in range(0, len(datas), self.max_batch):
            out.extend(
                self._run_batch(datas[i : i + self.max_batch], elems,
                                descending, packspec,
                                ctxs[i : i + self.max_batch] if ctxs else None)
            )
        return out

    def _run_batch(self, datas: list[np.ndarray], elems: int,
                   descending: bool, packspec=None,
                   ctxs: list | None = None) -> list[tuple]:
        p = self.n_procs
        per = -(-elems // p)  # ceil: row capacity p*per covers elems for any p
        dtype = datas[0].dtype
        fill = self._fill(dtype, descending)
        b = _next_pow2(len(datas))
        kind = ("packed" if packspec is not None
                else "descending" if descending else "plain")
        fctx = obs_flight.FlushContext(
            kind=kind, batch=len(datas), padded_batch=b, elems=elems,
            dtype=dtype,
            trace_ids=[c.trace_id for c in ctxs] if ctxs else None,
        )
        t0 = time.monotonic()
        batch = np.full((b, p, per), fill, dtype)
        for i, d in enumerate(datas):
            batch[i] = _pad_chunk(d, p, per, fill)

        fn = self.cache.get(b, p, per, dtype, self.config, self.investigator,
                            flat=True, descending=descending,
                            packspec=packspec)
        t_staged = time.monotonic()
        # the profiler annotation brackets the flush program dispatch so
        # captured device profiles attribute the vmapped sort
        with _span(None, "service.flush_batch"):
            res = fn(jnp.asarray(batch))
            jax.block_until_ready(res.flat)
        t_sorted = time.monotonic()
        self.stats["batches"] += 1

        overflowed = np.asarray(res.overflowed)
        # ONE D2H transfer of the decoded (b, p*per) output — the decode
        # (compaction + flip + the packed-multi-key unpack) already ran
        # inside the vmapped program, so per-request materialization is
        # a host slice, and the padded (b, p, p*cap) exchange grid never
        # crosses to the host
        flat = (tuple(np.asarray(c) for c in res.flat)
                if packspec is not None else np.asarray(res.flat))
        t_d2h = time.monotonic()
        fctx.phases = {
            "stage_ms": (t_staged - t0) * 1e3,
            "sort_ms": (t_sorted - t_staged) * 1e3,
            "d2h_ms": (t_d2h - t_sorted) * 1e3,
        }
        fctx.overflowed = int(overflowed[: len(datas)].sum())
        out: list = []
        for i, d in enumerate(datas):
            retries = 0
            if overflowed[i]:
                try:
                    entry = self._retry_one(d, elems, descending, packspec)
                except SortOverflowError as e:
                    entry = (e, self.max_doublings)
                retries = entry[1]
                out.append(entry)
            else:
                out.append((self._slice_result(flat, i, d.shape[0]), 0))
            if ctxs:
                ctxs[i].flush_id = fctx.flush_id
                ctxs[i].coalesced = len(datas)
                ctxs[i].retries = retries
                ctxs[i].phases = fctx.phases
            fctx.retries += retries
        _M_COALESCE_SIZE.labels(kind=kind).observe(len(datas))
        obs_flight.RECORDER.record_flush(fctx.summary())
        return out

    @staticmethod
    def _slice_result(flat, i: int, n: int):
        if isinstance(flat, tuple):
            return tuple(c[i, :n].copy() for c in flat)
        return flat[i, :n].copy()

    def _retry_one(self, data: np.ndarray, elems: int,
                   descending: bool, packspec=None) -> tuple:
        """Unified capacity ladder for a single overflowed request — the
        batched attempt at ``self.config`` counts as the failed initial
        attempt, so the ladder starts at the first capacity bump exactly
        like ``repro.sort``'s overflow policy would. Returns
        ``(sorted array | tuple of columns, ladder_steps_taken)``."""
        p, per = self.n_procs, -(-elems // self.n_procs)
        x = jnp.asarray(_pad_chunk(data, p, per, self._fill(data.dtype,
                                                            descending)))

        def on_retry(_cfg):
            with self._stats_lock:
                self.stats["retries"] += 1

        r, _cfg, n = retry_overflowed(
            lambda cfg: sim.sample_sort_sim_flat(
                x, cfg, investigator=self.investigator, descending=descending,
                packspec=packspec,
            ),
            self.config, self.policy, on_retry=on_retry,
        )
        if packspec is not None:
            return (tuple(np.asarray(c)[: data.shape[0]].copy()
                          for c in r.flat), n)
        return np.asarray(r.flat)[: data.shape[0]].copy(), n


class SortServiceError(RuntimeError):
    """Some requests failed terminally. ``results`` holds the flush's
    completed sorts (rid -> array); ``errors`` the per-rid failures."""

    def __init__(self, msg: str, results: dict, errors: dict):
        super().__init__(msg)
        self.results = results
        self.errors = errors


@dataclasses.dataclass
class SortService:
    """Micro-batching sort server over the virtual-processor sample sort.

    max_batch: requests per vmapped program (batch is padded to a
      power of two so batch sizes also shape-bucket).
    policy: overflow ladder for per-request retries — the library-wide
      default, so service and ``repro.sort`` behavior cannot diverge.
    """

    config: SortConfig = SortConfig()
    n_procs: int = 8
    investigator: bool = True
    max_doublings: int = 3
    max_batch: int = 64

    def __post_init__(self):
        self._queue: list[SortRequest] = []
        self._next_rid = 0
        self.stats = {"programs": 0, "hits": 0, "batches": 0, "retries": 0}
        self._engine = FlushEngine(
            config=self.config, n_procs=self.n_procs,
            investigator=self.investigator, max_doublings=self.max_doublings,
            max_batch=self.max_batch, stats=self.stats,
        )

    @property
    def policy(self) -> OverflowPolicy:
        return self._engine.policy

    def _bucket_elems(self, n: int) -> int:
        return self._engine.bucket_elems(n)

    # ---------------------------------------------------------- batching
    def submit(self, data: np.ndarray) -> int:
        """Enqueue a sort request; returns its rid. ``flush`` executes the
        queue in as few programs as the shape mix allows. Each request is
        minted a ``trace_id`` for the flight recorder's flush linkage."""
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(SortRequest(rid, np.asarray(data).reshape(-1),
                                       trace_id=obs_flight.new_trace_id()))
        return rid

    def flush(self) -> dict[int, np.ndarray]:
        """Run all queued requests, micro-batched by shape bucket.

        Every request is executed even when one fails terminally: the
        ``SortServiceError`` raised at the end carries the completed
        results, so one hopeless request never destroys its batch-mates."""
        groups: dict[tuple, list[SortRequest]] = {}
        for req in self._queue:
            groups.setdefault(self._engine.bucket_key(req.data), []).append(req)
        self._queue = []
        out: dict[int, np.ndarray] = {}
        errors: dict[int, Exception] = {}
        for reqs in groups.values():
            now = time.monotonic()
            ctxs = [obs_flight.RequestContext(
                        now, trace_id=r.trace_id, kind="coalesced",
                        n=r.data.shape[0], dtype=r.data.dtype, backend="sim")
                    for r in reqs]
            for c in ctxs:
                c.dispatched(now)  # sync service: no queue-wait to split
            results = self._engine.run_group([r.data for r in reqs],
                                             ctxs=ctxs)
            for req, ctx, (res, _retries) in zip(reqs, ctxs, results):
                if isinstance(res, Exception):
                    errors[req.rid] = RuntimeError(
                        f"sort request rid={req.rid}: {res}"
                    )
                    ctx.finish("failed", error=res)
                else:
                    out[req.rid] = res
                    ctx.finish("completed")
                obs_flight.RECORDER.record_request(ctx.summary())
        if errors:
            rids = sorted(errors)
            raise SortServiceError(
                f"{len(errors)} sort request(s) failed terminally "
                f"(rids {rids}): {errors[rids[0]]}",
                out, errors,
            )
        return out

    def sort_many(self, arrays: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Sort several independent arrays; same-shape-bucket arrays share
        one vmapped program execution."""
        rids = [self.submit(a) for a in arrays]
        done = self.flush()
        return [done[r] for r in rids]

    def sort(self, x: np.ndarray) -> np.ndarray:
        return self.sort_many([x])[0]
