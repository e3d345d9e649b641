"""Execution planner + backend registry for the unified sort front end.

The paper pitches one library call that stays load-balanced everywhere;
backend choice (single-device virtual processors, real-mesh shard_map,
out-of-core streaming) is therefore a *planner decision* driven by input
placement/size/shape — not a method name the caller memorizes (cf. Cérin
et al.'s partitioning-method selection for heterogeneous clusters).

    plan   = repro.plan(keys, ...)    # inspect: which backend, and why
    output = repro.sort(keys, ...)    # plan + execute -> SortOutput

Placement rules (in order):
  1. ``where`` names a backend, or is a ``jax.sharding.Mesh`` (-> mesh).
  2. Iterator inputs stream (size unknown / not host-resident).
  3. Inputs above ``limits.stream_threshold`` elements stream.
  4. Everything else runs on the virtual-processor simulator.

Capabilities (descending / argsort / multi-key) are *front-end
encodings* over the stable kv machinery (see ``keyenc``), so every
registered backend inherits them at once. The overflow-retry ladder is
the single policy in ``overflow.py`` for all backends. Decoding those
encodings back out happens ON DEVICE by default (``plan.decode ==
"device"``): each backend's materialization is one fused jitted program
(compaction gather + inverse flip + tie fix, ``keyenc.decode_grid``)
followed by a single D2H copy; ``SortLimits(decode="host")`` keeps the
legacy numpy decode for differential testing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from repro import tune as _tune
from repro.core import keyenc, sample_sort, sim
from repro.core.overflow import (
    OverflowPolicy,
    ladder_totals,
    measured_capacity_need,
    run_with_capacity_retry,
)
from repro.core.result import SortMeta, SortOutput
from repro.core.splitters import SortConfig
from repro.kernels import ops as kops
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.obs.tracing import maybe_span as _span

# one counter for every sort the planner dispatches, labeled by the
# backend it chose — the registry-side view of the placement rules
_SORTS_TOTAL = obs_metrics.counter(
    "repro_sorts_total",
    "Sorts executed by the unified front end, by planner backend.",
    labels=("backend",),
)


# the cast remedy named in the 64-bit rejection, per offending dtype
_NEAREST_NARROW = {"int64": "int32", "uint64": "uint32", "float64": "float32"}


def check_key_dtype(dt, what: str = "keys", *, x64: bool | None = None) -> None:
    """Reject 64-bit dtypes at the door — unless x64 mode admits them.

    In the default 32-bit mode the device sort would silently truncate
    64-bit keys/payloads, and the int64 padding sentinel overflows deep
    in the kernel with an opaque error — so the rejection happens here,
    with the remedy spelled out: the x64 opt-in (``repro.enable_x64()``
    / ``REPRO_X64=1`` / ``SortLimits(x64=True)``, see ``core.x64``) or a
    cast to the nearest 32-bit dtype. Applied to key arrays and value
    payloads at ``repro.sort`` input checking, and to every staged chunk
    of iterator (stream) inputs — the earliest point their dtype is
    knowable. ``x64=None`` reads the ambient mode; the planner passes
    the request's resolved mode so ``SortLimits(x64=...)`` wins.
    """
    if str(dt) == "bfloat16":
        return  # sorted as f32 on device — supported
    if np.dtype(str(dt)).itemsize <= 4:
        return
    if x64 is None:
        from repro.core import x64 as _x64

        x64 = _x64.x64_enabled()
    if x64:
        return
    narrow = _NEAREST_NARROW.get(str(dt), "a 32-bit dtype")
    raise TypeError(
        f"64-bit {what} ({dt}) need x64 mode, which is off: without jax "
        f"x64 the device sort would truncate to 32 bits and the padding "
        f"sentinel overflows. Opt in with repro.enable_x64(), REPRO_X64=1, "
        f"or SortLimits(x64=True) — or cast to {narrow} first (note np "
        f"defaults Python ints to int64)."
    )


def _effective_x64(limits) -> bool:
    """Resolve a request's x64 mode: ``SortLimits.x64`` wins, else the
    ambient switch. A per-request ``x64=True`` also flips jax's own
    x64 flag — 64-bit device arrays are impossible without it."""
    from repro.core import x64 as _x64

    if limits is not None and limits.x64 is not None:
        if limits.x64:
            _x64.ensure_jax_x64()
        return bool(limits.x64)
    return _x64.x64_enabled()


@dataclasses.dataclass(frozen=True)
class SortLimits:
    """Resource hints the planner dispatches on.

    n_procs: virtual processors for sim/stream chunk sorts.
    chunk_elems: device-program capacity of one stream chunk.
    stream_threshold: element count above which the planner picks the
      out-of-core backend; None disables size-based streaming (explicit
      ``where="stream"`` and iterator inputs still stream).
    max_doublings / growth / raise_on_overflow: the unified overflow
      policy (see ``overflow.OverflowPolicy``). The stream backend
      honors max_doublings and growth but always raises when the ladder
      is exhausted — a partially exchanged run cannot be returned.
    max_request_elems: serving admission control — the async sort server
      (``repro.serve.sortd``) rejects a single request above this many
      elements at submit time (``RequestTooLargeError``) so one huge
      sort cannot monopolize the flush loop. None (default) disables
      the limit; plain ``repro.sort`` calls ignore it.
    decode: output materialization path. ``"device"`` (default) fuses
      the compaction gather, inverse order-flip, stable-argsort tie fix
      and value gather into one jitted device program per backend, so
      materialization is a single D2H copy of exactly n elements
      (``keyenc.decode_grid``). ``"host"`` keeps the legacy numpy
      decode — per-row unpad+concat, host flip, host tie fix — for
      differential testing and the decode benchmark baseline.
    multikey: multi-key strategy. ``"auto"`` (default) fuses the tuple
      into ONE packed integer sort when the per-key effective bit widths
      fit the pack budget (``keyenc.PACK_BUDGET_BITS`` = 31 in the
      default 32-bit mode; 63 under x64 mode, packing into int64),
      else falls back to the LSD stable passes; ``"packed"`` requires
      packing (raises with the fallback reason when the tuple cannot
      pack); ``"lsd"`` always runs the stable passes (the differential-
      testing baseline). The decision and its reason are recorded on
      ``plan.multikey`` / ``plan.reasons``.
    key_bits: optional per-key declared bit widths for the packer, e.g.
      ``(4, None, 10)`` — entry i promises key i's values lie in
      ``[0, 2**bits)`` (validated at pack time; ints only, None =
      measure from the data). Declaring widths keeps the PackSpec
      identical across requests, which is what lets the async sort
      server coalesce packed multi-key traffic into shared buckets —
      measured specs vary with each request's data. Ignored for
      single-key sorts.
    trace: record the phase-level span breakdown of this sort (plan,
      encode, stage, local sort, splitter, exchange, merge, decode, D2H)
      on ``SortOutput.meta.trace`` — a ``repro.obs.tracing.Trace`` with
      per-processor counts, per-phase imbalance, and Chrome trace-event
      export. The sim and mesh backends run the same fused program as
      an untraced sort, under one fenced ``sort`` span; its per-phase
      device split (local sort, splitter, exchange, merge, decode) is
      in a ``jax.profiler`` capture, as ``jax.named_scope`` phases on
      the same clock as the spans. Default False. An ambient
      ``obs.trace()`` block traces regardless of this flag.
    x64: per-request x64-mode override (see ``core.x64``). None
      (default) follows the ambient switch (``repro.enable_x64()`` /
      ``REPRO_X64=1``); True admits 64-bit keys/values for THIS request
      (and ensures jax's own x64 flag, so device arrays really are
      64-bit); False pins the request to the 32-bit contract even when
      the ambient mode is on — the differential-testing escape hatch.
      With the mode off (resolved False) plans and outputs are
      bit-identical to the 32-bit-only library.
    """

    n_procs: int = 8
    chunk_elems: int = 1 << 16
    stream_threshold: int | None = 1 << 22
    max_doublings: int = 3
    growth: float = 2.0
    raise_on_overflow: bool = True
    max_request_elems: int | None = None
    decode: str = "device"
    multikey: str = "auto"
    key_bits: tuple | None = None
    trace: bool = False
    x64: bool | None = None

    def policy(self) -> OverflowPolicy:
        return OverflowPolicy(
            max_doublings=self.max_doublings,
            growth=self.growth,
            raise_on_overflow=self.raise_on_overflow,
        )


@dataclasses.dataclass(frozen=True)
class SortPlan:
    """The planner's decision: backend + shape of the execution."""

    backend: str
    n_procs: int
    chunk_elems: int
    limits: SortLimits
    reasons: tuple = ()
    mesh: Any = None
    axis_name: Any = "data"
    decode: str = "device"
    multikey: str | None = None  # "packed" | "lsd"; None for single-key
    packspec: Any = None         # keyenc.PackSpec when multikey == "packed"
    cost_source: str = "static"  # "model" when an ambient repro.tune cost
    #                              model (confidently) made the placement
    cost_predicted: Any = None   # {backend: {"us", "confidence"}} — the
    #                              model's per-candidate predictions, kept
    #                              even when below the confidence bar
    key_width: int = 32          # key lane width in bits (64 only under
    #                              x64 mode; iterator inputs record the
    #                              widest admissible width)
    x64: bool = False            # the request's RESOLVED x64 mode
    #                              (SortLimits.x64 or the ambient switch)

    def explain(self) -> str:
        lines = [f"repro.sort plan: backend={self.backend!r}"]
        for r in self.reasons:
            lines.append(f"  - {r}")
        if self.cost_predicted:
            lines.append(f"  cost: source={self.cost_source}")
            for b in sorted(self.cost_predicted):
                d = self.cost_predicted[b]
                chosen = "  <- chosen" if (
                    self.cost_source == "model" and b == self.backend
                ) else ""
                lines.append(
                    f"    {b}: predicted {d['us']:.0f}us "
                    f"(confidence {d['confidence']:.2f}){chosen}"
                )
        if self.multikey is not None:
            detail = (f" ({self.packspec.describe()})"
                      if self.packspec is not None else "")
            lines.append(f"  multikey={self.multikey}{detail}")
        lines.append(
            f"  n_procs={self.n_procs} chunk_elems={self.chunk_elems} "
            f"decode={self.decode} "
            f"key_width={self.key_width}{' (x64 mode)' if self.x64 else ''} "
            f"overflow: up to {self.limits.max_doublings} capacity bumps "
            f"(x{self.limits.growth})"
        )
        return "\n".join(lines)


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    description: str
    execute: Callable  # (_Req, SortPlan) -> SortOutput


BACKENDS: dict[str, Backend] = {}


def register_backend(name: str, execute: Callable, description: str) -> None:
    BACKENDS[name] = Backend(name, description, execute)


# --------------------------------------------------------------- request


@dataclasses.dataclass
class _Req:
    """Normalized sort request (internal)."""

    keys: Any  # array | list of arrays (multi-key) | iterator
    values: Any
    want: str  # "values" | "order"
    descending: tuple  # per-key flags
    config: SortConfig
    investigator: bool
    n: int | None  # None for iterator inputs
    n_local: int | None  # set for (p, n_local) global-view inputs
    dtype: Any
    is_iterator: bool
    multikey: bool
    packspec: Any = None  # set on the packed-multikey SUB-request: the
    #                       single-key backends thread it into the fused
    #                       decode so the keys unpack on device
    pack_ranks: Any = None  # per-column uint32 rank arrays measured at
    #                         plan time; pack_keys reuses them instead of
    #                         recomputing the monotone transforms
    trace: Any = None  # obs.tracing.Trace when this sort is traced; the
    #                    backends record their phase spans on it and the
    #                    meta carries it out (sub-requests inherit it)

    @property
    def needs_payload(self) -> bool:
        return self.want == "order" or self.values is not None


def _normalize(keys, values, *, order, want, config, investigator,
               x64: bool | None = None) -> _Req:
    if want not in ("values", "order"):
        raise ValueError(f"want must be 'values' or 'order', got {want!r}")
    if want == "order" and values is not None:
        raise ValueError(
            'want="order" returns the permutation itself; pass values with '
            'want="values", or gather them with keys[out.order()]'
        )
    # multi-key is a *tuple* of key arrays; a list is an iterable of
    # chunks (stream input), matching the stream drivers' contract
    multikey = isinstance(keys, tuple)
    klist = list(keys) if multikey else [keys]
    n_keys = len(klist)
    if multikey and n_keys == 0:
        raise ValueError(
            "multi-key sort needs a non-empty tuple of key arrays "
            "(got an empty tuple)"
        )
    if multikey and n_keys == 1:
        multikey, keys = False, klist[0]

    if isinstance(order, (tuple, list)):
        orders = tuple(order)
    else:
        orders = (order,) * n_keys
    if len(orders) != n_keys:
        raise ValueError(f"{len(orders)} order flags for {n_keys} keys")
    for o in orders:
        if o not in ("asc", "desc"):
            raise ValueError(f"order must be 'asc' or 'desc', got {o!r}")
    descending = tuple(o == "desc" for o in orders)

    if values is not None:
        # payloads ride the device sort too: a silently truncated int64
        # payload is a corrupted result, not a slow one — same door check
        if not hasattr(values, "dtype"):
            values = np.asarray(values)
        check_key_dtype(values.dtype, what="values payload", x64=x64)

    is_iterator = not multikey and not hasattr(keys, "dtype")
    if isinstance(keys, list) and keys and not hasattr(keys[0], "dtype"):
        # a bare list of Python scalars: treat as one flat array
        keys = np.asarray(keys)
        is_iterator = False
    n = n_local = None
    dtype = None
    if multikey:
        klist = [np.asarray(k).reshape(-1) for k in klist]
        n = klist[0].shape[0]
        if any(k.shape[0] != n for k in klist):
            raise ValueError("multi-key arrays must have equal lengths")
        keys = klist
        dtype = klist[0].dtype
        for k in klist:
            check_key_dtype(k.dtype, x64=x64)
    elif not is_iterator:
        check_key_dtype(keys.dtype, x64=x64)
        dtype = np.dtype(str(keys.dtype)) if keys.dtype != "bfloat16" else keys.dtype
        if getattr(keys, "ndim", 1) == 2:
            n_local = int(keys.shape[1])
            n = int(keys.shape[0] * keys.shape[1])
        elif getattr(keys, "ndim", 1) > 2:
            raise ValueError("keys must be flat, (p, n_local), or an iterator")
        else:
            n = int(keys.shape[0])

    if multikey and is_iterator:
        raise ValueError("multi-key sorts need array inputs")
    return _Req(
        keys=keys, values=values, want=want, descending=descending,
        config=config or SortConfig(), investigator=investigator,
        n=n, n_local=n_local, dtype=dtype, is_iterator=is_iterator,
        multikey=multikey,
    )


def _dtype_width(dt) -> int:
    """Key-lane width in bits (bfloat16 has no numpy dtype string)."""
    if dt is None:
        return 32
    if str(dt) == "bfloat16":
        return 16
    return 8 * np.dtype(str(dt)).itemsize


def _make_plan(req: _Req, where, limits: SortLimits | None,
               x64: bool | None = None) -> SortPlan:
    limits = limits or SortLimits()
    eff_x64 = _effective_x64(limits) if x64 is None else bool(x64)
    if limits.decode not in ("device", "host"):
        raise ValueError(
            f'SortLimits.decode must be "device" or "host", got '
            f"{limits.decode!r}"
        )
    mesh = None
    axis_name = "data"
    reasons: list[str] = []
    cost_source = "static"
    cost_predicted = None

    choice = None
    if where is not None:
        if isinstance(where, str):
            choice = where
            reasons.append(f"caller pinned backend {where!r}")
        elif isinstance(where, (tuple, list)) and len(where) == 2:
            choice, (mesh, axis_name) = "mesh", where
            reasons.append("caller provided (mesh, axis)")
        else:  # a jax.sharding.Mesh
            choice, mesh = "mesh", where
            reasons.append("caller provided a device mesh")
    elif req.is_iterator:
        choice = "stream"
        reasons.append("iterator input: size unknown, not host-resident")
    else:
        # size rule — the one placement an ambient cost model may
        # override (pins and iterator inputs are constraints, not costs)
        if (limits.stream_threshold is not None
                and req.n > limits.stream_threshold):
            static_choice = "stream"
            static_reason = (
                f"n={req.n} exceeds stream_threshold="
                f"{limits.stream_threshold}"
            )
        else:
            static_choice = "sim"
            static_reason = (
                f"n={req.n} fits one device program "
                f"(stream_threshold={limits.stream_threshold})"
            )
        choice, cost_source, cost_predicted = _consult_cost_model(
            req, static_choice, static_reason, reasons
        )
    if choice not in BACKENDS:
        raise KeyError(f"unknown backend {choice!r}; have {sorted(BACKENDS)}")
    if choice == "mesh" and mesh is None:
        raise ValueError('backend "mesh" needs where=<Mesh> or (mesh, axis)')
    if req.is_iterator and choice != "stream":
        raise ValueError(
            f"iterator inputs can only run on the stream backend, "
            f"not {choice!r} (sim/mesh need the whole array resident)"
        )
    if any(req.descending):
        reasons.append("descending: order-flip key encoding (keyenc.flip)")
    multikey_decision = None
    packspec = None
    if req.multikey:
        multikey_decision, packspec = _decide_multikey(req, limits, reasons,
                                                       x64=eff_x64)
    if req.want == "order":
        reasons.append("argsort: provenance-index payload over the kv sort")

    n_procs = limits.n_procs
    if req.n_local is not None and choice == "sim":
        n_procs = int(req.keys.shape[0])
        reasons.append(f"(p={n_procs}, n_local) input: rows are the shards")
    elif choice == "mesh":
        axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
        n_procs = 1
        for a in axes:
            n_procs *= mesh.shape[a]
        reasons.append(f"mesh sort axis spans {n_procs} device(s)")
    if limits.decode == "host":
        reasons.append(
            'decode="host": legacy numpy materialization (differential-'
            "testing / baseline path)"
        )
    chunk_elems = limits.chunk_elems
    if choice == "stream":
        chunk_elems = _pick_chunk_elems(req, limits.chunk_elems, reasons)
    if req.is_iterator:
        # chunk dtypes are unknowable until staging; record the widest
        # width the mode admits (runs.py checks each chunk against it)
        key_width = 64 if eff_x64 else 32
    elif req.multikey:
        key_width = max(_dtype_width(k.dtype) for k in req.keys)
    else:
        key_width = _dtype_width(req.dtype)
    if eff_x64 and key_width > 32:
        reasons.append(
            f"x64 mode: {key_width}-bit key lane admitted "
            f"(sentinels/staging widen per dtype)"
        )
    if req.config.use_pallas and not req.is_iterator:
        dts = ([packspec.pack_dtype] if packspec is not None
               else [k.dtype for k in req.keys] if req.multikey else [req.dtype])
        if req.values is not None:
            dts.append(req.values.dtype)
        reasons.append(f"row sorts and merges: {kops.kernel_path(*dts)}")
    return SortPlan(
        backend=choice, n_procs=n_procs, chunk_elems=chunk_elems,
        limits=limits, reasons=tuple(reasons), mesh=mesh, axis_name=axis_name,
        decode=limits.decode, multikey=multikey_decision, packspec=packspec,
        cost_source=cost_source, cost_predicted=cost_predicted,
        key_width=key_width, x64=eff_x64,
    )


# the placements the size rule arbitrates between — mesh needs caller
# topology and is never chosen on cost alone
_COST_CANDIDATES = ("sim", "stream")


def _consult_cost_model(req: _Req, static_choice: str, static_reason: str,
                        reasons: list):
    """Size-rule placement, possibly overridden by the ambient cost model.

    Returns ``(choice, cost_source, cost_predicted)``. With no tuner
    installed — or a cold/low-confidence store — the static choice and
    its exact reason string come back untouched, so cold starts plan
    bit-identically to the pre-tune library."""
    tuner = _tune.current()
    if tuner is None:
        reasons.append(static_reason)
        return static_choice, "static", None
    winner, preds = tuner.model.choose(
        "sort", _COST_CANDIDATES, str(req.dtype), req.n,
        min_confidence=tuner.min_confidence,
    )
    predicted = {
        b: {"us": p.us, "confidence": p.confidence}
        for b, p in preds.items() if p is not None
    } or None
    if winner is None:
        _tune.note_plan("static")
        reasons.append(static_reason)
        return static_choice, "static", predicted
    _tune.note_plan("model")
    costs = " ".join(
        f"{b}~{preds[b].us:.0f}us" for b in sorted(preds)
    )
    if winner == static_choice:
        reasons.append(
            f"cost model confirms the static rule ({static_reason}): {costs}"
        )
    else:
        reasons.append(
            f"cost model overrides the static rule ({static_reason}): "
            f"{costs} -> {winner} predicted fastest"
        )
    return winner, "model", predicted


def _pick_chunk_elems(req: _Req, base: int, reasons: list) -> int:
    """Stream chunk sizing from measured per-chunk sort cost.

    Considers halving/doubling the configured chunk (clamped to
    [2^12, 2^22]) and keeps the candidate with the best predicted
    chunk-sort *throughput*; any candidate below the confidence bar
    keeps the static size — resizing on a hunch would thrash the
    compiled-program cache."""
    tuner = _tune.current()
    if tuner is None:
        return base
    dtype = str(req.dtype) if req.dtype is not None else "float32"
    scored = []
    for cand in sorted({max(1 << 12, base // 2), base,
                        min(1 << 22, base * 2)}):
        pred = tuner.model.predict("chunk_sort", "stream", dtype, cand)
        if pred is None or pred.confidence < tuner.min_confidence:
            return base
        scored.append((cand / pred.us, cand))
    best = max(scored)[1]
    if best != base:
        reasons.append(
            f"cost model: chunk_elems {base} -> {best} "
            f"(best predicted chunk-sort throughput)"
        )
    return best


def _decide_multikey(req: _Req, limits: SortLimits, reasons: list,
                     x64: bool = False):
    """Pack-vs-LSD decision for a multi-key request, with its reason.

    ``"auto"`` packs whenever the tuple's (measured or declared) bit
    widths fit the mode's pack budget (31 bits; 63 under x64 mode) —
    one ascending integer exchange pass instead of one stable pass per
    key; anything unpackable (wide tuples, unpackable dtypes, NaN
    floats) records why and falls back to the LSD construction."""
    k = len(req.keys)
    if limits.multikey not in ("auto", "packed", "lsd"):
        raise ValueError(
            f'SortLimits.multikey must be "auto", "packed" or "lsd", '
            f"got {limits.multikey!r}"
        )
    if limits.multikey == "lsd":
        reasons.append(
            f"{k}-key lexicographic: LSD stable-argsort passes "
            f"(SortLimits.multikey='lsd')"
        )
        return "lsd", None
    ranks: dict = {}
    budget = (keyenc.PACK_BUDGET_BITS_X64 if x64
              else keyenc.PACK_BUDGET_BITS)
    spec, why = keyenc.plan_pack(req.keys, req.descending, limits.key_bits,
                                 ranks=ranks, budget=budget)
    if spec is not None:
        # hand the measured rank arrays to the execution path: packing
        # reuses them instead of redoing the O(n * n_keys) transforms
        req.pack_ranks = ranks
        word = np.dtype(spec.pack_dtype).name
        reasons.append(
            f"{k}-key lexicographic: packed into ONE {word} sort ({why})"
        )
        return "packed", spec
    if limits.multikey == "packed":
        raise ValueError(
            f"SortLimits(multikey='packed') but this key tuple cannot "
            f"pack: {why}"
        )
    reasons.append(f"{k}-key lexicographic: LSD stable-argsort passes ({why})")
    return "lsd", None


# ------------------------------------------------------------- execution


def pad_grid(flat: np.ndarray, p: int, per: int, fill) -> np.ndarray:
    """Pack a flat host array into the (p, per) shard grid, sentinel
    padded, spreading the real elements EVENLY across rows (balanced
    contiguous blocks) rather than packing them head-first.

    Head-first packing makes every trailing row pure sentinel for
    far-from-capacity inputs — a degenerate shard for the investigator,
    whose ideal-rank division then funnels the whole head of the
    sentinel-tied range at one destination and overflows the static
    buckets (the serve coalescing pathology: a per-request capacity-
    ladder retry on every flush of a far-from-pow2 bucket). With each
    row holding the same real/pad occupancy, per-destination traffic
    stays inside the standard ``SortConfig.capacity`` slack and steady-
    state ladder retries are zero. Pads still carry the order-maximal
    sentinel, so they sort to the global tail and unpadding is
    unchanged. The canonical pad helper — ``stream/runs.py`` and the
    SortService reuse it for chunk staging."""
    n = flat.shape[0]
    buf = np.full((p, per), fill, flat.dtype)
    base, extra = divmod(n, p)
    off = 0
    for r in range(p):
        take = base + (1 if r < extra else 0)
        buf[r, :take] = flat[off : off + take]
        off += take
    return buf


def unpad_grid(values, counts, m: int) -> np.ndarray:
    """Concatenate valid per-shard prefixes, drop sentinel padding (pads
    sort to the global tail, so the first m slots are the real data).
    One bulk device->host transfer, then numpy slicing."""
    values = np.asarray(values)
    counts = np.asarray(counts)
    parts = [values[i, : int(counts[i])] for i in range(values.shape[0])]
    return np.concatenate(parts)[:m]


_pad_grid = pad_grid
_unpad_grid = unpad_grid


def _trim_pad_counts(counts, pad: int) -> np.ndarray:
    """Per-shard counts with the sentinel pads removed. Pads carry the
    order-maximal sentinel, so they occupy the global tail — walk shards
    from the back subtracting until ``pad`` elements are gone. Keeps
    SortOutput.counts/imbalance() honest for non-divisible inputs (the
    raw backend result keeps the padded counts)."""
    counts = np.asarray(counts).copy()
    i = counts.shape[0] - 1
    while pad > 0 and i >= 0:
        take = min(int(counts[i]), pad)
        counts[i] -= take
        pad -= take
        i -= 1
    return counts


def _stable_order_fix(ks: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Restore exact stability of an argsort permutation.

    The investigator deliberately splits tied key ranges across
    destinations to balance load (paper Fig. 3c), so the raw index
    payload comes back segment-interleaved within runs of equal keys.
    Reordering the payload inside each equal-key segment (a cheap host
    pass over already-sorted keys) yields exactly
    ``np.argsort(kind="stable")``.
    """
    if idx.size <= 1:
        return idx
    seg = np.empty(ks.size, np.int64)
    seg[0] = 0
    np.cumsum(ks[1:] != ks[:-1], out=seg[1:])
    return idx[np.lexsort((idx, seg))]


def _stitch_bucket_ties(ks: np.ndarray, vs: np.ndarray, bucket_sizes,
                        descending: bool = False) -> np.ndarray:
    """Boundary stitch for the stream backend's DEVICE tie fix.

    With ``segment_stable=True`` the per-bucket segment-stable pass runs
    on device inside each bucket merge (``external_merge_kv``), so the
    payload is already exactly stable WITHIN every bucket. The one case
    the per-bucket pass cannot see is a run of equal keys split ACROSS
    bucket boundaries (the investigator splits tied ranges to balance
    load, paper Fig. 3c). At each cumulative bucket offset whose
    neighbors tie, expand to the full equal-key run and sort the payload
    ascending — within an equal-key run of a provenance (iota) payload,
    exact stability IS ascending payload order, and each side arrives
    already ascending, so the sort merely interleaves the two sides.
    O(crossing runs) host work instead of the legacy whole-array pass.
    """
    if not bucket_sizes or len(bucket_sizes) <= 1 or ks.size <= 1:
        return vs
    n = ks.shape[0]
    rev = ks[::-1] if descending else None
    out = None
    off = 0
    for s in bucket_sizes[:-1]:
        off += int(s)
        if off <= 0 or off >= n or ks[off - 1] != ks[off]:
            continue
        v = ks[off]
        if descending:
            lo = n - int(np.searchsorted(rev, v, side="right"))
            hi = n - int(np.searchsorted(rev, v, side="left"))
        else:
            lo = int(np.searchsorted(ks, v, side="left"))
            hi = int(np.searchsorted(ks, v, side="right"))
        if out is None:
            out = np.array(vs)  # the D2H buffer may be read-only
        out[lo:hi] = np.sort(out[lo:hi])
    return vs if out is None else out


def _sentinel(dtype) -> np.ndarray:
    import jax.numpy as jnp

    return np.asarray(kops.sentinel_for(jnp.dtype(dtype)))


def _prep_single(req: _Req, *, raw: bool = False, x64: bool = False):
    """Encode the key array + build the payload for a single-key sort.

    Returns (enc_keys flat-or-grid np/jnp, payload or None, descending,
    keys_only_reverse) — keys-only descending sorts run ascending on the
    raw keys and reverse at materialization (no key-range restriction).
    ``raw=True`` skips the host-side order-flip encode (the sentinel
    check and payload construction still run): the stream backend's
    device-decode path flips each chunk on device after H2D, so a
    whole-array host flip here would be allocated and thrown away.
    ``x64``: the request's resolved mode — past 2^31 elements the
    provenance payload must widen to int64, which only the mode admits
    (``keyenc.provenance_dtype`` raises the opt-in TypeError otherwise).
    """
    descending = req.descending[0]
    keys = req.keys
    payload = None
    if req.needs_payload:
        # a key colliding with the (encoded-space) padding sentinel —
        # dtype max ascending, dtype min descending — leaks sentinel
        # payload into the output via the exchange's in-program pads,
        # front-end padding or not: reject loudly, always (for packed
        # multi-key keys the packspec names the saturated source tuple)
        keyenc.check_payload_keys(keys, descending, packspec=req.packspec)
        enc = keys if (raw or not descending) else keyenc.encode(keys, True)
        if req.want == "order":
            payload = np.arange(
                req.n, dtype=keyenc.provenance_dtype(req.n, x64=x64)
            )
            if req.n_local is not None:
                payload = payload.reshape(keys.shape)
        else:
            payload = req.values
        return enc, payload, descending, False
    # keys-only: ascending sort + reverse is exact and unrestricted
    return keys, None, descending, descending


def _grid_materialize(req: _Req, plan: SortPlan, keys_grid, values_grid,
                      counts, m: int, descending: bool, reverse: bool):
    """Materialization closure for the grid-shaped (sim / mesh) backends.

    decode="device" (default): one fused jitted program
    (``keyenc.decode_grid``) runs the compaction gather, the inverse
    order-flip, the stable-argsort tie fix and the keys-only reverse on
    device, and the host does a single D2H copy of exactly m elements
    per array. decode="host": the legacy numpy path (per-row unpad +
    concat, host flip / reverse / ``_stable_order_fix``), kept for
    differential testing and as the decode benchmark baseline.
    """
    want_order = req.want == "order"
    tr = req.trace

    if plan.decode == "device":
        from repro.kernels.ops import _next_pow2

        # dispatch the fused decode program NOW (jax dispatch is async):
        # it executes on device behind the caller's back, exactly like
        # the sort itself, so the closure below — the first .keys /
        # .values access — is a D2H copy plus a host slice. The program
        # length rounds n up to a power-of-two shape bucket so varied
        # request sizes (a serving workload) reuse O(log) compiled
        # decode programs instead of one per distinct n. Tracing fences
        # the program inside the "decode" span — losing the overlap but
        # charging the decode to the right phase.
        with _span(tr, "decode") as sp:
            dk, dv = keyenc.decode_grid(
                keys_grid, counts, values_grid, m=_next_pow2(m),
                descending=descending and not reverse, want_order=want_order,
                packspec=req.packspec,
            )
            if tr is not None:
                sp.fence((dk, dv))

        def materialize():
            with _span(tr, "d2h"):
                if isinstance(dk, tuple):
                    # packed multi-key: the program unpacked the columns
                    ks = tuple(np.asarray(c)[:m] for c in dk)
                else:
                    ks = np.asarray(dk)[:m]
                    if reverse:
                        # keys-only descending ran ascending on the raw
                        # keys: the descending view is the first m
                        # positions read backwards (a stride trick, not a
                        # host pass)
                        ks = ks[::-1]
                return ks, (np.asarray(dv)[:m] if dv is not None else None)

        return materialize

    def materialize():
        # host decode: the D2H copy and the numpy decode are one phase
        with _span(tr, "decode", path="host"):
            if values_grid is None:
                ks, vs = _unpad_grid(keys_grid, counts, m), None
            else:
                ks = _unpad_grid(keys_grid, counts, m)
                vs = _unpad_grid(values_grid, counts, m)
                if want_order:
                    # the tie fix must see the PACKED keys when unpacking
                    # follows: a packed tie is exactly an all-columns tie
                    vs = _stable_order_fix(ks, vs)
            if reverse:
                ks = ks[::-1].copy()
            elif descending:
                ks = keyenc.decode_np(ks, True)
            if req.packspec is not None:
                ks = keyenc.unpack_np(ks, req.packspec)
            return ks, vs

    return materialize


def _measured_hook(p: int, n_local: int):
    """Measured-imbalance ladder start (``overflow.measured_capacity_need``)
    for the sim/mesh retry loops — only when a tuner is ambient, so the
    cold-start ladder walks exactly the pre-tune geometric steps."""
    if _tune.current() is None:
        return None
    return measured_capacity_need(p, n_local)


def _sort_fused(req: _Req, plan: SortPlan, backend: str, program: Callable,
                counts_of: Callable, p: int, n_local: int, pad: int) -> SortOutput:
    """Run an in-core backend's one fused sort program through the
    overflow ladder, traced or not; the caller adds the decode.

    ``dispatch`` enqueues an attempt and ``overflow_check`` is the
    host's wait on its overflow flag and send counts: the program's
    outputs are ready together, so this is where the host waits out the
    sort. A trace gets one fenced ``sort`` span around the ladder, with
    the result's per-processor counts and their imbalance; the program's
    per-phase device split is in a profile, under its
    ``jax.named_scope`` phases."""
    tr = req.trace

    def attempt(cfg):
        with _span(tr, "dispatch"):
            res = program(cfg)
        with _span(tr, "overflow_check"):
            np.asarray(res.overflowed)
            np.asarray(res.send_counts)
        return res

    with _span(tr, "sort", phases="local_sort+splitter+exchange+merge") as sp:
        res, cfg_used, retries = run_with_capacity_retry(
            attempt, req.config, plan.limits.policy(),
            measured=_measured_hook(p, n_local),
        )
        out = SortOutput(
            _meta(req, plan, backend, cfg_used, retries),
            counts=_trim_pad_counts(counts_of(res), pad),
            overflowed=bool(np.any(np.asarray(res.overflowed))),
            send_counts=np.asarray(res.send_counts),
            raw=res,
        )
        sp.fence(res)
        sp.counts(out.counts)
    return out


def _exec_sim(req: _Req, plan: SortPlan) -> SortOutput:
    import jax.numpy as jnp

    tr = req.trace
    with _span(tr, "encode"):
        enc, payload, descending, reverse = _prep_single(req, x64=plan.x64)
    p = plan.n_procs
    m = req.n
    with _span(tr, "stage") as sp:
        if req.n_local is not None:
            xk = jnp.asarray(enc)
            xv = jnp.asarray(payload) if payload is not None else None
            pad = 0
        else:
            per = max(1, -(-req.n // p))
            pad = p * per - m
            if pad == 0:
                # divisible: no host round-trip, the array stays
                # device-resident
                xk = jnp.asarray(enc).reshape(p, per)
                xv = (jnp.asarray(payload).reshape(p, per)
                      if payload is not None else None)
            else:
                flat = np.asarray(enc).reshape(-1)
                xk = jnp.asarray(_pad_grid(flat, p, per, _sentinel(flat.dtype)))
                xv = None
                if payload is not None:
                    vflat = np.asarray(payload).reshape(-1)
                    xv = jnp.asarray(
                        _pad_grid(vflat, p, per, _sentinel(vflat.dtype))
                    )
        if tr is not None:
            sp.fence((xk, xv))  # charge the H2D copy to staging

    if xv is None:
        program = lambda cfg: sim.sample_sort_sim(
            xk, cfg, investigator=req.investigator
        )
    else:
        program = lambda cfg: sim.sample_sort_sim_kv(
            xk, xv, cfg, investigator=req.investigator
        )
    out = _sort_fused(req, plan, "sim", program, lambda r: r.counts, p,
                      int(xk.shape[1]), pad)
    # the decode program is dispatched last, so its span ends the call
    res = out.raw
    kg, vg = (res.values, None) if xv is None else (res.keys, res.values)
    out._materialize = _grid_materialize(req, plan, kg, vg, res.counts, m,
                                         descending, reverse)
    return out


def _exec_mesh(req: _Req, plan: SortPlan) -> SortOutput:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    tr = req.trace
    with _span(tr, "encode"):
        enc, payload, descending, reverse = _prep_single(req, x64=plan.x64)
    axes = plan.axis_name if isinstance(plan.axis_name, tuple) else (plan.axis_name,)
    p = 1
    for a in axes:
        p *= plan.mesh.shape[a]
    per = max(1, -(-req.n // p))
    m = req.n
    # host inputs are copied straight into each device's shard
    shards = NamedSharding(plan.mesh, P(axes))

    def stage(x):
        if isinstance(x, jax.Array):
            # a device array (possibly already mesh-sharded) goes straight
            # to shard_map — no host materialization round-trip
            return x.reshape(-1)
        return jax.device_put(np.asarray(x).reshape(-1), shards)

    with _span(tr, "stage") as sp:
        pad = p * per - m
        if pad == 0:
            xk = stage(enc)
            xv = stage(payload) if payload is not None else None
        else:
            flat = np.asarray(enc).reshape(-1)
            xk = stage(_pad_grid(flat, p, per, _sentinel(flat.dtype)))
            xv = None
            if payload is not None:
                vflat = np.asarray(payload).reshape(-1)
                xv = stage(_pad_grid(vflat, p, per, _sentinel(vflat.dtype)))
        if tr is not None:
            sp.fence((xk, xv))

    if xv is None:
        program = lambda cfg: sample_sort.distributed_sort(
            xk, plan.mesh, plan.axis_name, cfg, investigator=req.investigator
        )
    else:
        program = lambda cfg: sample_sort.distributed_sort_kv(
            xk, xv, plan.mesh, plan.axis_name, cfg, investigator=req.investigator
        )
    out = _sort_fused(req, plan, "mesh", program, lambda r: r.count, p, per,
                      pad)
    # the rows are sharded over the sort axis; the device decode gathers
    # them under this mesh (keyenc.decode_grid), dispatched last so that
    # its span ends the call
    res = out.raw
    kg, vg = (res.values, None) if xv is None else (res.keys, res.values)
    with jax.set_mesh(plan.mesh):
        out._materialize = _grid_materialize(req, plan, kg, vg, res.count, m,
                                             descending, reverse)
    return out


def _exec_stream(req: _Req, plan: SortPlan) -> SortOutput:
    from repro.stream import StreamConfig, sort_external_kv, sort_stream

    if req.is_iterator and req.needs_payload:
        raise ValueError(
            "streamed argsort/kv over an iterator needs array inputs "
            "(the index payload must chunk with the keys)"
        )
    scfg = StreamConfig(
        chunk_elems=plan.chunk_elems,
        n_procs=plan.n_procs,
        sort=req.config,
        max_doublings=plan.limits.max_doublings,
        growth=plan.limits.growth,
        # the request's resolved mode rides into per-chunk staging: 64-bit
        # iterator chunks are admitted (or rejected, naming the opt-in)
        # by the same door check, at the earliest point their dtype exists
        x64=plan.x64,
    )
    # device decode pushes the order-flip INTO the stream pipeline: every
    # chunk is flip-encoded on device right after H2D and flip-decoded on
    # device right before each output D2H (stream/runs.py +
    # stream/external_merge.py), so descending keys-only results stream —
    # chunks() yields descending chunks in bounded memory — and kv
    # results skip the whole-array host flip (raw=True below keeps
    # _prep_single from allocating one just to be discarded). Under
    # decode="host" the legacy paths remain: keys-only reverses the
    # materialized output, kv flip-decodes on host.
    device_decode = plan.decode == "device"
    tr = req.trace
    with _span(tr, "encode"):
        enc, payload, descending, reverse = _prep_single(
            req, raw=device_decode, x64=plan.x64)
    stream_desc = device_decode and descending
    if stream_desc:
        reverse = False  # enc is already raw; the pipeline encodes on device
    if not req.is_iterator:
        enc = np.asarray(enc).reshape(-1)
    meta = _meta(req, plan, "stream", req.config, 0)

    # per-chunk ladder accounting: pass 1 fills stats["chunk_retries"]
    # when it runs (lazily, at materialization / first chunk), and the
    # meta is updated in place — SortMeta is mutable for exactly this
    stats: dict = {}

    def _account() -> None:
        cr = stats.get("chunk_retries")
        if cr is not None:
            meta.chunk_retries = tuple(cr)
            meta.retries, _ = ladder_totals(cr)

    def _accounted(g):
        for c in g:
            _account()  # pass 1 has run once the first chunk arrives
            yield c
        _account()

    if payload is None:
        gen = _accounted(
            sort_stream(enc, scfg, investigator=req.investigator,
                        stats=stats, descending=stream_desc, trace=tr)
        )
        if reverse:
            out = SortOutput(meta, materialize=None)

            def materialize():
                parts = list(gen)
                out.counts = np.asarray([p.shape[0] for p in parts], np.int64)
                ks = (np.concatenate(parts) if parts
                      else np.empty(0, req.dtype or np.float32))
                return ks[::-1].copy(), None

            out._materialize = materialize
            return out
        return SortOutput(meta, chunks=gen)

    vflat = np.asarray(payload).reshape(-1)
    # want="order" under the default device decode runs the segment-
    # stable tie fix ON DEVICE, per bucket, inside each bucket merge
    # (bounded memory: the device pass sees one O(bucket) working set at
    # a time); only equal-key runs that the investigator split ACROSS
    # buckets need the host boundary stitch below. decode="host" keeps
    # the legacy whole-array host pass as the differential baseline.
    seg_stable = device_decode and req.want == "order"

    def materialize():
        ks, vs = sort_external_kv(enc, vflat, scfg,
                                  investigator=req.investigator, stats=stats,
                                  descending=stream_desc, trace=tr,
                                  segment_stable=seg_stable)
        _account()
        if req.want == "order":
            if seg_stable:
                vs = _stitch_bucket_ties(ks, vs, stats.get("bucket_sizes"),
                                         descending=stream_desc)
            else:
                vs = _stable_order_fix(ks, vs)
        if descending and not stream_desc:
            ks = keyenc.decode_np(ks, True)
        return ks, vs

    return SortOutput(meta, materialize=materialize)


def _meta(req: _Req, plan: SortPlan, backend: str, cfg, retries: int) -> SortMeta:
    orders = tuple("desc" if d else "asc" for d in req.descending)
    return SortMeta(
        backend=backend,
        plan=plan,
        config=cfg,
        retries=retries,
        n=req.n or 0,
        want=req.want,
        order=orders[0] if len(orders) == 1 else orders,
        n_keys=len(req.keys) if req.multikey else 1,
        n_local=req.n_local,
        dtype=req.dtype,
        multikey=plan.multikey if req.multikey else None,
        trace=req.trace,
    )


# ------------------------------------------------------------ multi-key


def _exec_packed_multikey(req: _Req, plan: SortPlan) -> SortOutput:
    """Lexicographic sort as ONE packed single-key pass.

    The tuple is fused into a non-negative integer key — int32, or int64
    for x64-mode wide packs (``keyenc.pack_keys``
    — per-key order flips and monotone transforms live inside the bit
    fields), so the plain ascending single-key machinery of whichever
    backend the planner chose does the whole job in one exchange pass;
    the fused decode unpacks the columns back out (on device for
    sim/mesh under ``decode="device"``, on host for the stream backend
    and the legacy decode path). Payload-bearing requests run as
    ``want="order"`` over the packed key — the device tie fix restores
    exact stability on packed ties (= all-columns ties), which makes the
    resulting permutation, and any gathered values, bit-identical to the
    LSD construction and to ``np.lexsort``.
    """
    spec = plan.packspec
    with _span(req.trace, "encode", pack=spec.describe() if spec else None):
        packed = keyenc.pack_keys(req.keys, spec, ranks=req.pack_ranks)
    sub_want = "order" if req.needs_payload else "values"
    sub = _Req(
        keys=packed, values=None, want=sub_want, descending=(False,),
        config=req.config, investigator=req.investigator, n=req.n,
        n_local=None, dtype=np.dtype(spec.pack_dtype), is_iterator=False,
        multikey=False, packspec=spec, trace=req.trace,
    )
    out = BACKENDS[plan.backend].execute(sub, plan)
    # the wrapper's meta carries the trace; the sub-result materializing
    # inside materialize() below must not freeze it prematurely
    out.meta.trace = None
    meta = _meta(req, plan, plan.backend, out.meta.config, out.meta.retries)
    if out._chunks is not None and plan.decode == "device":
        # stream keys-only: unpack each packed output chunk ON DEVICE
        # (keyenc.unpack_chunk — the same fused field decode
        # decode_grid runs for sim/mesh, compiled per (spec, pow2 len)),
        # so packed multi-key results stream via .chunks() in bounded
        # memory instead of host-unpacking at materialization
        wrapper = SortOutput(
            meta, overflowed=out.overflowed,
            send_counts=out.send_counts, raw=out.raw,
        )

        def _unpacked_chunks():
            for c in out.chunks():
                yield keyenc.unpack_chunk(c, spec)
            # the stream backend fills counts/retries lazily — sync them
            # once the sub-stream is exhausted
            wrapper.counts = out.counts
            wrapper.overflowed = out.overflowed
            meta.retries = out.meta.retries
            meta.config = out.meta.config
            meta.chunk_retries = out.meta.chunk_retries

        wrapper._chunks = _unpacked_chunks()
        return wrapper
    wrapper = SortOutput(
        meta, counts=out.counts, overflowed=out.overflowed,
        send_counts=out.send_counts, raw=out.raw, materialize=None,
    )

    def materialize():
        ks, perm = out.keys, out.values
        if not isinstance(ks, tuple):
            # stream / host paths return the packed flat array
            ks = keyenc.unpack_np(np.asarray(ks), spec)
        # the stream backend fills counts/retries lazily — sync them
        wrapper.counts = out.counts
        wrapper.overflowed = out.overflowed
        meta.retries = out.meta.retries
        meta.config = out.meta.config
        meta.chunk_retries = out.meta.chunk_retries
        if req.want == "order":
            return ks, perm
        if req.values is not None:
            # gather user values through the exactly-stable permutation:
            # bit-identical to the LSD passes' composition
            return ks, np.asarray(req.values)[np.asarray(perm)]
        return ks, None

    wrapper._materialize = materialize
    return wrapper


def _exec_multikey(req: _Req, plan: SortPlan) -> SortOutput:
    """Lexicographic sort: one packed pass when the planner fused the
    tuple (``plan.multikey == "packed"``), else LSD stable-argsort
    passes over the backend.

    LSD: perm = argsort(k_last); then for each earlier key:
    perm = perm[argsort(k[perm])] — every pass is the backend's exactly
    stable kv sort, so the composition matches np.lexsort.
    """
    if plan.multikey == "packed":
        return _exec_packed_multikey(req, plan)
    backend = BACKENDS[plan.backend]

    def sub_sort(karr: np.ndarray, descending: bool) -> SortOutput:
        sub = _Req(
            keys=karr, values=None, want="order",
            descending=(descending,), config=req.config,
            investigator=req.investigator, n=int(karr.shape[0]), n_local=None,
            dtype=karr.dtype, is_iterator=False, multikey=False,
            trace=req.trace,
        )
        out = backend.execute(sub, plan)
        # LSD passes materialize mid-flight; only the top-level output
        # may freeze the shared trace
        out.meta.trace = None
        return out

    klist = req.keys
    perm = np.asarray(sub_sort(klist[-1], req.descending[-1]).values)
    last = None
    for karr, desc in zip(klist[-2::-1], req.descending[-2::-1]):
        last = sub_sort(karr[perm], desc)
        perm = perm[np.asarray(last.values)]

    sorted_keys = tuple(k[perm] for k in klist)
    values = req.values[perm] if req.values is not None else None
    meta = _meta(req, plan, plan.backend, req.config,
                 last.meta.retries if last is not None else 0)
    if req.trace is not None:
        # the LSD composition is fully materialized here — no lazy
        # _force will run, so the trace completes now
        req.trace.materialized()
    if req.want == "order":
        return SortOutput(meta, keys=sorted_keys, values=perm,
                          counts=last.counts if last is not None else None)
    return SortOutput(meta, keys=sorted_keys, values=values,
                      counts=last.counts if last is not None else None)


# --------------------------------------------------------------- public


register_backend("sim", _exec_sim, "virtual processors on one device")
register_backend("mesh", _exec_mesh, "shard_map over a real mesh axis")
register_backend("stream", _exec_stream, "out-of-core runs/partition/merge")


def make_plan(keys, values=None, *, order="asc", want="values", where=None,
              limits=None, config=None, investigator=True) -> SortPlan:
    eff_x64 = _effective_x64(limits)
    req = _normalize(keys, values, order=order, want=want, config=config,
                     investigator=investigator, x64=eff_x64)
    return _make_plan(req, where, limits, x64=eff_x64)


def execute_request(req: _Req, plan: SortPlan, ctx=None) -> SortOutput:
    """Execute an already-normalized request on an already-made plan.

    ``repro.sort`` plans and dispatches in one call; the async serving
    front end (``repro.serve.sortd``) plans every request at admission
    time (via ``serve_profile``) and dispatches later from its flush
    loop — both funnel through here, so serving traffic cannot bypass
    the planner's backend decision.

    ``ctx`` is the request's ``obs.flight.RequestContext`` when the
    serve tier minted one: the executed backend is stamped on it and
    its ``trace_id`` lands on the result meta, so the flight recorder
    can attribute this dispatch end to end."""
    _SORTS_TOTAL.labels(backend=plan.backend).inc()
    if ctx is not None:
        ctx.backend = plan.backend
    if req.n == 0:
        meta = _meta(req, plan, plan.backend, req.config, 0)
        if ctx is not None:
            meta.trace_id = ctx.trace_id
        if req.multikey:
            keys_out = tuple(np.empty(0, k.dtype) for k in req.keys)
        else:
            # req.dtype is None only for iterator inputs that never
            # yielded a chunk; default to the library's 32-bit mode
            keys_out = np.empty(0, req.dtype or np.float32)
        vals = np.empty(0, np.int32) if req.want == "order" else None
        out = SortOutput(meta, keys=keys_out, values=vals,
                         counts=np.zeros(0, np.int64))
        out._chunks = iter(())
        if req.trace is not None:
            req.trace.materialized()  # empty result: nothing lazy left
        return out
    t0 = time.perf_counter() if _tune.current() is not None else None
    if req.multikey:
        out = _exec_multikey(req, plan)
    else:
        out = BACKENDS[plan.backend].execute(req, plan)
    if ctx is not None:
        out.meta.trace_id = ctx.trace_id
    if t0 is not None:
        if out._keys is not None:
            # already materialized (LSD multi-key): the sort is complete
            _tune.record_sort(out.meta, time.perf_counter() - t0)
        else:
            # lazy result: SortOutput records at materialization, giving
            # the cost model the full dispatch->D2H wall time
            out.meta.t_start = t0
    return out


def serve_profile(keys, values=None, *, order="asc", want="values",
                  where=None, limits=None, config=None, investigator=True):
    """Normalize + plan one serving request, and decide coalescability.

    Returns ``(req, plan, batchable)``. ``batchable`` is True when the
    request may be stacked into ONE vmapped same-shape-bucket program by
    the async sort server's flush engine: a keys-only sort that the
    planner routed to the sim backend and that is either single-key
    (ascending OR descending — the order-flip encode/decode is fused
    into the vmapped program, see ``sim.sample_sort_sim_flat``) or a
    PACKED multi-key tuple (``plan.multikey == "packed"`` — the staged
    data is the packed ascending int32 array and the in-program decode
    unpacks the columns; such requests bucket per PackSpec, so declare
    ``SortLimits.key_bits`` to keep the spec — and therefore the bucket
    — stable across requests). Anything else (payloads, argsort, LSD
    multi-key, (p, n_local) global views, stream-/mesh-bound requests)
    must dispatch through ``execute_request`` individually — still
    planner-routed, just not vmap-coalesced."""
    eff_x64 = _effective_x64(limits)
    req = _normalize(keys, values, order=order, want=want, config=config,
                     investigator=investigator, x64=eff_x64)
    plan = _make_plan(req, where, limits, x64=eff_x64)
    batchable = (
        plan.backend == "sim"
        and (not req.multikey or plan.multikey == "packed")
        and not req.needs_payload
        and req.n_local is None
        and not req.is_iterator
        and req.n > 0
    )
    return req, plan, batchable


def execute(keys, values=None, *, order="asc", want="values", where=None,
            limits=None, config=None, investigator=True) -> SortOutput:
    lim = limits or SortLimits()
    eff_x64 = _effective_x64(lim)
    # an ambient obs.trace() block wins; else SortLimits(trace=True)
    # builds a per-sort trace that freezes when the output materializes
    tr = obs_tracing.current_trace()
    if tr is None and lim.trace and obs_tracing.enabled():
        tr = obs_tracing.Trace()
    with _span(tr, "plan"):
        req = _normalize(keys, values, order=order, want=want, config=config,
                         investigator=investigator, x64=eff_x64)
        plan = _make_plan(req, where, lim, x64=eff_x64)
        if tr is not None:
            tr.labels.setdefault("backend", plan.backend)
            req.trace = tr
    return execute_request(req, plan)
