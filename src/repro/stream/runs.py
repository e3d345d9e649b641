"""Run generation — pass 1 of the external sort (paper steps 1-2 at
dataset scale).

The in-core library sorts one (p, n_local) program per call; here the
host-side dataset is cut into device-sized *chunks*, each chunk is sorted
with the existing virtual-processor sample sort, and the sorted chunk is
copied back out as a *run*. Two latency-hiding tricks mirror the paper's
"let the process continue without waiting" philosophy:

  * **double buffering** — the host->device transfer of chunk i+1 is
    issued while the sort of chunk i is still executing (JAX dispatch is
    asynchronous; ``jax.device_put`` of the next chunk overlaps with the
    in-flight program exactly the way PGX.D overlaps communication with
    computation), and the blocking device->host copy of chunk i happens
    only after chunk i+1's transfer is on the wire;
  * **one program for every chunk** — all chunks are sentinel-padded to
    the same (n_procs, per) shape, so the whole pass reuses a single
    compiled executable (the last partial chunk included).

Overflow handling reuses ``sort_with_retry`` semantics: a chunk whose
static buckets overflowed is re-sorted with a doubled capacity_factor
(never silently dropped).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import overflow, sim
from repro.core import planner as planner_grid
from repro.core.splitters import SortConfig
from repro.kernels import ops as kops
from repro.obs.tracing import maybe_span as _span


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the out-of-core pipeline.

    chunk_elems: per-chunk element capacity — the "device-sized" unit. A
      run never exceeds this, and pass-3 merge memory is bounded by
      ~bucket size ~= chunk_elems (splitters balance buckets to it).
    n_procs: virtual processors used for each in-core chunk sort.
    sort: the in-core SortConfig (buffer rule, capacity, pallas path).
    max_doublings: capacity-ladder steps before a chunk sort fails.
    growth: capacity_factor multiplier per ladder step (the unified
      overflow policy's knob; overflow past the ladder always raises
      here — a partially exchanged run cannot be returned).
    n_buckets: range buckets for pass 2; None = ceil(total/chunk_elems),
      i.e. each bucket targets one device-sized merge.
    out_chunk_elems: granularity of the sorted output stream; None =
      chunk_elems.
    x64: the request's resolved x64 mode, threaded from the planner
      (``SortPlan.x64``): iterator chunk dtypes are only knowable at
      staging time, so the 64-bit door check
      (``planner.check_key_dtype``) runs per chunk against THIS flag —
      None falls back to the ambient ``core.x64`` switch (direct
      ``repro.stream`` users). Staging sentinels are width-correct
      either way (``kernels.ops.sentinel_for`` is dtype-driven).
    """

    chunk_elems: int = 1 << 16
    n_procs: int = 8
    sort: SortConfig = SortConfig()
    max_doublings: int = 3
    growth: float = 2.0
    n_buckets: int | None = None
    out_chunk_elems: int | None = None
    x64: bool | None = None


@dataclasses.dataclass
class Run:
    """One sorted, device-capacity-sized fragment of the dataset, resident
    on host. ``values`` (same order as ``keys``) is None for key-only
    sorts. ``retries`` is the number of capacity-ladder steps this
    chunk's sort took (0 = first attempt fit) — the drivers aggregate it
    into ``SortOutput.meta`` ladder accounting."""

    keys: np.ndarray
    values: np.ndarray | None = None
    retries: int = 0

    def __len__(self) -> int:
        return int(self.keys.shape[0])


def iter_chunks(
    data: np.ndarray | Iterable[np.ndarray], chunk_elems: int
) -> Iterator[np.ndarray]:
    """Re-chunk an array or an iterator of arrays into <= chunk_elems
    pieces (iterator pieces are split/coalesced as needed)."""
    if isinstance(data, np.ndarray):
        flat = data.reshape(-1)
        for i in range(0, flat.shape[0], chunk_elems):
            yield flat[i : i + chunk_elems]
        return
    buf: list[np.ndarray] = []
    have = 0
    for piece in data:
        piece = np.asarray(piece).reshape(-1)
        while piece.size:
            take = min(piece.size, chunk_elems - have)
            buf.append(piece[:take])
            have += take
            piece = piece[take:]
            if have == chunk_elems:
                yield np.concatenate(buf) if len(buf) > 1 else buf[0]
                buf, have = [], 0
    if have:
        yield np.concatenate(buf) if len(buf) > 1 else buf[0]


# the pad/unpad grid invariant lives in one place — the planner — and is
# shared by the chunk staging here and the SortService request path
_pad_chunk = planner_grid.pad_grid
_unpad = planner_grid.unpad_grid


def generate_runs(
    data: np.ndarray | Iterable[np.ndarray],
    cfg: StreamConfig = StreamConfig(),
    values: np.ndarray | Iterable[np.ndarray] | None = None,
    *,
    investigator: bool = True,
    descending: bool = False,
) -> list[Run]:
    """Pass 1: cut ``data`` into chunks, sort each in-core, return runs.

    ``values`` (optional payload, e.g. provenance indices) must chunk
    identically to ``data``.

    ``descending=True`` fuses the order-flip ENCODE into this pass: raw
    chunks are staged padded with the *flipped* sentinel (dtype min /
    -inf) and flipped on device right after H2D, so the runs come back
    in flip-encoded ascending order and no host pass ever touches the
    keys. Passes 2-3 operate in the encoded space unchanged; the
    matching device-side flip DECODE happens per output chunk in
    ``external_merge`` (the unified front end's ``decode="device"``
    stream path).
    """
    from repro.core import keyenc

    p, per = cfg.n_procs, -(-cfg.chunk_elems // cfg.n_procs)
    key_chunks = iter_chunks(data, p * per)
    val_chunks = iter_chunks(values, p * per) if values is not None else None

    runs: list[Run] = []
    # in-flight state: (device inputs, dispatched result, sort cfg, m)
    inflight = None

    def dispatch(dev_k, dev_v, sort_cfg):
        if descending:
            dev_k = keyenc.flip(dev_k)  # device encode, overlaps like H2D
        if dev_v is None:
            return sim.sample_sort_sim(dev_k, sort_cfg, investigator=investigator)
        return sim.sample_sort_sim_kv(dev_k, dev_v, sort_cfg, investigator=investigator)

    def finalize(state) -> Run:
        dev_k, dev_v, res, sort_cfg, m = state
        # unified capacity ladder (core.overflow) — recompiles, but
        # steady-state inputs converge to one program
        retries = 0
        if bool(res.overflowed):
            from repro import tune as _tune

            res, sort_cfg, retries = overflow.retry_overflowed(
                lambda c: dispatch(dev_k, dev_v, c),
                sort_cfg,
                overflow.OverflowPolicy(
                    max_doublings=cfg.max_doublings, growth=cfg.growth
                ),
                last=res,
                # with a tuner ambient the chunk ladder starts from the
                # capacity its own send_counts measured (see
                # overflow.measured_capacity_need); cold path unchanged
                measured=(overflow.measured_capacity_need(p, per)
                          if _tune.current() is not None else None),
            )
        if dev_v is None:
            return Run(_unpad(res.values, res.counts, m), retries=retries)
        return Run(
            _unpad(res.keys, res.counts, m), _unpad(res.values, res.counts, m),
            retries=retries,
        )

    for chunk in key_chunks:
        m = int(chunk.shape[0])
        planner_grid.check_key_dtype(chunk.dtype, what="stream chunk keys",
                                     x64=cfg.x64)
        kfill = np.asarray(kops.sentinel_for(jnp.dtype(chunk.dtype)))
        if descending:
            # pads must sort to the tail in the ENCODED space: stage the
            # flipped sentinel, which the on-device flip maps back to it
            kfill = keyenc.flip_np(kfill)
        # H2D of the NEXT chunk goes on the wire while the previous
        # chunk's sort is still executing (async dispatch) — the
        # double-buffer overlap. The profiler annotation makes that
        # overlap visible in a captured device profile.
        with _span(None, "stream.stage_chunk"):
            dev_k = jax.device_put(_pad_chunk(chunk, p, per, kfill))
            dev_v = None
            if val_chunks is not None:
                vchunk = next(val_chunks, None)
                if vchunk is None or vchunk.shape[0] != m:
                    raise ValueError("values must chunk identically to keys")
                planner_grid.check_key_dtype(vchunk.dtype,
                                             what="stream chunk values",
                                             x64=cfg.x64)
                vfill = np.asarray(kops.sentinel_for(jnp.dtype(vchunk.dtype)))
                dev_v = jax.device_put(_pad_chunk(vchunk, p, per, vfill))
        if inflight is not None:
            runs.append(finalize(inflight))  # blocks on the *previous* sort
        inflight = (dev_k, dev_v, dispatch(dev_k, dev_v, cfg.sort), cfg.sort, m)
    if inflight is not None:
        runs.append(finalize(inflight))
    if val_chunks is not None and next(val_chunks, None) is not None:
        raise ValueError("values must chunk identically to keys")
    return runs
