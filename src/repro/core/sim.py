"""Virtual-processor simulator of the PGX.D distributed sort.

Global view: ``x`` has shape (p, n_local) — axis 0 *is* the processor axis
and every collective is an explicit reshape/transpose. This is the
single-device execution path used by the paper benchmarks on CPU (the
container exposes one device) and by the hypothesis property tests; the
shard_map implementation in ``sample_sort.py`` shares all the local math
(splitters, investigator, merge tree) and differs only in using real
``jax.lax`` collectives.

The six paper steps map 1:1 onto the code below, each under a
``jax.named_scope`` named by ``repro.obs.tracing.PHASES`` (local sort and
merge scope themselves in ``local_sort.py`` / ``merge.py``), so a device
profile splits the fused program's time by phase.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import keyenc
from repro.core import merge as merge_lib
from repro.core import splitters as spl
from repro.core.local_sort import local_sort, local_sort_kv
from repro.kernels import ops as kops


class SortResult(NamedTuple):
    """Distributed sort output (global view: leading axis = processor).

    values:   (p, total_capacity) sorted per-processor, sentinel padded.
    counts:   (p,) valid prefix length per processor.
    overflowed: scalar bool — True iff any static bucket overflowed (the
      exchange then dropped data; callers must treat the result as invalid
      and retry with a larger capacity_factor).
    send_counts: (p, p) per (src, dst) bucket sizes — the Table II /
      load-balance diagnostic.
    """

    values: jnp.ndarray
    counts: jnp.ndarray
    overflowed: jnp.ndarray
    send_counts: jnp.ndarray


class SortKVResult(NamedTuple):
    keys: jnp.ndarray
    values: jnp.ndarray
    counts: jnp.ndarray
    overflowed: jnp.ndarray
    send_counts: jnp.ndarray


class FlatSortResult(NamedTuple):
    """``sample_sort_sim_flat`` output: the decode is fused in-program.

    flat: (p*n_local,) globally sorted, front-compacted elements — every
      staged element (sentinel pads included) in its final position, so
      materialization is one D2H copy plus a host slice. For
      ``descending=True`` programs the flip decode has been applied; for
      ``packspec`` programs (packed multi-key serving) ``flat`` is the
      TUPLE of unpacked column arrays instead of one array.
    counts / overflowed / send_counts: as in ``SortResult``.
    """

    flat: jnp.ndarray
    counts: jnp.ndarray
    overflowed: jnp.ndarray
    send_counts: jnp.ndarray


def _bounds_all(xs, splitters, investigator: bool):
    fn = spl.investigator_bounds if investigator else spl.naive_bounds
    return jax.vmap(fn, in_axes=(0, None))(xs, splitters)  # (p, p+1)


def _gather_buckets(xs_pad: jnp.ndarray, bounds: jnp.ndarray, cap: int, p: int):
    """Slice the p destination buckets out of one padded sorted shard.

    xs_pad has ``cap`` sentinels appended so dynamic_slice never clamps.
    Returns (p, cap) buckets with positions >= count masked to sentinel.
    """
    fill = kops.sentinel_for(xs_pad.dtype)
    pos = jnp.arange(cap, dtype=jnp.int32)

    def one(j):
        start = bounds[j]
        count = bounds[j + 1] - bounds[j]
        seg = jax.lax.dynamic_slice(xs_pad, (start,), (cap,))
        return jnp.where(pos < count, seg, fill)

    return jnp.stack([one(j) for j in range(p)])  # (p, cap)


def _gather_buckets_kv(ks_pad, vs_pad, bounds, cap: int, p: int):
    kfill = kops.sentinel_for(ks_pad.dtype)
    vfill = kops.sentinel_for(vs_pad.dtype)
    pos = jnp.arange(cap, dtype=jnp.int32)

    def one(j):
        start = bounds[j]
        count = bounds[j + 1] - bounds[j]
        seg_k = jax.lax.dynamic_slice(ks_pad, (start,), (cap,))
        seg_v = jax.lax.dynamic_slice(vs_pad, (start,), (cap,))
        return (jnp.where(pos < count, seg_k, kfill), jnp.where(pos < count, seg_v, vfill))

    ks, vs = zip(*(one(j) for j in range(p)))
    return jnp.stack(ks), jnp.stack(vs)


@functools.partial(jax.jit, static_argnames=("config", "investigator"))
def sample_sort_sim(
    x: jnp.ndarray,
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
) -> SortResult:
    """PGX.D sample sort over virtual processors. x: (p, n_local)."""
    p, n = x.shape
    cap = config.capacity(p, n)

    # (1) local sort — Fig. 2 tile sort + balanced merge tree per shard
    xs = jax.vmap(lambda r: local_sort(r, tile=config.tile, use_pallas=config.use_pallas))(x)

    with jax.named_scope("splitter"):
        # (2) buffer-sized regular sampling; (3) replicated splitter selection
        s = config.num_samples(p, n, key_bytes=x.dtype.itemsize)
        samples = jax.vmap(lambda r: spl.regular_sample(r, s))(xs)  # "send to master"
        splitters = spl.select_splitters(samples.reshape(-1), p)

        # (4) investigator binary search -> destination bounds per shard
        bounds = _bounds_all(xs, splitters, investigator)  # (p, p+1)
        send_counts = bounds[:, 1:] - bounds[:, :-1]  # (p, p)
        overflowed = jnp.any(send_counts > cap)

    with jax.named_scope("exchange"):
        # (5) exchange — static-capacity buckets, transpose = all_to_all
        fill = kops.sentinel_for(xs.dtype)
        xs_pad = jnp.concatenate([xs, jnp.full((p, cap), fill, xs.dtype)], axis=1)
        send = jax.vmap(lambda row, b: _gather_buckets(row, b, cap, p))(xs_pad, bounds)
        recv = jnp.swapaxes(send, 0, 1)  # (p_dst, p_src, cap)
        counts = send_counts.T.sum(axis=1)  # (p_dst,)

    # (6) balanced pairwise merge of the received runs
    merged = jax.vmap(
        lambda r: merge_lib.merge_padded_runs(r, use_pallas=config.use_pallas)
    )(recv)

    return SortResult(merged, counts, overflowed, send_counts)


@functools.partial(jax.jit, static_argnames=("config", "investigator"))
def sample_sort_sim_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
) -> SortKVResult:
    """Key/value variant — values ride along (provenance, MoE token ids).

    Stability: exact stable sort when ``values`` are globally-unique,
    processor-then-position-increasing indices (the provenance encoding the
    paper keeps per element); ``api.sort_with_provenance`` constructs that.
    """
    p, n = keys.shape
    cap = config.capacity(p, n)

    ks, vs = jax.vmap(
        lambda k, v: local_sort_kv(k, v, tile=config.tile, use_pallas=config.use_pallas)
    )(keys, values)

    with jax.named_scope("splitter"):
        s = config.num_samples(p, n, key_bytes=keys.dtype.itemsize)
        samples = jax.vmap(lambda r: spl.regular_sample(r, s))(ks)
        splitters = spl.select_splitters(samples.reshape(-1), p)

        bounds = _bounds_all(ks, splitters, investigator)
        send_counts = bounds[:, 1:] - bounds[:, :-1]
        overflowed = jnp.any(send_counts > cap)

    with jax.named_scope("exchange"):
        kfill = kops.sentinel_for(ks.dtype)
        vfill = kops.sentinel_for(vs.dtype)
        ks_pad = jnp.concatenate([ks, jnp.full((p, cap), kfill, ks.dtype)], axis=1)
        vs_pad = jnp.concatenate([vs, jnp.full((p, cap), vfill, vs.dtype)], axis=1)
        send_k, send_v = jax.vmap(
            lambda kk, vv, b: _gather_buckets_kv(kk, vv, b, cap, p)
        )(ks_pad, vs_pad, bounds)
        recv_k = jnp.swapaxes(send_k, 0, 1)
        recv_v = jnp.swapaxes(send_v, 0, 1)
        counts = send_counts.T.sum(axis=1)

    mk, mv = jax.vmap(
        lambda rk, rv: merge_lib.merge_padded_runs_kv(rk, rv, use_pallas=config.use_pallas)
    )(recv_k, recv_v)

    return SortKVResult(mk, mv, counts, overflowed, send_counts)


@functools.partial(
    jax.jit, static_argnames=("config", "investigator", "descending",
                              "packspec")
)
def sample_sort_sim_flat(
    x: jnp.ndarray,
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
    descending: bool = False,
    packspec=None,
) -> FlatSortResult:
    """Sample sort with the device decode fused into the same program.

    The serving flush engine's unit of work: ``x`` is the (p, per)
    staged grid (real elements + sentinel pads), and the output ``flat``
    already has the compaction gather — and, for ``descending=True``,
    the order-flip encode *and* inverse decode — applied on device, so
    the host never touches a padded (p, p*cap) grid again (an ~p-fold
    smaller D2H copy than transferring the raw exchange capacity).
    Descending inputs must arrive RAW, padded with the *flipped*
    sentinel (dtype min / -inf), which the in-program flip turns back
    into the ascending pad that sorts to the tail.

    ``packspec`` (a ``keyenc.PackSpec``, static): ``x`` holds PACKED
    multi-key values — the unpack back into the original tuple columns
    is fused after compaction, so a coalesced multi-key flush's D2H is
    the decoded columns and ``flat`` is a tuple of (p*n_local,) arrays.
    Packed grids always stage ascending (the per-key order flips live
    inside the bit fields), padded with the plain int32 sentinel.
    """
    if descending:
        x = keyenc.flip(x)
    res = sample_sort_sim(x, config, investigator=investigator)
    p, n = x.shape
    with jax.named_scope("decode"):
        flat = keyenc.compact_rows(res.values, res.counts, p * n)
        if descending:
            flat = keyenc.flip(flat)
        if packspec is not None:
            flat = keyenc.unpack_fields(flat, packspec)
    return FlatSortResult(flat, res.counts, res.overflowed, res.send_counts)
