"""PGX.D distributed sample sort over a real mesh axis (shard_map).

Per-device SPMD implementation of the paper's six steps with ``jax.lax``
collectives (DESIGN.md §2 mapping):

  master gather + broadcast  ->  all_gather + replicated selection
  async p2p send/recv        ->  one fused static-capacity all_to_all
                                 (XLA overlaps it with the local merge)

The local math — tile sort, regular sampling, splitter selection,
investigator bounds, balanced pairwise merge — is shared with the
virtual-processor simulator (``sim.py``) which doubles as its oracle.

The sort axis may be a single mesh axis ("data") or a tuple of axes
(("pod", "data")) — the multi-pod case: ``lax`` collectives accept axis
tuples, so a 2x16 pod*data sort runs over 32 virtual processors.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import merge as merge_lib
from repro.core import splitters as spl
from repro.core.local_sort import local_sort, local_sort_kv
from repro.core.sim import _gather_buckets, _gather_buckets_kv
from repro.kernels import ops as kops


class ShardSortResult(NamedTuple):
    """Sort result. Inside shard_map each field is one device's local
    view; ``distributed_sort`` returns the global view, a leading axis of
    one row per device sharded over the sort axis. On a ``jax.make_mesh``
    mesh (explicit axes) a row is read on the host, e.g.
    ``np.asarray(r.values)[i, :counts[i]]``."""

    values: jnp.ndarray  # (total_capacity,) sorted, sentinel padded
    count: jnp.ndarray  # () valid prefix length
    overflowed: jnp.ndarray  # () bool, globally reduced
    send_counts: jnp.ndarray  # (p,) this device's per-destination sizes


class ShardSortKVResult(NamedTuple):
    keys: jnp.ndarray
    values: jnp.ndarray
    count: jnp.ndarray
    overflowed: jnp.ndarray
    send_counts: jnp.ndarray


def sample_sort_shard(
    x_local: jnp.ndarray,
    axis_name,
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
) -> ShardSortResult:
    """Body to be called *inside* shard_map/pmap over ``axis_name``."""
    p = jax.lax.axis_size(axis_name)
    (n,) = x_local.shape
    cap = config.capacity(p, n)

    # (1) local sort
    xs = local_sort(x_local, tile=config.tile, use_pallas=config.use_pallas)

    # (2)+(3) sample -> all_gather -> replicated splitter selection
    s = config.num_samples(p, n, key_bytes=x_local.dtype.itemsize)
    samples = spl.regular_sample(xs, s)
    all_samples = jax.lax.all_gather(samples, axis_name, tiled=True)  # (p*s,)
    splitters = spl.select_splitters(all_samples, p)

    # (4) investigator binary search
    bounds = (
        spl.investigator_bounds(xs, splitters)
        if investigator
        else spl.naive_bounds(xs, splitters)
    )
    send_counts = bounds[1:] - bounds[:-1]  # (p,)
    overflowed = jax.lax.pmax(jnp.any(send_counts > cap), axis_name)

    # (5) fused static-capacity exchange
    fill = kops.sentinel_for(xs.dtype)
    xs_pad = jnp.concatenate([xs, jnp.full((cap,), fill, xs.dtype)])
    send = _gather_buckets(xs_pad, bounds, cap, p)  # (p, cap)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv_counts = jax.lax.all_to_all(
        send_counts, axis_name, split_axis=0, concat_axis=0, tiled=True
    )

    # (6) balanced pairwise merge of the p received runs
    merged = merge_lib.merge_padded_runs(recv, use_pallas=config.use_pallas)
    return ShardSortResult(merged, recv_counts.sum(), overflowed, send_counts)


def sample_sort_shard_kv(
    keys_local: jnp.ndarray,
    values_local: jnp.ndarray,
    axis_name,
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
) -> ShardSortKVResult:
    """Key/value body (provenance / MoE dispatch) inside shard_map."""
    p = jax.lax.axis_size(axis_name)
    (n,) = keys_local.shape
    cap = config.capacity(p, n)

    ks, vs = local_sort_kv(
        keys_local, values_local, tile=config.tile, use_pallas=config.use_pallas
    )

    s = config.num_samples(p, n, key_bytes=keys_local.dtype.itemsize)
    samples = spl.regular_sample(ks, s)
    all_samples = jax.lax.all_gather(samples, axis_name, tiled=True)
    splitters = spl.select_splitters(all_samples, p)

    bounds = (
        spl.investigator_bounds(ks, splitters)
        if investigator
        else spl.naive_bounds(ks, splitters)
    )
    send_counts = bounds[1:] - bounds[:-1]
    overflowed = jax.lax.pmax(jnp.any(send_counts > cap), axis_name)

    kfill = kops.sentinel_for(ks.dtype)
    vfill = kops.sentinel_for(vs.dtype)
    ks_pad = jnp.concatenate([ks, jnp.full((cap,), kfill, ks.dtype)])
    vs_pad = jnp.concatenate([vs, jnp.full((cap,), vfill, vs.dtype)])
    send_k, send_v = _gather_buckets_kv(ks_pad, vs_pad, bounds, cap, p)
    recv_k = jax.lax.all_to_all(send_k, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv_v = jax.lax.all_to_all(send_v, axis_name, split_axis=0, concat_axis=0, tiled=True)
    recv_counts = jax.lax.all_to_all(
        send_counts, axis_name, split_axis=0, concat_axis=0, tiled=True
    )

    mk, mv = merge_lib.merge_padded_runs_kv(recv_k, recv_v, use_pallas=config.use_pallas)
    return ShardSortKVResult(mk, mv, recv_counts.sum(), overflowed, send_counts)


# ------------------------------------------------------------ global entry


@functools.lru_cache(maxsize=None)
def _mesh_program(mesh, axis_name, config, investigator: bool, kv: bool):
    """One JITTED shard_map program per (mesh, axis, config, policy).

    The entry points used to rebuild the shard_map closure on every
    call, so every mesh sort re-traced eagerly — seconds per call on
    CPU, paid even by repeat same-shape traffic (the LSD multi-key
    passes and the differential fuzzer each issue dozens). All the
    arguments are hashable (Mesh, axis tuples/strings, the frozen
    SortConfig), so the closure and its ``jax.jit`` wrapper are built
    once and repeat calls land in jax's compiled-program cache keyed by
    input shape/dtype."""
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    if kv:
        body = functools.partial(
            sample_sort_shard_kv, axis_name=axis_name, config=config,
            investigator=investigator,
        )

        def wrapped(kl, vl):
            r = body(kl[0], vl[0])
            return ShardSortKVResult(
                r.keys[None], r.values[None], r.count[None], r.overflowed[None],
                r.send_counts[None],
            )

        f = jax.shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(P(axes), P(axes)),
            out_specs=ShardSortKVResult(P(axes), P(axes), P(axes), P(axes),
                                        P(axes)),
            check_vma=False,
        )
    else:
        body = functools.partial(
            sample_sort_shard, axis_name=axis_name, config=config,
            investigator=investigator,
        )

        def wrapped(xl):
            r = body(xl[0])  # strip the leading local-processor axis of size 1
            return ShardSortResult(
                r.values[None], r.count[None], r.overflowed[None],
                r.send_counts[None],
            )

        f = jax.shard_map(
            wrapped,
            mesh=mesh,
            in_specs=P(axes),
            out_specs=ShardSortResult(P(axes), P(axes), P(axes), P(axes)),
            check_vma=False,
        )
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _mesh_phase_programs(mesh, axis_name, config, investigator: bool):
    """Per-phase shard_map programs for traced mesh sorts (keys-only).

    The fused ``_mesh_program`` keeps communication overlapped with the
    local merge — the paper's latency-hiding — but is opaque to phase
    attribution. Traced sorts trade that overlap for the breakdown: the
    same shard bodies run as four programs (local sort / splitter
    selection / exchange / merge) so each span fences on its own output.
    kv mesh sorts keep the fused program under tracing (one "sort" span)
    — phase splitting both paths is not worth doubling this table."""
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)

    def local_body(xl):
        xs = local_sort(xl[0], tile=config.tile, use_pallas=config.use_pallas)
        return xs[None]

    def split_body(xsl):
        xs = xsl[0]
        p = jax.lax.axis_size(axis_name)
        (n,) = xs.shape
        cap = config.capacity(p, n)
        s = config.num_samples(p, n, key_bytes=xs.dtype.itemsize)
        samples = spl.regular_sample(xs, s)
        all_samples = jax.lax.all_gather(samples, axis_name, tiled=True)
        splitters = spl.select_splitters(all_samples, p)
        bounds = (
            spl.investigator_bounds(xs, splitters)
            if investigator
            else spl.naive_bounds(xs, splitters)
        )
        send_counts = bounds[1:] - bounds[:-1]
        overflowed = jax.lax.pmax(jnp.any(send_counts > cap), axis_name)
        return bounds[None], send_counts[None], overflowed[None]

    def exch_body(xsl, bl):
        xs, bounds = xsl[0], bl[0]
        p = jax.lax.axis_size(axis_name)
        (n,) = xs.shape
        cap = config.capacity(p, n)
        fill = kops.sentinel_for(xs.dtype)
        xs_pad = jnp.concatenate([xs, jnp.full((cap,), fill, xs.dtype)])
        send = _gather_buckets(xs_pad, bounds, cap, p)
        recv = jax.lax.all_to_all(
            send, axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        send_counts = bounds[1:] - bounds[:-1]
        recv_counts = jax.lax.all_to_all(
            send_counts, axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        return recv[None], recv_counts.sum()[None]

    def merge_body(rl):
        merged = merge_lib.merge_padded_runs(rl[0], use_pallas=config.use_pallas)
        return merged[None]

    def program(body, in_specs, out_specs):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    local_f = program(local_body, P(axes), P(axes))
    split_f = program(split_body, P(axes), (P(axes), P(axes), P(axes)))
    exch_f = program(exch_body, (P(axes), P(axes)), (P(axes), P(axes)))
    merge_f = program(merge_body, P(axes), P(axes))
    return local_f, split_f, exch_f, merge_f


def distributed_sort_phased(
    x: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis_name="data",
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
    trace,
) -> ShardSortResult:
    """Traced mesh sort: same result as ``distributed_sort``, run as four
    fenced phase programs recording spans on ``trace`` with per-device
    counts. Each overflow-ladder step appends a fresh set of spans."""
    p = _axis_product(mesh, axis_name)
    local_f, split_f, exch_f, merge_f = _mesh_phase_programs(
        mesh, axis_name, config, investigator
    )
    xg = x.reshape(p, -1)
    n = xg.shape[1]
    with trace.span("local_sort") as sp:
        xs = sp.fence(local_f(xg))
        sp.counts([n] * p)
    with trace.span("splitter") as sp:
        bounds, send_counts, overflowed = sp.fence(split_f(xs))
        sp.set(overflowed=bool(jnp.any(overflowed)))
    with trace.span("exchange") as sp:
        recv, counts = sp.fence(exch_f(xs, bounds))
        sp.counts(np.asarray(counts).tolist())
    with trace.span("merge") as sp:
        merged = sp.fence(merge_f(recv))
        sp.counts(np.asarray(counts).tolist())
    return ShardSortResult(merged, counts, overflowed, send_counts)


def _axis_product(mesh, axis_name) -> int:
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    return p


def distributed_sort(
    x: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis_name="data",
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
):
    """Sort a globally (axis 0)-sharded flat array. Returns global-view
    (p, cap_total) values + (p,) counts + overflow flag, like ``sim``."""
    f = _mesh_program(mesh, axis_name, config, investigator, False)
    return f(x.reshape(_axis_product(mesh, axis_name), -1))


def distributed_sort_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    axis_name="data",
    config: spl.SortConfig = spl.SortConfig(),
    *,
    investigator: bool = True,
):
    p = _axis_product(mesh, axis_name)
    f = _mesh_program(mesh, axis_name, config, investigator, True)
    return f(keys.reshape(p, -1), values.reshape(p, -1))
