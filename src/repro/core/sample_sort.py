"""PGX.D distributed sample sort over a real mesh axis (shard_map).

Per-device SPMD implementation of the paper's six steps with ``jax.lax``
collectives (DESIGN.md §2 mapping):

  master gather + broadcast  ->  all_gather + replicated selection
  async p2p send/recv        ->  one fused static-capacity all_to_all
                                 (XLA overlaps it with the local merge)

The local math — tile sort, regular sampling, splitter selection,
investigator bounds, balanced pairwise merge — is shared with the
virtual-processor simulator (``sim.py``) which doubles as its oracle,
and so are the ``jax.named_scope`` phase names (``obs.tracing.PHASES``)
that split the one fused program's device time in a profile.

The sort axis may be a single mesh axis ("data") or a tuple of axes
(("pod", "data")) — the multi-pod case: ``lax`` collectives accept axis
tuples, so a 2x16 pod*data sort runs over 32 virtual processors.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
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

    with jax.named_scope("splitter"):
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

    with jax.named_scope("exchange"):
        # (5) fused static-capacity exchange
        fill = kops.sentinel_for(xs.dtype)
        xs_pad = jnp.concatenate([xs, jnp.full((cap,), fill, xs.dtype)])
        send = _gather_buckets(xs_pad, bounds, cap, p)  # (p, cap)
        recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                                  tiled=True)
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

    with jax.named_scope("splitter"):
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

    with jax.named_scope("exchange"):
        kfill = kops.sentinel_for(ks.dtype)
        vfill = kops.sentinel_for(vs.dtype)
        ks_pad = jnp.concatenate([ks, jnp.full((cap,), kfill, ks.dtype)])
        vs_pad = jnp.concatenate([vs, jnp.full((cap,), vfill, vs.dtype)])
        send_k, send_v = _gather_buckets_kv(ks_pad, vs_pad, bounds, cap, p)
        recv_k = jax.lax.all_to_all(send_k, axis_name, split_axis=0, concat_axis=0,
                                    tiled=True)
        recv_v = jax.lax.all_to_all(send_v, axis_name, split_axis=0, concat_axis=0,
                                    tiled=True)
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
