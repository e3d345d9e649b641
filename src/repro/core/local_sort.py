"""Local (per-device) sort phase — paper §IV step 1.

The paper sorts each machine's shard with per-thread parallel quicksort
followed by the Fig. 2 balanced pairwise merge. On TPU the "threads" are
VMEM tiles and quicksort becomes a bitonic network (see DESIGN.md §2);
``repro.kernels.ops.tile_sort`` implements exactly that structure. The
``lax`` path (XLA's sort) is kept as the production fallback and as an
independent implementation for differential testing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def local_sort(x: jnp.ndarray, *, tile: int = 1024, use_pallas: bool = True) -> jnp.ndarray:
    """Sort a flat local shard ascending."""
    with jax.named_scope("local_sort"):
        if not use_pallas:
            return jnp.sort(x)
        return kops.tile_sort(x, tile=tile, use_pallas=True)


def local_sort_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    tile: int = 1024,
    use_pallas: bool = True,
    stable: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort (keys, values) by key. Stable when values are unique indices
    (always true for the provenance/dispatch paths); for arbitrary values
    the caller wraps with an index payload first (see api.sort_kv)."""
    with jax.named_scope("local_sort"):
        if not use_pallas:
            k, v = jax.lax.sort([keys, values], dimension=0, is_stable=stable,
                                num_keys=1)
            return k, v
        return kops.tile_sort_kv(keys, values, tile=tile, stable=stable,
                                 use_pallas=True)


def segment_stable_kv(keys: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """Device tie fix: reorder ``values`` ascending within each run of
    equal (already sorted) ``keys``.

    The investigator deliberately splits tied key ranges across
    destinations to balance load (paper Fig. 3c), so a provenance
    payload comes back segment-interleaved within runs of equal keys.
    Sorting the (segment id, payload) pairs — segment ids are already
    non-decreasing, so the permutation only moves payloads *within*
    their segment — restores exactly ``np.argsort(kind="stable")``.
    This is the on-device replacement for the planner's host
    ``_stable_order_fix`` numpy pass (``idx[np.lexsort((idx, seg))]``),
    fused into the decode program by ``keyenc.decode_grid``.
    """
    if keys.shape[0] <= 1:
        return values
    seg = jnp.cumsum(
        jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), (keys[1:] != keys[:-1]).astype(jnp.int32)]
        )
    )
    _, out = jax.lax.sort([seg, values], dimension=0, is_stable=True, num_keys=2)
    return out
