"""Balanced pairwise merge tree over received runs — paper §IV step 6, Fig. 2.

After the exchange each processor holds p sorted runs (one per sender),
padded to the static bucket capacity with order-preserving sentinels. The
merge tree pairs equal-length runs each round (the paper's "handler" that
keeps merge inputs equally sized for cache friendliness); sentinels stay
glued to the tail of every intermediate run, so padding never needs to be
compacted until the very end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def _pad_runs_pow2(runs: jnp.ndarray, fill) -> jnp.ndarray:
    p = runs.shape[0]
    p2 = 1
    while p2 < p:
        p2 *= 2
    if p2 == p:
        return runs
    pad = jnp.full((p2 - p, runs.shape[1]), fill, runs.dtype)
    return jnp.concatenate([runs, pad], axis=0)


def merge_padded_runs(runs: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Merge (p, C) row-sorted runs into one sorted (p2*C,) array.

    Sentinel padding (+inf / INT_MAX) must already sit at each row's tail.
    """
    fill = kops.sentinel_for(runs.dtype)
    with jax.named_scope("merge"):
        return kops.merge_tree(_pad_runs_pow2(runs, fill), use_pallas=use_pallas)


def merge_padded_runs_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    use_pallas: bool = True,
    stable: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Key/value variant; the value payload rides the same permutation."""
    kfill = kops.sentinel_for(keys.dtype)
    vfill = kops.sentinel_for(values.dtype)
    with jax.named_scope("merge"):
        return kops.merge_tree_kv(_pad_runs_pow2(keys, kfill),
                                  _pad_runs_pow2(values, vfill),
                                  stable=stable, use_pallas=use_pallas)
