"""The paper's Fig. 4 input distributions (arXiv:1611.00463), made on the
device from a PRNG key, as int32 keys with int32 row-id payloads.

Parameters (from the cell's file):
  distribution: ``right_skewed``  floor(u^6 * 64): 64 distinct values, most
                                  of them near 0 (the investigator's case);
                ``exponential``   floor(Exp(1) * 8): a geometric-like tail
                                  of moderate duplication;
                ``uniform``       uniform over the int32 range below its
                                  maximum (the maximum is the sort's
                                  padding sentinel for payload sorts):
                                  practically no duplicates.

Arrays made: ``keys`` (n,) int32 and ``values`` (n,) int32 = 0..n-1, the
row ids a graph system carries to reorder properties. Both are laid out
by ``sharding`` (one device, or split over a mesh axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_I32 = jnp.iinfo(jnp.int32)


def _right_skewed(key, n):
    u = jax.random.uniform(key, (n,), jnp.float32)
    return jnp.floor(u ** 6 * 64).astype(jnp.int32)


def _exponential(key, n):
    e = jax.random.exponential(key, (n,), jnp.float32)
    return jnp.floor(e * 8).astype(jnp.int32)


def _uniform(key, n):
    return jax.random.randint(key, (n,), _I32.min, _I32.max, jnp.int32)


DISTRIBUTIONS = {"right_skewed": _right_skewed, "exponential": _exponential,
                 "uniform": _uniform}


def build(params: dict, *, n: int, sharding):
    """A jitted ``key -> {"keys", "values"}`` for ``n`` elements in all."""
    keys = DISTRIBUTIONS[params["distribution"]]

    def make(key):
        return {"keys": keys(key, n), "values": jnp.arange(n, dtype=jnp.int32)}

    return jax.jit(make, out_shardings=sharding)
