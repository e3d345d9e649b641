"""Gradient compression for the DP all-reduce (distributed-optimization
trick, DESIGN.md §8).

A full-precision all-reduce = reduce-scatter + all-gather. The
reduce-scatter half must stay exact (it sums), but after it every shard
holds its *final* gradient slice — the all-gather half is a pure
broadcast and tolerates quantization. ``compressed_psum_mean`` therefore:

    reduce-scatter fp32 -> int8-quantize (per-chunk scale) -> all-gather
    -> dequantize

saving ~4x bandwidth on the all-gather half at ~0.4% RMS error (validated
by tests/test_optim.py). Opt-in via TrainConfig.grad_compression; used in
one §Perf hillclimb iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256  # elements per quantization scale


def quantize_int8(x: jnp.ndarray):
    """x: flat fp32 (N,) with N % CHUNK == 0. Returns (int8 (N,), scales)."""
    xc = x.reshape(-1, CHUNK)
    scale = jnp.max(jnp.abs(xc), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xc / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray):
    return (q.reshape(-1, CHUNK).astype(jnp.float32) * scale[:, None]).reshape(-1)


def compressed_psum_mean(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Mean-reduce ``x`` over ``axis_name`` inside shard_map with an int8
    all-gather half. x: flat fp32, length divisible by p*CHUNK."""

    p = jax.lax.axis_size(axis_name)
    part = jax.lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True) / p
    q, s = quantize_int8(part)
    qg = jax.lax.all_gather(q, axis_name, tiled=True)
    sg = jax.lax.all_gather(s, axis_name, tiled=True)
    return dequantize_int8(qg, sg)
