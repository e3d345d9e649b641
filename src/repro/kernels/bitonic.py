"""Pallas TPU bitonic sorting-network kernels.

Hardware adaptation: the paper sorts each worker thread's slice with
quicksort — a branchy, data-dependent algorithm that maps poorly to the
TPU vector unit. We replace it with a *bitonic sorting network*: an
oblivious, fixed compare-exchange schedule that vectorizes perfectly and
runs entirely out of VMEM tiles.

Every compare-exchange stage at distance ``j`` fetches each lane's
partner (lane ``i ^ j``) with two lane rotations (``pltpu.roll`` by ``j``
and by ``n - j``) and a select, so the row keeps its (sublane, lane)
layout: no reshape of the lane dimension, no gathers, no scatters. For a
row of length N = 2**k the network has k*(k+1)/2 stages (k=10 → 55 for
N=1024), each O(N) work.

Kernels:
  * ``_sort_kernel``      — sort each row of a (R, N) block, keys only.
  * ``_sort_kv_kernel``   — key/value row sort, optional stable tie-break on
                            values (used by MoE dispatch: values carry the
                            token index, making the sort stable by
                            construction).
  * ``_merge_kv_kernel``  — merge two sorted rows via the bitonic *merge*
                            half-network (k+1 stages, not O(k^2)): this is
                            the paper's Fig. 2 balanced pairwise merge,
                            TPU-style (a ++ reverse(b) is bitonic; the
                            reversal happens in the XLA wrapper, because
                            Mosaic has no lane-reversal primitive).

All padding / pow2 / dtype handling lives in ``ops.py``; kernels assume N
is a power of two of at least one 128-lane vector and 32-bit elements.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cmpx(
    keys: jnp.ndarray,
    payloads: tuple[jnp.ndarray, ...],
    j: int,
    stage_span: int,
    tiebreak: int,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, ...]]:
    """One compare-exchange stage at distance ``j``.

    keys: (R, N). payloads: tuple of (R, N) arrays permuted identically.
    tiebreak: index into payloads used as a lexicographic tie-break
    (-1 = none). Lane ``i`` pairs with lane ``i ^ j``; the pair sorts
    ascending iff bit ``stage_span`` of ``i`` is 0 (``stage_span`` is the
    length of the runs this stage builds, constant within a pair because
    2j <= stage_span). Each lane keeps its own element or takes its
    partner's, so both lanes of a pair reach the same decision.
    """
    rows, n = keys.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    # the low lane of an ascending pair keeps the smaller element, the
    # high lane the larger; a descending pair the other way round. Low
    # and ascending are bits j and stage_span of the lane index, so
    # "keep the smaller" is their XOR being 0 (integer math: Mosaic
    # cannot compare two boolean vectors)
    jb, sb = j.bit_length() - 1, stage_span.bit_length() - 1
    keep_small = (((lane >> jb) ^ (lane >> sb)) & 1) == 0
    if 2 * j == n:  # both rotations are the same
        partner = lambda x: pltpu.roll(x, j, 1)
    else:
        # which of the two rotations brings lane i ^ j to lane i, read off
        # a rotated lane index, so the choice holds for either direction
        from_up = pltpu.roll(lane, n - j, 1) == (lane ^ j)

        def partner(x):
            return jnp.where(from_up, pltpu.roll(x, n - j, 1), pltpu.roll(x, j, 1))

    pk = partner(keys)
    p_lt = pk < keys
    p_gt = pk > keys
    if tiebreak >= 0:
        t = payloads[tiebreak]
        pt = partner(t)
        eq = pk == keys
        p_lt = p_lt | (eq & (pt < t))
        p_gt = p_gt | (eq & (pt > t))
    take = (keep_small & p_lt) | (~keep_small & p_gt)

    new_keys = jnp.where(take, pk, keys)
    new_payloads = []
    for i, p in enumerate(payloads):
        pp = pt if i == tiebreak else partner(p)
        new_payloads.append(jnp.where(take, pp, p))
    return new_keys, tuple(new_payloads)


def _sort_network(keys, payloads, tiebreak: int):
    """Full bitonic sort network, ascending. Static unrolled schedule."""
    n = keys.shape[-1]
    k = int(math.log2(n))
    assert 1 << k == n, f"row length {n} must be a power of two"
    for s in range(k):
        span = 1 << (s + 1)
        for sub in range(s, -1, -1):
            keys, payloads = _cmpx(keys, payloads, 1 << sub, span, tiebreak)
    return keys, payloads


def _merge_network(keys, payloads, tiebreak: int):
    """Bitonic *merge* half-network: input rows must be bitonic sequences.

    Used to merge two sorted runs (a ++ reverse(b) is bitonic). Only k+1
    stages — this is why the paper's balanced pairwise merge tree is cheap.
    """
    n = keys.shape[-1]
    k = int(math.log2(n))
    assert 1 << k == n
    span = 1 << k  # single ascending run spanning the whole row
    for sub in range(k - 1, -1, -1):
        keys, payloads = _cmpx(keys, payloads, 1 << sub, span, tiebreak)
    return keys, payloads


# ---------------------------------------------------------------- kernels


def _sort_kernel(k_ref, o_ref):
    keys, _ = _sort_network(k_ref[...], (), tiebreak=-1)
    o_ref[...] = keys


def _sort_kv_kernel(k_ref, v_ref, ok_ref, ov_ref, *, stable: bool):
    keys, (vals,) = _sort_network(k_ref[...], (v_ref[...],), tiebreak=0 if stable else -1)
    ok_ref[...] = keys
    ov_ref[...] = vals


def _merge_kernel(a_ref, b_ref, o_ref):
    # b_ref holds b reversed, so a ++ b_ref is bitonic
    keys = jnp.concatenate([a_ref[...], b_ref[...]], axis=-1)
    keys, _ = _merge_network(keys, (), tiebreak=-1)
    o_ref[...] = keys


def _merge_kv_kernel(ak_ref, av_ref, bk_ref, bv_ref, ok_ref, ov_ref, *, stable: bool):
    keys = jnp.concatenate([ak_ref[...], bk_ref[...]], axis=-1)
    vals = jnp.concatenate([av_ref[...], bv_ref[...]], axis=-1)
    # stable=True makes the comparator lexicographic in (key, value); when
    # values are unique global indices (dispatch use-case) this is exactly a
    # stable merge, and the runs stay lexicographically sorted inductively.
    keys, (vals,) = _merge_network(keys, (vals,), tiebreak=0 if stable else -1)
    ok_ref[...] = keys
    ov_ref[...] = vals


# ---------------------------------------------------------- pallas_call API

# Row-block height per grid step. 8 sublanes is the fp32 tile height; larger
# blocks amortize grid overhead while keeping (in+out) * block comfortably
# under VMEM (e.g. 8 x 8192 keys+vals fp32 in+out = 2 MiB).
_BLOCK_ROWS = 8


def _row_grid_call(n_in: int, n_out_cols: int, out_dtypes, rows: int, n: int):
    """Common pallas_call builder: 1-D grid over row blocks, full rows in VMEM.
    A partial last block (rows % 8 != 0) reads padding rows whose results
    are never written back."""
    br = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, br),)
    in_specs = [pl.BlockSpec((br, n), lambda i: (i, 0)) for _ in range(n_in)]
    out_specs = [pl.BlockSpec((br, n_out_cols), lambda i: (i, 0)) for _ in out_dtypes]
    out_shape = [jax.ShapeDtypeStruct((rows, n_out_cols), d) for d in out_dtypes]
    if len(out_specs) == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]
    return grid, in_specs, out_specs, out_shape


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_rows(keys: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Sort each row of ``keys`` (R, N) ascending. N must be a power of 2."""
    rows, n = keys.shape
    grid, in_specs, out_specs, out_shape = _row_grid_call(
        1, n, [keys.dtype], rows, n
    )
    return pl.pallas_call(
        _sort_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(keys)


@functools.partial(jax.jit, static_argnames=("stable", "interpret"))
def bitonic_sort_rows_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    stable: bool = True,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Key/value row sort. ``stable=True`` tie-breaks on values, which gives
    a stable sort whenever values are the original indices (the MoE dispatch
    use-case) and a deterministic total order otherwise."""
    rows, n = keys.shape
    grid, in_specs, out_specs, out_shape = _row_grid_call(
        2, n, [keys.dtype, values.dtype], rows, n
    )
    return pl.pallas_call(
        functools.partial(_sort_kv_kernel, stable=stable),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(keys, values)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_merge_rows(
    a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """Merge row-wise sorted (R, N) + (R, N) -> sorted (R, 2N)."""
    rows, n = a.shape
    b = jnp.flip(b, axis=-1)
    grid, in_specs, out_specs, out_shape = _row_grid_call(
        2, 2 * n, [a.dtype], rows, n
    )
    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("stable", "interpret"))
def bitonic_merge_rows_kv(
    ak: jnp.ndarray,
    av: jnp.ndarray,
    bk: jnp.ndarray,
    bv: jnp.ndarray,
    *,
    stable: bool = True,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    rows, n = ak.shape
    bk, bv = jnp.flip(bk, axis=-1), jnp.flip(bv, axis=-1)
    grid, in_specs, out_specs, out_shape = _row_grid_call(
        4, 2 * n, [ak.dtype, av.dtype], rows, n
    )
    return pl.pallas_call(
        functools.partial(_merge_kv_kernel, stable=stable),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(ak, av, bk, bv)
