"""Jit'd dispatch wrappers around the Pallas sorting kernels.

Responsibilities:
  * pad rows to a power of two of at least one 128-lane vector with
    order-preserving sentinels,
  * widen narrow dtypes to the kernels' 32-bit lanes (bf16/f16 keys ->
    f32, 8/16-bit ints -> 32-bit) and cast back,
  * choose the execution path: the Pallas kernels (compiled by Mosaic on
    TPU, interpret=True on the CPU test backend) vs. ``jax.lax.sort``
    (XLA — also the production path for rows that exceed the VMEM tile
    budget and for 64-bit elements, which Mosaic does not vectorize).
    ``kernel_path`` names the choice for a dtype, and the planner
    records it in ``plan.reasons``,
  * expose ``tile_sort`` — a flat 1-D shard sort built exactly like the
    paper's local phase: sort fixed-size tiles ("worker threads"), then a
    balanced pairwise merge tree (Fig. 2, ``merge_tree``),
  * merge key/value runs wider than one kernel row in merge-path rounds
    (``merge_path_kv``, ``kernels/merge_path.py``), where ``merge_tree_kv``
    would end in one XLA sort.

The per-kernel correctness sweeps in ``tests/test_kernels.py`` validate
every path against ``ref.py``; ``tests/test_tpu_compile.py`` lowers the
kernels for a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitonic, merge_path

# Above this row length the working set stops fitting a comfortable VMEM
# tile (keys+values, in+out, double-buffered) and we fall back to lax.sort.
MAX_PALLAS_ROW = 8192
# Narrowest kernel row: one vreg's 128 lanes. Narrower rows are padded up.
MIN_PALLAS_ROW = 128
# Tile width used by tile_sort for the paper's local phase.
DEFAULT_TILE = 1024


def _interpret() -> bool:
    """Interpret the kernels on the CPU test backend, compile them with
    Mosaic on TPU. Any other backend is an error, never a silent
    interpreter run on an accelerator."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas sort kernels run on TPU (or interpreted on the CPU "
        f"test backend), not on {backend!r}; use SortConfig(use_pallas=False)"
    )


def _work_dtype(dtype):
    """The 32-bit lane dtype the kernels sort ``dtype`` in (an order-
    preserving widening), or None for 64-bit dtypes, which go to XLA."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize > 4:
        return None
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.dtype(jnp.float32)
    if jnp.issubdtype(dtype, jnp.signedinteger):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.uint32)  # unsigned ints and bool


def kernel_path(*dtypes) -> str:
    """Where the row sorts and merges of arrays of ``dtypes`` (keys, then
    payloads) run when Pallas is enabled, and why when it is not the
    kernels. Merges of runs wider than one kernel row take the merge-path
    kernel with payloads and one XLA sort without."""
    wide = [str(jnp.dtype(d)) for d in dtypes if _work_dtype(d) is None]
    if wide:
        return (f"XLA lax.sort: 64-bit elements "
                f"({', '.join(wide)}) have no Mosaic vector lowering")
    return (f"Pallas bitonic kernels on rows of {MIN_PALLAS_ROW}-{MAX_PALLAS_ROW} "
            f"elements; wider merges: key/value runs in Pallas merge-path "
            f"rounds, keys alone in one XLA lax.sort")


def sentinel_for(dtype: jnp.dtype) -> jnp.ndarray:
    """Largest representable value — padding that sorts to the end."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_rows(x: jnp.ndarray, n_to: int, fill) -> jnp.ndarray:
    pad = n_to - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=fill)


def _kernel_width(n: int) -> int:
    return max(MIN_PALLAS_ROW, _next_pow2(n))


def _merge_in_kernel(n: int, *dtypes, use_pallas: bool) -> bool:
    """Whether two rows of ``n`` elements of ``dtypes`` merge in the
    Pallas kernel (else in XLA)."""
    return (use_pallas and 2 * _kernel_width(n) <= MAX_PALLAS_ROW
            and all(_work_dtype(d) is not None for d in dtypes))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def sort_rows(keys: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Sort each row ascending; any row length, any numeric dtype."""
    rows, n = keys.shape
    np2 = _kernel_width(n)
    work_dtype = _work_dtype(keys.dtype)
    if not use_pallas or np2 > MAX_PALLAS_ROW or work_dtype is None:
        return jax.lax.sort(keys, dimension=-1)
    padded = _pad_rows(keys.astype(work_dtype), np2, sentinel_for(work_dtype))
    out = bitonic.bitonic_sort_rows(padded, interpret=_interpret())
    return out[:, :n].astype(keys.dtype)


@functools.partial(jax.jit, static_argnames=("stable", "use_pallas"))
def sort_rows_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    stable: bool = True,
    use_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Key/value row sort (values carried through the same permutation)."""
    rows, n = keys.shape
    np2 = _kernel_width(n)
    kdtype, vdtype = _work_dtype(keys.dtype), _work_dtype(values.dtype)
    if not use_pallas or np2 > MAX_PALLAS_ROW or kdtype is None or vdtype is None:
        k, v = jax.lax.sort([keys, values], dimension=-1, is_stable=stable, num_keys=1)
        return k, v
    pk = _pad_rows(keys.astype(kdtype), np2, sentinel_for(kdtype))
    pv = _pad_rows(values.astype(vdtype), np2, sentinel_for(vdtype))
    ok, ov = bitonic.bitonic_sort_rows_kv(pk, pv, stable=stable, interpret=_interpret())
    return ok[:, :n].astype(keys.dtype), ov[:, :n].astype(values.dtype)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def merge_rows(a: jnp.ndarray, b: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Merge two row-wise sorted (R, N) arrays -> sorted (R, 2N).

    Non-power-of-two (and sub-vector) widths are sentinel-padded for the
    bitonic path; the sentinels sort to the tail so the leading 2N
    outputs are the merge. (Keys equal to the sentinel itself are
    therefore not representable — documented library restriction,
    checked by the property tests.)
    """
    rows, n = a.shape
    if not _merge_in_kernel(n, a.dtype, use_pallas=use_pallas):
        return jax.lax.sort(jnp.concatenate([a, b], axis=-1), dimension=-1)
    np2 = _kernel_width(n)
    work_dtype = _work_dtype(a.dtype)
    fill = sentinel_for(work_dtype)
    out = bitonic.bitonic_merge_rows(
        _pad_rows(a.astype(work_dtype), np2, fill),
        _pad_rows(b.astype(work_dtype), np2, fill),
        interpret=_interpret(),
    )
    return out[:, : 2 * n].astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("stable", "use_pallas"))
def merge_rows_kv(ak, av, bk, bv, *, stable: bool = True, use_pallas: bool = True):
    rows, n = ak.shape
    if not _merge_in_kernel(n, ak.dtype, av.dtype, use_pallas=use_pallas):
        return _xla_sort_kv(jnp.concatenate([ak, bk], axis=-1),
                            jnp.concatenate([av, bv], axis=-1))
    np2 = _kernel_width(n)
    kdtype, vdtype = _work_dtype(ak.dtype), _work_dtype(av.dtype)
    kfill = sentinel_for(kdtype)
    vfill = sentinel_for(vdtype)
    ok, ov = bitonic.bitonic_merge_rows_kv(
        _pad_rows(ak.astype(kdtype), np2, kfill),
        _pad_rows(av.astype(vdtype), np2, vfill),
        _pad_rows(bk.astype(kdtype), np2, kfill),
        _pad_rows(bv.astype(vdtype), np2, vfill),
        stable=stable,
        interpret=_interpret(),
    )
    return ok[:, : 2 * n].astype(ak.dtype), ov[:, : 2 * n].astype(av.dtype)


def merge_path_engages(runs: int, width: int, *dtypes, use_pallas: bool) -> bool:
    """Whether a key/value merge of ``runs`` sorted runs of ``width``
    elements of ``dtypes`` runs as merge-path rounds: Pallas on, 32-bit
    lanes, runs too wide for one bitonic merge row, and a layout the
    kernel takes (``merge_path.geometry``)."""
    return (use_pallas and all(_work_dtype(d) is not None for d in dtypes)
            and not _merge_in_kernel(width, *dtypes, use_pallas=use_pallas)
            and merge_path.geometry(runs, width) is not None)


@jax.custom_batching.custom_vmap
def _merge_path_kv(keys, values):
    *lead, runs, width = keys.shape
    kdtype, vdtype = _work_dtype(keys.dtype), _work_dtype(values.dtype)
    k, v = merge_path.merge_runs_kv(keys.reshape(-1, runs, width).astype(kdtype),
                                    values.reshape(-1, runs, width).astype(vdtype),
                                    interpret=_interpret())
    n = runs * width
    return (k.reshape(*lead, n).astype(keys.dtype),
            v.reshape(*lead, n).astype(values.dtype))


@_merge_path_kv.def_vmap
def _merge_path_kv_vmap(axis_size, in_batched, keys, values):
    # a batch axis is one more leading axis of independent merges (a
    # Pallas call with manual DMAs has no batching rule of its own)
    keys, values = (x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
                    for x, b in zip((keys, values), in_batched))
    return _merge_path_kv(keys, values), (True, True)


@jax.jit
def merge_path_kv(keys, values):
    """Merge (..., R, C) row-sorted key/value runs into (..., R*C): the
    merge-path rounds of ``kernels/merge_path.py`` in the kernels' 32-bit
    lanes, bit for bit the stable XLA sort of the concatenated runs."""
    return _merge_path_kv(keys, values)


def _xla_sort_kv(keys, values):
    """Stable key/value sort along the last axis in XLA: ties keep their
    order, so on a concatenation of sorted runs it is a stable merge."""
    k, v = jax.lax.sort([keys, values], dimension=-1, is_stable=True, num_keys=1)
    return k, v


def merge_tree(runs: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Merge (R, W) row-sorted runs, R a power of two, into one sorted
    (R*W,) array: the balanced pairwise merge of the paper's Fig. 2 in
    the Pallas kernel while two runs fit one kernel row, then one XLA
    sort of what is left. XLA on TPU has no fast element gather or
    scatter, so an XLA merge round costs a whole sort; one sort stands in
    for all the wider rounds."""
    while runs.shape[0] > 1 and _merge_in_kernel(runs.shape[1], runs.dtype,
                                                  use_pallas=use_pallas):
        runs = merge_rows(runs[0::2], runs[1::2], use_pallas=use_pallas)
    return jax.lax.sort(runs.reshape(-1)) if runs.shape[0] > 1 else runs[0]


def merge_tree_kv(keys, values, *, stable: bool = True, use_pallas: bool = True):
    """Key/value ``merge_tree``. The XLA sort is stable, so ties keep the
    order of their runs, as a stable merge round would."""
    while keys.shape[0] > 1 and _merge_in_kernel(
            keys.shape[1], keys.dtype, values.dtype, use_pallas=use_pallas):
        keys, values = merge_rows_kv(keys[0::2], values[0::2], keys[1::2],
                                     values[1::2], stable=stable,
                                     use_pallas=use_pallas)
    if keys.shape[0] > 1:
        return _xla_sort_kv(keys.reshape(-1), values.reshape(-1))
    return keys[0], values[0]


# ------------------------------------------------------- paper local phase


@functools.partial(jax.jit, static_argnames=("tile", "use_pallas"))
def tile_sort(
    x: jnp.ndarray, *, tile: int = DEFAULT_TILE, use_pallas: bool = True
) -> jnp.ndarray:
    """Sort a flat shard exactly like the paper's local phase (Fig. 2).

    1. split the shard into ``tile``-sized slices — the paper's per-thread
       slices, here VMEM tiles;
    2. sort every tile with the bitonic network (one pallas_call, batched
       over rows);
    3. balanced pairwise merge tree (``merge_tree``): each round merges
       equal-length neighbor runs (even/odd rows), exactly the handler
       pairing of Fig. 2.
    """
    (n,) = x.shape
    np2 = _next_pow2(n)
    fill = sentinel_for(x.dtype if x.dtype != jnp.bfloat16 else jnp.float32)
    work = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    work = jnp.pad(work, (0, np2 - n), constant_values=fill)
    t = min(tile, np2)
    runs = work.reshape(np2 // t, t)
    runs = sort_rows(runs, use_pallas=use_pallas)
    return merge_tree(runs, use_pallas=use_pallas)[:n].astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "stable", "use_pallas"))
def tile_sort_kv(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    tile: int = DEFAULT_TILE,
    stable: bool = True,
    use_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Flat key/value shard sort via tile sort + balanced merge tree.

    Stability across tiles: the merge tree is stable by construction
    (the XLA sort keeps ties in run order; the bitonic merge path is made
    stable at the tile level by the value tie-break, which is exact when
    values are unique indices — the dispatch use-case)."""
    (n,) = keys.shape
    np2 = _next_pow2(n)
    kdtype = jnp.float32 if keys.dtype == jnp.bfloat16 else keys.dtype
    kfill = sentinel_for(kdtype)
    vfill = sentinel_for(values.dtype)
    wk = jnp.pad(keys.astype(kdtype), (0, np2 - n), constant_values=kfill)
    wv = jnp.pad(values, (0, np2 - n), constant_values=vfill)
    t = min(tile, np2)
    rk = wk.reshape(np2 // t, t)
    rv = wv.reshape(np2 // t, t)
    rk, rv = sort_rows_kv(rk, rv, stable=stable, use_pallas=use_pallas)
    sk, sv = merge_tree_kv(rk, rv, stable=stable, use_pallas=use_pallas)
    return sk[:n].astype(keys.dtype), sv[:n]
