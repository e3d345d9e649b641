"""Merge-path rounds: a wide key/value merge of sorted runs in one Pallas kernel.

``merge_runs_kv`` merges each processor's R sorted runs (R a power of two)
in log2(R) rounds; a round merges pairs of equal-length runs (the paper's
Fig. 2 handler pairing). Every round splits each pair's output into tiles
of ``TILE`` elements. Tile ``t`` of a pair (A, B) starts at diagonal
``d = t * TILE``; its co-rank ``a0`` is the number of A elements among the
pair's first ``d`` outputs, so the tile is the first ``TILE`` outputs of
the merge of ``A[a0:]`` and ``B[d - a0:]`` (merge path: Odeh et al.,
"Merge Path - Parallel Merging Made Simple", IPDPS workshops 2012).

Per tile the kernel DMAs a ``WIN``-element window of A and of B from HBM,
reverses the B window so that A ++ reverse(B) is bitonic, and runs the
bitonic merge network over the 8192-element sequence. Windows start
on a 128-element row, at ``a0`` and ``b0 = d - a0`` rounded down, so the
sorted windows begin with ``(a0 mod 128) + (b0 mod 128)`` elements that
precede the tile; ``d`` is a multiple of 128, so that is 0 or 128, and the
tile's ``TILE`` outputs are written from the sorted sequence's first or
second row.

Co-ranks come from the windows themselves: a pair's first tile has
``a0 = 0``, and the next tile's co-rank is ``a0`` plus the number of A
elements among this tile's outputs, counted on the sorted sequence. The
grid walks the tiles in order, interleaving processors, so a tile's
windows load while the other processors' tiles merge, and no search over
HBM is needed. A grid step merges two processors' tiles in one network
call: the two independent halves fill each other's latencies.

Ties go to A, then to the lower index within a run: the network compares
(key, source position) and positions come from an iota, never from HBM.
Each round is therefore a stable merge, and ``merge_runs_kv`` returns
exactly what a stable sort of each processor's concatenated runs returns
(``ops._xla_sort_kv``), bit for bit.

Every round has the same shapes (the runs are laid out at a stride that
is a multiple of ``TILE``; only the round index changes), so the rounds
are one ``lax.fori_loop`` around one ``pallas_call``: one kernel body to
trace, lower and compile per shape. Positions past a run's valid length
are masked to the sentinel key and a source position above every real
one inside the kernel, so the stride padding is never compared with data
and never reaches the valid output.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
WIN_ROWS = 32  # a window: 32 rows of 128 lanes
WIN = WIN_ROWS * LANES  # two windows are one 8192-element merge network
TILE_ROWS = WIN_ROWS - 1  # a tile starts in a window's first or second row
TILE = TILE_ROWS * LANES
BIG = jnp.iinfo(jnp.int32).max  # masked elements take positions just below this
MAX_CHAINS = 8  # processors whose tiles interleave (window buffers in VMEM)
PER_STEP = 2  # processors' tiles merged in one grid step


def geometry(runs: int, width: int):
    """(padded run stride, tiles per processor and round, slab rows) of a
    merge of ``runs`` runs of ``width`` elements, or None where the merge
    path cannot take it (fewer than 2 runs, or int32 positions
    overflowing)."""
    if runs < 2 or runs & (runs - 1):
        return None
    stride = -(-width // TILE) * TILE
    rows = runs * stride // LANES + WIN_ROWS
    if rows * LANES + WIN >= BIG:  # masked positions lie above real ones
        return None
    return stride, runs * stride // TILE, rows


def _gather(x, idx, axis):
    """``jnp.take_along_axis(x, idx, axis)`` of 2-D blocks, as the one
    Mosaic dynamic gather it lowers to, without its index arithmetic."""
    dims = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(axis,), start_index_map=(axis,),
        operand_batching_dims=(1 - axis,), start_indices_batching_dims=(1 - axis,))
    return lax.gather(x, idx[..., None], dims, (1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _reverse(x):
    """(32, 128) window reversed in row-major order: the lanes through one
    lane gather, the rows of each 8-row vreg through a sublane gather, the
    vregs in reverse order (Mosaic lowers neither ``rev`` nor a gather
    across vregs)."""
    x = _gather(x, LANES - 1 - lax.broadcasted_iota(jnp.int32, x.shape, 1), 1)
    sub = 7 - lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    return lax.concatenate([_gather(x[g:g + 8], sub, 0)
                            for g in range(x.shape[0] - 8, -1, -8)], 0)


def _merge_network(keys, vals, src, groups):
    """The bitonic merge network (``bitonic._merge_network``) over a
    (G, 8, 128) block of keys, values and source positions read as one
    row-major sequence of G*1024 elements, in (key, source) order. Pairs
    whole vregs apart are exchanged between vregs, with no data movement
    within a vreg; pairs within a vreg meet by sublane rotations or by one
    lane gather (``bitonic``'s network keeps a row in one sublane, which
    would leave seven of eight sublanes idle here). Source positions are
    distinct, so the order is strict and a pair's high element takes its
    partner exactly when the low one does: one comparison per element and
    stage. Written in ``lax`` ops: each ``jnp`` call would be one more
    traced function in every process's set-up."""
    n, subl, lanes = keys.shape
    assert groups & (groups - 1) == 0 and (subl, lanes) == (8, LANES)
    arrays = (keys, vals, src)

    def before(x, y):  # y sorts before x
        return lax.bitwise_or(lax.lt(y[0], x[0]), lax.bitwise_and(
            lax.eq(y[0], x[0]), lax.lt(y[2], x[2])))

    d = groups // 2
    while d >= 1:  # partners d vregs apart: the halves of blocks of 2d vregs
        blocks = [a.reshape(n // (2 * d), 2, d, subl, lanes) for a in arrays]
        lo, hi = [a[:, 0] for a in blocks], [a[:, 1] for a in blocks]
        swap = before(lo, hi)
        arrays = [lax.concatenate([lax.select(swap, y, x)[:, None],
                                   lax.select(swap, x, y)[:, None]], 1).reshape(keys.shape)
                  for x, y in zip(lo, hi)]
        d //= 2

    j = subl * lanes // 2
    while j >= 1:  # partners j elements apart within each vreg
        axis, shift = (2, j) if j < lanes else (1, j // lanes)
        idx = lax.broadcasted_iota(jnp.int32, keys.shape, axis)
        is_high = lax.ne(lax.bitwise_and(idx, jnp.int32(shift)), jnp.int32(0))
        if axis == 2:  # one lane gather over every vreg's rows
            rows = lax.bitwise_xor(idx, jnp.int32(shift)).reshape(n * subl, lanes)
            partner = lambda x: _gather(x.reshape(n * subl, lanes), rows, 1).reshape(x.shape)
        elif 2 * shift == subl:
            partner = lambda x: pltpu.roll(x, shift, 1)
        else:
            partner = lambda x: lax.select(is_high, pltpu.roll(x, shift, 1),
                                           pltpu.roll(x, subl - shift, 1))

        parts = [partner(a) for a in arrays]
        # the low element takes a partner that sorts first, the high one
        # a partner that sorts after it
        take = lax.bitwise_xor(is_high, before(arrays, parts))
        arrays = [lax.select(take, p, a) for a, p in zip(arrays, parts)]
        j //= 2
    return arrays


def _kernel(rnd_ref, k_hbm, v_hbm, ok_hbm, ov_hbm, win_k, win_v, out_k, out_v,
            a0_ref, in_sem, out_sem, *, width, stride, tiles, chains, per_step):
    s = pl.program_id(0)
    last = pl.num_programs(0) - 1
    rnd = rnd_ref[0]
    valid = width << rnd  # valid length of each run this round
    run_stride = stride << rnd
    tpp0 = 2 * stride // TILE  # tiles per pair in round 0
    # steps walk ``chains`` processors at a time, tile by tile, so that a
    # processor's next windows have ``chains // per_step`` steps to
    # arrive; a step merges ``per_step`` processors' tiles in one network,
    # whose independent halves fill each other's latencies. (``lax``
    # division: ``//`` and ``%`` on traced integers trace their
    # negative-operand fixups into every process's set-up.)
    steps = chains // per_step
    group, r = lax.div(s, steps * tiles), lax.rem(s, steps * tiles)
    t, c0 = lax.div(r, steps), lax.rem(r, steps) * per_step
    proc0 = group * chains + c0
    slot = s & 1

    def pair_of(t):
        """(pair, tile index within the pair) of tile ``t``"""
        pair = lax.div(t, tpp0) >> rnd
        return pair, t - pair * (tpp0 << rnd)

    def windows(t, a0):
        """Row offsets of the A and B windows of tile ``t`` at co-rank
        ``a0``, the run indices of their first elements, and the diagonal."""
        pair, i = pair_of(t)
        diag = jnp.minimum(i * TILE, 2 * valid)
        a_start, b_start = a0 & -LANES, (diag - a0) & -LANES
        base = pair * 2 * run_stride
        return (lax.div(base + a_start, LANES), lax.div(base + run_stride + b_start, LANES),
                a_start, b_start, diag)

    def window_copies(proc, t, a0, c):
        a_row, b_row, *_ = windows(t, a0)
        buf = t & 1
        srcs = ((k_hbm, win_k, 0, a_row), (v_hbm, win_v, 0, a_row),
                (k_hbm, win_k, 1, b_row), (v_hbm, win_v, 1, b_row))
        return [pltpu.make_async_copy(src.at[proc, pl.ds(row, WIN_ROWS)],
                                      win.at[c, buf, half], in_sem.at[c, buf, i])
                for i, (src, win, half, row) in enumerate(srcs)]

    def out_copies(sl, k, row):
        return [pltpu.make_async_copy(buf.at[sl, k, pl.ds(row, TILE_ROWS)],
                                      dst.at[proc0 + k, pl.ds(t * TILE_ROWS, TILE_ROWS)],
                                      out_sem.at[sl, k, i])
                for i, (buf, dst) in enumerate(((out_k, ok_hbm), (out_v, ov_hbm)))]

    @pl.when(r == 0)
    def _():  # a group's first tiles all start at co-rank 0
        def first(cc, carry):
            a0_ref[cc] = 0
            for cp in window_copies(group * chains + cc, 0, 0, cc):
                cp.start()
            return carry

        lax.fori_loop(0, chains, first, 0)

    flat = (lax.broadcasted_iota(jnp.int32, (WIN_ROWS, LANES), 0) * LANES
            + lax.broadcasted_iota(jnp.int32, (WIN_ROWS, LANES), 1))
    sentinel = jnp.array(jnp.inf if jnp.issubdtype(win_k.dtype, jnp.floating)
                         else jnp.iinfo(win_k.dtype).max, win_k.dtype)
    seq = lambda x: x.reshape(2 * WIN_ROWS // 8, 8, LANES)
    cols, keys, vals, src = [], [], [], []
    for k in range(per_step):
        c = c0 + k
        a0 = a0_ref[c]
        for cp in window_copies(proc0 + k, t, a0, c):
            cp.wait()
        _, _, a_start, b_start, diag = windows(t, a0)
        pos_a = a_start + flat
        pos_b = b_start + (WIN - 1) - flat  # B's index of the element at ``flat`` once reversed
        in_a, in_b = pos_a < valid, pos_b < valid
        keys.append(seq(jnp.concatenate([
            jnp.where(in_a, win_k[c, t & 1, 0], sentinel),
            jnp.where(in_b, _reverse(win_k[c, t & 1, 1]), sentinel)])))
        vals.append(seq(jnp.concatenate([win_v[c, t & 1, 0], _reverse(win_v[c, t & 1, 1])])))
        # A before B, then the index within the run: a stable merge. Masked
        # elements take distinct positions above every real one (``geometry``)
        src.append(seq(jnp.concatenate([jnp.where(in_a, pos_a, BIG - flat),
                                        jnp.where(in_b, valid + pos_b, BIG - WIN - flat)])))
        cols.append((a0, a_start, b_start, diag))
    groups = 2 * WIN_ROWS // 8
    keys, vals, src = (x.reshape(per_step, groups, 8, LANES) for x in _merge_network(
        *(jnp.concatenate(x) for x in (keys, vals, src)), groups=groups))

    @pl.when(s >= 2)
    def _():  # this slot's writes from two steps ago have left VMEM
        for k in range(per_step):
            for cp in out_copies(slot, k, 0):
                cp.wait()

    flat2 = seq(jnp.concatenate([flat, flat + WIN]))
    for k in range(per_step):
        c = c0 + k
        a0, a_start, b_start, diag = cols[k]
        # the sorted windows open with the (a0 mod 128) + (b0 mod 128)
        # elements before the tile: no row, or one (the module docstring)
        skip = (a0 - a_start) + (diag - a0 - b_start)
        # the next tile's co-rank: a0 plus the A elements among this tile's outputs
        in_tile = (flat2 >= skip) & (flat2 < skip + TILE)
        taken = jnp.sum(((src[k] < valid) & in_tile).astype(jnp.int32))
        diag_next = jnp.minimum(diag + TILE, 2 * valid)
        # (the clip is a no-op for a true co-rank, and keeps every DMA in bounds)
        a0_next = jnp.clip(a0 + taken, jnp.maximum(diag_next - valid, 0),
                           jnp.minimum(diag_next, valid))
        a0_next = jnp.where(pair_of(t + 1)[1] == 0, 0, a0_next)

        @pl.when(t + 1 < tiles)
        def _():  # this processor's next windows load while the others merge
            a0_ref[c] = a0_next
            for cp in window_copies(proc0 + k, t + 1, a0_next, c):
                cp.start()

        out_k[slot, k] = keys[k].reshape(2 * WIN_ROWS, LANES)
        out_v[slot, k] = vals[k].reshape(2 * WIN_ROWS, LANES)
        for cp in out_copies(slot, k, lax.div(skip, LANES)):
            cp.start()

    @pl.when(s == last)
    def _():
        for k in range(per_step):
            for cp in out_copies(slot, k, 0):
                cp.wait()

        @pl.when(s >= 1)
        def _():
            for k in range(per_step):
                for cp in out_copies(1 - slot, k, 0):
                    cp.wait()


def _round(rnd, kbuf, vbuf, *, width, stride, tiles, interpret):
    procs, rows, _ = kbuf.shape
    chains = math.gcd(procs, MAX_CHAINS)
    per_step = math.gcd(chains, PER_STEP)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, width=width, stride=stride, tiles=tiles,
                          chains=chains, per_step=per_step),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(procs * tiles // per_step,),
            in_specs=[any_spec, any_spec],
            out_specs=[any_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((chains, 2, 2, WIN_ROWS, LANES), kbuf.dtype),
                pltpu.VMEM((chains, 2, 2, WIN_ROWS, LANES), vbuf.dtype),
                pltpu.VMEM((2, per_step, 2 * WIN_ROWS, LANES), kbuf.dtype),
                pltpu.VMEM((2, per_step, 2 * WIN_ROWS, LANES), vbuf.dtype),
                pltpu.SMEM((chains,), jnp.int32),
                pltpu.SemaphoreType.DMA((chains, 2, 4)),
                pltpu.SemaphoreType.DMA((2, per_step, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(kbuf.shape, kbuf.dtype),
                   jax.ShapeDtypeStruct(vbuf.shape, vbuf.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="merge_path_kv",
    )(rnd, kbuf, vbuf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_runs_kv(keys, values, *, interpret: bool):
    """Stable merge of each processor's sorted runs: (B, R, C) keys and
    values of 32-bit dtypes, R a power of two >= 2, -> (B, R*C), equal bit
    for bit to a stable sort of each processor's concatenated runs."""
    procs, runs, width = keys.shape
    stride, tiles, rows = geometry(runs, width)
    pad = ((0, 0), (0, 0), (0, stride - width))

    def slab(x):
        x = jnp.pad(x, pad).reshape(procs, runs * stride)
        return jnp.pad(x, ((0, 0), (0, WIN))).reshape(procs, rows, LANES)

    def one_round(rnd, bufs):
        return _round(rnd[None], *bufs, width=width, stride=stride, tiles=tiles,
                      interpret=interpret)

    n_rounds = runs.bit_length() - 1
    kbuf, vbuf = jax.lax.fori_loop(0, n_rounds, one_round, (slab(keys), slab(values)))
    n = runs * width
    return (kbuf.reshape(procs, rows * LANES)[:, :n],
            vbuf.reshape(procs, rows * LANES)[:, :n])
