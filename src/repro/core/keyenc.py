"""Key-encoding layer: front-end capabilities as encodings over the
stable single-key kv machinery, plus the fused device-side decode.

Every capability the unified ``repro.sort`` front end grows — descending
order, argsort (``want="order"``), lexicographic multi-key — is expressed
here as a *key transformation* plus a payload convention, so all three
backends (sim / mesh / stream) inherit each capability at once instead of
re-implementing it:

  * descending  -> ``flip``: an order-reversing bijection per dtype
                   (``~x`` for integers, ``-x`` for floats). Ascending
                   sort of flipped keys == stable descending sort.
  * argsort     -> payload = the flat global index (the paper's
                   provenance encoding); the kv sort is exactly stable
                   for unique increasing payloads, so the returned
                   permutation matches ``np.argsort(kind="stable")``.
  * multi-key   -> two strategies, chosen by the planner per request
                   (``plan.multikey``):
                   ``"packed"`` — when the tuple's effective bit widths
                   (measured from the data, or declared via
                   ``SortLimits.key_bits``) fit the pack budget — 31 bits
                   in the default 32-bit mode, 63 under the x64 opt-in
                   (``core.x64``) — the columns are fused into ONE
                   non-negative integer key (``pack_keys``; int32 for
                   packs <= 31 bits, int64 above — ``PackSpec.pack_dtype``):
                   each column becomes a bit field holding its monotone
                   unsigned rank (sign-xor for ints, the IEEE total-order
                   bit trick for float32/float64, minus the measured
                   range offset), per-key descending flags reverse the
                   field in place, and the single ascending integer sort
                   IS the lexicographic sort — one exchange pass instead
                   of one stable pass per key, and (keys-only)
                   coalescable by the serve flush engine.
                   ``"lsd"`` — the fallback: stable argsort by the last
                   key, then by each earlier key over the gathered order
                   — the classic radix-over-columns construction on top
                   of the stable single-key sort.

Device-side decode (``decode_grid`` / ``compact_rows``): the inverse of
the encodings above runs *on device*, fused into one jitted program per
backend output shape — compaction gather out of the sentinel-padded
(p, W) result grid, the inverse order-flip, the stable-argsort tie fix
(``local_sort.segment_stable_kv``) and the keys-only reverse — so
``SortOutput`` materialization is a single device->host copy of exactly
the n result elements instead of copy-then-decode host passes. The
numpy twins (``flip_np``/``decode_np``) remain as the legacy
``decode="host"`` path for differential testing (see ``SortLimits``).

Representable-key restriction: payload sorts cannot contain the key
dtype's order-maximal value in the ENCODED space — the dtype maximum
when ascending, the dtype minimum when descending (it flips onto the
sentinel) — enforced loudly and unconditionally by
``check_payload_keys`` at the planner boundary (the exchange's
in-program capacity pads corrupt the payload even when the front end
never pads; NaN keys are rejected for the same reason — they order past
the sentinel). Keys-only sorts of NaN-free keys have no restriction in
either direction: a sentinel-valued key is value-identical to a pad, so
the decoded keys are still bit-exact. NaN keys are unsupported
throughout (seed-era limitation: they sort past the padding sentinel).
For PACKED multi-key payload sorts the restriction lives in the packed
space: a tuple saturating a full-budget pack (exactly 31 bits into
int32, or — under x64 mode — exactly 63 bits into int64) lands on the
pack dtype's sentinel, and ``check_payload_keys`` names both the packed
value and the source column values (narrower packs cannot collide at
all, and packed keys-only sorts are unrestricted).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


def flip(x):
    """Order-reversing bijection; its own inverse. np and jnp arrays."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return -x
    return ~x


def flip_np(x: np.ndarray) -> np.ndarray:
    """numpy-side flip (legacy host materialization decode path)."""
    if np.issubdtype(x.dtype, np.floating):
        return -x
    return ~x


def encode(keys, descending: bool):
    return flip(keys) if descending else keys


def decode_np(keys: np.ndarray, descending: bool) -> np.ndarray:
    return flip_np(keys) if descending else keys


# ----------------------------------------------------- provenance payload

PROVENANCE_INT32_CAP = 1 << 31
"""Largest element count an int32 provenance payload can index (global
positions 0..n-1 fit int32 iff n <= 2^31). Module-level so boundary
tests can shrink it instead of allocating 2 GiB arrays."""


def provenance_dtype(n: int, *, x64: bool = False):
    """The index dtype of an n-element provenance payload.

    int32 up to ``PROVENANCE_INT32_CAP`` elements; past that the payload
    MUST widen to int64, which only the x64 mode can carry on device —
    without the mode a silently truncated int32 iota would wrap negative
    and corrupt every ``want="order"`` permutation past 2^31, so the
    overflow is rejected loudly at the door instead."""
    if n <= PROVENANCE_INT32_CAP:
        return np.int32
    if not x64:
        raise TypeError(
            f"provenance payload for n={n} elements overflows int32 "
            f"(more than 2^31 global positions): the index payload must "
            f"be int64, which needs x64 mode. Opt in with "
            f"repro.enable_x64(), REPRO_X64=1, or SortLimits(x64=True)."
        )
    return np.int64


# ------------------------------------------------- multi-key bit packing

PACK_BUDGET_BITS = 31
"""Packed keys are NON-NEGATIVE integer fields. In the default 32-bit
mode the pack is an int32: 31 usable bits — without jax x64 a wider
pack has nowhere to go, and tuples whose widths exceed the budget fall
back to the LSD stable passes. Staying non-negative also keeps the
whole packed space below the padding sentinel except for the single
saturated value of an exactly-full pack (see ``check_payload_keys``)."""

PACK_BUDGET_BITS_X64 = 63
"""The x64-mode budget (``core.x64`` opt-in): a non-negative int64 pack
holds 63 usable bits, so (timestamp, shard)-style tuples that overflow
the 31-bit budget fuse into ONE int64 sort instead of LSD passes.
Packs that fit 31 bits still pack into int32 (``PackSpec.pack_dtype``)
— the 32-bit path is bit-identical with the mode on or off."""


def pack_budget_bits() -> int:
    """The ambient pack budget: 63 when x64 mode is on, else 31."""
    from repro.core import x64 as _x64

    return PACK_BUDGET_BITS_X64 if _x64.x64_enabled() else PACK_BUDGET_BITS


_PACK_KINDS = {
    "uint8": "uint", "uint16": "uint", "uint32": "uint", "uint64": "uint",
    "int8": "int", "int16": "int", "int32": "int", "int64": "int",
    "float32": "float", "float64": "float",
}

_SIGN32 = 1 << 31
_SIGN64 = 1 << 63


def _rank_wide(dtype_name: str) -> bool:
    """Does this column rank in uint64 space (8-byte dtype) or uint32?"""
    return np.dtype(dtype_name).itemsize == 8


@dataclasses.dataclass(frozen=True)
class KeyFieldSpec:
    """How one key column maps to/from its bit field in the packed key.

    dtype: numpy dtype name of the source column (``"int16"``, ...).
    kind: ``"uint" | "int" | "float"`` — which monotone rank transform
      applies (identity / sign-bit xor / IEEE total-order bit trick).
    lo: rank-space offset subtracted before packing (the measured
      minimum rank, or the declared-range origin for ``key_bits``).
    width: field bits; 0 for constant columns.
    descending: the field is stored order-reversed (``mask - field``) so
      the ascending packed sort realizes this key's descending order.
    declared: width came from ``SortLimits.key_bits`` (a caller promise,
      validated at pack time) rather than measurement.
    """

    dtype: str
    kind: str
    lo: int
    width: int
    descending: bool
    declared: bool = False


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Complete recipe for fusing a key tuple into one integer key —
    hashable, so it keys jit static arguments, compiled-program caches
    and the serve flush buckets. MSB-first: field 0 (the primary key)
    occupies the most significant bits. The pack WIDTH is a derived
    property, not stored state: packs that fit 31 bits are int32, wider
    packs (x64 mode only) are int64 — so a narrow tuple planned under
    x64 mode produces the same spec, program keys and packed bits as
    the 32-bit mode would."""

    fields: tuple

    @property
    def total_bits(self) -> int:
        return sum(f.width for f in self.fields)

    @property
    def pack_bits(self) -> int:
        """Usable bits of the pack word this spec occupies (31 or 63)."""
        return (PACK_BUDGET_BITS if self.total_bits <= PACK_BUDGET_BITS
                else PACK_BUDGET_BITS_X64)

    @property
    def pack_dtype(self):
        """numpy dtype of the packed key: int32, or int64 for wide packs."""
        return np.int32 if self.pack_bits == PACK_BUDGET_BITS else np.int64

    def describe(self) -> str:
        widths = "+".join(str(f.width) for f in self.fields)
        return f"widths {widths}={self.total_bits}/{self.pack_bits} bits"


def _rank_np(col: np.ndarray, kind: str, *, wide: bool = False) -> np.ndarray:
    """Monotone map of a column into unsigned rank space (host side):
    uint32 for <=4-byte dtypes, uint64 for the x64-mode 8-byte ones."""
    if wide:
        if kind == "float":
            b = np.ascontiguousarray(col, np.float64).view(np.uint64)
            mask = np.where(b >> np.uint64(63),
                            np.uint64(0xFFFFFFFFFFFFFFFF), np.uint64(_SIGN64))
            return b ^ mask
        if kind == "int":
            # sign-bit xor == add 2^63 mod 2^64: the int64 order as uint64
            return np.ascontiguousarray(col, np.int64).view(np.uint64) \
                ^ np.uint64(_SIGN64)
        return col.astype(np.uint64)
    if kind == "float":
        b = np.ascontiguousarray(col, np.float32).view(np.uint32)
        # IEEE-754 total-order trick: flip all bits of negatives, only
        # the sign bit of non-negatives -> unsigned compare == float <
        mask = np.where(b >> np.uint32(31), np.uint32(0xFFFFFFFF),
                        np.uint32(0x80000000))
        return b ^ mask
    if kind == "int":
        return (col.astype(np.int64) + _SIGN32).astype(np.uint32)
    return col.astype(np.uint32)


def _unrank_np(rank: np.ndarray, f: KeyFieldSpec) -> np.ndarray:
    if _rank_wide(f.dtype):
        if f.kind == "float":
            mask = np.where(rank >> np.uint64(63), np.uint64(_SIGN64),
                            np.uint64(0xFFFFFFFFFFFFFFFF))
            return (rank ^ mask).view(np.float64)
        if f.kind == "int":
            return (rank ^ np.uint64(_SIGN64)).view(np.int64)
        return rank.astype(f.dtype)
    if f.kind == "float":
        mask = np.where(rank >> np.uint32(31), np.uint32(0x80000000),
                        np.uint32(0xFFFFFFFF))
        return (rank ^ mask).view(np.float32)
    if f.kind == "int":
        return (rank ^ np.uint32(_SIGN32)).view(np.int32).astype(f.dtype)
    return rank.astype(f.dtype)


def plan_pack(klist, descending, key_bits=None, ranks: dict | None = None,
              budget: int | None = None):
    """Decide whether a key tuple can fuse into one packed integer sort.

    Measures each column's effective width (rank-range bits) unless
    ``key_bits`` declares it — a declared width ``w`` promises the
    column's values lie in ``[0, 2**w)`` (ints only; float widths are
    always measured, since a bit budget over the IEEE rank space is not
    a meaningful caller contract) and is validated at pack time. Returns
    ``(PackSpec, reason)`` when the widths fit ``budget`` — the
    planner passes its mode-resolved budget (31, or 63 under x64 mode);
    None reads the ambient ``pack_budget_bits()`` — else
    ``(None, reason)``; the planner records either way.

    ``ranks``: optional dict the caller passes to capture the measured
    unsigned rank array per column index, so ``pack_keys(..., ranks=...)``
    does not recompute the O(n) monotone transform the measurement
    already paid for (PackSpec itself must stay a small hashable recipe
    — it keys jit static args and serve buckets — so the arrays ride
    this side channel instead).
    """
    if budget is None:
        budget = pack_budget_bits()
    if key_bits is not None:
        if not isinstance(key_bits, tuple):
            raise ValueError(
                f"SortLimits.key_bits must be a tuple (hashable limits), "
                f"got {type(key_bits).__name__}"
            )
        if len(key_bits) != len(klist):
            raise ValueError(
                f"SortLimits.key_bits has {len(key_bits)} entries for "
                f"{len(klist)} keys (use None entries to measure a key)"
            )
    fields = []
    for i, (col, desc) in enumerate(zip(klist, descending)):
        name = str(col.dtype)
        kind = _PACK_KINDS.get(name)
        if kind is None:
            return None, f"key {i} dtype {name} is not packable"
        wide = _rank_wide(name)
        declared = key_bits[i] if key_bits is not None else None
        if declared is not None:
            if kind == "float":
                raise ValueError(
                    f"SortLimits.key_bits[{i}]: declared widths are "
                    f"unsupported for {name} keys — float field widths "
                    f"are measured from the monotone rank range (pass "
                    f"None for this key)"
                )
            declared = int(declared)
            bits_max = 8 * np.dtype(name).itemsize
            if not 0 <= declared <= bits_max:
                raise ValueError(
                    f"SortLimits.key_bits[{i}]={declared} out of range "
                    f"[0, {bits_max}]"
                )
            lo = (_SIGN64 if wide else _SIGN32) if kind == "int" else 0
            fields.append(KeyFieldSpec(name, kind, lo, declared,
                                       bool(desc), declared=True))
            continue
        col = np.asarray(col).reshape(-1)
        if kind == "float" and col.size and bool(np.isnan(col).any()):
            # NaN has no place in the rank order (the library rejects it
            # everywhere); fall back so the LSD pass raises the standard
            # loud NaN error instead of packing silently diverging
            return None, f"key {i} contains NaN (unsupported keys)"
        if col.size == 0:
            lo, width = 0, 0
        else:
            r = _rank_np(col, kind, wide=wide)
            if ranks is not None:
                ranks[i] = r
            lo = int(r.min())
            width = int(int(r.max()) - lo).bit_length()
        fields.append(KeyFieldSpec(name, kind, lo, width, bool(desc)))
    spec = PackSpec(tuple(fields))
    if spec.total_bits > budget:
        widths = "+".join(str(f.width) for f in spec.fields)
        hint = ""
        if (budget == PACK_BUDGET_BITS
                and spec.total_bits <= PACK_BUDGET_BITS_X64):
            hint = (
                " (would fit the 63-bit x64 budget: opt in with "
                "repro.enable_x64() / REPRO_X64=1 / SortLimits(x64=True))"
            )
        return None, (
            f"total width {widths}={spec.total_bits} bits exceeds the "
            f"{budget}-bit pack budget{hint}"
            f"{_float_band_hint(klist, spec)}"
        )
    return spec, spec.describe()


def _float_band_hint(klist, spec: PackSpec) -> str:
    """Why did a float column measure wide? Its IEEE rank range spans
    the full exponent band of its values — name that band (and a zero
    crossing, which forces the rank range across the sign boundary) in
    the pack-fallback reason so ``repro.explain()`` says WHY the budget
    broke instead of just that it did. Only measured float fields can
    be at fault (int widths are exact, and declared widths raise their
    own errors), so the hint is empty for everything else."""
    notes = []
    for i, f in enumerate(spec.fields):
        if f.kind != "float" or f.width == 0:
            continue
        col = np.asarray(klist[i]).reshape(-1).astype(np.float64)
        finite = col[np.isfinite(col) & (col != 0.0)]
        if finite.size == 0:
            continue
        _, exp = np.frexp(np.abs(finite))
        lo, hi = int(exp.min()) - 1, int(exp.max()) - 1
        crosses = bool((col > 0).any() and (col < 0).any())
        notes.append(
            f"key {i} ({f.dtype}) measured {f.width} rank bits from the "
            f"exponent band [2^{lo}, 2^{hi}]"
            + (" crossing zero" if crosses else "")
        )
    if not notes:
        return ""
    return (
        "; " + "; ".join(notes)
        + " — packing floats needs a narrow exponent band on one side "
        "of zero"
    )


def pack_keys(klist, spec: PackSpec, ranks: dict | None = None) -> np.ndarray:
    """Fuse the key tuple into the packed non-negative integer array
    (int32 for <=31-bit specs, int64 above — ``spec.pack_dtype``).

    Host-side numpy (multi-key inputs are host arrays after request
    normalization): per column, monotone unsigned rank minus the spec
    offset, order-reversed within the field for descending keys, then
    accumulated MSB-first into a uint64 word (explicit casts throughout:
    numpy would otherwise promote mixed int64/uint64 column math to
    float64 and corrupt high bits). Declared (``key_bits``) widths are
    validated here — a value outside the promised range raises instead
    of packing a corrupt key. ``ranks``: per-column rank arrays already
    computed by ``plan_pack`` measurement (skips recomputing the
    monotone transform)."""
    acc = np.zeros(np.asarray(klist[0]).reshape(-1).shape[0], np.uint64)
    for i, (col, f) in enumerate(zip(klist, spec.fields)):
        col = np.asarray(col).reshape(-1)
        r = ranks.get(i) if ranks is not None else None
        if r is None:
            r = _rank_np(col, f.kind, wide=_rank_wide(f.dtype))
        rt = r.dtype.type  # np.uint32 | np.uint64 — stay in rank space
        field = (r - rt(f.lo)).astype(r.dtype)
        if f.declared and f.width < 8 * r.dtype.itemsize:
            over = field >> rt(f.width)
            if bool(over.any()):
                j = int(np.argmax(over != 0))
                raise ValueError(
                    f"key {i} value {col[j]!r} does not fit the declared "
                    f"SortLimits.key_bits[{i}]={f.width} bits (declared "
                    f"keys must lie in [0, {2 ** f.width})); widen the "
                    f"declaration or pass None to measure this key"
                )
        if f.descending:
            field = rt((1 << f.width) - 1) - field
        acc = (acc << np.uint64(f.width)) | field.astype(np.uint64)
    return acc.astype(spec.pack_dtype)


def unpack_np(packed: np.ndarray, spec: PackSpec) -> tuple:
    """Host-side inverse of ``pack_keys`` — the ``decode="host"`` /
    stream-backend twin of the device ``unpack_fields``."""
    u = np.asarray(packed).astype(np.uint64)
    cols = []
    shift = spec.total_bits
    for f in spec.fields:
        shift -= f.width
        mask = (1 << f.width) - 1
        rt = np.uint64 if _rank_wide(f.dtype) else np.uint32
        field = ((u >> np.uint64(shift)) & np.uint64(mask)).astype(rt)
        if f.descending:
            field = rt(mask) - field
        cols.append(_unrank_np(field + rt(f.lo), f))
    return tuple(cols)


def unpack_fields(packed: jnp.ndarray, spec: PackSpec) -> tuple:
    """Device-side unpack: packed int32/int64 -> the original columns.

    Pure elementwise bit surgery (shift/mask, the field reversal for
    descending keys, and the inverse rank transforms), so it fuses into
    whatever jitted program holds the packed result — ``decode_grid``
    for ``repro.sort`` materialization, ``sim.sample_sort_sim_flat``
    for coalesced serve flushes. ``spec`` is a static (hashable) arg.
    Wide (int64) packs require jax x64 mode in the tracing context —
    guaranteed by construction, since producing an int64 pack required
    it; an int64 column whose measured range fits a 31-bit int32 pack
    still ranks in uint64 space here."""
    wide_word = spec.total_bits > PACK_BUDGET_BITS
    word = jnp.uint64 if wide_word else jnp.uint32
    u = packed.astype(word)
    cols = []
    shift = spec.total_bits
    for f in spec.fields:
        shift -= f.width
        mask = word((1 << f.width) - 1)
        field = (u >> shift) & mask if f.width else jnp.zeros_like(u)
        if f.descending:
            field = mask - field
        if _rank_wide(f.dtype):
            rank = field.astype(jnp.uint64) + jnp.uint64(f.lo)
            if f.kind == "float":
                m = jnp.where(rank >> 63 != 0, jnp.uint64(_SIGN64),
                              jnp.uint64(0xFFFFFFFFFFFFFFFF))
                cols.append(
                    jax.lax.bitcast_convert_type(rank ^ m, jnp.float64))
            elif f.kind == "int":
                cols.append(jax.lax.bitcast_convert_type(
                    rank ^ jnp.uint64(_SIGN64), jnp.int64))
            else:
                cols.append(rank.astype(f.dtype))
            continue
        rank = field.astype(jnp.uint32) + jnp.uint32(f.lo)
        if f.kind == "float":
            m = jnp.where(rank >> 31 != 0, jnp.uint32(0x80000000),
                          jnp.uint32(0xFFFFFFFF))
            cols.append(jax.lax.bitcast_convert_type(rank ^ m, jnp.float32))
        elif f.kind == "int":
            v32 = jax.lax.bitcast_convert_type(
                rank ^ jnp.uint32(_SIGN32), jnp.int32)
            cols.append(v32.astype(f.dtype))
        else:
            cols.append(rank.astype(f.dtype))
    return tuple(cols)


@functools.lru_cache(maxsize=None)
def _unpack_chunk_prog(spec: PackSpec, m: int):
    # one compiled program per (spec, pow2 length) bucket — a steady
    # stream of output chunks reuses O(log) programs, not one per size
    return jax.jit(lambda x: unpack_fields(x, spec))


def unpack_chunk(packed: np.ndarray, spec: PackSpec) -> tuple:
    """Device-unpack ONE packed output chunk into its column tuple.

    The per-chunk twin of the fused unpack ``decode_grid`` runs for
    sim/mesh materialization: the stream backend's sorted output arrives
    as host chunks of the packed integer key, and this pushes each chunk
    back through ``unpack_fields`` on device (padded to the next power
    of two for program reuse, sliced back after D2H) so packed
    multi-key results stream via ``SortOutput.chunks()`` without a host
    bit-surgery pass per column."""
    packed = np.asarray(packed)
    n = int(packed.shape[0])
    if n == 0:
        return unpack_np(packed, spec)
    from repro.kernels.ops import _next_pow2

    m = _next_pow2(n)
    if m != n:
        buf = np.zeros(m, packed.dtype)
        buf[:n] = packed
    else:
        buf = packed
    cols = _unpack_chunk_prog(spec, m)(jnp.asarray(buf))
    return tuple(np.asarray(c)[:n] for c in cols)


def check_payload_keys(keys, descending: bool, *, packspec=None) -> None:
    """Reject payload sorts whose keys collide with the padding sentinel.

    Ascending payload sorts cannot contain the key dtype's MAXIMUM (it
    is the padding sentinel); descending payload sorts cannot contain
    the dtype's MINIMUM (the order-flip encoding maps it onto the
    sentinel). Either way the colliding key is indistinguishable from a
    pad once staged, the exchange's *in-program* capacity pads
    interleave with it under stable ties, and sentinel payload values
    leak into the output — front-end padding is NOT required (verified
    empirically on shard-divisible inputs), which is why this check runs
    unconditionally at the planner boundary for every sort that carries
    a payload (user values or the argsort provenance index): a loud
    ValueError naming the offending value instead of silent corruption.
    Keys-only sorts are exempt in both directions — a sentinel-valued
    key and a pad are value-identical, so the decoded keys stay
    bit-exact.

    ``packspec``: set when ``keys`` is a PACKED multi-key array — only
    an exactly-full pack (31 bits into int32, or 63 bits into the
    x64-mode int64) can reach its pack dtype's sentinel (every narrower
    pack tops out below it), and the error then names the packed value
    AND the source column values it decodes to, so the caller can see
    which tuple saturated the budget.
    """
    if packspec is not None:
        if packspec.total_bits < packspec.pack_bits:
            return  # packed space tops out below the pack-dtype sentinel
        pdt = np.dtype(packspec.pack_dtype)
        bad = pdt.type(np.iinfo(pdt).max)
        hits = np.asarray(keys) == bad
        if not bool(hits.any()):
            return
        row = int(np.argmax(hits))
        src = unpack_np(np.asarray([bad], pdt), packspec)
        cols = ", ".join(
            f"key {i} ({f.dtype})={c[0]!r}"
            for i, (c, f) in enumerate(zip(src, packspec.fields))
        )
        raise ValueError(
            f"multi-key sort with a payload cannot represent the packed "
            f"key {int(bad)} (it is the {pdt.name} padding sentinel: this "
            f"tuple saturates the full {packspec.total_bits}-bit pack, "
            f"first at row {row}) — source columns: {cols}. Shift or "
            f"drop those rows, force the LSD fallback with "
            f"SortLimits(multikey='lsd'), or sort keys-only (packed "
            f"keys-only sorts have no restriction)."
        )
    dt_s = str(keys.dtype)
    floating = dt_s == "bfloat16" or np.issubdtype(np.dtype(dt_s), np.floating)
    if floating and bool(np.asarray((keys != keys).any())):
        # NaN orders AFTER the +-inf sentinel in the device sort, so the
        # in-program pads leak into the first-n slice ahead of the NaN
        # elements — the same silent corruption mode as a sentinel
        # collision, caught the same loud way (x != x is the dtype-
        # agnostic NaN probe: works for np, jnp and bfloat16 alike)
        raise ValueError(
            "sort with a payload cannot contain NaN keys: NaN orders "
            "after the padding sentinel, so padding would leak into the "
            "output and the payload would come back corrupted. Drop or "
            "impute the NaNs first (np.nan_to_num / boolean masking)."
        )
    if dt_s == "bfloat16":
        # bf16 keys sort as f32 whose sentinel is +-inf — a bf16 inf key
        # upcasts onto it, so the collision check applies here too
        bad = -np.inf if descending else np.inf
    else:
        dt = np.dtype(dt_s)
        if np.issubdtype(dt, np.floating):
            bad = dt.type(-np.inf if descending else np.inf)
        else:
            info = np.iinfo(dt)
            bad = dt.type(info.min if descending else info.max)
    if bool(np.asarray((keys == bad).any())):
        direction = "descending" if descending else "ascending"
        cause = (
            f"the order-flip encoding maps the {dt_s} minimum onto the "
            f"padding sentinel" if descending
            else f"it is the {dt_s} padding sentinel"
        )
        raise ValueError(
            f"{direction} sort with a payload cannot represent the key "
            f"{bad!r}: {cause}, so its payload would come back corrupted. "
            f"Shift or drop those keys first, or sort them keys-only "
            f"(no restriction without values/want='order')."
        )


def stable_argsort(keys: jnp.ndarray, *, tile: int = 1024,
                   use_pallas: bool = False):
    """Stable local argsort: (sorted_keys, order) for a flat shard.

    The shared primitive under MoE sorted dispatch (expert ids are the
    keys, slots the payload) and the front end's local argsort paths —
    payload = iota is globally unique and increasing, which makes the kv
    sort exactly stable.
    """
    from repro.core.local_sort import local_sort_kv

    slots = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return local_sort_kv(keys, slots, tile=tile, use_pallas=use_pallas)


# ------------------------------------------------------ device-side decode


def compact_rows(grid: jnp.ndarray, counts, m: int) -> jnp.ndarray:
    """Front-compact a sorted, sentinel-padded (p, W) result grid into
    its first ``m`` global elements on device (``m`` is static).

    Row r holds its sorted bucket in positions [0, counts[r]); the
    concatenation of those prefixes is the globally sorted dataset
    (range-partitioned rows). Implemented as p contiguous
    ``dynamic_update_slice`` row copies walked in row order — row r+1's
    write starts exactly where row r's valid prefix ends, overwriting
    row r's sentinel tail, so after the last row positions [0, m) hold
    the answer. (An element gather expresses the same thing but lowers
    to scalarized HLO on CPU and runs ~10x slower than these straight
    row memcpys.) The ``+W`` scratch tail absorbs the last row's pads;
    a row whose start offset exceeds m is pad-only beyond the result
    and lands harmlessly in the scratch (dynamic_update_slice clamps
    its start to m).
    """
    p, w = grid.shape
    counts = jnp.asarray(counts).astype(jnp.int32).reshape(-1)
    starts = jnp.cumsum(counts) - counts
    buf = jnp.zeros((m + w,), grid.dtype)
    for r in range(p):  # unrolled: p is the (small, static) shard count
        buf = jax.lax.dynamic_update_slice(buf, grid[r], (starts[r],))
    return buf[:m]


def _replicated(x):
    """A mesh backend's rows arrive sharded over the sort axis (explicit
    mesh axes); compaction walks every row in order, so the decode runs on
    a full copy of the grid. Needs the caller's ``jax.set_mesh``."""
    if x is None or all(s is None for s in jax.typeof(x).sharding.spec):
        return x
    return jax.sharding.reshard(x, jax.sharding.PartitionSpec())


@functools.partial(
    jax.jit, static_argnames=("m", "descending", "want_order", "packspec")
)
def decode_grid(keys_grid, counts, values_grid=None, *, m: int,
                descending: bool = False, want_order: bool = False,
                packspec: PackSpec | None = None):
    """Fused device-side materialization: one program, one D2H copy.

    Collapses everything the host decode used to do after the sort —
    per-row unpad + concatenate, the ``want="order"`` stability tie fix,
    and the descending inverse flip — into a single jitted program over
    the backend's (p, W) sentinel-padded result grid, returning the
    first ``m`` output positions. ``m`` is a static PROGRAM length, not
    the request length: the planner rounds the request's n up to a
    power-of-two shape bucket and slices ``[:n]`` on host, so serving
    traffic with arbitrarily varied request sizes compiles O(log)
    decode programs instead of one per distinct n. The planner
    dispatches this program eagerly, right after the overflow ladder
    resolves, so by the time a caller touches ``.keys`` the decode has
    already executed asynchronously and materialization really is just
    the D2H copy.

      descending: keys were flip-encoded; apply the inverse flip.
      want_order: payload is the provenance index; restore exact
                  stability with the device segment-stable pass (the
                  investigator splits tied ranges across destinations,
                  so the raw payload comes back segment-interleaved).
                  Output positions past the staged total (possible when
                  the shape bucket exceeds it) are masked to the
                  sentinel first, so tail garbage can never join a real
                  tie segment.
      packspec:   the keys grid holds PACKED multi-key values; unpack
                  them back into the original tuple columns as the last
                  fused step (after the tie fix, which must see the
                  packed keys — a packed tie IS an all-columns tie).
                  ``keys`` is then a TUPLE of (m,) column arrays.

    Returns ``(keys, values-or-None)`` device arrays of shape (m,);
    only the first min(n, m) positions are meaningful.
    """
    from repro.core.local_sort import segment_stable_kv
    from repro.kernels.ops import sentinel_for

    with jax.named_scope("decode"):
        keys_grid, counts, values_grid = (
            _replicated(x) for x in (keys_grid, counts, values_grid))
        ks = compact_rows(keys_grid, counts, m)
        vs = None
        if values_grid is not None:
            vs = compact_rows(values_grid, counts, m)
            if want_order:
                total = jnp.sum(jnp.asarray(counts).astype(jnp.int32))
                valid = jnp.arange(m, dtype=jnp.int32) < total
                vs = segment_stable_kv(
                    jnp.where(valid, ks, sentinel_for(ks.dtype)),
                    jnp.where(valid, vs, sentinel_for(vs.dtype)),
                )
        if descending:
            ks = flip(ks)
        if packspec is not None:
            ks = unpack_fields(ks, packspec)
    return ks, vs
