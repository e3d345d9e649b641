"""Graph500 Kronecker edge lists, made on the device from a PRNG key.

Follows the Graph500 reference generator: each of the ``scale`` bit levels
of an edge's (start, end) vertex pair picks one quadrant of the adjacency
matrix with probabilities A, B, C and D = 1 - A - B - C
(``kronecker_generator.m``: ``ii_bit = rand > A+B``, then
``jj_bit = rand > (C/(C+D) if ii_bit else A/(A+B))``), with 16-bit uniforms. Vertex labels are
then permuted by the reference C generator's ``scramble``: add, multiply
by an odd constant and bit-reverse within ``scale`` bits, twice, with
constants drawn from the key. That is a bijection on [0, 2^scale), so
degrees keep their power law and land on scattered labels. Edges are
drawn independently, so their order is already random and the
reference's final shuffle of the edge list is left out.

Parameters: ``scale``, ``edgefactor``, ``A``, ``B``, ``C``. Arrays made:
``src`` and ``dst``, (edgefactor * 2^scale,) int32 each, laid out by
``sharding``; with a mesh each chip makes its own share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the odd multipliers the reference ORs into its two scramble constants
_ODD = (0x11493211, 0x02C843A5)


def level_bits(key, n: int, level: int, a: float, b: float, c: float):
    """The (start, end) bit pair of ``n`` edges at one level of recursion.
    One 32-bit draw per edge gives both 16-bit uniforms: the quadrant
    probabilities are kept to 1/65536."""
    r = jax.random.bits(jax.random.fold_in(key, level), (n,), jnp.uint32)
    u, w = r >> 16, r & 0xFFFF
    ii = u >= _threshold(a + b)
    jj = w >= jnp.where(ii, _threshold(c / (1.0 - a - b)), _threshold(a / (a + b)))
    return ii.astype(jnp.uint32), jj.astype(jnp.uint32)


def _threshold(p: float) -> int:
    """``u16 >= t`` holds with probability 1 - p for a uniform 16-bit u16."""
    return int(round(p * 65536))


def _bitreverse(v, bits: int):
    v = ((v >> 1) & 0x55555555) | ((v & 0x55555555) << 1)
    v = ((v >> 2) & 0x33333333) | ((v & 0x33333333) << 2)
    v = ((v >> 4) & 0x0F0F0F0F) | ((v & 0x0F0F0F0F) << 4)
    v = ((v >> 8) & 0x00FF00FF) | ((v & 0x00FF00FF) << 8)
    v = (v >> 16) | (v << 16)
    return v >> (32 - bits)


def scramble(v, scale: int, c0, c1):
    """Bijection of [0, 2^scale) onto itself (uint32 in and out)."""
    v = (v + c0 + c1) * (c0 | _ODD[0])
    v = _bitreverse(v, scale)
    v = v * (c1 | _ODD[1])
    return _bitreverse(v, scale)


def edges(key, n: int, scale: int, a: float, b: float, c: float):
    gen_key, perm_key = jax.random.split(key)

    def level(i, uv):
        ii, jj = level_bits(gen_key, n, i, a, b, c)
        return uv[0] | (ii << i), uv[1] | (jj << i)

    zero = jnp.zeros((n,), jnp.uint32)
    u, v = jax.lax.fori_loop(0, scale, level, (zero, zero))
    c0, c1 = jax.random.bits(perm_key, (2,), jnp.uint32)
    return (scramble(u, scale, c0, c1).astype(jnp.int32),
            scramble(v, scale, c0, c1).astype(jnp.int32))


def build(params: dict, *, n: int, sharding):
    """A jitted ``key -> {"src", "dst"}`` for ``n`` edges in all."""
    scale = int(params["scale"])
    if not 1 <= scale <= 31:
        raise ValueError(f"scale {scale} does not fit int32 vertex ids")
    a, b, c = (float(params[k]) for k in ("A", "B", "C"))
    if n != int(params["edgefactor"]) << scale:
        raise ValueError(f"n={n} is not edgefactor * 2^scale")

    def make(key):
        src, dst = edges(key, n, scale, a, b, c)
        return {"src": src, "dst": dst}

    return jax.jit(make, out_shardings=sharding)
