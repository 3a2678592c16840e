"""Quantization: float64 inputs -> integer matrices -> per-modulus residues ->
low-precision (e4m3 / int8) operand matrices.

Pipeline (paper §II step 1 + §III-B/C/D splits):

  A' = trunc(2^lmu * A)          exact in float64 (power-of-two scale, trunc)
  (m, e) = mant/exp decomposition of A'       exact, any magnitude
  r_l = centred residue of A' mod p_l          exact int32 (pow2 tables)
  e4m3 splits:
    Karatsuba modulus (p <= 513, s = 16):  hi = sign(r) * ceil(|r|/16),
        lo = r - 16*hi, plus hs = hi + lo.  |hi|,|hs| <= 16, |lo| <= 15. (I2)
    Square modulus (p = s^2 <= 1089):      hi = round(r/s), lo = r - s*hi.
        |hi|,|lo| <= 16.                                                (I3)
  int8 family: residues are emitted directly as int8 (|r| <= 128).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import numerics
from .moduli import KARATSUBA_S, ModuliSet


class QuantizedOperand(NamedTuple):
    """Per-modulus low-precision operand matrices, selection order.

    For the fp8 families each element is a tuple of e4m3 arrays:
      square modulus    -> (hi, lo)
      karatsuba modulus -> (hi, lo, hs)   with hs = hi + lo
    For int8 each element is a single int8 array in a 1-tuple.
    """

    parts: tuple[tuple[jax.Array, ...], ...]


def scaled_int(a: jax.Array, lscale: jax.Array, axis: int) -> jax.Array:
    """trunc(2^lscale * a) along rows (axis=0 scales rows of A via lscale[i])
    or columns. Returns integer-valued float64."""
    e = jnp.expand_dims(lscale, 1 - axis if a.ndim == 2 else tuple(i for i in range(a.ndim) if i != axis))
    return jnp.trunc(numerics.ldexp_wide(a, e))


def residues_all(a_int: jax.Array, ms: ModuliSet, pow2_tables: jax.Array) -> list[jax.Array]:
    """Centred residues of integer-valued float64 ``a_int`` for every modulus."""
    m, e = numerics.f64_to_mant_exp(a_int)
    return numerics.residues(m, e, ms.ps, pow2_tables)


def split_karatsuba(r: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Ceil-split of a residue |r| <= 256 into (hi, lo, hi+lo), all e4m3-exact."""
    s = KARATSUBA_S
    absr = jnp.abs(r)
    hi = jnp.sign(r) * ((absr + (s - 1)) // s)
    lo = r - s * hi
    hs = hi + lo
    f8 = lambda x: x.astype(jnp.float32).astype(numerics.E4M3)
    return f8(hi), f8(lo), f8(hs)


def split_square(r: jax.Array, s: int) -> tuple[jax.Array, jax.Array]:
    """Round-split of a residue of a square modulus p = s^2: r = s*hi + lo,
    |hi|, |lo| <= 16 (paper §III-C/D). Rounding on f32 is exact (|r| <= 544)."""
    hi = jnp.round(r.astype(jnp.float32) / jnp.float32(s)).astype(jnp.int32)
    lo = r - s * hi
    f8 = lambda x: x.astype(jnp.float32).astype(numerics.E4M3)
    return f8(hi), f8(lo)


def quantize_operand(
    a: jax.Array, lscale: jax.Array, axis: int, ms: ModuliSet, pow2_tables: jax.Array
) -> QuantizedOperand:
    """Full quantization of one operand. ``axis``: 0 -> scale rows (A-side),
    1 -> scale columns (B-side)."""
    a_int = scaled_int(a, lscale, axis=0 if axis == 0 else 1)
    rs = residues_all(a_int, ms, pow2_tables)
    parts: list[tuple[jax.Array, ...]] = []
    for r, p, sq, s in zip(rs, ms.ps, ms.is_square, ms.split_s):
        if ms.family == "int8":
            parts.append((r.astype(jnp.int8),))
        elif sq:
            parts.append(split_square(r, s))
        else:
            parts.append(split_karatsuba(r))
    return QuantizedOperand(tuple(parts))
