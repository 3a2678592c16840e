"""FP8-based Ozaki-I scheme (paper §IV-A; comparison baseline from [21]).

A is approximated by S e4m3 slices per row: a_i ~= sum_l 2^{lz_l[i]} A_l[i,:]
with |A_l| <= 16 integer-valued (4 bits per slice + 1 redundant sign bit
between slices -> 5S-1 effective bits). Products A_i @ B_j are error-free FP8
GEMMs (k <= 2^16); the result is the doubly-scaled sum over slice pairs:

  accurate mode: all S^2 pairs        (paper: S^2 GEMMs)
  fast mode:     pairs with i+j <= S+1 (paper: S(S+1)/2 GEMMs, drops small terms)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import numerics

#: Effective bits gained per additional slice (4 mantissa + 1 sign-redundancy).
BITS_PER_SLICE = 5


class SlicedOperand(NamedTuple):
    slices: tuple[jax.Array, ...]  # each e4m3 (m,k) or (k,n)
    lz: jax.Array  # int32 (S, m) or (S, n): log2 slice scales


def slice_operand(a: jax.Array, num_slices: int, axis: int) -> SlicedOperand:
    """Extract S e4m3 slices along rows (axis=0: A-side) or columns (axis=1)."""
    amax = jnp.max(jnp.abs(a), axis=1 - axis)
    _, e = numerics.frexp(amax)  # floor(log2 amax) = e - 1
    base = jnp.where(amax > 0, e.astype(jnp.int32) - 1, 0)

    slices = []
    lzs = []
    r = a
    for l in range(num_slices):
        lz = base - 3 - BITS_PER_SLICE * l  # zeta_l = 2^lz
        lze = jnp.expand_dims(lz, 1 - axis)
        # ldexp_wide, not raw jnp.ldexp: denormal-range rows push |lz| toward
        # ~1080 (base ~ -1020, minus 5 bits/slice), past the single-factor
        # 2.0**e float64 range — same overflow class ldexp_wide fixed for
        # Ozaki-II in PR 1.
        q = jnp.round(numerics.ldexp_wide(r, -lze))  # |q| <= 16, integer, exact
        slices.append(q.astype(jnp.float32).astype(numerics.E4M3))
        r = r - numerics.ldexp_wide(q, lze)  # exact residual (DESIGN.md Ozaki-I note)
        lzs.append(lz)
    return SlicedOperand(tuple(slices), jnp.stack(lzs))


def ozmm_ozaki1_fp8(
    a: jax.Array,
    b: jax.Array,
    *,
    num_slices: int = 11,
    mode: str = "accurate",
) -> jax.Array:
    a = a.astype(jnp.float64)
    b = b.astype(jnp.float64)
    sa = slice_operand(a, num_slices, axis=0)
    sb = slice_operand(b, num_slices, axis=1)

    m, n = a.shape[0], b.shape[1]
    acc = jnp.zeros((m, n), jnp.float64)
    for i in range(num_slices):
        for j in range(num_slices):
            if mode == "fast" and (i + 1) + (j + 1) > num_slices + 1:
                continue
            cij = numerics.matmul_exact_fp8(sa.slices[i], sb.slices[j])
            scale = sa.lz[i][:, None] + sb.lz[j][None, :]
            acc = acc + numerics.ldexp_wide(cij.astype(jnp.float64), scale)
    return acc


def num_matmuls(num_slices: int, mode: str) -> int:
    """Paper Table II counts."""
    s = num_slices
    return s * (s + 1) // 2 if mode == "fast" else s * s


def effective_bits(num_slices: int) -> int:
    return BITS_PER_SLICE * num_slices - 1
