"""Residue-product combination and CRT reconstruction (paper §II step 2-3).

Per modulus, the low-precision GEMM results are combined into the centred
residue C'_l = mod(A'_l B'_l, p_l):

  int8:       C'_l = centred_mod(int32 GEMM, p)
  square p:   eq. (12): C'_l = mod(s*(A1B2 + A2B1) + A2B2, p)        3 GEMMs
  karatsuba:  eq. (9):  A'B' = 256*C1 + C2 + 16*(C3 - C1 - C2)       3 GEMMs
              (mod-reduce C1, C2, C3-C1-C2 first to stay inside int32)

Reconstruction uses balanced Garner mixed-radix digits (DESIGN.md I5): with
centred digits x_i and radix weights W_i = prod_{j<i} p_j the value
V = sum_i x_i W_i is the unique symmetric representative of A'B' mod P, and
the final float64 result is ldexp(V, -(lmu_i + lnu_j)) with V accumulated by
a compensated (Kahan) weighted sum (I6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import numerics
from .moduli import KARATSUBA_S, ModuliSet
from .numerics import centered_mod


def combine_residue_product(
    cparts: tuple[jax.Array, ...], p: int, is_square: bool, s: int, family: str
) -> jax.Array:
    """Centred residue C'_l from the per-modulus GEMM outputs (int32)."""
    if family == "int8":
        (c,) = cparts
        return centered_mod(c, p)
    if is_square:
        c_hilo, c_lohi, c_lolo = (x.astype(jnp.int32) for x in cparts)
        # |s*(c1+c2)+c3| <= 33*2^25 + 2^24 < 2^31  -> int32 exact
        t = s * (c_hilo + c_lohi) + c_lolo
        return centered_mod(t, p)
    c1, c2, c3 = (x.astype(jnp.int32) for x in cparts)
    s2 = KARATSUBA_S * KARATSUBA_S
    # A'B' = s^2 c1 + c2 + s (c3 - c1 - c2); mod-reduce the big terms first so
    # every intermediate stays below 2^31 (DESIGN.md I-notes).
    t = (
        s2 * centered_mod(c1, p)
        + centered_mod(c2, p)
        + KARATSUBA_S * centered_mod(c3 - c1 - c2, p)
    )
    return centered_mod(t, p)


@jax.named_scope("ozmm.reconstruct")
@jax.named_scope("ozmm.garner")
def garner_digits(cs: list[jax.Array], ms: ModuliSet) -> jax.Array:
    """Balanced mixed-radix digits from centred residues.

    ``cs`` is in selection order; digits are produced in radix order (even
    modulus first). All arithmetic is int32: |t - x_j| <= p_i/2 + p_j/2 and
    the product with inv < p_i keeps magnitudes < 1089^2 < 2^21.
    """
    order = ms.radix_order
    ps = ms.radix_ps
    inv = ms.garner_inv  # numpy (N, N) int32
    digits: list[jax.Array] = []
    for i in range(ms.n):
        t = cs[order[i]].astype(jnp.int32)
        pi = ps[i]
        for j in range(i):
            t = centered_mod((t - digits[j]) * int(inv[j, i]), pi)
        digits.append(centered_mod(t, pi))
    return jnp.stack(digits)


@jax.named_scope("ozmm.reconstruct")
def reconstruct(
    digits: jax.Array, ms: ModuliSet, lmu: jax.Array, lnu: jax.Array
) -> jax.Array:
    """C = V / (mu_i nu_j) with V = sum_i digits[i] * W_i (float64)."""
    weights = jnp.asarray(ms.radix_weights_f64)
    v = numerics.kahan_weighted_sum(digits, weights)
    return numerics.ldexp_wide(v, -(lmu[:, None] + lnu[None, :]))
