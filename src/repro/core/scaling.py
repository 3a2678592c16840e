"""Scaling-vector construction (paper §II eq. (3) and §III-E).

Both modes return integer base-2 exponents ``log2(mu)`` (per row of A) and
``log2(nu)`` (per column of B) such that the truncated integer matrices
A' = trunc(2^lmu * A), B' = trunc(B * 2^lnu) satisfy the inner-product bound

    2 * sum_h |a'_ih| |b'_hj|  <  P          (eq. (3))

*Fast mode* bounds the sum by Cauchy-Schwarz on row/column norms.
*Accurate mode* bounds it with one extra error-free-ish low-precision GEMM of
round-up-cast inputs, inflated by the rigorous FP32 accumulation bound
(1 + k*2^-24) (paper §III-E).

Rounding-mode emulation: every floating-point step that the paper performs in
a directed rounding mode is replaced by float64 computation plus a guard that
errs on the side of a SMALLER mu/nu (conservative for eq. (3); costs at most
one bit of accuracy in adversarial cases, usually nothing). See DESIGN.md.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import numerics
from .moduli import ModuliSet

#: Hard cap on |log2 scale| so scaled values stay finite in float64 and the
#: pow2 residue tables stay in range (moduli.POW2_TABLE_LEN).
MAX_LOG2_SCALE = 900


class ScalingResult(NamedTuple):
    lmu: jax.Array  # int32 (m,)  log2 of row scales of A
    lnu: jax.Array  # int32 (n,)  log2 of column scales of B
    extra_matmuls: int  # 1 for accurate mode (the bound GEMM), else 0


def _log2_sqrt_half_p(ms: ModuliSet) -> float:
    """(log2(P-1) - 1) / 2 rounded down a hair (paper's P')."""
    return (math.log2(ms.P - 1) - 1.0) / 2.0 - 2.0 ** -40


def _clip_scale(e: jax.Array, abs_max: jax.Array) -> jax.Array:
    """Clamp exponents so 2^e * abs_max <= 2^MAX_LOG2_SCALE (keeps scaled
    integers finite and inside the pow2 residue tables); zero rows get e = 0.

    NOTE: the cap constrains the PRODUCT exponent, not e itself — inputs in
    the denormal range legitimately need e ~ +1900 (covered by a regression
    test); likewise 1e300-range inputs need e ~ -950.
    """
    _, emax = numerics.frexp(abs_max)
    cap = MAX_LOG2_SCALE - emax.astype(jnp.int32)
    e = jnp.minimum(e, cap)
    return jnp.where(abs_max > 0, e, 0).astype(jnp.int32)


def fast_exponents(sq_norm: jax.Array, abs_max: jax.Array, k: int,
                   ms: ModuliSet) -> jax.Array:
    """Per-operand Cauchy-Schwarz exponents: mu * ||v|| <= sqrt((P-1)/2).

    ``sq_norm``/``abs_max`` are the squared norms / abs-maxima of the vectors
    along the contraction axis (rows of A or columns of B); ``k`` is the
    contraction length. Depends on ONE operand only — this decoupling is what
    lets fast-mode quantization plans be built per operand and reused across
    partners (core.plan).
    """
    pprime = _log2_sqrt_half_p(ms)
    # Norms in f64 inflated by the summation error bound (k+2 ulps relative).
    infl = 1.0 + (k + 2) * 2.0 ** -52
    l2 = 0.5 * numerics.log2_up(jnp.where(sq_norm > 0, sq_norm * infl, 1.0))
    e = jnp.floor(pprime - l2).astype(jnp.int32)
    return _clip_scale(e, abs_max)


def scaling_fast(a: jax.Array, b: jax.Array, ms: ModuliSet) -> ScalingResult:
    """Cauchy-Schwarz mode: mu_i * ||a_i|| <= sqrt((P-1)/2), likewise nu."""
    k = a.shape[-1]
    lmu = fast_exponents(jnp.sum(a * a, axis=1), jnp.max(jnp.abs(a), axis=1), k, ms)
    lnu = fast_exponents(jnp.sum(b * b, axis=0), jnp.max(jnp.abs(b), axis=0), k, ms)
    return ScalingResult(lmu, lnu, 0)


def accurate_prescale(x: jax.Array, axis: int,
                      abs_max: jax.Array | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Per-operand half of accurate mode (paper §III-E step (14)):

      mu'_i = 2^7 / ufp(max_h |x_ih|)  ->  lpre[i] = 7 - floor(log2 max)
      cast 2^lpre * |x| (exact scale) to e4m3 in ROUND-UP mode -> Xbar

    ``axis`` is the contraction axis (1 for the A side, 0 for the B side).
    ``abs_max`` lets callers inject globally-reduced maxima (k-sharding).
    Returns (lpre, Xbar); this pair is the cacheable per-operand sketch — it
    does not depend on the partner matrix.
    """
    amax = jnp.max(jnp.abs(x), axis=axis) if abs_max is None else abs_max
    _, e = numerics.frexp(amax)  # floor(log2 amax) = e - 1 for amax > 0
    # No symmetric clamp here: denormal-range rows need lpre ~ +1010 and
    # 1e300-range rows need ~ -1000; the scaled target is 2^7 < inf either
    # way (regression: tests/core/test_ozmm_accuracy.py::test_edge_inputs).
    lpre = jnp.where(amax > 0, 7 - (e.astype(jnp.int32) - 1), 0)
    # Bound matrices are |x| scaled: the round-up cast must dominate the
    # MAGNITUDE for sum_h |a||b| <= (Abar @ Bbar)_ij to hold. ldexp_wide:
    # lpre exceeds 1023 for denormal-range rows (plain ldexp -> nan).
    scaled = numerics.ldexp_wide(jnp.abs(x), jnp.expand_dims(lpre, axis))
    # f64 -> f32 must also round up to preserve the upper bound: inflate
    # by 2^-22 (> the 2^-24 f32 cast error) before the nearest-cast.
    scaled32 = (scaled * (1.0 + 2.0 ** -22)).astype(jnp.float32)
    return lpre, numerics.cast_e4m3_roundup(scaled32)


def bound_gemm_inflate(cbar_f32: jax.Array, k: int) -> jax.Array:
    """Rigorous FP32 accumulation inflation of the bound GEMM (paper §III-E):
    (1 + k 2^-24) for the f32 sum, (1 + 2^-50) for the f64 bookkeeping. The
    Rump bound holds for any summation order, so ``cbar_f32`` may itself be a
    psum of per-shard partials (distributed accurate mode)."""
    return cbar_f32.astype(jnp.float64) * (1.0 + k * 2.0 ** -24) * (1.0 + 2.0 ** -50)


def accurate_exponents(cbar_max: jax.Array, lpre: jax.Array,
                       abs_max: jax.Array, ms: ModuliSet) -> jax.Array:
    """Paper eq. (15): lmu[i] = lpre[i] + floor(P' - 0.5*log2 max_h Cbar[i,h]).

    The 0.5 factor splits the bound symmetrically between A and B; the
    construction is rigorous because Cbar_ij <= sqrt(maxrow_i * maxcol_j)
    always holds for non-negative Cbar (DESIGN.md).
    """
    pprime = _log2_sqrt_half_p(ms)
    l2 = 0.5 * numerics.log2_up(jnp.maximum(cbar_max, 2.0 ** -64))
    e = jnp.floor(pprime - l2).astype(jnp.int32) + lpre
    return _clip_scale(e, abs_max)


def scaling_accurate(a: jax.Array, b: jax.Array, ms: ModuliSet) -> ScalingResult:
    """Accurate mode (paper §III-E), via one FP8 GEMM of round-up casts.

    ``accurate_prescale`` builds the per-operand round-up casts, one FP8 GEMM
    Cbar' = Abar @ Bbar bounds the inner products, and ``accurate_exponents``
    turns the row/column maxima of the inflated bound into scale exponents.
    For the int8 family the same e4m3 round-up bound GEMM is used (valid
    upper bound; see DESIGN.md "assumptions changed").
    """
    k = a.shape[-1]
    lmu2, abar = accurate_prescale(a, 1)
    lnu2, bbar = accurate_prescale(b, 0)
    cbar = bound_gemm_inflate(numerics.matmul_exact_fp8(abar, bbar), k)
    lmu = accurate_exponents(jnp.max(cbar, axis=1), lmu2,
                             jnp.max(jnp.abs(a), axis=1), ms)
    lnu = accurate_exponents(jnp.max(cbar, axis=0), lnu2,
                             jnp.max(jnp.abs(b), axis=0), ms)
    return ScalingResult(lmu, lnu, 1)


@jax.named_scope("ozmm.scaling")
def compute_scaling(a: jax.Array, b: jax.Array, ms: ModuliSet, mode: str) -> ScalingResult:
    if mode == "fast":
        return scaling_fast(a, b, ms)
    if mode == "accurate":
        return scaling_accurate(a, b, ms)
    raise ValueError(f"unknown mode {mode!r}")
