"""Numeric-format helpers shared by the emulation schemes.

Everything here is exactness-critical; each helper documents the window in
which it is exact (DESIGN.md §6) and is covered by property tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E4M3_MAX = 448.0
#: Largest magnitude up to which *consecutive* integers are exact in e4m3.
E4M3_EXACT_INT = 16

F32_EXACT_INT = 2 ** 24  # consecutive-integer window of float32
F64_EXACT_INT = 2 ** 53


def ensure_x64() -> None:
    """The emulation operates on float64 inputs; enable x64 if needed."""
    if not jax.config.read("jax_enable_x64"):
        jax.config.update("jax_enable_x64", True)


def _per_platform(tpu, default):
    """``tpu`` where the program is lowered for XLA:TPU, ``default``
    elsewhere; the choice is made at lowering, so a compiler sees one of
    them. The TPU formulations avoid ops that XLA:TPU lacks or compiles
    slowly, but take XLA:CPU longer to compile; both give bitwise-equal
    results (tests run the TPU ones on CPU against the defaults). Every
    operand must reach every output in both: inside ``shard_map`` the
    outputs' varying mesh axes have to match."""
    def f(*args):
        return jax.lax.platform_dependent(*args, tpu=tpu, default=default)
    return f


def _pow2(e: jax.Array) -> jax.Array:
    """2.0**e as float64 for integer e in [-1022, 1023], assembled from its
    bit pattern (an int64 bitcast to float64). Unlike ``2.0 ** e`` or
    ``jnp.ldexp`` this lowers on XLA:TPU, which has no float64 -> integer
    bitcast (the integer -> float64 direction compiles)."""
    bits = jax.lax.shift_left((e + 1023).astype(jnp.int64), jnp.int64(52))
    return jax.lax.bitcast_convert_type(bits, jnp.float64)


def ldexp2_tpu(x: jax.Array, e: jax.Array) -> jax.Array:
    """x * 2**e for float64 ``x`` and any int32 ``e``, rounded once, exactly
    as ``jnp.ldexp`` (bitwise, subnormal and overflowing results included).

    Built from multiplies by exact powers of two only (musl's ``scalbn``
    scheme), so it lowers on XLA:TPU. Exponents beyond one float64 factor
    are applied in at most two extra steps of 2^1023 / 2^-969: 2^-969 =
    2^-1022 * 2^53 leaves a remaining exponent < -53 whenever the
    intermediate turns subnormal, so such a result underflows to zero in
    both computations and no double rounding can occur. The step factors
    (1.0 where a step is not taken) depend on ``e`` alone, so for per-row
    exponents they are computed on the row vector, not per element.
    """
    x = jnp.asarray(x, jnp.float64)
    e = jnp.asarray(e, jnp.int32)
    factors = []
    for step, lo, hi in ((1023, 1023, None), (1023, 1023, None),
                         (-969, None, -1022), (-969, None, -1022)):
        take = e > lo if lo is not None else e < hi
        factors.append(jnp.where(take, 2.0 ** step, 1.0))
        e = jnp.where(take, e - step, e)
    for f in factors:
        x = x * f
    return x * _pow2(jnp.clip(e, -1022, 1023))


def frexp_tpu(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(m, e) with x = m * 2**e and |m| in [0.5, 1), bitwise as
    ``jnp.frexp`` (zeros, infinities and nan give (x, 0)).

    XLA:TPU cannot bitcast float64 to an integer type, so the exponent is
    not read from the bit pattern: a binary search over its bits scales |x|
    into [1, 2) by exact powers of two. Each step tests the sign of an exact
    difference, not a float64 compare: a float32 pair (XLA:TPU) just below
    2.0 can have hi == 2.0 and a negative lo. Subnormals are pre-scaled by
    2^64 into the normal range.
    """
    x = jnp.asarray(x, jnp.float64)
    ax = jnp.abs(x)
    sub = ax < 2.0 ** -1022
    ax = jnp.where(sub, ax * 2.0 ** 64, ax)
    finite = (ax > 0) & jnp.isfinite(ax)
    ax = jnp.where(finite, ax, 1.0)
    e = jnp.where(sub, -64, 0).astype(jnp.int32)
    for k in _EXP_BITS:  # |x| >= 2^k: divide by 2^k
        take = (ax - 2.0 ** k) >= 0
        ax = jnp.where(take, ax * 2.0 ** -k, ax)
        e = e + jnp.where(take, k, 0)
    for k in _EXP_BITS:  # |x| < 2^(1-k): multiply by 2^k
        take = (ax - 2.0 ** (1 - k)) < 0
        ax = jnp.where(take, ax * 2.0 ** k, ax)
        e = e - jnp.where(take, k, 0)
    m = jnp.where(x < 0, -0.5 * ax, 0.5 * ax)  # ax in [1, 2)
    return jnp.where(finite, m, x), jnp.where(finite, e + 1, 0)


#: Exponent bits of a normal float64, for ``frexp_tpu``'s binary search.
_EXP_BITS = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


@jax.jit
def ldexp2(x: jax.Array, e: jax.Array) -> jax.Array:
    """x * 2**e with exact power-of-two scaling (float64 ``x``, int32 ``e``):
    ``jnp.ldexp``, or ``ldexp2_tpu`` on XLA:TPU."""
    return _per_platform(ldexp2_tpu, jnp.ldexp)(
        jnp.asarray(x, jnp.float64), jnp.asarray(e, jnp.int32))


@jax.jit
def frexp(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """float64 ``x`` -> (m, int32 e): ``jnp.frexp``, or ``frexp_tpu`` on
    XLA:TPU."""
    return _per_platform(frexp_tpu, jnp.frexp)(jnp.asarray(x, jnp.float64))


@jax.jit
def ldexp_wide(x: jax.Array, e: jax.Array) -> jax.Array:
    """x * 2**e in two halves: ldexp2(ldexp2(x, e // 2), e - e // 2).

    The intermediate magnitude lies between |x| and the result, so each
    halving is an exact power-of-two multiply whenever both are normal; the
    split also keeps each factor small where float64 has a narrower range
    than IEEE binary64 (XLA:TPU's float64 is a pair of float32s). Denormal-
    range inputs need scale exponents up to ~1900 (scaling._clip_scale).
    """
    e = jnp.asarray(e, dtype=jnp.int32)
    e1 = e // 2
    return ldexp2(ldexp2(x, e1), e - e1)


def cast_e4m3_roundup(x: jax.Array) -> jax.Array:
    """Cast float32 -> e4m3 rounding toward +inf (paper §III-E round-up cast).

    JAX exposes no rounding-mode control, so emulate: round-to-nearest cast,
    then bump one ulp toward +inf wherever the cast landed below ``x``.
    e4m3fn bit patterns are monotone within each sign half, so the bump is a
    +-1 on the uint8 view. Valid for |x| <= 448 (callers guarantee < 256).
    """
    x = x.astype(jnp.float32)
    y = x.astype(E4M3)
    yf = y.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(y, jnp.uint8)
    # toward +inf: positives step up the uint ladder, negatives step down
    # (negative patterns grow with magnitude). -0 never needs a bump for x<=0,
    # and x>0 never casts to -0, so the 0x80 wrap case cannot arise.
    bumped = jnp.where(yf >= 0, bits + jnp.uint8(1), bits - jnp.uint8(1))
    out_bits = jnp.where(yf < x, bumped, bits)
    return jax.lax.bitcast_convert_type(out_bits, E4M3)


def f64_to_mant_exp(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Decompose integer-valued float64 ``a`` into (m, e) with a = m * 2**e,
    m int64, e int32 >= 0, exactly.

    Works for any magnitude representable in float64 (unlike an int64 cast,
    which overflows beyond 2**63 — residue scalings reach 2**100+ for large
    moduli products). For |a| >= 1 the frexp exponent is >= 1, so the
    normalising right-shift is at most 52 bits and divides exactly.
    """
    m, e = frexp(a)  # a = m * 2**e, |m| in [0.5, 1)
    m53 = (m * (2.0 ** 53)).astype(jnp.int64)  # exact: |m*2^53| < 2^53
    e53 = (e - 53).astype(jnp.int32)
    shift = jnp.maximum(-e53, 0)
    m_out = jax.lax.shift_right_arithmetic(m53, shift.astype(jnp.int64))
    e_out = jnp.maximum(e53, 0)
    return m_out, e_out


def centered_mod(x: jax.Array, p: int) -> jax.Array:
    """Symmetric residue of integer array ``x`` modulo ``p``.

    Odd p: range [-(p-1)/2, (p-1)/2]. Even p: [-p/2, p/2-1].
    Exact for any integer dtype (jnp.mod yields non-negative for p > 0).
    """
    r = jnp.mod(x, p)
    half = (p - 1) // 2
    return (r - jnp.where(r > half, p, 0).astype(r.dtype)).astype(jnp.int32)


def residues_from_mant_exp(m: jax.Array, e: jax.Array, p: int, pow2_table: jax.Array) -> jax.Array:
    """Centred residue of (m * 2**e) mod p, exact, int32 output.

    ``pow2_table[j] = 2**j mod p``. (m mod p) * (2^e mod p) < p^2 < 2^21 for
    p <= 1089, so the combining product is exact in int32/int64.
    """
    r = jnp.mod(m, p)  # int64, [0, p)
    t = pow2_table[jnp.clip(e, 0, pow2_table.shape[0] - 1)].astype(jnp.int64)
    return centered_mod(jnp.mod(r * t, p), p)


def mant_limbs(m: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int64 mantissa |m| < 2^53 -> int32 limbs (mh, ml) with
    m = mh * 2^26 + ml, ml in [0, 2^26) (arithmetic shift: mh carries the
    sign)."""
    mh = jax.lax.shift_right_arithmetic(m, jnp.int64(26)).astype(jnp.int32)
    ml = (m & ((1 << 26) - 1)).astype(jnp.int32)
    return mh, ml


def residues_from_limbs(mh: jax.Array, ml: jax.Array, e: jax.Array, p: int,
                        pow2_table: jax.Array) -> jax.Array:
    """``residues_from_mant_exp`` of m = mh * 2^26 + ml in int32 only: every
    product is reduced before it is added, and each is < p^2 < 2^21 for
    p <= 1089 (int64 remainders are slow to compile and run on TPU, which
    emulates 64-bit integers).
    """
    r = jnp.mod(jnp.mod(jnp.mod(mh, p) * ((1 << 26) % p), p) + jnp.mod(ml, p), p)
    t = pow2_table[jnp.clip(e, 0, pow2_table.shape[0] - 1)].astype(jnp.int32)
    return centered_mod(jnp.mod(r * t, p), p)


def residues_tpu(m: jax.Array, e: jax.Array, ps, pow2_tables: jax.Array) -> list[jax.Array]:
    """``residues_from_limbs`` for every modulus of ``ps``. The limbs are
    materialized once: XLA:TPU would otherwise recompute the decomposition
    inside every per-modulus fusion, which is slow to compile."""
    mh, ml, e = jax.lax.optimization_barrier((*mant_limbs(m), e))
    return [residues_from_limbs(mh, ml, e, p, pow2_tables[l]) for l, p in enumerate(ps)]


def residues(m: jax.Array, e: jax.Array, ps, pow2_tables: jax.Array) -> list[jax.Array]:
    """Centred residues of m * 2**e (``f64_to_mant_exp`` output) for every
    modulus of ``ps``; ``pow2_tables[l][j] = 2**j mod ps[l]``."""
    def default(m, e, tables):
        return [residues_from_mant_exp(m, e, p, tables[l]) for l, p in enumerate(ps)]
    return _per_platform(lambda m, e, tables: residues_tpu(m, e, ps, tables), default)(
        m, e, jnp.asarray(pow2_tables))


def kahan_weighted_sum(digits: jax.Array, weights: jax.Array) -> jax.Array:
    """Compensated sum_i digits[i] * weights[i] over leading axis, float64.

    digits: (N, ...) integer dtype; weights: (N,) float64. Kahan compensation
    keeps the relative error ~2^-52 independent of N (DESIGN.md I6).
    """
    def body(carry, xw):
        s, c = carry
        x, w = xw
        term = x.astype(jnp.float64) * w - c
        t = s + term
        c = (t - s) - term
        return (t, c), None

    # Derive the carry init from the data so it inherits any shard_map
    # varying-manual-axes tags (required for use inside shard_map bodies).
    zero = digits[0].astype(jnp.float64) * 0.0
    (s, _), _ = jax.lax.scan(body, (zero, zero), (digits, weights))
    return s


def matmul_exact_fp8(a: jax.Array, b: jax.Array) -> jax.Array:
    """e4m3 x e4m3 -> f32 GEMM. Exact when entries are integers |x| <= 16 and
    k <= 2^16 (paper eq. (11)); maps to the FP8 MMA path on TPU v6e+."""
    return _unfused(jnp.matmul(*_unfused((a, b)), preferred_element_type=jnp.float32))


def matmul_exact_int8(a: jax.Array, b: jax.Array) -> jax.Array:
    """int8 x int8 -> int32 GEMM. Exact for k <= 2^17 (paper §II)."""
    return _unfused(jnp.matmul(*_unfused((a, b)), preferred_element_type=jnp.int32))


def _unfused(x):
    """Materialize GEMM operands and results on XLA:TPU, which would
    otherwise fuse the float64/int64 quantization and reconstruction chains
    around them into the dot: such a compile takes many minutes instead of
    seconds. Values are unchanged; elsewhere the barrier only slows the
    compile."""
    return _per_platform(jax.lax.optimization_barrier, _join_varying)(x)


def _join_varying(x):
    """Identity with ``optimization_barrier``'s types: inside ``shard_map``
    every output varies over the mesh axes any input varies over."""
    leaves, tree = jax.tree.flatten(x)
    axes = frozenset().union(*(jax.typeof(v).vma for v in leaves))
    return jax.tree.unflatten(tree, [
        jax.lax.pcast(v, tuple(axes - jax.typeof(v).vma), to="varying")
        if axes - jax.typeof(v).vma else v for v in leaves])


def log2_up(x: jax.Array, guard: float = 2.0 ** -40) -> jax.Array:
    """Upper bound on log2(x) in float64: libm log2 is a few ulps accurate;
    an absolute 2^-40 guard dominates that error for |log2| <= 1100."""
    return jnp.log2(x) + guard


def two_sum(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Error-free transformation: a + b = s + t exactly (Knuth)."""
    s = a + b
    bp = s - a
    t = (a - (s - bp)) + (b - bp)
    return s, t
