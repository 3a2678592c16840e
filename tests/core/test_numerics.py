"""Property tests of the exactness-critical numeric helpers (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests are optional-dep gated
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import numerics

settings.register_profile("ci", max_examples=60, deadline=None)
settings.load_profile("ci")


@given(st.lists(st.floats(min_value=-448.0, max_value=448.0,
                          allow_nan=False, allow_infinity=False), min_size=1, max_size=64))
def test_cast_e4m3_roundup_dominates(xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    y = numerics.cast_e4m3_roundup(x).astype(jnp.float32)
    # round-up property: y >= x always
    assert bool(jnp.all(y >= x))
    # tightness: y is within one e4m3 ulp above x (ulp <= 32 near 448)
    assert bool(jnp.all(y - x <= jnp.maximum(jnp.abs(x) * 2.0 ** -3, 2.0 ** -9) + 1e-7))


def test_cast_e4m3_roundup_exact_on_representable():
    ints = jnp.arange(-16, 17, dtype=jnp.float32)
    assert bool(jnp.all(numerics.cast_e4m3_roundup(ints).astype(jnp.float32) == ints))


@given(st.integers(min_value=-(2 ** 62), max_value=2 ** 62),
       st.integers(min_value=0, max_value=40))
def test_f64_to_mant_exp_roundtrip(base, shift):
    v = float(base * (2 ** shift))
    if abs(v) > 2.0 ** 1000 or v != int(v):
        return
    m, e = numerics.f64_to_mant_exp(jnp.asarray([v], jnp.float64))
    got = int(m[0]) * (2 ** int(e[0]))
    # frexp keeps only the f64 significand; compare against the f64 value
    assert got == int(float(np.float64(v)))


@given(st.integers(min_value=-(10 ** 9), max_value=10 ** 9),
       st.sampled_from([256, 255, 1024, 1089, 961, 511, 17, 2, 529]))
def test_centered_mod(x, p):
    r = int(numerics.centered_mod(jnp.asarray([x], jnp.int64), p)[0])
    assert (r - x) % p == 0
    if p % 2 == 1:
        assert abs(r) <= (p - 1) // 2
    else:
        assert -p // 2 <= r <= p // 2 - 1


@given(st.lists(st.integers(min_value=-500, max_value=500), min_size=2, max_size=16))
def test_kahan_weighted_sum_exact_smallcase(digits):
    d = jnp.asarray(np.asarray(digits, np.int32)[:, None, None])
    w = jnp.asarray(np.ones(len(digits), np.float64))
    s = numerics.kahan_weighted_sum(d, w)
    assert float(s[0, 0]) == float(sum(digits))


def test_two_sum():
    a, b = jnp.float64(1e16), jnp.float64(1.0)
    s, t = numerics.two_sum(a, b)
    assert float(s) + float(t) == 1e16 + 1.0 or (float(s), float(t)) == (1e16, 1.0)
    assert float(t) == (1e16 + 1.0) - float(s) or abs(float(t)) <= 1.0


def _bits(x):
    return np.asarray(x, np.float64).view(np.uint64)


def _wide_range_values(seed, n=20000):
    """Normal-range float64s across the whole exponent range plus zeros,
    infinities and nan. Subnormal INPUTS are left out: XLA:CPU treats them
    as zero in arithmetic (DAZ), which makes jnp.frexp return garbage."""
    rng = np.random.default_rng(seed)
    x = (1.0 + rng.random(n)) * np.ldexp(1.0, rng.integers(-1022, 1024, n))
    x *= rng.choice([-1.0, 1.0], n)
    return np.concatenate([x, [0.0, -0.0, np.inf, -np.inf, np.nan, 2.0 ** -1022,
                               np.finfo(np.float64).max, 1.0, 0.5]])


def test_frexp_bitwise_equals_jnp():
    x = jnp.asarray(_wide_range_values(0))
    m0, e0 = jnp.frexp(x)
    m1, e1 = jax.jit(numerics.frexp_tpu)(x)
    assert np.array_equal(_bits(m0), _bits(m1))
    assert np.array_equal(np.asarray(e0), np.asarray(e1)) and e1.dtype == jnp.int32


@pytest.mark.parametrize("span", [30, 1100, 2200])
def test_ldexp2_bitwise_equals_jnp(span):
    """Any exponent, overflowing and subnormal-range results included."""
    x = _wide_range_values(1)
    e = np.random.default_rng(2).integers(-span, span, x.shape[0]).astype(np.int32)
    got = _bits(jax.jit(numerics.ldexp2_tpu)(jnp.asarray(x), jnp.asarray(e)))
    ref = _bits(jnp.ldexp(jnp.asarray(x), jnp.asarray(e)))
    nan = np.isnan(x)
    assert np.array_equal(got[~nan], ref[~nan])


def test_ldexp_wide_denormal_range_exponents():
    """|e| ~ 1900 (denormal-range rows) via two exact halvings."""
    x = jnp.asarray([1.5e-300, -3.0e-305, 7.0e300])
    e = jnp.asarray([1900, 1700, -1900], jnp.int32)
    got = numerics.ldexp_wide(x, e)
    ref = np.ldexp(np.ldexp(np.asarray(x), np.asarray(e) // 2), np.asarray(e) - np.asarray(e) // 2)
    assert np.array_equal(_bits(got), _bits(ref))
    e1 = e // 2
    tpu = jax.jit(lambda x, e: numerics.ldexp2_tpu(numerics.ldexp2_tpu(x, e1), e - e1))(x, e)
    assert np.array_equal(_bits(tpu), _bits(ref))


def test_residues_from_limbs_match_int64_mod():
    rng = np.random.default_rng(3)
    m = rng.integers(-(2 ** 53) + 1, 2 ** 53, 4096)
    e = rng.integers(0, 1024, 4096).astype(np.int32)
    for p in (1089, 1024, 511, 256, 199):
        table = jnp.asarray([pow(2, j, p) for j in range(1024)], jnp.int32)
        mh, ml = numerics.mant_limbs(jnp.asarray(m, jnp.int64))
        got = np.asarray(numerics.residues_from_limbs(mh, ml, jnp.asarray(e), p, table))
        want = np.asarray([(int(v) * pow(2, int(s), p)) % p for v, s in zip(m, e)])
        want = np.where(want > (p - 1) // 2, want - p, want)
        assert np.array_equal(got, want), p
        tpu = numerics.residues_tpu(jnp.asarray(m, jnp.int64), jnp.asarray(e), [p], table[None])
        assert np.array_equal(np.asarray(tpu[0]), want), p
        assert np.array_equal(np.asarray(numerics.residues_from_mant_exp(
            jnp.asarray(m, jnp.int64), jnp.asarray(e), p, table)), want), p


def test_tpu_formulations_give_the_same_ozmm_bits(monkeypatch):
    """The whole emulated GEMM with the XLA:TPU formulations of frexp,
    ldexp and the residues, run here on the CPU, is bitwise equal to the
    default one, rows that need scale exponents beyond 1023 and zero rows
    included (the residues of every family's moduli are pinned above)."""
    from repro.core import ozmm

    rng = np.random.default_rng(4)
    a = rng.standard_normal((24, 40))
    b = rng.standard_normal((40, 16))
    a[3] = np.abs(a[3]) * 1e-307 + 1e-307
    a[5] *= 1e290
    a[7] = 0.0
    spec = "ozaki2-fp8/accurate"
    want = np.asarray(ozmm(jnp.asarray(a), jnp.asarray(b), spec))
    monkeypatch.setattr(numerics, "frexp", numerics.frexp_tpu)
    monkeypatch.setattr(numerics, "ldexp2", numerics.ldexp2_tpu)
    monkeypatch.setattr(numerics, "residues", numerics.residues_tpu)
    jax.clear_caches()  # traces of the default formulations are cached
    try:
        got = np.asarray(ozmm(jnp.asarray(a), jnp.asarray(b), spec))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.array_equal(_bits(got), _bits(want))
