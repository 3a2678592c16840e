"""Fused emulated GEMM (kernels.fused): bitwise parity vs the
core path across families/moduli/modes, prepared-plan interchange, arbitrary
(prime-ish) shapes through the pad/crop wrappers, block-size selection, and
the +pallas/+unfused routing + guard messages."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ozmm
from repro.core.gemm import _resolve_backend
from repro.core.moduli import DEFAULT_NUM_MODULI, make_moduli_set
from repro.core.ozaki2 import ozmm_ozaki2
from repro.core.plan import ozmm_prepared, pair_scales, quantize_matrix
from repro.kernels import (decompose_raw, ozmm_fused_quantize,
                           ozmm_pallas_fused, ozmm_pallas_fused_prepared,
                           select_blocks, stack_parts)
from repro.kernels.fused.ops import BLOCKS_ENV, _pad2
from repro.precision import PrecisionPolicy, parse_policy
from repro.testing import lognormal_matrix

#: Small blocks so CI-sized operands sweep several (i, j, k) grid steps —
#: padding, accumulator init and the last-step finalize all get exercised.
BLOCKS = (16, 32, 32)


def _operands(rng, m=48, k=80, n=40, phi=2.0):
    a = jnp.asarray(lognormal_matrix(rng, (m, k), phi))
    b = jnp.asarray(lognormal_matrix(rng, (k, n), phi))
    return a, b


# The acceptance sweep: both families, 2..default moduli, both modes. The
# full 2..N range runs on the smaller arities plus each family default so
# the sweep stays minutes-cheap under the interpreter.
@pytest.mark.parametrize("family,num_moduli", [
    ("fp8-hybrid", 2), ("fp8-hybrid", 3), ("fp8-hybrid", 4),
    ("fp8-hybrid", 7), ("fp8-hybrid", DEFAULT_NUM_MODULI["fp8-hybrid"]),
    ("int8", 2), ("int8", 4), ("int8", DEFAULT_NUM_MODULI["int8"]),
])
@pytest.mark.parametrize("mode", ["fast", "accurate"])
def test_fused_bitwise_vs_core(rng, family, num_moduli, mode):
    a, b = _operands(rng)
    core = ozmm_ozaki2(a, b, family=family, num_moduli=num_moduli, mode=mode)
    got = ozmm_pallas_fused(a, b, family=family, num_moduli=num_moduli,
                            mode=mode, interpret=True, blocks=BLOCKS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


def test_fused_bitwise_karatsuba_family(rng):
    a, b = _operands(rng)
    core = ozmm_ozaki2(a, b, family="fp8-karatsuba", num_moduli=5, mode="fast")
    got = ozmm_pallas_fused(a, b, family="fp8-karatsuba", num_moduli=5,
                            mode="fast", interpret=True, blocks=BLOCKS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


@pytest.mark.parametrize("reconstruct", ["onchip", "xla"])
def test_fused_reconstruct_modes_bitwise(rng, reconstruct):
    """Digit-stack + XLA epilogue and the on-chip f64 combine agree with
    core bitwise — the epilogue placement must not change a single bit."""
    a, b = _operands(rng)
    core = ozmm_ozaki2(a, b, family="fp8-hybrid", num_moduli=6, mode="fast")
    got = ozmm_pallas_fused(a, b, family="fp8-hybrid", num_moduli=6,
                            mode="fast", interpret=True, blocks=BLOCKS,
                            reconstruct=reconstruct)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


def test_fused_batched_matches_core(rng):
    a = jnp.asarray(rng.standard_normal((2, 24, 40)))
    b = jnp.asarray(rng.standard_normal((2, 40, 16)))
    core = ozmm(a, b, "ozaki2-fp8/fast@4+core")  # core ozmm vmaps batch dims
    got = ozmm_pallas_fused(a, b, family="fp8-hybrid", num_moduli=4,
                            mode="fast", interpret=True, blocks=BLOCKS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


@pytest.mark.parametrize("family", ["fp8-hybrid", "int8"])
@pytest.mark.parametrize("mode", ["fast", "accurate"])
def test_fused_prepared_interchange(rng, family, mode):
    """Core-built plans execute on the fused kernel bitwise-equal to
    ozmm_prepared — plans interchange between executors."""
    a, b = _operands(rng, m=50, k=70, n=30)
    ms = make_moduli_set(family, 5)
    qa = quantize_matrix(a, "lhs", ms, mode=mode)
    qb = quantize_matrix(b, "rhs", ms, mode=mode)
    core = ozmm_prepared(qa, qb)
    got = ozmm_pallas_fused_prepared(qa, qb, interpret=True, blocks=BLOCKS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))
    if mode == "fast":
        # wire-style slimmed plans (no f64 source) still stream through
        got2 = ozmm_pallas_fused_prepared(qa.drop_source(), qb.drop_source(),
                                          interpret=True, blocks=BLOCKS)
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(core))


@pytest.mark.parametrize("family", ["fp8-hybrid", "fp8-karatsuba", "int8"])
@pytest.mark.parametrize("mode", ["fast", "accurate"])
@pytest.mark.parametrize("shape", [(32, 64, 32), (50, 70, 30)])
def test_quantize_kernel_bitwise_vs_core_parts(rng, family, mode, shape):
    """The quantize pre-pass writes each operand's residue parts under the
    pairing exponents bitwise as the core plan's parts in the stacked
    kernel layout; the padding of the prime-ish shape holds only zeros."""
    m, k, n = shape
    a, b = _operands(rng, m, k, n)
    ms = make_moduli_set(family, DEFAULT_NUM_MODULI[family])
    lmu, lnu, parts_a, parts_b = pair_scales(
        quantize_matrix(a, "lhs", ms, mode=mode),
        quantize_matrix(b, "rhs", ms, mode=mode))
    bm, bn, bk = BLOCKS
    for x, l, parts, (br, bc) in ((a, _pad2(lmu[:, None], bm, 1), parts_a, (bm, bk)),
                                  (b, _pad2(lnu[None, :], 1, bn), parts_b, (bk, bn))):
        rows, cols = x.shape
        frame = [_pad2(v, br, bc) for v in decompose_raw(x)]
        got = ozmm_fused_quantize(*frame, l, ms=ms, blocks=(br, bc),
                                  interpret=True)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(stack_parts(parts, ms))):
            want = np.zeros(g.shape, g.dtype)
            want[:, :rows, :cols] = np.asarray(w)
            np.testing.assert_array_equal(np.asarray(g).view(np.uint8),
                                          want.view(np.uint8))


@pytest.mark.parametrize("shape", [(250, 94, 61), (127, 33, 129), (1, 5, 3)])
def test_fused_prime_ish_shapes(rng, shape):
    """Arbitrary m/k/n route through zero-pad + crop exactly."""
    m, k, n = shape
    a = jnp.asarray(rng.standard_normal((m, k)))
    b = jnp.asarray(rng.standard_normal((k, n)))
    core = ozmm_ozaki2(a, b, family="fp8-hybrid", num_moduli=4, mode="fast")
    got = ozmm_pallas_fused(a, b, family="fp8-hybrid", num_moduli=4,
                            mode="fast", interpret=True, blocks=(32, 64, 64))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


def test_unfused_pipeline_prime_ish_shapes(rng):
    """The phase-split pipeline handles non-block-multiple shapes too
    (each op pads/crops) — pinned here at a prime-ish size."""
    a = jnp.asarray(rng.standard_normal((250, 94)))
    b = jnp.asarray(rng.standard_normal((94, 61)))
    core = ozmm(a, b, "ozaki2-fp8/fast@4")
    got = ozmm(a, b, "ozaki2-fp8/fast@4+pallas+unfused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


def test_extreme_magnitudes_bitwise(rng):
    """Denormal-to-huge inputs: the raw-frame shift/mod quantization and the
    wide ldexp epilogue must track core across the full exponent range."""
    m, k, n = 24, 40, 16
    mag = 10.0 ** rng.integers(-300, 300, (m, k)).astype(np.float64)
    a = jnp.asarray(rng.standard_normal((m, k)) * mag)
    b = jnp.asarray(rng.standard_normal((k, n)) * 1e-280)
    for mode in ("fast", "accurate"):
        core = ozmm_ozaki2(a, b, family="fp8-hybrid", num_moduli=6, mode=mode)
        got = ozmm_pallas_fused(a, b, family="fp8-hybrid", num_moduli=6,
                                mode=mode, interpret=True, blocks=BLOCKS)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


# ---- block-size selection ----

def test_select_blocks_table_and_overrides(monkeypatch):
    monkeypatch.delenv(BLOCKS_ENV, raising=False)
    bm, bn, bk = select_blocks("fp8-hybrid", 12, True)
    assert all(v > 0 for v in (bm, bn, bk))
    # kwarg beats everything
    assert select_blocks("fp8-hybrid", 12, True, (8, 16, 32)) == (8, 16, 32)
    # env beats the table
    monkeypatch.setenv(BLOCKS_ENV, "32,64,128")
    assert select_blocks("int8", 14, True) == (32, 64, 128)
    # ... but not the kwarg
    assert select_blocks("int8", 14, True, (8, 8, 8)) == (8, 8, 8)
    monkeypatch.setenv(BLOCKS_ENV, "not,a,shape")
    with pytest.raises(ValueError, match="REPRO_FUSED_BLOCKS"):
        select_blocks("fp8-hybrid", 12, True)


def test_env_blocks_change_tiling_not_bits(rng, monkeypatch):
    a, b = _operands(rng, m=30, k=50, n=20)
    core = ozmm_ozaki2(a, b, family="fp8-hybrid", num_moduli=3, mode="fast")
    monkeypatch.setenv(BLOCKS_ENV, "8,16,16")
    got = ozmm_pallas_fused(a, b, family="fp8-hybrid", num_moduli=3,
                            mode="fast", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(core))


# ---- routing + guard messages (ISSUE satellite: resolve_interpret coupling) ----

def test_pallas_policy_routes_fused_by_default(rng):
    a, b = _operands(rng, m=16, k=64, n=16)
    core = ozmm(a, b, "ozaki2-fp8/fast@6")
    fused = ozmm(a, b, "ozaki2-fp8/fast@6+pallas")
    unfused = ozmm(a, b, "ozaki2-fp8/fast@6+pallas+unfused")
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(core))
    np.testing.assert_array_equal(np.asarray(unfused), np.asarray(core))


def test_backend_auto_resolution():
    fast8 = parse_policy("ozaki2-fp8/fast@8")
    assert _resolve_backend(fast8, device="tpu") == "pallas"
    assert _resolve_backend(fast8, device="cpu") == "core"
    assert _resolve_backend(fast8, device="gpu") == "core"
    assert _resolve_backend(parse_policy("native"), device="tpu") == "core"
    assert _resolve_backend(parse_policy("ozaki2-fp8/fast@8+core"),
                            device="tpu") == "core"
    assert _resolve_backend(parse_policy("ozaki2-int8/fast+pallas"),
                            device="cpu") == "pallas"


def test_explicit_pallas_grad_guard_names_fused_kernel(rng):
    a, b = _operands(rng, m=8, k=16, n=8, phi=1.0)
    with pytest.raises(NotImplementedError,
                       match=r"forward-only.*ozmm_pallas_fused"):
        jax.grad(lambda x, y: jnp.sum(
            ozmm(x, y, "ozaki2-fp8/fast@4+pallas")))(a, b)
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda x, y: jnp.sum(
            ozmm(x, y, "ozaki2-fp8/fast@4+pallas+unfused")))(a, b)


def test_pallas_validation_error_mentions_unfused():
    with pytest.raises(ValueError, match=r"\+unfused"):
        PrecisionPolicy(scheme="native", backend="pallas")
    with pytest.raises(ValueError, match="unfused"):
        PrecisionPolicy(scheme="ozaki2-fp8", backend="core", fused=False)


def test_auto_backend_bwd_falls_back_to_core(rng):
    """The auto-derived pallas route (TPU) keeps a usable VJP: the bwd rule
    computes the core-path cotangent GEMMs from the saved operands."""
    from repro.core.gemm import _ozmm_pallas_bwd, _ozmm_2d_raw

    a = jnp.asarray(rng.standard_normal((8, 12)))
    b = jnp.asarray(rng.standard_normal((12, 6)))
    g = jnp.asarray(rng.standard_normal((8, 6)))
    pol = parse_policy("ozaki2-fp8/fast@4")  # backend=auto
    ga, gb = _ozmm_pallas_bwd(pol, (a, b), g)
    ga_ref = _ozmm_2d_raw(g, b.T, pol.scheme, pol.mode, pol.num_moduli,
                          pol.num_slices)
    gb_ref = _ozmm_2d_raw(a.T, g, pol.scheme, pol.mode, pol.num_moduli,
                          pol.num_slices)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(ga_ref))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(gb_ref))


@pytest.mark.parametrize("family", ["fp8-hybrid", "fp8-karatsuba", "int8"])
def test_in_kernel_pow2_mod_equals_table(family):
    """The kernel's square-and-multiply 2^s mod p reproduces the moduli
    tables that the core path gathers from, for every table index."""
    from repro.kernels.fused.kernel import _pow2_mod

    ms = make_moduli_set(family, 12)
    idx = jnp.arange(ms.pow2_mod_tables.shape[1], dtype=jnp.int32)
    for l, p in enumerate(ms.ps):
        got = np.asarray(_pow2_mod(idx, p, 10))
        assert np.array_equal(got, ms.pow2_mod_tables[l]), p
