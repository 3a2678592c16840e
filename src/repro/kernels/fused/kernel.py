"""Pallas TPU kernels: the Ozaki-II emulated GEMM in two ``pallas_call``s.

The phase-split pipeline (``kernels/pipeline.py``) materializes every
intermediate in HBM: residue-part stacks after quantization, N (or 3N)
per-modulus GEMM outputs, the digit stack after requant. The fused schedule
keeps only the residue parts:

  1. ``ozmm_fused_quantize`` (once per operand, a 2-D grid over its tiles)
     quantizes each tile to centred residues, folding the ``quant_residues``
     exponent-frame math into the tile loop (the f64 -> raw-frame
     decomposition stays in XLA, see ops.decompose_raw; applying the pairing
     scale 2^l and the truncation is pure int32 shift/mod arithmetic, done
     here), and splits them into e4m3 parts (or int8 residues). A residue
     of A depends only on A and its row exponents, one of B only on B and
     its column exponents, so each tile is quantized once however many
     partner tiles it meets.
  2. ``ozmm_fused_parts`` runs a k-innermost blocked schedule: per (bm, bn)
     output tile and per k step it issues the eq. (8)/(12) FP8 MMA schedule
     (or the single int8 MMA) on the streamed part tiles and accumulates
     the per-modulus partial products into int32 VMEM scratch.

At the last k step the scratch accumulators run the residue combine +
balanced Garner digits (identical int32 arithmetic to ``crt_reconstruct`` —
the helpers are literally imported from there) and either write the int16
digit stack (``reconstruct="xla"``; the f64 combine is a cheap XLA epilogue,
TPU Mosaic has no native f64) or perform the compensated f64 digit combine
in-kernel (``reconstruct="onchip"``, interpreter mode) so only the final f64
tile touches HBM.

Exactness => bitwise equality (DESIGN.md I1): every phase is exact integer
arithmetic — residues are exact by construction, the low-precision partial
dots are integers <= bk*2^9 (exact in f32), and int32 partial-sum
accumulation is associative — so the digit planes are bitwise-identical to
the core path for ANY tiling, and the final f64 matches bitwise because the
epilogue performs the same Kahan scan + ldexp_wide in the same order.

Accumulator bounds: fp8 families |c| <= k * 2^9  (k <= 2^21 fits int32; the
f32 partial-dot exactness already requires bk*2^9 <= 2^24); int8 family
|c| <= k * 2^14 (k <= 2^16). VMEM budget at (128, 128) tiles, N = 12:
3 accumulators x 12 x 128 x 128 x 4 B = 2.25 MiB + 2 x 1.125 MiB of
double-buffered part tiles — comfortably inside ~16 MiB (docs/kernels.md
has the full budget table, and the parts' HBM footprint).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import numerics
from repro.core.moduli import KARATSUBA_S, ModuliSet

# The combine/Garner arithmetic MUST be the phase-split kernel's, verbatim:
# sharing the helpers is what makes "bitwise-equal digits" true by
# construction rather than by parallel maintenance.
from ..crt_reconstruct.kernel import _centered, _cmod, _combine, _garner

E4M3 = jnp.float8_e4m3fn
MANT_SPLIT = 26  # raw frame: mant = mh * 2^26 + ml (ops.decompose_raw)
#: Block index 0 for the index maps, as int32: a bare 0 is an int64 literal
#: under x64, which Mosaic cannot lower.
_I0 = np.int32(0)


def _pow2_mod(idx, p, nbits):
    """2^idx mod p for int32 ``idx`` in [0, 2^nbits), by square-and-multiply
    over the bits of ``idx`` with compile-time factors 2^(2^b) mod p. Every
    product is < p^2 <= 1089^2 < 2^21, so int32 is exact; equals the
    moduli ``pow2_mod_tables`` row entry for entry (Mosaic has no 1-D
    gather to look the table up)."""
    i32 = jnp.int32
    w = jnp.ones_like(idx)
    for b in range(nbits):
        f = pow(2, 1 << b, p)
        if f == 1:
            continue
        on = (idx & i32(1 << b)) != 0
        w = jnp.where(on, jnp.mod(w * i32(f), i32(p)), w)
    return w


def _residue_tile(mh, ml, sc, p, table_len):
    """Centred residue mod ``p`` of trunc(2^sc * x) for x = (mh + ml*2^-26)
    * 2^(sc - s0) given in the sign-folded raw frame (ops.decompose_raw):
    ``sc`` is the TOTAL power-of-two exponent of the scaled value relative to
    the 53-bit integer mantissa, i.e. scaled x = (mh*2^26 + ml) * 2^sc.

    All int32 (constants included: under x64 a bare Python int becomes an
    int64 literal, which Mosaic cannot lower): negative ``sc`` truncates by
    logical right-shifts of the magnitudes (sign is re-applied afterwards —
    an arithmetic shift of a negative mantissa would round toward -inf, not
    toward zero), positive ``sc`` multiplies by 2^sc mod p (``_pow2_mod``).
    floor((|mh|*2^26 + |ml|) / 2^t) == |mh| >> (t - 26) for t > 26 because
    the discarded remainder is < 2^t, so the two-limb shift is exact.
    """
    i32 = jnp.int32
    zero = i32(0)
    amh, aml = jnp.abs(mh), jnp.abs(ml)
    sg = jnp.where(mh != 0, jnp.sign(mh), jnp.sign(ml))
    t = jnp.maximum(-sc, zero)
    tl = jnp.minimum(t, i32(MANT_SPLIT))
    th = jnp.clip(t - i32(MANT_SPLIT), zero, i32(31))  # shifts >= 32 are UB; mh < 2^27
    mh_sh = jax.lax.shift_right_logical(amh, th)
    ml_sh = jax.lax.shift_right_logical(aml, tl)
    sp = jnp.maximum(sc, zero)
    # Limb weights 2^(26 - tl + sp) and 2^sp mod p (tl = 0 whenever sp > 0).
    # The core path clamps table indices to the last entry; that only
    # matters for ZERO elements in extreme-exponent rows (scaling._clip_scale
    # caps the scaled magnitude of nonzero values at 2^900 < 2^table_len),
    # whose residue is 0 regardless of the weights.
    w_l = _pow2_mod(jnp.clip(sp, zero, i32(table_len - 1)), p,
                    (table_len - 1).bit_length())
    w_h = jnp.mod(_pow2_mod(i32(MANT_SPLIT) - tl, p, MANT_SPLIT.bit_length()) * w_l,
                  i32(p))
    pc = i32(p)
    r = jnp.mod(jnp.mod(mh_sh, pc) * w_h + jnp.mod(ml_sh, pc) * w_l, pc)
    return _centered(jnp.mod(sg * r, pc), p)


def _split_fp8(r, sq, s):
    """Centred residue -> e4m3 parts: (hi, lo) for square moduli p = s^2
    (round split), (hi, lo, hs) for Karatsuba moduli (ceil split, s = 16).
    Same arithmetic as the ``quant_residues`` kernel; |parts| <= 16 so every
    value is exact in e4m3."""
    f8 = lambda x: x.astype(jnp.float32).astype(E4M3)
    if sq:
        hi = jnp.round(r.astype(jnp.float32) / jnp.float32(s)).astype(jnp.int32)
        lo = r - jnp.int32(s) * hi
        return f8(hi), f8(lo)
    ks = jnp.int32(KARATSUBA_S)
    hi = jnp.sign(r) * ((jnp.abs(r) + (ks - jnp.int32(1))) // ks)
    lo = r - ks * hi
    return f8(hi), f8(lo), f8(hi + lo)


def _dot_i32(x, y):
    """Exact integer MMA: e4m3 x e4m3 -> f32 (integer-valued, <= bk*2^9
    < 2^24 so the f32 sum is exact) -> int32 partial."""
    return jnp.dot(x.astype(jnp.float32), y.astype(jnp.float32),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _mma_fp8(pa, pb, sq):
    """One modulus' MMA schedule -> (d1, d2, d3) int32 partials.
    Square, eq. (12): A1B2, A2B1, A2B2. Karatsuba, eq. (8): A1B1, A2B2,
    (A1+A2)(B1+B2) — matching the c1/c2/c3 slots ``_combine`` expects."""
    if sq:
        a_hi, a_lo = pa
        b_hi, b_lo = pb
        return (_dot_i32(a_hi, b_lo), _dot_i32(a_lo, b_hi),
                _dot_i32(a_lo, b_lo))
    a_hi, a_lo, a_hs = pa
    b_hi, b_lo, b_hs = pb
    return (_dot_i32(a_hi, b_hi), _dot_i32(a_lo, b_lo), _dot_i32(a_hs, b_hs))


def _finalize(accs, lmu, lnu, out_ref, ms: ModuliSet, reconstruct: str):
    """Last k step: scratch accumulators -> combine -> Garner digits ->
    digit stack (int16) or on-chip compensated f64 combine."""
    if ms.family == "int8":
        (acc,) = accs
        cs = [_cmod(acc[l], p) for l, p in enumerate(ms.ps)]
    else:
        c1, c2, c3 = accs
        cs = [_combine(c1[l], c2[l], c3[l], p, sq, s)
              for l, (p, sq, s) in enumerate(zip(ms.ps, ms.is_square, ms.split_s))]
    ds = _garner(cs, ms)
    if reconstruct == "xla":
        out_ref[...] = jnp.stack(ds).astype(jnp.int16)
        return
    # On-chip epilogue: the same op sequence as core crt.reconstruct — the
    # Kahan scan unrolled over the radix weights (Pallas kernels cannot
    # capture array constants; Python-float weights produce the identical
    # f64 multiply), then the wide two-step ldexp. Bitwise-equal f64 tile.
    s = c = ds[0].astype(jnp.float64) * 0.0
    for x, w in zip(ds, ms.radix_weights_f64):
        term = x.astype(jnp.float64) * float(w) - c
        t = s + term
        c = (t - s) - term
        s = t
    out_ref[...] = numerics.ldexp_wide(s, -(lmu + lnu))


def _init_accs(accs):
    @pl.when(pl.program_id(2) == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)


def _maybe_finalize(accs, lmu, lnu, out_ref, ms, reconstruct):
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        _finalize(accs, lmu, lnu, out_ref, ms, reconstruct)


def _kernel_quantize(mh_ref, ml_ref, e_ref, l_ref, *out_refs, ms: ModuliSet):
    """One operand tile -> its residue parts for every modulus, in the
    ``common.stack_parts`` layout: fp8 families write (hi, lo, hs) e4m3
    stacks (hs zero for square moduli), int8 one int8 stack."""
    # Fold the pairing scale into the raw frame: scaled x = mant * 2^sc.
    sc = e_ref[...] + l_ref[...]  # (br, bc) + (br, 1) for A, (1, bc) for B
    table_len = ms.pow2_mod_tables.shape[1]
    for l, (p, sq, s) in enumerate(zip(ms.ps, ms.is_square, ms.split_s)):
        r = _residue_tile(mh_ref[...], ml_ref[...], sc, p, table_len)
        if ms.family == "int8":
            out_refs[0][l] = r.astype(jnp.int8)
            continue
        parts = _split_fp8(r, sq, s)
        if sq:
            parts += (jnp.zeros_like(parts[0]),)
        for ref, v in zip(out_refs, parts):
            ref[l] = v


def _kernel_parts_fp8(a_hi_ref, a_lo_ref, a_hs_ref, b_hi_ref, b_lo_ref,
                      b_hs_ref, lmu_ref, lnu_ref, out_ref, *accs,
                      ms: ModuliSet, reconstruct: str):
    """Fused MMA + reconstruct from residue parts (``_kernel_quantize``'s, or
    the cached ones of fast-mode plans)."""
    _init_accs(accs)
    for l, sq in enumerate(ms.is_square):
        pa = (a_hi_ref[l], a_lo_ref[l]) if sq else (a_hi_ref[l], a_lo_ref[l], a_hs_ref[l])
        pb = (b_hi_ref[l], b_lo_ref[l]) if sq else (b_hi_ref[l], b_lo_ref[l], b_hs_ref[l])
        for acc, d in zip(accs, _mma_fp8(pa, pb, sq)):
            acc[l] += d
    _maybe_finalize(accs, lmu_ref[...], lnu_ref[...], out_ref, ms, reconstruct)


def _kernel_parts_int8(ra_ref, rb_ref, lmu_ref, lnu_ref, out_ref, acc,
                       *, ms: ModuliSet, reconstruct: str):
    _init_accs((acc,))
    for l in range(ms.n):
        acc[l] += jnp.dot(ra_ref[l], rb_ref[l], preferred_element_type=jnp.int32)
    _maybe_finalize((acc,), lmu_ref[...], lnu_ref[...], out_ref, ms, reconstruct)


def _call(kern, in_specs, m, n, k, ms, bm, bn, bk, reconstruct, interpret):
    """Shared pallas_call builder: k-innermost grid, (bm, bn)-resident
    output, int32 scratch accumulators (3 for the fp8 3-GEMM schedules,
    1 for int8)."""
    grid = (m // bm, n // bn, k // bk)
    if reconstruct == "onchip":
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))
        out_shape = jax.ShapeDtypeStruct((m, n), jnp.float64)
    else:
        out_spec = pl.BlockSpec((ms.n, bm, bn), lambda i, j, s: (_I0, i, j))
        out_shape = jax.ShapeDtypeStruct((ms.n, m, n), jnp.int16)
    n_acc = 1 if ms.family == "int8" else 3
    return pl.pallas_call(
        functools.partial(kern, ms=ms, reconstruct=reconstruct),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((ms.n, bm, bn), jnp.int32)] * n_acc,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("ms", "blocks", "interpret"))
def ozmm_fused_quantize(mh, ml, e, l, *, ms: ModuliSet, blocks, interpret: bool):
    """Residue parts of one operand, each tile quantized once. Inputs: the
    operand's sign-folded raw frame (int32 (rows, cols) triple,
    ops.decompose_raw) and its pairing scale exponents, ``l`` (rows, 1)
    for A's rows or (1, cols) for B's columns. Dims must be multiples of
    ``blocks`` = (br, bc) (ops pads). Returns the ``common.stack_parts``
    layout that ozmm_fused_parts takes: fp8 families (hi, lo, hs) e4m3
    stacks (N, rows, cols), int8 one int8 stack."""
    rows, cols = mh.shape
    br, bc = blocks
    assert rows % br == 0 and cols % bc == 0 and l.shape in ((rows, 1), (1, cols)), \
        (mh.shape, l.shape, blocks)
    frame_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    if l.shape == (rows, 1):
        l_spec = pl.BlockSpec((br, 1), lambda i, j: (i, _I0))
    else:
        l_spec = pl.BlockSpec((1, bc), lambda i, j: (_I0, j))
    n_out, dtype = (1, jnp.int8) if ms.family == "int8" else (3, E4M3)
    out = pl.pallas_call(
        functools.partial(_kernel_quantize, ms=ms),
        grid=(rows // br, cols // bc),
        in_specs=[frame_spec] * 3 + [l_spec],
        out_specs=[pl.BlockSpec((ms.n, br, bc), lambda i, j: (_I0, i, j))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((ms.n, rows, cols), dtype)] * n_out,
        interpret=interpret,
    )(mh, ml, e, l)
    return out[0] if ms.family == "int8" else tuple(out)


@functools.partial(jax.jit, static_argnames=("ms", "bm", "bn", "bk",
                                             "reconstruct", "interpret"))
def ozmm_fused_parts(sa, sb, lmu, lnu, *, ms: ModuliSet, bm: int, bn: int,
                     bk: int, reconstruct: str, interpret: bool):
    """Fused MMA + reconstruct from stacked residue parts (common.stack_parts
    layout): fp8 families take ((hi, lo, hs), ...) e4m3 stacks (N, m, k) /
    (N, k, n); int8 takes single int8 stacks. lmu (m, 1) / lnu (1, n) are
    the pairing scale exponents. Dims must be multiples of the block shape
    (ops pads). Returns the f64 product (``reconstruct="onchip"``) or the
    int16 Garner digit stack (N, m, n) (``"xla"``)."""
    if ms.family == "int8":
        m, k = sa.shape[1:]
        n = sb.shape[2]
    else:
        m, k = sa[0].shape[1:]
        n = sb[0].shape[2]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    a_spec = pl.BlockSpec((ms.n, bm, bk), lambda i, j, s: (_I0, i, s))
    b_spec = pl.BlockSpec((ms.n, bk, bn), lambda i, j, s: (_I0, s, j))
    lmu_spec = pl.BlockSpec((bm, 1), lambda i, j, s: (i, _I0))
    lnu_spec = pl.BlockSpec((1, bn), lambda i, j, s: (_I0, j))
    if ms.family == "int8":
        call = _call(_kernel_parts_int8, [a_spec, b_spec, lmu_spec, lnu_spec],
                     m, n, k, ms, bm, bn, bk, reconstruct, interpret)
        return call(sa, sb, lmu, lnu)
    call = _call(_kernel_parts_fp8,
                 [a_spec] * 3 + [b_spec] * 3 + [lmu_spec, lnu_spec],
                 m, n, k, ms, bm, bn, bk, reconstruct, interpret)
    return call(*sa, *sb, lmu, lnu)
