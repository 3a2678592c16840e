"""Jitted wrappers for the fused emulated GEMM: XLA-side scaling + raw-frame
decomposition, zero-pad/crop shape handling, block-size selection, and the
optional XLA digit-combine epilogue.

Entry points mirror the phase-split pipeline:

* ``ozmm_pallas_fused(a, b, ...)`` — plain operands; scaling (fast or
  accurate) runs in XLA; after the exponent frames, one quantize kernel per
  operand and the fused MMA + reconstruct kernel.
* ``ozmm_pallas_fused_prepared(qa, qb, ...)`` — core.plan operands; fast
  mode streams the plans' cached residue digits through the fused
  MMA+reconstruct epilogue, accurate mode re-enters the raw-frame path with
  the pairing-time exponents from ``pair_exponents``.

Shape handling: arbitrary (m, k) @ (k, n) — operands are zero-padded to
block multiples and the result is cropped. Exactness-preserving: a zero
element decomposes to an all-zero raw frame, its residues are 0 for every
modulus, and zero residue parts contribute exact zeros to every partial
product and digit, so padded results equal unpadded results bitwise.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core import crt, numerics, scaling
from repro.core import plan as core_plan
from repro.core.moduli import DEFAULT_NUM_MODULI, ModuliSet, make_moduli_set
from repro.core.plan import QuantizedMatrix

from ..common import resolve_interpret, resolve_reconstruct, stack_parts
from .kernel import MANT_SPLIT, ozmm_fused_parts, ozmm_fused_quantize

#: Env override for the block-size table: "bm,bn,bk" (read per call; the
#: kwarg ``blocks=`` wins over the env, the env wins over the table).
BLOCKS_ENV = "REPRO_FUSED_BLOCKS"

#: (backend, family) -> [(max_moduli, (bm, bn, bk)), ...] — first row whose
#: ``max_moduli`` covers the request wins. Mosaic needs the last two block
#: dims of every operand tile to be multiples of (8, 128), so the TPU rows
#: keep bk = 128 at every modulus count; at N = 13 the 3 int32 accumulator
#: stacks (3*N*bm*bn*4 B = 2.4 MiB at 128x128) plus double-buffered operand
#: tiles stay well inside the 16 MiB of VMEM. Interpreter rows use smaller
#: tiles so CI-sized problems still sweep several grid steps. ``"default"``
#: covers any other backend.
BLOCK_TABLE = {
    ("tpu", "fp8-hybrid"): [(99, (128, 128, 128))],
    ("tpu", "fp8-karatsuba"): [(99, (128, 128, 128))],
    ("tpu", "int8"): [(99, (128, 128, 128))],
    ("interpret", "fp8-hybrid"): [(99, (64, 128, 64))],
    ("interpret", "fp8-karatsuba"): [(99, (64, 128, 64))],
    ("interpret", "int8"): [(99, (64, 128, 64))],
    ("default", "fp8-hybrid"): [(99, (128, 128, 128))],
    ("default", "fp8-karatsuba"): [(99, (128, 128, 128))],
    ("default", "int8"): [(99, (128, 128, 128))],
}


def select_blocks(family: str, num_moduli: int, interpret: bool,
                  override=None) -> tuple[int, int, int]:
    """Resolve the fused kernel's (bm, bn, bk) tile shape.

    Precedence: explicit ``override`` (the ``blocks=`` kwarg) > the
    ``REPRO_FUSED_BLOCKS`` env var ("bm,bn,bk") > a fresh perf-model preset
    that swept a tiling for exactly this (family, modulus count, backend)
    (``repro.perf.model.preset_blocks``; docs/perf.md) > the static
    per-(backend, family) table row matching ``num_moduli``. Benchmarks
    record the resolved tiling in their rows so perf trajectories stay
    attributable. Tiling affects schedule, not values — every choice is
    bitwise-equal (the fused tiling-invariance test).
    """
    if override is not None:
        bm, bn, bk = (int(v) for v in override)
        return bm, bn, bk
    env = os.environ.get(BLOCKS_ENV)
    if env:
        try:
            bm, bn, bk = (int(v) for v in env.split(","))
        except ValueError:
            raise ValueError(
                f"{BLOCKS_ENV} must be 'bm,bn,bk' integers, got {env!r}") from None
        return bm, bn, bk
    key = "interpret" if interpret else jax.default_backend()
    preset = _preset_blocks(family, num_moduli, key)
    if preset is not None:
        return preset
    rows = BLOCK_TABLE.get((key, family)) or BLOCK_TABLE[("default", family)]
    for max_moduli, blocks in rows:
        if num_moduli <= max_moduli:
            return blocks
    return rows[-1][1]


def _preset_blocks(family: str, num_moduli: int, key: str):
    """Measured tiling from the checked-in perf presets, or None. The
    import is deferred so the kernels layer does not import repro.perf at
    module load. Unreadable preset files are skipped where they are loaded
    (``repro.perf.model.default_model``); any other failure is a bug and
    raises."""
    from repro.perf.model import preset_blocks
    return preset_blocks(family, num_moduli, key)


@jax.named_scope("ozmm.quantize")
def decompose_raw(x: jax.Array):
    """f64 -> sign-folded two-limb raw frame: x = (mh*2^26 + ml) * 2^e with
    mh, ml, e int32, sign carried by BOTH limbs (|mh| < 2^27, |ml| < 2^26).

    Unlike ``quant_residues``' ``decompose_int`` this does NOT require the
    input to be pre-scaled to an integer: ``e`` may be negative, and the
    kernel folds the pairing scale in and truncates by magnitude shifts
    (kernel._residue_tile). That makes the frame pairing-INDEPENDENT — the
    accurate mode's pairing-coupled exponents apply inside the kernel, so
    the same cached frames serve any partner.
    """
    mant, e = numerics.frexp(x)
    m53 = (mant * (2.0 ** 53)).astype(jnp.int64)
    e53 = (e - 53).astype(jnp.int32)
    sg = jnp.sign(m53)
    am = jnp.abs(m53)
    mh = (sg * jax.lax.shift_right_logical(am, jnp.int64(MANT_SPLIT))).astype(jnp.int32)
    ml = (sg * (am & ((1 << MANT_SPLIT) - 1))).astype(jnp.int32)
    return mh, ml, e53


def _pad2(x, m0, m1):
    p0, p1 = (-x.shape[0]) % m0, (-x.shape[1]) % m1
    return jnp.pad(x, ((0, p0), (0, p1))) if (p0 or p1) else x


def _pad3(x, m1, m2):
    p1, p2 = (-x.shape[1]) % m1, (-x.shape[2]) % m2
    return jnp.pad(x, ((0, 0), (0, p1), (0, p2))) if (p1 or p2) else x


def _epilogue(out, m, n, ms, lmu, lnu, reconstruct):
    """Crop padding; for digit-stack output run the core f64 combine (same
    Kahan scan + ldexp_wide as ``crt.reconstruct`` => bitwise-equal)."""
    if reconstruct == "onchip":
        return out[:m, :n]
    return crt.reconstruct(out[:, :m, :n], ms, lmu, lnu)


def _products(sa, lmu, sb, lnu, m, n, *, ms: ModuliSet, blocks,
              reconstruct: str, interpret: bool) -> jax.Array:
    """Residue-part stacks (zero-padded here to block multiples) -> the fused
    MMA + reconstruct kernel -> epilogue, cropped to (m, n)."""
    bm, bn, bk = blocks
    pa = jax.tree.map(lambda v: _pad3(v, bm, bk), sa)
    pb = jax.tree.map(lambda v: _pad3(v, bk, bn), sb)
    lmu_p = _pad2(lmu[:, None], bm, 1)
    lnu_p = _pad2(lnu[None, :], 1, bn)
    # Scoped here, outside the kernel's jit: a scope inside it would rename
    # the kernel's HLO instruction.
    with jax.named_scope("ozmm.products"):
        out = ozmm_fused_parts(pa, pb, lmu_p, lnu_p, ms=ms, bm=bm, bn=bn, bk=bk,
                               reconstruct=reconstruct, interpret=interpret)
    return _epilogue(out, m, n, ms, lmu, lnu, reconstruct)


@functools.partial(jax.jit, static_argnames=("ms", "blocks", "reconstruct",
                                             "interpret"))
def _fused_from_frames(a, lmu, b, lnu, *, ms: ModuliSet, blocks,
                       reconstruct: str, interpret: bool) -> jax.Array:
    """Raw-frame fused path: decompose both operands (XLA), pad, quantize
    each operand once (one pallas_call each), then the parts kernel."""
    (m, k), n = a.shape, b.shape[1]
    bm, bn, bk = blocks
    fa = tuple(_pad2(v, bm, bk) for v in decompose_raw(a))
    fb = tuple(_pad2(v, bk, bn) for v in decompose_raw(b))
    with jax.named_scope("ozmm.quantize"):
        sa = ozmm_fused_quantize(*fa, _pad2(lmu[:, None], bm, 1), ms=ms,
                                 blocks=(bm, bk), interpret=interpret)
        sb = ozmm_fused_quantize(*fb, _pad2(lnu[None, :], 1, bn), ms=ms,
                                 blocks=(bk, bn), interpret=interpret)
    return _products(sa, lmu, sb, lnu, m, n, ms=ms, blocks=blocks,
                     reconstruct=reconstruct, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("ms", "blocks", "reconstruct",
                                             "interpret"))
def _fused_from_parts(sa, lmu, sb, lnu, *, ms: ModuliSet, blocks,
                      reconstruct: str, interpret: bool) -> jax.Array:
    """Prepared fast-mode path: cached residue-part stacks straight into the
    fused MMA + reconstruct epilogue."""
    m, n = lmu.shape[0], lnu.shape[0]
    return _products(sa, lmu, sb, lnu, m, n, ms=ms, blocks=blocks,
                     reconstruct=reconstruct, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("ms", "mode", "blocks",
                                             "reconstruct", "interpret"))
def _fused_2d(a, b, *, ms: ModuliSet, mode: str, blocks, reconstruct: str,
              interpret: bool) -> jax.Array:
    scal = scaling.compute_scaling(a, b, ms, mode)
    return _fused_from_frames(a, scal.lmu, b, scal.lnu, ms=ms, blocks=blocks,
                              reconstruct=reconstruct, interpret=interpret)


def ozmm_pallas_fused(
    a: jax.Array,
    b: jax.Array,
    *,
    family: str = "fp8-hybrid",
    num_moduli: int | None = None,
    mode: str = "accurate",
    interpret: bool | None = None,
    reconstruct: str | None = None,
    blocks=None,
) -> jax.Array:
    """Fused emulated FP64 matmul (the EmuGEMM-style fused schedule;
    kernel.py). Bitwise-equal to ``core.ozaki2.ozmm_ozaki2`` / ``ozmm_pallas``
    by construction. Supports (..., m, k) @ (..., k, n) with matching leading
    batch dims (vmapped, like core ``ozmm``); any m/n/k (zero-pad + crop).

    ``interpret``/``reconstruct`` default per backend (common.py);
    ``blocks=(bm, bn, bk)`` overrides the selection table (select_blocks).
    """
    interpret = resolve_interpret(interpret)
    reconstruct = resolve_reconstruct(reconstruct, interpret)
    if num_moduli is None:
        num_moduli = DEFAULT_NUM_MODULI[family]
    ms = make_moduli_set(family, num_moduli)
    blocks = select_blocks(family, ms.n, interpret, blocks)
    a = jnp.asarray(a).astype(jnp.float64)
    b = jnp.asarray(b).astype(jnp.float64)
    fn = functools.partial(_fused_2d, ms=ms, mode=mode, blocks=blocks,
                           reconstruct=reconstruct, interpret=interpret)
    if a.ndim == b.ndim == 2:
        return fn(a, b)
    if a.ndim != b.ndim:
        raise ValueError(f"rank mismatch {a.shape} @ {b.shape}")
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a, b)


def ozmm_pallas_fused_prepared(
    qa: QuantizedMatrix,
    qb: QuantizedMatrix,
    *,
    interpret: bool | None = None,
    reconstruct: str | None = None,
    blocks=None,
) -> jax.Array:
    """Execute a prepared pairing (core.plan) on the fused kernel.

    Fast mode reuses the plans' residue digits bitwise — the cached part
    stacks stream through the fused MMA + Garner/reconstruct epilogue
    without re-quantizing. Accurate mode derives the pairing exponents from
    the cached casts (``pair_exponents``: the bound GEMM) and runs the
    raw-frame fused path, quantizing on-chip under those exponents.
    Bitwise-equal to ``ozmm_prepared`` in both modes.
    """
    interpret = resolve_interpret(interpret)
    reconstruct = resolve_reconstruct(reconstruct, interpret)
    ms = qa.ms
    blocks = select_blocks(ms.family, ms.n, interpret, blocks)
    lmu, lnu = core_plan.pair_exponents(qa, qb)
    if qa.mode == "fast":
        sa = stack_parts(qa.parts, ms)
        sb = stack_parts(qb.parts, ms)
        return _fused_from_parts(sa, lmu, sb, lnu, ms=ms, blocks=blocks,
                                 reconstruct=reconstruct, interpret=interpret)
    return _fused_from_frames(qa.x, lmu, qb.x, lnu, ms=ms, blocks=blocks,
                              reconstruct=reconstruct, interpret=interpret)
