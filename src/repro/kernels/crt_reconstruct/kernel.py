"""Pallas TPU kernel: fused requant (residue-product combine) + balanced
Garner digits.

GPU reference implementations run 'requant' (mod-reduce each GEMM output)
and 'dequant' (CRT reconstruction) as separate passes over N matrices. Here
one kernel reads all N (or 3N) GEMM output tiles from VMEM once and emits
the N int16 Garner digit planes; the final digit-weighted f64 combine stays
in XLA (TPU has no native f64 — DESIGN.md hardware adaptation; that combine
is a cheap memory-bound epilogue over N small-int planes).

All kernel arithmetic is int32 with |values| < 1089^2 < 2^21 (I5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.moduli import KARATSUBA_S, ModuliSet

from ..common import resolve_interpret


#: Block index 0 for the index maps, as int32: a bare 0 is an int64 literal
#: under x64, which Mosaic cannot lower.
_I0 = np.int32(0)


def _centered(r, p):
    # Constants are cast to the operand dtype: a bare Python int becomes an
    # int64 literal under x64, which Mosaic cannot lower.
    half = (p - 1) // 2
    return r - jnp.where(r > half, r.dtype.type(p), r.dtype.type(0))


def _cmod(x, p):
    return _centered(jnp.mod(x, x.dtype.type(p)), p)


def _combine(c1, c2, c3, p, sq, s):
    if sq:  # eq. (12): C' = mod(s*(A1B2 + A2B1) + A2B2, p)
        return _cmod(s * (c1 + c2) + c3, p)
    s2 = KARATSUBA_S * KARATSUBA_S  # eq. (9), big terms pre-reduced
    return _cmod(s2 * _cmod(c1, p) + _cmod(c2, p) + KARATSUBA_S * _cmod(c3 - c1 - c2, p), p)


def _garner(cs, ms: ModuliSet):
    order, ps, inv = ms.radix_order, ms.radix_ps, ms.garner_inv
    digits = []
    for i in range(ms.n):
        t = cs[order[i]]
        pi = ps[i]
        for j in range(i):
            t = _cmod((t - digits[j]) * int(inv[j, i]), pi)
        digits.append(_cmod(t, pi))
    return digits


def _kernel_fp8(c1_ref, c2_ref, c3_ref, d_ref, *, ms: ModuliSet):
    cs = [
        _combine(
            c1_ref[l].astype(jnp.int32),
            c2_ref[l].astype(jnp.int32),
            c3_ref[l].astype(jnp.int32),
            p, sq, s,
        )
        for l, (p, sq, s) in enumerate(zip(ms.ps, ms.is_square, ms.split_s))
    ]
    d_ref[...] = jnp.stack(_garner(cs, ms)).astype(jnp.int16)


def _kernel_int8(c_ref, d_ref, *, ms: ModuliSet):
    cs = [_cmod(c_ref[l], p) for l, p in enumerate(ms.ps)]
    d_ref[...] = jnp.stack(_garner(cs, ms)).astype(jnp.int16)


@functools.partial(jax.jit, static_argnames=("ms", "bm", "bn", "interpret"))
def requant_garner(
    cparts,
    *,
    ms: ModuliSet,
    bm: int = 128,
    bn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """cparts: (c1, c2, c3) stacks (N, m, n) f32 for fp8 families, or a
    single-element tuple of an (N, m, n) int32 stack for int8. Returns the
    balanced Garner digits (N, m, n) int16 in radix order."""
    n_mod, m, n = cparts[0].shape
    assert n_mod == ms.n and m % bm == 0 and n % bn == 0
    grid = (m // bm, n // bn)
    spec = pl.BlockSpec((n_mod, bm, bn), lambda i, j: (_I0, i, j))
    kern = _kernel_int8 if ms.family == "int8" else _kernel_fp8
    return pl.pallas_call(
        functools.partial(kern, ms=ms),
        grid=grid,
        in_specs=[spec] * len(cparts),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n_mod, m, n), jnp.int16),
        interpret=resolve_interpret(interpret),
    )(*cparts)
