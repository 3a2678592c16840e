"""Pallas TPU kernel: fused residue quantization for ALL moduli in one pass.

The GPU reference implementation launches one quant kernel per modulus,
reading the f64 input N times. On TPU this phase is memory-bound, so we fuse:
each (bm, bk) tile of the integer-decomposed input is read ONCE and the
e4m3 residue splits for every modulus are emitted from VMEM.

TPU-native integer path (DESIGN.md "hardware adaptation"): the f64 -> exact
integer decomposition (ops.py, XLA) yields
    a' = (mh * 2^26 + ml) * 2^e,   mh int32 (signed, |mh| < 2^27),
                                   ml int32 in [0, 2^26), e int32 >= 0,
so the kernel needs ONLY int32 arithmetic:
    r = ((mh mod p) * (2^26 mod p) + ml mod p) * (2^e mod p) mod p
with every intermediate < 2^22 * 1089 < 2^31. No f64 ops on the VPU.

Outputs: hi/lo/hs stacks (M_parts, bm, bk) e4m3 where hs is only meaningful
for Karatsuba moduli (zeros for square moduli, sliced away by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.moduli import KARATSUBA_S, ModuliSet

from ..common import resolve_interpret

E4M3 = jnp.float8_e4m3fn
MANT_SPLIT = 26  # mant = mh * 2^26 + ml


def _centered(r, p):
    half = (p - 1) // 2
    return r - jnp.where(r > half, p, 0).astype(r.dtype)


def _quant_kernel(mh_ref, ml_ref, e_ref, tbl_ref, hi_ref, lo_ref, hs_ref, *, ms: ModuliSet):
    mh = mh_ref[...]
    ml = ml_ref[...]
    e = e_ref[...]
    f8 = lambda x: x.astype(jnp.float32).astype(E4M3)
    for l, (p, sq, s) in enumerate(zip(ms.ps, ms.is_square, ms.split_s)):
        t26 = (1 << MANT_SPLIT) % p
        rm = (jnp.mod(mh, p) * t26 + jnp.mod(ml, p))  # < 2^22 + p
        pw = tbl_ref[l, :]  # (table_len,) int32: 2^e mod p
        r = jnp.mod(jnp.mod(rm, p) * pw[e], p)
        r = _centered(r, p)
        if sq:
            hi = jnp.round(r.astype(jnp.float32) / jnp.float32(s)).astype(jnp.int32)
            lo = r - s * hi
            hi_ref[l] = f8(hi)
            lo_ref[l] = f8(lo)
            hs_ref[l] = jnp.zeros_like(r, E4M3)
        else:
            absr = jnp.abs(r)
            hi = jnp.sign(r) * ((absr + (KARATSUBA_S - 1)) // KARATSUBA_S)
            lo = r - KARATSUBA_S * hi
            hi_ref[l] = f8(hi)
            lo_ref[l] = f8(lo)
            hs_ref[l] = f8(hi + lo)


def _quant_kernel_int8(mh_ref, ml_ref, e_ref, tbl_ref, r_ref, *, ms: ModuliSet):
    mh = mh_ref[...]
    ml = ml_ref[...]
    e = e_ref[...]
    for l, p in enumerate(ms.ps):
        t26 = (1 << MANT_SPLIT) % p
        rm = jnp.mod(mh, p) * t26 + jnp.mod(ml, p)
        pw = tbl_ref[l, :]
        r = _centered(jnp.mod(jnp.mod(rm, p) * pw[e], p), p)
        r_ref[l] = r.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("ms", "bm", "bk", "interpret"))
def quant_residues(
    mh: jax.Array,
    ml: jax.Array,
    e: jax.Array,
    pow2_tables: jax.Array,
    *,
    ms: ModuliSet,
    bm: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
):
    """Returns (hi, lo, hs) stacks (N, m, k) e4m3 for fp8 families, or a
    single (N, m, k) int8 stack for the int8 family."""
    m, k = mh.shape
    assert m % bm == 0 and k % bk == 0, (mh.shape, bm, bk)
    grid = (m // bm, k // bk)
    n = ms.n
    tl = pow2_tables.shape[1]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        pl.BlockSpec((n, tl), lambda i, j: (0, 0)),
    ]
    stack_spec = pl.BlockSpec((n, bm, bk), lambda i, j: (0, i, j))
    if ms.family == "int8":
        return pl.pallas_call(
            functools.partial(_quant_kernel_int8, ms=ms),
            grid=grid,
            in_specs=in_specs,
            out_specs=stack_spec,
            out_shape=jax.ShapeDtypeStruct((n, m, k), jnp.int8),
            interpret=resolve_interpret(interpret),
        )(mh, ml, e, pow2_tables)
    return pl.pallas_call(
        functools.partial(_quant_kernel, ms=ms),
        grid=grid,
        in_specs=in_specs,
        out_specs=(stack_spec, stack_spec, stack_spec),
        out_shape=tuple(jax.ShapeDtypeStruct((n, m, k), E4M3) for _ in range(3)),
        interpret=resolve_interpret(interpret),
    )(mh, ml, e, pow2_tables)
