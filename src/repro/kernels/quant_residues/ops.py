"""Jitted wrapper: f64 input -> exact int32 triple (XLA) -> fused Pallas pass."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantize
from repro.core.moduli import ModuliSet

from .kernel import quant_residues
from .ref import decompose_int


def _pad2(x, m0, m1):
    p0, p1 = (-x.shape[0]) % m0, (-x.shape[1]) % m1
    return jnp.pad(x, ((0, p0), (0, p1))) if (p0 or p1) else x


@functools.partial(jax.jit, static_argnames=("ms", "axis", "bm", "bk", "interpret"))
def quant_residues_op(
    a: jax.Array,
    lscale: jax.Array,
    *,
    ms: ModuliSet,
    axis: int = 0,
    bm: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
):
    """A (f64) + per-row (axis=0) or per-column (axis=1) log2 scales ->
    stacked low-precision residue operands, kernel-fused over moduli."""
    m, k = a.shape
    a_int = quantize.scaled_int(a, lscale, axis)
    mh, ml, e = decompose_int(a_int)
    mh, ml, e = (_pad2(x, bm, bk) for x in (mh, ml, e))
    out = quant_residues(mh, ml, e, jnp.asarray(ms.pow2_mod_tables),
                         ms=ms, bm=bm, bk=bk, interpret=interpret)
    if ms.family == "int8":
        return out[:, :m, :k]
    hi, lo, hs = out
    return hi[:, :m, :k], lo[:, :m, :k], hs[:, :m, :k]
