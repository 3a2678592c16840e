"""Jitted wrapper + final f64 reconstruction epilogue."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import numerics
from repro.core.moduli import ModuliSet

from .kernel import requant_garner


def _pad3(x, m0, m1):
    p0, p1 = (-x.shape[1]) % m0, (-x.shape[2]) % m1
    return jnp.pad(x, ((0, 0), (0, p0), (0, p1))) if (p0 or p1) else x


@functools.partial(jax.jit, static_argnames=("ms", "bm", "bn", "interpret"))
def requant_garner_op(cparts, *, ms: ModuliSet, bm: int = 128, bn: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    m, n = cparts[0].shape[1], cparts[0].shape[2]
    padded = tuple(_pad3(c, bm, bn) for c in cparts)
    d = requant_garner(padded, ms=ms, bm=bm, bn=bn, interpret=interpret)
    return d[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("ms",))
def reconstruct_f64(digits: jax.Array, ms: ModuliSet, lmu: jax.Array,
                    lnu: jax.Array) -> jax.Array:
    """Digit-weighted compensated f64 combine (XLA epilogue; see kernel.py).

    ldexp_wide, not jnp.ldexp: denormal-range rows carry |scale exponents|
    beyond the single-factor f64 range (scaling._clip_scale caps the PRODUCT
    exponent, not the exponent itself) — same fix as core crt.reconstruct."""
    v = numerics.kahan_weighted_sum(digits, jnp.asarray(ms.radix_weights_f64))
    return numerics.ldexp_wide(v, -(lmu[:, None] + lnu[None, :]))
