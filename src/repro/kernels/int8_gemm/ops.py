"""Jitted public wrapper for the int8 GEMM kernel: pads to block multiples."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import int8_gemm


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_gemm_op(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                 bk: int = 128, interpret: bool | None = None) -> jax.Array:
    m, n = a.shape[0], b.shape[1]
    out = int8_gemm(_pad_to(a, bm, bk), _pad_to(b, bk, bn),
                    bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]
