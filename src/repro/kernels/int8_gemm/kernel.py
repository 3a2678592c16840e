"""Pallas TPU kernel: int8 x int8 -> int32 GEMM (INT8 Ozaki-II baseline path).

Same tiling/accumulation structure as fp8_gemm; the MXU consumes int8
natively on every TPU generation (v5e: 2x the bf16 rate). Exact for
k <= 2^17 (|residues| <= 128 -> partial sums < 2^31, DESIGN.md §6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import resolve_interpret


def _gemm_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a, b)
