"""Pallas TPU kernel: e4m3 x e4m3 -> f32 GEMM (the scheme's hot spot).

Tiled (bm, bk) x (bk, bn); the output block's index map ignores the innermost
grid dimension, so it stays VMEM-resident across the k steps and serves as
the f32 accumulator (standard TPU Pallas matmul pattern). The inner jnp.dot
lowers to the MXU (native e4m3 operands on v6e+/TPU7x; on v5e XLA's 8-bit
float path upconverts in-flight). 128-aligned blocks keep the MXU fed; VMEM
residency is bm*bk + bk*bn bytes of operands + 4*bm*bn accumulator.

Exactness (DESIGN.md I1): operands are integer-valued with |x| <= 16, so all
partial sums are integers <= k*256 <= 2^24 — every f32 add is exact and the
result is independent of the reduction order (grid order included).
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

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fp8_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """C (m, n) f32 = A (m, k) e4m3 @ B (k, n) e4m3. Dims must be multiples
    of the block shape (ops.py pads)."""
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
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a, b)
