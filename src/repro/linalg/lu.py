"""Right-looking blocked LU with partial pivoting, trailing update emulated.

This replaces the no-pivot prototype that used to live in examples/hpl_lu.py:
pivoting makes the factorization valid for general (not diagonally dominant)
matrices — the HPL setting — while keeping the flop profile GEMM-dominant:
per panel step, one blocked TRSM forms U12 and the rank-b trailing update
A22 -= L21 @ U12 applies >= 2/3 of all flops for b << n.

Plan reuse (core.plan): under Ozaki-II policies the per-step reuse lives in
the U12 TRSM — each solved block-row's residue plan is quantized once and
folded into every later block step (see blas3.trsm) — and the trailing
update executes through a prepared, device-resident panel plan. Results are
identical to the plan-less path (same single pairing per update).

The panel internals (pivot argmax, pivot-column scaling, rank-1 update, the
substitution scans behind the TRSM diagonal blocks) are the grid-agnostic
block ops of ``blocks.py``, shared with ``repro.linalg.dist`` — which is
what makes the block-cyclic factorization AND its distributed
triangular-solve epilogue bitwise-equal to this path in fast mode.

Spans (``repro.obs``, on the JAX profiler's clock): ``linalg.lu.factor``
over the whole factorization and, per block step ``step=k0``,
``linalg.lu.panel`` (the column loop), ``linalg.lu.trsm`` (U12) and
``linalg.lu.update`` (the trailing update with its host copy).
"""
from __future__ import annotations

import numpy as np

from repro.core import resolve_policy
from repro.obs import span

from .blas3 import DEFAULT_BLOCK, device_matmul, gemm, prepare, trsm
from .blocks import pivot_argmax, rank1_update, scale_pivot_column


@span("linalg.lu.factor")
def lu_factor(a, policy=None, *, block: int = DEFAULT_BLOCK
              ) -> tuple[np.ndarray, np.ndarray]:
    """Factor square A with partial pivoting: ``A[perm] = L @ U``.

    ``policy`` is a ``PrecisionPolicy`` / spec string / None (precision
    context). Returns ``(lu, perm)``: ``lu`` packs unit-lower L (implicit
    diagonal) below U in one array (LAPACK dgetrf storage), ``perm`` is the
    row permutation as an index vector (apply as ``a[perm]`` / ``b[perm]``).
    """
    pol = resolve_policy(policy)
    a = np.array(a, dtype=np.float64)  # owned copy, factored in place
    n, m = a.shape
    if n != m:
        raise ValueError(f"lu_factor requires a square matrix, got {a.shape}")
    perm = np.arange(n)
    for k0 in range(0, n, block):
        k1 = min(k0 + block, n)
        # Panel: unblocked partial-pivoting LU of a[k0:, k0:k1]. Row swaps
        # apply to the FULL rows (left factors and trailing matrix alike),
        # so the packed storage stays consistent. The pivot search runs on
        # device (blocks.pivot_argmax); the O(n·b^2) updates are host work
        # shared with the block-cyclic path (blocks.py).
        with span("linalg.lu.panel", step=k0):
            for j in range(k0, k1):
                off, mag = pivot_argmax(a[j:, j])
                p = j + off
                if mag == 0.0:
                    raise np.linalg.LinAlgError(f"singular: zero pivot column {j}")
                if p != j:
                    a[[j, p]] = a[[p, j]]
                    perm[[j, p]] = perm[[p, j]]
                a[j + 1:, j] = scale_pivot_column(a[j + 1:, j], a[j, j])
                rank1_update(a[j + 1:, j + 1:k1], a[j + 1:, j], a[j, j + 1:k1])
        if k1 == n:
            break
        # U12 := L11^{-1} A12 — blocked TRSM (GEMM-backed for wide panels)
        with span("linalg.lu.trsm", step=k0):
            a[k0:k1, k1:] = trsm(a[k0:k1, k0:k1], a[k0:k1, k1:], pol,
                                 side="left", lower=True, unit_diag=True,
                                 block=block)
        # trailing update A22 -= L21 @ U12: THE emulated DGEMM of the step.
        # One GEMM already quantizes each panel exactly once — tiling it
        # would only multiply dispatches — so the plan path's job here is
        # keeping the prepared panel device-resident; the per-step REUSE in
        # blocked LU lives in the TRSM above (solved U12 block-rows).
        with span("linalg.lu.update", step=k0):
            if pol.plans_enabled:
                l21 = prepare(a[k1:, k0:k1], "lhs", pol)
                a[k1:, k1:] -= np.asarray(device_matmul(l21, a[k0:k1, k1:], pol))
            else:
                a[k1:, k1:] = gemm(a[k1:, k0:k1], a[k0:k1, k1:], pol,
                                   alpha=-1.0, beta=1.0, c=a[k1:, k1:])
    return a, perm


def lu_unpack(lu: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Split packed dgetrf storage into (unit-lower L, upper U)."""
    n = lu.shape[0]
    return np.tril(lu, -1) + np.eye(n), np.triu(lu)
