"""Solves on the emulated factorizations + mixed-precision refinement.

``refine_solve`` is the paper's motivating loop made concrete: factor once
under a (possibly fast-mode) policy, then drive iterative refinement whose
residual ``b - A @ x`` is computed through the ACCURATE-mode emulation — the
classic mixed-precision HPL pattern where the refinement GEMM's accuracy,
not the factorization's, sets the final solution quality.

Condition-aware precision (repro.precision.resolve): pass
``target_rel_err=`` and the solve resolves its ``num_moduli`` from the
system matrix's exponent-range sketch before factoring — the ROADMAP's
"condition-number-aware num_moduli selection per solve".

Spans (``repro.obs``, on the JAX profiler's clock): ``linalg.refine_solve``
over the whole call, ``linalg.lu.solve`` over each LU solve and
``linalg.refine.residual`` over each residual product.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import resolve_policy
from repro.obs import span

from .blas3 import DEFAULT_BLOCK, emulated_matmul, trsm
from .cholesky import cholesky
from .lu import lu_factor


def _as_cols(b) -> tuple[np.ndarray, bool]:
    b = np.asarray(b, dtype=np.float64)
    if b.ndim == 1:
        return b[:, None], True
    return b, False


@span("linalg.lu.solve")
def lu_solve(lu: np.ndarray, perm: np.ndarray, b, policy=None, *,
             block: int = DEFAULT_BLOCK) -> np.ndarray:
    """Solve A x = b given ``(lu, perm)`` from :func:`repro.linalg.lu_factor`.

    Both sweeps run ``blas3.trsm`` on the packed factors — diagonal blocks
    (unit-L AND general-U) solve on device via ``blocks.solve_triangular``,
    and solved block-rows fold in elimination order, so the distributed
    ``lu_solve_dist`` reproduces this solve bitwise in fast mode.
    """
    pol = resolve_policy(policy)
    rhs, was_vec = _as_cols(b)
    y = trsm(lu, rhs[perm], pol, side="left", lower=True, unit_diag=True,
             block=block)
    x = trsm(lu, y, pol, side="left", lower=False, block=block)
    return x[:, 0] if was_vec else x


def cholesky_solve(l_fac: np.ndarray, b, policy=None, *,
                   block: int = DEFAULT_BLOCK) -> np.ndarray:
    """Solve A x = b given lower L from :func:`repro.linalg.cholesky`."""
    pol = resolve_policy(policy)
    rhs, was_vec = _as_cols(b)
    y = trsm(l_fac, rhs, pol, side="left", lower=True, block=block)
    x = trsm(l_fac, y, pol, side="left", lower=True, trans=True, block=block)
    return x[:, 0] if was_vec else x


@span("linalg.refine.residual")
def _residual(a, x, rhs, res_pol) -> np.ndarray:
    """``rhs - A x`` with the product on ``res_pol``."""
    return rhs - emulated_matmul(a, x, res_pol)


@span("linalg.refine_solve")
def refine_solve(a, b, policy=None, *, factor: str = "lu",
                 refine_steps: int = 2, block: int = DEFAULT_BLOCK,
                 residual_policy=None, target_rel_err: float | None = None
                 ) -> tuple[np.ndarray, dict]:
    """Factor, solve, then ``refine_steps`` rounds of iterative refinement.

    The residual r = b - A x runs through ``residual_policy`` (default:
    ``policy`` forced to mode="accurate"), so a fast-mode factorization still
    converges to FP64-grade. ``target_rel_err`` resolves the factorization's
    ``num_moduli`` from A's exponent-range sketch (Ozaki-II policies only;
    see ``PrecisionPolicy.resolve_for``). Returns ``(x, info)`` where
    ``info["residuals"]`` is the relative inf-norm residual history (entry 0
    = before any refinement) and ``info["policy"]`` the resolved spec.
    """
    if factor not in ("lu", "cholesky"):
        raise ValueError(f"factor must be 'lu' or 'cholesky', got {factor!r}")
    pol = resolve_policy(policy)
    a = np.asarray(a, dtype=np.float64)
    rhs, was_vec = _as_cols(b)
    if target_rel_err is not None and pol.supports_plans:
        pol = pol.resolve_for(a, a, target_rel_err=target_rel_err)
    if residual_policy is None:
        res_pol = (dataclasses.replace(pol, mode="accurate")
                   if pol.is_emulated else pol)
    else:
        res_pol = resolve_policy(residual_policy)

    if factor == "lu":
        lu, perm = lu_factor(a, pol, block=block)
        solve = lambda r: lu_solve(lu, perm, r, pol, block=block)  # noqa: E731
    else:
        l_fac = cholesky(a, pol, block=block)
        solve = lambda r: cholesky_solve(l_fac, r, pol, block=block)  # noqa: E731

    scale = np.linalg.norm(a, np.inf) + np.linalg.norm(rhs, np.inf)
    x = solve(rhs)
    residuals = []
    for _ in range(refine_steps):
        r = _residual(a, x, rhs, res_pol)
        residuals.append(float(np.linalg.norm(r, np.inf)) / scale)
        x = x + solve(r)
    r = _residual(a, x, rhs, res_pol)
    residuals.append(float(np.linalg.norm(r, np.inf)) / scale)
    info = {"residuals": residuals, "refine_steps": refine_steps,
            "factor": factor, "scheme": pol.scheme,
            "policy": pol.spec, "residual_policy": res_pol.spec,
            "residual_scheme": res_pol.scheme}
    return (x[:, 0] if was_vec else x), info
