"""Solves + mixed-precision iterative refinement + the HPL acceptance gate."""
import numpy as np
import pytest

from repro.core import PrecisionPolicy
from repro.linalg import (HPL_THRESHOLD, cholesky, cholesky_solve, hpl_matrix,
                          hpl_scaled_residual, lu_factor, lu_solve,
                          refine_solve, run_hpl)
from repro.testing import graded_matrix, spd_matrix, well_conditioned_matrix

EMU = PrecisionPolicy(scheme="ozaki2-fp8")


def test_lu_solve_multi_rhs(rng):
    a = well_conditioned_matrix(rng, 160)
    b = rng.standard_normal((160, 8))
    lu, perm = lu_factor(a, EMU, block=64)
    x = lu_solve(lu, perm, b, EMU, block=64)
    np.testing.assert_allclose(a @ x, b, rtol=1e-11, atol=1e-11)


def test_cholesky_solve_vector(rng):
    a = spd_matrix(rng, 128, log10_cond=1.0)
    b = rng.standard_normal(128)
    l_fac = cholesky(a, EMU, block=48)
    x = cholesky_solve(l_fac, b, EMU, block=48)
    assert x.shape == (128,)
    np.testing.assert_allclose(a @ x, b, rtol=1e-11, atol=1e-11)


def test_refinement_recovers_fast_mode(rng):
    """Fast-mode factorization + accurate-mode residual refinement must land
    at FP64-grade — the mixed-precision pattern the subsystem exists for."""
    a = graded_matrix(rng, 160, log10_cond=6.0)
    x_true = rng.standard_normal(160)
    b = a @ x_true
    x, info = refine_solve(a, b, PrecisionPolicy(scheme="ozaki2-fp8", mode="fast"),
                           refine_steps=3, block=64)
    res = info["residuals"]
    assert info["residual_scheme"] == "ozaki2-fp8"
    assert res[-1] <= max(1e-14, res[0])  # refinement converged, not diverged
    assert np.linalg.norm(a @ x - b, np.inf) / np.linalg.norm(b, np.inf) <= 1e-9


def test_refine_solve_cholesky_route(rng):
    a = spd_matrix(rng, 128, log10_cond=2.0)
    b = rng.standard_normal(128)
    x, info = refine_solve(a, b, EMU, factor="cholesky", refine_steps=1,
                           block=64)
    assert info["factor"] == "cholesky"
    np.testing.assert_allclose(a @ x, b, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("scheme,n", [("native", 250), ("ozaki2-fp8", 256),
                                      ("ozaki2-fp8", 250)])
def test_hpl_gate(rng, scheme, n):
    """Acceptance criterion: lu_solve + one refinement step on the HPL
    problem scores <= 16 (the standard HPL pass threshold) — at a divisible
    n and a ragged one (250 = 3·64 + 58)."""
    res = run_hpl(n, PrecisionPolicy(scheme=scheme), block=64, refine_steps=1)
    assert res["passed"], res
    assert res["scaled_residual"] <= HPL_THRESHOLD


def test_hpl_scaled_residual_metric():
    """Exact solve scores ~0; a garbage solve fails the gate."""
    a, b = hpl_matrix(64, seed=1)
    x = np.linalg.solve(a, b)
    assert hpl_scaled_residual(a, x, b) <= HPL_THRESHOLD
    assert hpl_scaled_residual(a, np.zeros_like(x), b) > HPL_THRESHOLD


def test_refine_solve_spans_cover_the_solve(tmp_path):
    """Under a CPU profiler trace, ``refine_solve`` at n=256, NB=64 with one
    refinement step puts its phases on the host plane: a panel per block
    step, a U12 TRSM and a trailing update per step but the last, an LU
    solve and a residual per round. Together they cover >= 90% of the call.
    (The shapes and policy of ``test_hpl_gate`` above, whose programs this
    process has compiled already.)"""
    import glob

    import jax
    from jax.profiler import ProfileData

    a, b = hpl_matrix(256, seed=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        refine_solve(a, b, EMU, factor="lu", refine_steps=1, block=64)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    total: dict[str, float] = {}
    count: dict[str, int] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("linalg."):
                        total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                        count[e.name] = count.get(e.name, 0) + 1
    assert count == {"linalg.refine_solve": 1, "linalg.lu.factor": 1,
                     "linalg.lu.panel": 4, "linalg.lu.trsm": 3, "linalg.lu.update": 3,
                     "linalg.lu.solve": 2, "linalg.refine.residual": 2}
    phases = ("linalg.lu.panel", "linalg.lu.trsm", "linalg.lu.update",
              "linalg.lu.solve", "linalg.refine.residual")
    assert sum(total[p] for p in phases) >= 0.9 * total["linalg.refine_solve"]
