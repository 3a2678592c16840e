"""Plain reference of the HPL cells: the netlib scaled residual, in host
numpy float64, of every solve of the window.

    ||A x - b||_inf / (eps * (||A||_inf ||x||_inf + ||b||_inf) * n) <= 16
"""
from __future__ import annotations

import math

import numpy as np


def scaled_residual(a: np.ndarray, x: np.ndarray, b: np.ndarray) -> float:
    n = a.shape[0]
    eps = np.finfo(np.float64).eps
    r = np.linalg.norm(a @ x - b, np.inf)
    den = eps * (np.linalg.norm(a, np.inf) * np.linalg.norm(x, np.inf)
                 + np.linalg.norm(b, np.inf)) * n
    return float(r / den)


def compare(answers: dict, config: dict, log) -> dict:
    limit = float(config["limits"]["scaled_residual"])
    pool = answers["pool"]
    res = []
    for i, x in answers["solutions"]:
        r = scaled_residual(pool[i][0], np.asarray(x, np.float64), pool[i][1])
        res.append(r if math.isfinite(r) else math.inf)
    worst = max(res) if res else math.inf
    failed = sum(1 for r in res if not r <= limit)
    log(f"reference: {len(res)} solves; scaled residuals {res!r}")
    return {"correct": bool(res) and failed == 0, "attempted": len(res), "failed": failed,
            "metrics": {},
            "checks": {"scaled_residual": {"value": worst, "limit": limit}}}


def control(a, b):
    """The reference in the program's place, one precision down: LAPACK's
    LU solve in float32 on the host, returned as float64."""
    x = np.linalg.solve(np.asarray(a, np.float32), np.asarray(b, np.float32))
    return x.astype(np.float64)
