"""Plain reference of the DGEMM cells: host numpy float64 on the operands as
the device holds them (``device_put`` already rounds float64 on a TPU).

For every call of the window, each sampled row of C is compared with the
reference row: err = max |C - C_ref| / (|A| |B|), the normalisation of the
paper's accuracy figures. The worst over all calls is the number compared,
and ``dgemm_bits`` = -log2 of it.
"""
from __future__ import annotations

import math

import numpy as np


def row_errors(c_rows, ref: np.ndarray, den: np.ndarray) -> float:
    """max |c - ref| / den over the rows (den = |a| |b|); inf where c is
    not finite."""
    c_rows = np.asarray(c_rows, np.float64)
    if not np.all(np.isfinite(c_rows)):
        return math.inf
    diff = np.abs(c_rows - ref)
    err = np.where(den > 0, diff / np.where(den > 0, den, 1.0), np.where(diff > 0, np.inf, 0.0))
    return float(np.max(err))


def compare(answers: dict, config: dict, log) -> dict:
    limit = float(config["limits"]["max_err"])
    rows = answers["rows"]
    outputs = answers["outputs"]
    refs = {}
    for p in sorted({p for p, _ in outputs}):
        a, b = answers["pairs"][p]
        a_rows, b = np.asarray(a, np.float64)[rows], np.asarray(b, np.float64)
        refs[p] = (a_rows @ b, np.abs(a_rows) @ np.abs(b))
    errs = [row_errors(c, *refs[p]) for p, c in outputs]
    worst = max(errs) if errs else math.inf
    failed = sum(1 for e in errs if not e <= limit)
    log(f"reference: {len(errs)} calls, {len(rows)} rows each; worst max_err {worst!r} "
        f"(2^{math.log2(worst) if 0 < worst < math.inf else worst})")
    bits = -math.log2(max(worst, 2.0 ** -1074)) if worst < math.inf else 0.0
    return {"correct": bool(errs) and failed == 0, "attempted": len(errs), "failed": failed,
            "metrics": {"dgemm_bits": bits},
            "checks": {"max_err": {"value": worst, "limit": limit}}}


def control(a, b):
    """The reference in the program's place, one precision down: the
    product in float32 (at ``HIGHEST``, so a true float32 product on a TPU),
    returned as float64."""
    import jax
    import jax.numpy as jnp
    c = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return c.astype(jnp.float64)
