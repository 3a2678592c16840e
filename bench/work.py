"""The work a call needs, by the paper's count, independent of the program.

``m_n`` is eq. (17) of arXiv:2603.10634 (the number of unit GEMMs an FP8
Ozaki-II product issues per modulus set), ``hpl_flop_count`` the netlib HPL
operation count. Both are copies: the benchmark's yardstick must not move
when the program's schedule does.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def m_n(n: int) -> int:
    """Eq. (17): unit FP8 GEMMs of the hybrid schedule at ``n`` moduli."""
    return 2 * n if n <= 6 else 3 * n - 6


#: scheme -> (MXU the residue products run on, unit GEMMs at n moduli).
#: int8 issues one int8 GEMM per modulus; the FP8 hybrid schedule M_N;
#: Karatsuba three per modulus. On v5e e4m3 runs on the bf16 MXU.
SCHEMES = {
    "ozaki2-fp8": ("bf16", m_n),
    "ozaki2-karatsuba": ("bf16", lambda n: 3 * n),
    "ozaki2-int8": ("int8", lambda n: n),
}


def dgemm_mma(scheme: str, num_moduli: int, m: int, n: int, k: int) -> tuple[str, float]:
    """(MXU, operations) of the residue products of one emulated m x k x n
    GEMM: 2mnk per unit GEMM. The accurate mode's bound GEMM runs outside
    the fused kernel (in the scaling stage) and is not counted."""
    mxu, units = SCHEMES[scheme]
    return mxu, 2.0 * m * n * k * units(num_moduli)


def dgemm_kernel_bytes(num_moduli: int, m: int, n: int, k: int) -> float:
    """Least HBM bytes of the fused kernel: each operand's int32 raw frame
    (three words an element) read once, the int16 digit stack written once."""
    return 12.0 * (m * k + k * n) + 2.0 * num_moduli * m * n


def hpl_flop_count(n: int) -> float:
    """The HPL operation count, 2/3 n^3 + 3/2 n^2."""
    return 2.0 * n ** 3 / 3.0 + 1.5 * n ** 2


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    """Peaks of ``device_kind`` from ``peaks.json``; an unknown kind raises."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def least_seconds(mxu: str, ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Roofline: the larger of ops / MXU peak and bytes / HBM peak, and
    which of the two bounds it."""
    t_ops = ops / peaks["mxu_ops_per_s"][mxu]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
