"""Pallas TPU kernels for the compute hot-spots of the Ozaki-II emulation.

Each kernel package follows the kernel.py (pallas_call + BlockSpec) /
ops.py (jitted wrapper) / ref.py (pure-jnp oracle) layout and is validated
in interpret=True mode against the oracle across shape/dtype sweeps.

Two executors share the packages: the phase-split ``pipeline`` (one
pallas_call per phase; residue parts/products/digits round-trip HBM) and
the ``fused`` schedule (each operand quantized once to residue parts, then
one kernel for residue MMA -> Garner reconstruct — the `+pallas` default
route).
"""
from .common import resolve_interpret, resolve_reconstruct, stack_parts
from .crt_reconstruct import reconstruct_f64, requant_garner, requant_garner_op, requant_garner_ref
from .fp8_gemm import fp8_gemm, fp8_gemm_op, fp8_gemm_ref
from .fused import (BLOCK_TABLE, decompose_raw, fused_digits_ref,
                    ozmm_fused_parts, ozmm_fused_quantize, ozmm_fused_ref,
                    ozmm_pallas_fused, ozmm_pallas_fused_prepared,
                    select_blocks)
from .int8_gemm import int8_gemm, int8_gemm_op, int8_gemm_ref
from .pipeline import ozmm_pallas, ozmm_pallas_prepared
from .quant_residues import decompose_int, quant_residues, quant_residues_op, quant_residues_ref

__all__ = [
    "fp8_gemm", "fp8_gemm_op", "fp8_gemm_ref",
    "int8_gemm", "int8_gemm_op", "int8_gemm_ref",
    "quant_residues", "quant_residues_op", "quant_residues_ref", "decompose_int",
    "requant_garner", "requant_garner_op", "requant_garner_ref", "reconstruct_f64",
    "ozmm_pallas", "ozmm_pallas_prepared",
    "ozmm_pallas_fused", "ozmm_pallas_fused_prepared",
    "ozmm_fused_quantize", "ozmm_fused_parts", "ozmm_fused_ref", "fused_digits_ref",
    "decompose_raw", "select_blocks", "BLOCK_TABLE",
    "resolve_interpret", "resolve_reconstruct", "stack_parts",
]
