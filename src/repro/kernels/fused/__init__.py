from .kernel import ozmm_fused_parts, ozmm_fused_quantize
from .ops import (BLOCK_TABLE, BLOCKS_ENV, decompose_raw, ozmm_pallas_fused,
                  ozmm_pallas_fused_prepared, select_blocks)
from .ref import fused_digits_ref, ozmm_fused_ref

__all__ = [
    "ozmm_fused_quantize", "ozmm_fused_parts",
    "ozmm_pallas_fused", "ozmm_pallas_fused_prepared",
    "decompose_raw", "select_blocks", "BLOCK_TABLE", "BLOCKS_ENV",
    "ozmm_fused_ref", "fused_digits_ref",
]
