"""Compile rehearsal for TPU v5e: the fused kernels and the core route are
lowered and compiled for a described (not attached) v5e chip, with x64 on
as the program runs. Nothing executes; a pass means XLA:TPU and Mosaic
accept the programs at real tile sizes.

The topology is described inside a module-scoped fixture (never at import):
only the worker that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gemm import _ozmm_2d_raw
from repro.core.moduli import DEFAULT_NUM_MODULI, make_moduli_set
from repro.kernels.fused.kernel import ozmm_fused_parts, ozmm_fused_raw
from repro.kernels.fused.ops import BLOCK_TABLE

jax.config.update("jax_enable_x64", True)

FAMILIES = ("fp8-hybrid", "fp8-karatsuba", "int8")
SCHEME = {"fp8-hybrid": "ozaki2-fp8", "fp8-karatsuba": "ozaki2-karatsuba",
          "int8": "ozaki2-int8"}
M = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kw(family):
    ms = make_moduli_set(family, DEFAULT_NUM_MODULI[family])
    blocks = next(b for max_n, b in BLOCK_TABLE[("tpu", family)] if ms.n <= max_n)
    bm, bn, bk = blocks
    return ms, dict(ms=ms, bm=bm, bn=bn, bk=bk, reconstruct="xla", interpret=False)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", FAMILIES)
def test_fused_raw_compiles_for_v5e(one_chip, family):
    ms, kw = _kw(family)
    frame = _spec(one_chip, (M, M), jnp.int32)
    lmu = _spec(one_chip, (M, 1), jnp.int32)
    lnu = _spec(one_chip, (1, M), jnp.int32)
    compiled = jax.jit(lambda *x: ozmm_fused_raw(*x, **kw)).lower(
        frame, frame, frame, lmu, frame, frame, frame, lnu).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().output_size_in_bytes == ms.n * M * M * 2


@pytest.mark.parametrize("family", FAMILIES)
def test_fused_parts_compiles_for_v5e(one_chip, family):
    ms, kw = _kw(family)
    lmu = _spec(one_chip, (M, 1), jnp.int32)
    lnu = _spec(one_chip, (1, M), jnp.int32)
    if family == "int8":
        parts = _spec(one_chip, (ms.n, M, M), jnp.int8)
    else:
        parts = (_spec(one_chip, (ms.n, M, M), jnp.float8_e4m3fn),) * 3
    compiled = jax.jit(lambda a, b, x, y: ozmm_fused_parts(a, b, x, y, **kw)).lower(
        parts, parts, lmu, lnu).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("family", FAMILIES)
def test_core_route_compiles_for_v5e(one_chip, family):
    # Four moduli: the per-modulus code is unrolled, so more moduli repeat
    # the same ops and only lengthen the compile (chip_smoke.py compiles the
    # default counts on the chip).
    x = _spec(one_chip, (M, M), jnp.float64)
    compiled = jax.jit(lambda a, b: _ozmm_2d_raw(a, b, SCHEME[family], "fast", 4, 11)
                       ).lower(x, x).compile()
    assert compiled.memory_analysis().output_size_in_bytes == M * M * 8
