"""Compile rehearsal for TPU v5e: the fused kernels and the core route are
lowered and compiled for a described (not attached) v5e chip, with x64 on
as the program runs. Nothing executes; a pass means XLA:TPU and Mosaic
accept the programs at real tile sizes.

The topology is described inside a module-scoped fixture (never at import):
only the worker that runs these tests loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gemm import _ozmm_2d_raw
from repro.core.moduli import DEFAULT_NUM_MODULI, make_moduli_set
from repro.kernels.fused.kernel import ozmm_fused_parts, ozmm_fused_quantize
from repro.kernels.fused.ops import BLOCK_TABLE, _fused_from_frames, ozmm_pallas_fused

jax.config.update("jax_enable_x64", True)

FAMILIES = ("fp8-hybrid", "fp8-karatsuba", "int8")
SCHEME = {"fp8-hybrid": "ozaki2-fp8", "fp8-karatsuba": "ozaki2-karatsuba",
          "int8": "ozaki2-int8"}
M = 2048
#: The benchmark's square size: a whole operand's parts are quantized at once.
M_FULL = 8192


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
def test_fused_quantize_compiles_for_v5e(one_chip, family):
    ms, kw = _kw(family)
    frame = _spec(one_chip, (M_FULL, M_FULL), jnp.int32)
    lmu = _spec(one_chip, (M_FULL, 1), jnp.int32)
    compiled = jax.jit(lambda *x: ozmm_fused_quantize(
        *x, ms=ms, blocks=(kw["bm"], kw["bk"]), interpret=False)).lower(
        frame, frame, frame, lmu).compile()
    assert _has_kernel(compiled)
    n_stacks, dtype = (1, jnp.int8) if family == "int8" else (3, jnp.float8_e4m3fn)
    assert ([(o.shape, o.dtype) for o in jax.tree.leaves(compiled.out_info)]
            == [((ms.n, M_FULL, M_FULL), dtype)] * n_stacks)


def test_fused_from_frames_compiles_for_v5e(one_chip):
    """The whole raw-frame route at 8192^3, fp8-hybrid at 12 moduli: both
    operands' parts (2.4 GB each) fit the chip's 16 GB beside the digits."""
    ms, kw = _kw("fp8-hybrid")
    x = _spec(one_chip, (M_FULL, M_FULL), jnp.float64)
    l = _spec(one_chip, (M_FULL,), jnp.int32)
    compiled = jax.jit(lambda a, lmu, b, lnu: _fused_from_frames(
        a, lmu, b, lnu, ms=ms, blocks=(kw["bm"], kw["bn"], kw["bk"]),
        reconstruct="xla", interpret=False)).lower(x, l, x, l).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == M_FULL * M_FULL * 8
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


def test_fused_route_kernels_in_compiled_hlo(one_chip):
    """An accurate ozmm on the fused route compiles to two quantize kernels
    (one per operand) under the ``ozmm.quantize`` scope and one parts kernel,
    each HLO instruction named by its kernel's jit, not by a scope (the
    name a device trace's ops and breakdowns are read by)."""
    a = _spec(one_chip, (512, 512), jnp.float64)
    b = _spec(one_chip, (512, 256), jnp.float64)
    hlo = jax.jit(lambda x, y: ozmm_pallas_fused(
        x, y, mode="accurate", interpret=False, reconstruct="xla",
        blocks=(128, 128, 128))).lower(a, b).compile().as_text()
    kernels = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.match(r"\s*(?:ROOT )?%([\w.]+) =", ln).group(1) for ln in kernels]
    assert sorted(n.split(".")[0] for n in names) == [
        "ozmm_fused_parts", "ozmm_fused_quantize", "ozmm_fused_quantize"], names
    for name, line in zip(names, kernels):
        (op_name,) = re.findall(r'op_name="([^"]*)"', line)
        scope = "quantize" if name.startswith("ozmm_fused_quantize") else "products"
        assert f"/ozmm.{scope}/jit({name.split('.')[0]})/" in op_name, op_name


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
