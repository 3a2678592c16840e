"""Production mesh construction. A FUNCTION (not module-level constant) so
importing never touches jax device state (dry-run forces 512 host devices
before any jax init; tests/benches must keep seeing the single real device).

Meshes are built with Auto axis types (``jax.make_mesh`` defaults to
Explicit), which the bare-PartitionSpec sharding sites expect.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def use_mesh(mesh):
    """Ambient-mesh context (``jax.set_mesh``). It matters for bare-
    PartitionSpec ``with_sharding_constraint`` sites (e.g. context-parallel
    attention) and for jitted code under a mesh with Explicit axes."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips (v5e pod).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips; the pod
    axis composes with "data" for DP (sharding.py folds them)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for tests on fake host devices."""
    return make_mesh((data, model), ("data", "model"))


#: Axis names of a 2-D block-cyclic process grid (repro.linalg.dist).
GRID_AXES = ("row", "col")


def make_grid_mesh(nprow: int, npcol: int):
    """P x Q process-grid mesh with axes ``("row", "col")`` — the collective
    substrate of the block-cyclic factorizations. Requires ``nprow * npcol``
    visible devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    on CPU); callers that may run on fewer devices should catch the failure
    and fall back to host-mediated collectives (see ``linalg.dist.grid``)."""
    return make_mesh((nprow, npcol), GRID_AXES)
