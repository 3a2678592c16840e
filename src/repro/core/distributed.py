"""Distributed emulated GEMM (the paper's technique at multi-pod scale).

Two sharding strategies, mirroring the paper's §IV-C blocking discussion:

* ``ozmm_mn_sharded`` — m/n-blocking mapped onto the mesh: device (i, j)
  holds a row-block of A and a column-block of B (k unsharded, as the paper
  recommends: small-k GEMMs underutilise MMA units) and runs a fully local
  emulation. No communication inside the GEMM at all.

* ``ozmm_k_sharded`` — k-contraction sharding. Exactness survives
  distribution because modular reduction is linear: each device computes
  centred residue partial products on its k-slice, the int32 partials are
  ``psum``-ed across the k axis, and the sum is re-reduced mod p. The
  reduction moves N int32 matrices (4N bytes/element) instead of one FP64
  matrix — i.e. *exact* k-sharding costs ~6x the collective bytes of a
  (non-exact) f64 reduction at N=12. This asymmetric cost is a genuine
  finding of mapping the scheme to meshes; the roofline section quantifies
  it, and mn-sharding is the default for that reason.

Scaling vectors need global row/column statistics; fast mode psums the
squared norms (an (m,)+(n,) vector reduction), accurate mode psums the f32
bound-GEMM partials before the (1 + k 2^-24) inflation (the Rump bound holds
for any summation order, including the cross-device tree).

Both strategies are thin drivers over ``core.plan``: the local shard work is
quantize-both-operands + ``residue_products`` + reconstruction, with the
scaling statistics swapped for globally-reduced ones where the sharding
demands it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import crt, numerics, quantize, scaling
from .moduli import DEFAULT_NUM_MODULI, make_moduli_set
from .plan import (QuantizedMatrix, ozmm_prepared, plan_from_wire,
                   plan_to_wire, quantize_matrix, residue_products,
                   wire_bytes)

from repro.launch.mesh import use_mesh


def ozmm_mn_sharded(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    m_axis: str = "data",
    n_axis: str = "model",
    family: str = "fp8-hybrid",
    num_moduli: int | None = None,
    mode: str = "accurate",
) -> jax.Array:
    """Emulated GEMM with A row-sharded over ``m_axis`` and B column-sharded
    over ``n_axis``; each device emulates its (m_blk, n_blk) output block."""
    if num_moduli is None:
        num_moduli = DEFAULT_NUM_MODULI[family]
    ms = make_moduli_set(family, num_moduli)

    def local_fn(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        # Fully local: the shard is a complete emulation problem.
        qa = quantize_matrix(a_loc, "lhs", ms, mode=mode)
        qb = quantize_matrix(b_loc, "rhs", ms, mode=mode)
        return ozmm_prepared(qa, qb)

    fn = jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(m_axis, None), P(None, n_axis)),
        out_specs=P(m_axis, n_axis),
    ))
    with use_mesh(mesh):  # Explicit mesh axes need the ambient mesh
        return fn(a.astype(jnp.float64), b.astype(jnp.float64))


def ozmm_k_sharded(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    k_axis: str = "model",
    family: str = "fp8-hybrid",
    num_moduli: int | None = None,
    mode: str = "fast",
) -> jax.Array:
    """Emulated GEMM with the contraction dimension sharded over ``k_axis``.

    Exact: residue partials are psum-ed in int32 then re-reduced mod p. The
    psum of D centred int16-range residue GEMM partials stays well inside
    int32 (D * p_max/2 * ... bounded by D * 2^31/D headroom; |partial C'_l|
    <= p_max/2 <= 544 pre-psum, so the sum <= 544 * D < 2^20 for D <= 2048).

    Fast mode psums the squared norms / all-gathers the abs-maxima; accurate
    mode all-gathers the per-row/col maxima (so every shard casts with the same global
    prescale), runs the round-up bound GEMM on its k-slice, and psums the f32
    partials BEFORE the (1 + k 2^-24) inflation — the Rump bound holds for
    any summation order, with the global (unsharded) k in the inflation.
    """
    if num_moduli is None:
        num_moduli = DEFAULT_NUM_MODULI[family]
    ms = make_moduli_set(family, num_moduli)
    pow2 = ms.pow2_mod_tables
    k = a.shape[1]

    def gmax(x):  # exact cross-shard max; XLA:TPU has no float64 pmax
        return jnp.max(jax.lax.all_gather(x, k_axis), axis=0)

    def local_fn(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        # --- global scaling statistics across the k shards ---
        amax = gmax(jnp.max(jnp.abs(a_loc), axis=1))
        bmax = gmax(jnp.max(jnp.abs(b_loc), axis=0))
        if mode == "fast":
            sq_a = jax.lax.psum(jnp.sum(a_loc * a_loc, axis=1), k_axis)
            sq_b = jax.lax.psum(jnp.sum(b_loc * b_loc, axis=0), k_axis)
            lmu = scaling.fast_exponents(sq_a, amax, k, ms)
            lnu = scaling.fast_exponents(sq_b, bmax, k, ms)
        else:
            # Accurate mode (paper §III-E, distributed): the prescale uses the
            # GLOBAL per-row/col maxima so every shard's round-up cast shares
            # one exponent frame and the f32 partial GEMMs are summable.
            lmu2, abar = scaling.accurate_prescale(a_loc, 1, abs_max=amax)
            lnu2, bbar = scaling.accurate_prescale(b_loc, 0, abs_max=bmax)
            cbar_part = numerics.matmul_exact_fp8(abar, bbar)
            cbar = scaling.bound_gemm_inflate(
                jax.lax.psum(cbar_part, k_axis), k)
            lmu = scaling.accurate_exponents(jnp.max(cbar, axis=1), lmu2, amax, ms)
            lnu = scaling.accurate_exponents(jnp.max(cbar, axis=0), lnu2, bmax, ms)

        qa = quantize.quantize_operand(a_loc, lmu, 0, ms, jnp.asarray(pow2))
        qb = quantize.quantize_operand(b_loc, lnu, 1, ms, jnp.asarray(pow2))
        cs_partial = residue_products(qa, qb, ms)  # centred per-device
        cs = [
            numerics.centered_mod(jax.lax.psum(c, k_axis), p)
            for c, p in zip(cs_partial, ms.ps)
        ]
        digits = crt.garner_digits(cs, ms)
        return crt.reconstruct(digits, ms, lmu, lnu)

    # Jitted: called eagerly, shard_map would dispatch the body op by op.
    fn = jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, k_axis), P(k_axis, None)),
        out_specs=P(),
        # The output IS replicated (gmax/psum make every shard's statistics
        # and residues identical), but the checker cannot see through the
        # all_gather -> max in gmax.
        check_vma=False,
    ))
    with use_mesh(mesh):  # Explicit mesh axes need the ambient mesh
        return fn(a.astype(jnp.float64), b.astype(jnp.float64))


# ---------------------------------------------------------------------------
# Collectives for the block-cyclic factorizations (repro.linalg.dist)
# ---------------------------------------------------------------------------


def argmax_allreduce(vals, idxs, mesh: Mesh, axis: str) -> tuple[float, int]:
    """All-reduce argmax over one mesh axis with smallest-index tie-break.

    Each rank along ``axis`` contributes its local pivot candidate
    ``(value, global_index)``; every rank gets back the winning pair. Ties on
    the value go to the smallest index — the same first-occurrence semantics
    as ``np.argmax``/``jnp.argmax`` over the column in global row order, which
    is what keeps distributed pivot choices identical to the single-device
    factorization's. Runs as a real ``shard_map`` collective (``all_gather``
    along ``axis``); axes of ``mesh`` not named are treated as replicated.
    """
    size = mesh.shape[axis]
    vals = jnp.asarray(vals, jnp.float64)
    idxs = jnp.asarray(idxs, jnp.int32)
    if vals.shape != (size,) or idxs.shape != (size,):
        raise ValueError(f"expected one candidate per rank along {axis!r} "
                         f"({size}), got {vals.shape}/{idxs.shape}")
    m, win = _argmax_allreduce_fn(mesh, axis)(vals, idxs)
    return float(m), int(win)


@functools.lru_cache(maxsize=None)
def _argmax_allreduce_fn(mesh: Mesh, axis: str):
    """Build + cache the jitted collective per (mesh, axis): the pivot search
    calls it once per panel column, so retracing per call would dominate."""

    def local_fn(v, i):
        v = jax.lax.all_gather(v, axis, tiled=True)
        i = jax.lax.all_gather(i, axis, tiled=True)
        m = jnp.max(v)
        win = jnp.min(jnp.where(v == m, i, jnp.iinfo(jnp.int32).max))
        return m, win

    # check_vma=False: the outputs ARE replicated (every rank gathers the same
    # candidates), but the static replication checker cannot see through the
    # all_gather -> max/min chain.
    return jax.jit(jax.shard_map(local_fn, mesh=mesh, in_specs=(P(axis), P(axis)),
                                 out_specs=(P(), P()), check_vma=False))


def argmax_allreduce_host(vals, idxs) -> tuple[float, int]:
    """Host fallback with identical semantics, for grids larger than the
    device count (benchmark sweeps on a single real device)."""
    import numpy as np

    vals = np.asarray(vals, dtype=float)
    idxs = np.asarray(idxs)
    m = vals.max()
    return float(m), int(idxs[vals == m].min())


def broadcast_plan(q: QuantizedMatrix, devices=()) -> tuple[list[QuantizedMatrix], int]:
    """One-to-many panel broadcast with residue plans as the wire format.

    The owner serializes once (``plan_to_wire``); the low-precision leaves are
    moved to each receiver device and deserialized there into an execute-only
    plan (bitwise-equal pairing). Returns ``(received_plans, payload_bytes)``
    where ``payload_bytes`` is the size of ONE wire copy — multiply by hops
    for a given broadcast topology. With no ``devices`` (single-device grids,
    host simulation) the payload is deserialized in place, so the bytes
    accounting still reflects what a real interconnect would move.
    """
    header, leaves = plan_to_wire(q)
    payload = wire_bytes(leaves)
    if not devices:
        return [plan_from_wire(header, leaves)], payload
    received = []
    for d in devices:
        placed = [jax.device_put(leaf, d) for leaf in leaves]
        received.append(plan_from_wire(header, placed))
    return received, payload


def broadcast_f64(x, devices=()) -> tuple[list[jax.Array], int]:
    """The baseline panel broadcast: the raw f64 block travels and every
    receiver re-quantizes locally. Returns ``(received, payload_bytes)``."""
    x = jnp.asarray(x, jnp.float64)
    payload = int(x.size * x.dtype.itemsize)
    if not devices:
        return [x], payload
    return [jax.device_put(x, d) for d in devices], payload


def collective_bytes_per_output_elem(family: str, num_moduli: int, strategy: str) -> int:
    """Roofline helper: reduction bytes per output element inside the GEMM."""
    if strategy == "mn":
        return 0
    if strategy == "k":
        return 4 * num_moduli  # int32 psum per modulus
    raise ValueError(strategy)
