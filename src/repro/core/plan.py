"""Plan / quantize / execute split for the Ozaki-II emulated GEMM.

The fused ``ozmm_ozaki2`` pays the whole quantization pipeline (scaling +
trunc/mod residue extraction) on every call. But decomposition is a
per-operand transform (Ozaki et al., arXiv:2504.08009): nothing in the
residue digits of A depends on B in fast mode, and even accurate mode only
needs one bound GEMM between per-operand sketches. This module makes
"quantize once, multiply many" first-class:

  qa = quantize_matrix(A, "lhs", ms, mode="fast")   # plan + quantize
  qb = quantize_matrix(B, "rhs", ms, mode="fast")
  C  = ozmm_prepared(qa, qb)                        # execute (reuses digits)

``QuantizedMatrix`` is a frozen pytree (registered with JAX, so plans pass
through jit/scan/vmap and can live inside parameter trees) holding:

* magnitude sketches — row/col abs-maxima and squared norms (both axes, so a
  plan's transpose and the custom-VJP cotangent GEMMs reuse them);
* fast mode: the scale exponents ``lscale`` and the per-modulus low-precision
  residue ``parts`` — execution reuses these BITWISE;
* accurate mode: the round-up e4m3 cast ``bar`` + its prescale ``lpre``
  (paper eq. (14)). The scale exponents couple the two operands through the
  bound GEMM, so residues are extracted at pairing time from the original
  matrix (retained as ``x``) — the expensive per-operand cast is reused, and
  the result is numerically identical to the fused path.

Reuse contract: fast-mode execution is bitwise-equal to ``ozmm``; accurate-
mode execution reproduces the fused path exactly when paired (same bound
GEMM, same exponents) — see docs/architecture.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import crt, numerics, quantize, scaling
from .moduli import ModuliSet, make_moduli_set

ROLES = ("lhs", "rhs")
MODES = ("fast", "accurate")


@dataclasses.dataclass(frozen=True)
class OperandStats:
    """Magnitude sketches of one operand along both axes (all O(m+n) sized)."""

    row_sq: jax.Array   # (m,) sum of squares along axis 1
    row_max: jax.Array  # (m,) abs-max along axis 1
    col_sq: jax.Array   # (k,) sum of squares along axis 0
    col_max: jax.Array  # (k,) abs-max along axis 0

    def transpose(self) -> "OperandStats":
        return OperandStats(self.col_sq, self.col_max, self.row_sq, self.row_max)


jax.tree_util.register_pytree_node(
    OperandStats,
    lambda s: ((s.row_sq, s.row_max, s.col_sq, s.col_max), None),
    lambda _, leaves: OperandStats(*leaves),
)


@dataclasses.dataclass(frozen=True)
class QuantizedMatrix:
    """A prepared Ozaki-II operand: plan metadata + cached quantization.

    ``role`` is "lhs" (rows scaled, contraction along axis 1) or "rhs"
    (columns scaled, contraction along axis 0). ``family``/``num_moduli``/
    ``mode`` are static (part of the pytree treedef, so jit specializes on
    them); everything else is arrays.
    """

    role: str
    family: str
    num_moduli: int
    mode: str
    x: Optional[jax.Array]           # original float64 operand (see drop_source)
    stats: OperandStats
    lscale: Optional[jax.Array]      # fast mode: int32 scale exponents
    parts: Optional[tuple]           # fast mode: per-modulus residue operands
    lpre: Optional[jax.Array]        # accurate mode: prescale exponents
    bar: Optional[jax.Array]         # accurate mode: round-up e4m3 cast

    # ---- derived (static) ----
    @property
    def ms(self) -> ModuliSet:
        return make_moduli_set(self.family, self.num_moduli)

    @property
    def shape(self) -> tuple[int, ...]:
        if self.x is not None:
            return self.x.shape
        return self.parts[0][0].shape  # residue parts mirror the operand shape

    @property
    def contract_dim(self) -> int:
        """Length of the contraction axis (k of the pairing GEMM)."""
        return self.shape[1] if self.role == "lhs" else self.shape[0]

    def drop_source(self) -> "QuantizedMatrix":
        """Shed the retained f64 source (fast mode only).

        Fast-mode execution reads only ``lscale``/``parts``; long-lived plan
        caches (serve weights) drop ``x`` to avoid holding an f64 copy of
        every weight. The slimmed plan cannot be transposed (backward) or
        used as a native fallback — those need the source.
        """
        if self.mode != "fast":
            raise ValueError("accurate-mode plans need x for pairing-time "
                             "residue extraction; cannot drop it")
        return dataclasses.replace(self, x=None)

    @property
    def scale_stats(self) -> tuple[jax.Array, jax.Array]:
        """(sq_norm, abs_max) along the contraction axis — the fast-mode
        scaling inputs and the accurate-mode clip guard."""
        if self.role == "lhs":
            return self.stats.row_sq, self.stats.row_max
        return self.stats.col_sq, self.stats.col_max


jax.tree_util.register_pytree_node(
    QuantizedMatrix,
    lambda q: ((q.x, q.stats, q.lscale, q.parts, q.lpre, q.bar),
               (q.role, q.family, q.num_moduli, q.mode)),
    lambda aux, leaves: QuantizedMatrix(*aux, *leaves),
)


def operand_stats(x: jax.Array) -> OperandStats:
    """Both-axis magnitude sketches (row/col squared norms and abs-maxima)."""
    ax = jnp.abs(x)
    sq = x * x
    return OperandStats(jnp.sum(sq, axis=1), jnp.max(ax, axis=1),
                        jnp.sum(sq, axis=0), jnp.max(ax, axis=0))


def quantize_matrix(
    x: jax.Array,
    role: str,
    ms: ModuliSet,
    *,
    mode: str = "accurate",
    stats: OperandStats | None = None,
) -> QuantizedMatrix:
    """Build the reusable quantization plan of one operand.

    Fast mode materializes the scale exponents and residue parts (the full
    per-operand pipeline — Cauchy-Schwarz decouples them from the partner).
    Accurate mode materializes the round-up e4m3 cast (the bound-GEMM input);
    residues follow at pairing time. ``stats`` lets callers inject already-
    computed sketches (e.g. the transposed stats of a forward operand inside
    the custom VJP).

    Memory note: the plan retains the f64 source ``x`` — the backward
    transpose plans, accurate-mode residue extraction, and the native
    fallback read it — so a cached plan costs ~2x the operand plus its
    residue parts. Long-lived fast-mode caches (serve weights) call
    ``drop_source()`` to shed it.
    """
    numerics.ensure_x64()  # like ozmm: plans must be built in f64, not f32
    return _quantize_matrix_jit(x, role, ms, mode=mode, stats=stats)


@functools.partial(jax.jit, static_argnames=("role", "ms", "mode"))
@jax.named_scope("ozmm.quantize")
def _quantize_matrix_jit(
    x: jax.Array,
    role: str,
    ms: ModuliSet,
    *,
    mode: str,
    stats: OperandStats | None,
) -> QuantizedMatrix:
    if role not in ROLES:
        raise ValueError(f"role must be one of {ROLES}, got {role!r}")
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    x = x.astype(jnp.float64)
    if x.ndim != 2:
        raise ValueError(f"quantize_matrix needs a 2-D operand, got {x.shape}")
    st = operand_stats(x) if stats is None else stats
    lscale = parts = lpre = bar = None
    if mode == "fast":
        k = x.shape[1] if role == "lhs" else x.shape[0]
        sq, mx = (st.row_sq, st.row_max) if role == "lhs" else (st.col_sq, st.col_max)
        lscale = scaling.fast_exponents(sq, mx, k, ms)
        parts = quantize.quantize_operand(
            x, lscale, 0 if role == "lhs" else 1, ms,
            jnp.asarray(ms.pow2_mod_tables)).parts
    else:
        lpre, bar = scaling.accurate_prescale(x, 1 if role == "lhs" else 0)
    return QuantizedMatrix(role=role, family=ms.family, num_moduli=ms.n,
                           mode=mode, x=x, stats=st, lscale=lscale,
                           parts=parts, lpre=lpre, bar=bar)


def transpose_plan(q: QuantizedMatrix) -> QuantizedMatrix:
    """Plan for ``q.x.T`` in the SAME role, reusing the magnitude sketches.

    The scaling axis flips with the transpose, so residue parts / the bound
    cast are re-derived — but the O(n^2) norm/max reductions are reused. This
    is the backward-pass primitive: dA = dC @ B^T pairs B^T as rhs with the
    forward rhs plan's row statistics.
    """
    if q.x is None:
        raise ValueError("plan source was dropped (drop_source); transposing "
                         "needs the original operand")
    return quantize_matrix(q.x.T, q.role, q.ms, mode=q.mode,
                           stats=q.stats.transpose())


@jax.named_scope("ozmm.products")
def residue_products(qa, qb, ms: ModuliSet) -> list[jax.Array]:
    """Run the low-precision GEMM schedule; return centred residues C'_l.

    ``qa``/``qb`` are per-modulus part tuples (``QuantizedMatrix.parts`` or
    ``quantize.QuantizedOperand``). Schedule per modulus (all error-free,
    DESIGN.md I1): int8 1 GEMM; square p = s^2 3 GEMMs (eq. 12); karatsuba
    3 GEMMs (eq. 8/9).
    """
    pa = qa.parts if hasattr(qa, "parts") else qa
    pb = qb.parts if hasattr(qb, "parts") else qb
    cs: list[jax.Array] = []
    for l, (p, sq, s) in enumerate(zip(ms.ps, ms.is_square, ms.split_s)):
        ap, bp = pa[l], pb[l]
        if ms.family == "int8":
            parts: tuple[jax.Array, ...] = (numerics.matmul_exact_int8(ap[0], bp[0]),)
        elif sq:
            a1, a2 = ap
            b1, b2 = bp
            parts = (
                numerics.matmul_exact_fp8(a1, b2),
                numerics.matmul_exact_fp8(a2, b1),
                numerics.matmul_exact_fp8(a2, b2),
            )
        else:
            a1, a2, a3 = ap
            b1, b2, b3 = bp
            parts = (
                numerics.matmul_exact_fp8(a1, b1),
                numerics.matmul_exact_fp8(a2, b2),
                numerics.matmul_exact_fp8(a3, b3),
            )
        cs.append(crt.combine_residue_product(parts, p, sq, s, ms.family))
    return cs


# ---------------------------------------------------------------------------
# Wire format: plans as collective payloads (distributed HPL panel broadcast)
# ---------------------------------------------------------------------------
#
# A fast-mode plan executes from ``lscale`` + ``parts`` alone, so that IS the
# wire format: per-modulus low-precision residue matrices (1 byte/element
# each) plus one int32 exponent per scaled row/column. The f64 source, the
# magnitude sketches, and the derivable Karatsuba third part (hs = hi + lo,
# exact in e4m3 because |hs| <= 16) are NOT shipped — receivers can execute
# the pairing but not transpose or re-pair the plan. Accurate-mode plans are
# pairing-coupled (the bound GEMM runs between BOTH operands' round-up casts
# and residues are extracted per pairing), so their wire must carry the f64
# source alongside the cast and the contraction-axis maxima — shipping an
# accurate plan costs slightly MORE than the f64 block it replaces. That
# asymmetry is a real property of the scheme, and the distributed-HPL
# benchmark records it (docs/distributed_hpl.md).

#: Wire schema version (bump on layout changes).
PLAN_WIRE_VERSION = 1


def plan_to_wire(q: QuantizedMatrix) -> tuple[dict, list[jax.Array]]:
    """Serialize a plan into ``(header, leaves)`` for a collective.

    ``header`` is a small static dict (the treedef stand-in: schema version +
    the plan's static fields + per-modulus part counts); ``leaves`` is the
    flat list of arrays that actually travels. ``plan_from_wire`` inverts.
    """
    header = {"version": PLAN_WIRE_VERSION, "role": q.role,
              "family": q.family, "num_moduli": q.num_moduli, "mode": q.mode,
              "shape": tuple(int(s) for s in q.shape)}
    if q.mode == "fast":
        leaves: list[jax.Array] = [q.lscale]
        shipped: list[int] = []
        for part in q.parts:
            # Karatsuba (hi, lo, hs): hs is derivable, don't ship it.
            ship = part[:2] if len(part) == 3 else part
            shipped.append(len(ship))
            leaves.extend(ship)
        header["parts_per_modulus"] = tuple(shipped)
        return header, leaves
    # Accurate mode: pairing-time extraction needs the source; the bound GEMM
    # needs the cast + prescale; accurate_exponents needs the contraction-axis
    # abs-maxima of the *scaled* side (row_max for lhs, col_max for rhs).
    mx = q.stats.row_max if q.role == "lhs" else q.stats.col_max
    return header, [q.x, q.lpre, q.bar, mx]


def plan_from_wire(header: dict, leaves: list[jax.Array]) -> QuantizedMatrix:
    """Rebuild an executable plan from a received wire payload.

    The result supports ``ozmm_prepared`` pairing (bitwise-equal to the
    owner's plan) but is execute-only: the dropped source/sketches mean it
    cannot be transposed or re-paired under another mode.
    """
    if header.get("version") != PLAN_WIRE_VERSION:
        raise ValueError(f"plan wire version mismatch: {header.get('version')}"
                         f" != {PLAN_WIRE_VERSION}")
    ms = make_moduli_set(header["family"], header["num_moduli"])
    role, mode = header["role"], header["mode"]
    if mode == "fast":
        lscale, rest = leaves[0], leaves[1:]
        parts: list[tuple[jax.Array, ...]] = []
        i = 0
        for n_ship, sq in zip(header["parts_per_modulus"], ms.is_square):
            part = tuple(rest[i:i + n_ship])
            i += n_ship
            if ms.family != "int8" and not sq:
                hi, lo = part
                # hs = hi + lo is exact: |hs| <= 16 sits in e4m3's integer window
                hs = (hi.astype(jnp.float32)
                      + lo.astype(jnp.float32)).astype(hi.dtype)
                part = (hi, lo, hs)
            parts.append(part)
        return QuantizedMatrix(role=role, family=ms.family, num_moduli=ms.n,
                               mode=mode, x=None, stats=None,
                               lscale=lscale, parts=tuple(parts),
                               lpre=None, bar=None)
    x, lpre, bar, mx = leaves
    st = (OperandStats(None, mx, None, None) if role == "lhs"
          else OperandStats(None, None, None, mx))
    return QuantizedMatrix(role=role, family=ms.family, num_moduli=ms.n,
                           mode=mode, x=x, stats=st, lscale=None, parts=None,
                           lpre=lpre, bar=bar)


def wire_bytes(leaves) -> int:
    """Payload size of a wire leaf list (what one broadcast hop moves)."""
    return int(sum(l.size * l.dtype.itemsize for l in leaves))


def _check_pair(qa: QuantizedMatrix, qb: QuantizedMatrix) -> ModuliSet:
    if qa.role != "lhs" or qb.role != "rhs":
        raise ValueError(f"ozmm_prepared needs (lhs, rhs), got ({qa.role}, {qb.role})")
    if (qa.family, qa.num_moduli, qa.mode) != (qb.family, qb.num_moduli, qb.mode):
        raise ValueError(
            "operand plans disagree: "
            f"({qa.family}, {qa.num_moduli}, {qa.mode}) vs "
            f"({qb.family}, {qb.num_moduli}, {qb.mode})")
    if qa.shape[1] != qb.shape[0]:
        raise ValueError(f"contraction mismatch {qa.shape} @ {qb.shape}")
    return qa.ms


@jax.named_scope("ozmm.scaling")
def pair_exponents(qa: QuantizedMatrix, qb: QuantizedMatrix):
    """Scale exponents (lmu, lnu) of the pairing — cached in fast mode; the
    single bound GEMM between the cached round-up casts (paper §III-E) in
    accurate mode. Shared by the core executor and the Pallas pipeline."""
    ms = _check_pair(qa, qb)
    if qa.mode == "fast":
        return qa.lscale, qb.lscale
    k = qa.x.shape[1]
    cbar = scaling.bound_gemm_inflate(numerics.matmul_exact_fp8(qa.bar, qb.bar), k)
    lmu = scaling.accurate_exponents(jnp.max(cbar, axis=1), qa.lpre,
                                     qa.stats.row_max, ms)
    lnu = scaling.accurate_exponents(jnp.max(cbar, axis=0), qb.lpre,
                                     qb.stats.col_max, ms)
    return lmu, lnu


def pair_scales(qa: QuantizedMatrix, qb: QuantizedMatrix):
    """Resolve the pairing: returns (lmu, lnu, parts_a, parts_b).

    Fast mode returns the cached exponents and residues unchanged (bitwise
    reuse). Accurate mode derives the exponents via the bound GEMM and
    extracts residues for this pairing.
    """
    ms = _check_pair(qa, qb)
    lmu, lnu = pair_exponents(qa, qb)
    if qa.mode == "fast":
        return lmu, lnu, qa.parts, qb.parts
    pow2 = jnp.asarray(ms.pow2_mod_tables)
    with jax.named_scope("ozmm.quantize"):
        parts_a = quantize.quantize_operand(qa.x, lmu, 0, ms, pow2).parts
        parts_b = quantize.quantize_operand(qb.x, lnu, 1, ms, pow2).parts
    return lmu, lnu, parts_a, parts_b


def ozmm_prepared(qa: QuantizedMatrix, qb: QuantizedMatrix) -> jax.Array:
    """Execute the emulated GEMM from two prepared operands.

    Numerically identical to ``ozmm_ozaki2(a, b)`` — bitwise in fast mode
    (the digits are the cached ones), exactly reproduced in accurate mode
    (same bound GEMM, same exponents, same residues).
    """
    numerics.ensure_x64()
    return _ozmm_prepared_jit(qa, qb)


@jax.jit
def _ozmm_prepared_jit(qa: QuantizedMatrix, qb: QuantizedMatrix) -> jax.Array:
    ms = _check_pair(qa, qb)
    lmu, lnu, parts_a, parts_b = pair_scales(qa, qb)
    cs = residue_products(parts_a, parts_b, ms)
    digits = crt.garner_digits(cs, ms)
    return crt.reconstruct(digits, ms, lmu, lnu)
