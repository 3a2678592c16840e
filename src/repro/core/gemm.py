"""Public emulated-GEMM API: ``ozmm``, prepared-operand entry points, and the
policy router ``backend_matmul``.

Precision is expressed as a :class:`repro.precision.PrecisionPolicy` — a
frozen (scheme, mode, num_moduli, num_slices, backend) selection with a
compact spec string (``"ozaki2-fp8/accurate@8"``). Every entry point here
takes ``policy=`` (a policy, a spec string, or None to resolve from the
``repro.precision`` context stack); the legacy kwarg-threaded form
``ozmm(a, b, scheme=..., mode=..., num_moduli=...)`` and the old
``GemmConfig`` object still route identically but emit
``ReproDeprecationWarning``.

Operand reuse (core.plan): ``prepare_operand(x, role, policy)`` builds a
``QuantizedMatrix`` once; ``backend_matmul`` accepts prepared operands on
either side and skips the cached quantization phases. The custom VJP keeps
the forward plans as residuals so the backward cotangent GEMMs reuse the
forward magnitude sketches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs.metrics import metrics_enabled, record_gemm_call
from repro.precision.context import resolve_policy
from repro.precision.policy import GemmConfig  # noqa: F401  (re-export)
from repro.precision.policy import (DEFAULT_NUM_SLICES, OZAKI2_FAMILY,
                                    PrecisionPolicy, SCHEMES,
                                    warn_legacy_kwargs)

from . import numerics, plan
from .moduli import DEFAULT_NUM_MODULI, make_moduli_set
from .ozaki1 import ozmm_ozaki1_fp8
from .ozaki2 import ozmm_ozaki2
from .plan import QuantizedMatrix, ozmm_prepared, quantize_matrix, transpose_plan

#: ``ozmm``'s own fallback when neither a per-call policy nor a context is
#: set: the paper's flagship operating point (matches the legacy default).
OZMM_DEFAULT_POLICY = PrecisionPolicy(scheme="ozaki2-fp8", mode="accurate")


def _check_plan_matches_policy(q: QuantizedMatrix, pol: PrecisionPolicy) -> None:
    """A prepared operand must have been built for the requested scheme —
    silently executing a plan at a different scheme/mode than the caller's
    policy asked for would change accuracy without any signal."""
    want = (OZAKI2_FAMILY.get(pol.scheme), pol.mode)
    got = (q.family, q.mode)
    if want != got:
        raise ValueError(
            f"prepared operand was quantized for {got}, but the policy "
            f"requests {want} (scheme={pol.scheme!r}); re-prepare under the "
            "matching policy")
    if pol.num_moduli is not None and pol.num_moduli != q.num_moduli:
        raise ValueError(
            f"prepared operand has {q.num_moduli} moduli, policy requests "
            f"{pol.num_moduli}")


def prepare_operand(x, role: str, policy=None):
    """Quantize ``x`` once for reuse across GEMMs (see core.plan).

    ``policy`` may be a ``PrecisionPolicy``, a spec string, or None (resolve
    from the precision context). Returns a ``QuantizedMatrix`` for Ozaki-II
    schemes; for schemes with no plan support (native, ozaki1) the input is
    returned unchanged so callers can be scheme-agnostic. Already-prepared
    operands pass through (after a scheme/mode consistency check).
    """
    pol = resolve_policy(policy)
    if isinstance(x, QuantizedMatrix):
        if pol.supports_plans:
            _check_plan_matches_policy(x, pol)
        return x
    if not pol.supports_plans:
        return x
    numerics.ensure_x64()
    return quantize_matrix(jnp.asarray(x, jnp.float64), role, pol.moduli_set(),
                           mode=pol.mode)


#: Reverse of OZAKI2_FAMILY, for labeling prepared-plan executions (plans
#: carry the family; metrics are keyed by the user-facing scheme name).
_FAMILY_SCHEME = {fam: sch for sch, fam in OZAKI2_FAMILY.items()}


def _record_emulated(scheme: str, mode: str, family: str,
                     num_moduli: int | None, a_shape, b_shape) -> None:
    """Gated GEMM-call metric for one host-level emulated-GEMM entry.

    Leading batch dims fold into m (a vmapped batch of B GEMMs does B×
    the MMA work of one). No-op unless obs metrics are enabled.
    """
    if not metrics_enabled():
        return
    m = 1
    for d in a_shape[:-1]:
        m *= int(d)
    record_gemm_call(scheme, mode, family,
                     num_moduli or DEFAULT_NUM_MODULI[family],
                     m, int(a_shape[-1]), int(b_shape[-1]))


def _ozmm_2d_raw(a: jax.Array, b: jax.Array, scheme: str, mode: str,
                 num_moduli: int | None, num_slices: int) -> jax.Array:
    if scheme in OZAKI2_FAMILY:
        return ozmm_ozaki2(a, b, family=OZAKI2_FAMILY[scheme],
                           num_moduli=num_moduli, mode=mode)
    if scheme == "ozaki1-fp8":
        return ozmm_ozaki1_fp8(a, b, num_slices=num_slices, mode=mode)
    if scheme == "native":
        return jnp.matmul(a.astype(jnp.float64), b.astype(jnp.float64),
                          preferred_element_type=jnp.float64)
    raise ValueError(f"unknown scheme {scheme!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _ozmm_2d(a, b, scheme, mode, num_moduli, num_slices):
    """Differentiable emulated GEMM. Naive autodiff would differentiate
    trunc/mod (zero a.e.); the true derivative of an exact-product emulation
    is the matmul derivative, and the cotangent products are themselves
    DGEMMs — so the backward pass ALSO runs through the paper's scheme
    (dC -> dA = dC @ B^T, dB = A^T @ dC, both emulated)."""
    return _ozmm_2d_raw(a, b, scheme, mode, num_moduli, num_slices)


def _ozmm_fwd(a, b, scheme, mode, num_moduli, num_slices):
    if scheme in OZAKI2_FAMILY:
        family = OZAKI2_FAMILY[scheme]
        ms = make_moduli_set(family, num_moduli or DEFAULT_NUM_MODULI[family])
        qa = quantize_matrix(a.astype(jnp.float64), "lhs", ms, mode=mode)
        qb = quantize_matrix(b.astype(jnp.float64), "rhs", ms, mode=mode)
        # Keep the plans (not the raw matrices) as residuals: backward reuses
        # the forward magnitude sketches. Empty carriers keep the cotangent
        # dtypes (inputs may be f32/bf16 from the model layers).
        res = (qa, qb, jnp.empty((0,), a.dtype), jnp.empty((0,), b.dtype))
        return ozmm_prepared(qa, qb), res
    return _ozmm_2d_raw(a, b, scheme, mode, num_moduli, num_slices), (a, b)


def _ozmm_bwd(scheme, mode, num_moduli, num_slices, res, g):
    if scheme in OZAKI2_FAMILY:
        qa, qb, dta, dtb = res
        ms = qa.ms
        g64 = g.astype(jnp.float64)
        # The cotangent appears in BOTH backward GEMMs; sketch it once.
        gstats = plan.operand_stats(g64)
        qg_l = quantize_matrix(g64, "lhs", ms, mode=mode, stats=gstats)
        qg_r = quantize_matrix(g64, "rhs", ms, mode=mode, stats=gstats)
        # dA = dC @ B^T, dB = A^T @ dC: the transposed plans reuse the forward
        # row/col sketches (the scaling axis flips with the transpose).
        ga = ozmm_prepared(qg_l, transpose_plan(qb))
        gb = ozmm_prepared(transpose_plan(qa), qg_r)
        return ga.astype(dta.dtype), gb.astype(dtb.dtype)
    a, b = res
    ga = _ozmm_2d_raw(g, b.T, scheme, mode, num_moduli, num_slices)
    gb = _ozmm_2d_raw(a.T, g, scheme, mode, num_moduli, num_slices)
    return ga.astype(a.dtype), gb.astype(b.dtype)


_ozmm_2d.defvjp(_ozmm_fwd, _ozmm_bwd)


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "num_moduli", "num_slices"))
def _ozmm_core(a, b, scheme: str, mode: str, num_moduli: int | None,
               num_slices: int) -> jax.Array:
    if a.ndim == b.ndim == 2:
        return _ozmm_2d(a, b, scheme, mode, num_moduli, num_slices)
    if a.ndim != b.ndim:
        raise ValueError(f"rank mismatch {a.shape} @ {b.shape}")
    fn = functools.partial(_ozmm_2d, scheme=scheme, mode=mode,
                           num_moduli=num_moduli, num_slices=num_slices)
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a, b)


def ozmm(a, b, policy=None, *, scheme: str | None = None, mode: str | None = None,
         num_moduli: int | None = None, num_slices: int | None = None) -> jax.Array:
    """Emulated FP64 matmul. Supports (..., m, k) @ (..., k, n) with matching
    leading batch dims (vmapped over them); requires x64.

    ``policy`` is a ``PrecisionPolicy``, a spec string like
    ``"ozaki2-fp8/fast@8"``, or None — then the precision context
    (``use_policy`` / ``set_default_policy``) decides, falling back to the
    paper's flagship ``ozaki2-fp8/accurate``. The kwarg-threaded legacy form
    (``scheme=``/``mode=``/``num_moduli=``/``num_slices=``) still works but
    is deprecated.

    Either side may be a prepared ``QuantizedMatrix`` (2-D only): its cached
    quantization is reused and the other side is quantized on the fly. In
    that case the PLAN is the spec — the plan's family/mode/num_moduli are
    used and the policy is ignored (indistinguishable from its default
    here). Callers that carry an explicit policy should use
    ``backend_matmul``, which validates prepared operands against it.

    Ozaki-II policies route to the Pallas kernel path when the backend
    resolves to ``"pallas"`` — explicitly via ``+pallas``, or automatically
    on TPU under ``backend="auto"``. The default kernel is the fused
    single-pallas_call schedule (``ozmm_pallas_fused``, bitwise-equal
    digits); ``+unfused`` selects the phase-split pipeline. An explicit
    ``+pallas`` is forward-only (the guard below raises under autodiff);
    the auto-derived route falls back to core-backed cotangent GEMMs so
    training still differentiates.

    On TPU, XLA stores and computes float64 as a pair of float32s: about
    48 significand bits and float32's exponent range. ``device_put`` already
    rounds inputs to that format (it turns 2^1000 into inf and subnormals
    into 0), and entries below about 2^-78 lose their low half in device
    arithmetic. Results meet the FP64-grade bounds only where the inputs and
    the products lie within about [2^-78, 2^126] in magnitude (zeros
    aside); outside, they are not FP64 results, and nothing raises.
    ``chip_smoke.py`` reports this edge on every run.
    """
    numerics.ensure_x64()
    if (scheme is not None or mode is not None or num_moduli is not None
            or num_slices is not None):
        if policy is not None:
            raise TypeError("pass either policy= or the legacy "
                            "scheme/mode/num_moduli/num_slices kwargs, not both")
        warn_legacy_kwargs("ozmm(a, b, ...)",
                           "ozmm(a, b, 'ozaki2-fp8/accurate@8')")
        pol = PrecisionPolicy(
            scheme=scheme if scheme is not None else "ozaki2-fp8",
            mode=mode if mode is not None else "accurate",
            num_moduli=num_moduli,
            num_slices=num_slices if num_slices is not None else DEFAULT_NUM_SLICES)
    else:
        pol = resolve_policy(policy, fallback=OZMM_DEFAULT_POLICY)
    if isinstance(a, QuantizedMatrix) or isinstance(b, QuantizedMatrix):
        return _ozmm_prepared_mixed(a, b, pol)
    if pol.scheme in OZAKI2_FAMILY:
        _record_emulated(pol.scheme, pol.mode, OZAKI2_FAMILY[pol.scheme],
                         pol.num_moduli, a.shape, b.shape)
    if _resolve_backend(pol) == "pallas":
        return _ozmm_pallas_guarded(a, b, pol)
    return _ozmm_core(a, b, pol.scheme, pol.mode, pol.num_moduli, pol.num_slices)


def _resolve_backend(pol: PrecisionPolicy, device: str | None = None) -> str:
    """Concrete executor for a policy: ``"core"`` or ``"pallas"``.

    ``backend="auto"`` picks the fused Pallas kernels for Ozaki-II schemes
    when the accelerator actually has a kernel backend (TPU) and the core
    jnp path elsewhere (CPU CI, GPU) — the ROADMAP "default route" flip.
    ``device`` injects the platform for tests; None reads the live backend.
    """
    if pol.backend != "auto":
        return pol.backend
    if pol.scheme not in OZAKI2_FAMILY:
        return "core"
    device = jax.default_backend() if device is None else device
    return "pallas" if device == "tpu" else "core"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ozmm_pallas_guarded(a, b, pol):
    """Kernel-path forward (fused schedule by default,
    phase-split pipeline under ``+unfused``). The quantization kernels are
    trunc/mod (zero-derivative a.e.), so naive autodiff through them would
    silently return all-zero gradients — this custom VJP intercepts:
    an EXPLICIT ``+pallas`` policy is forward-only and raises; the
    auto-derived TPU route computes the cotangent GEMMs on the core path
    (the same emulated-DGEMM backward as ``_ozmm_bwd``)."""
    from repro.kernels import ozmm_pallas, ozmm_pallas_fused  # lazy

    fn = ozmm_pallas_fused if pol.fused else ozmm_pallas
    return fn(a, b, family=OZAKI2_FAMILY[pol.scheme],
              num_moduli=pol.num_moduli, mode=pol.mode,
              interpret=pol.interpret)


def _ozmm_pallas_fwd(a, b, pol):
    return _ozmm_pallas_guarded(a, b, pol), (a, b)


def _ozmm_pallas_bwd(pol, res, g):
    if pol.backend == "pallas":  # explicitly requested: refuse, don't reroute
        kernel = "ozmm_pallas_fused" if pol.fused else "ozmm_pallas"
        raise NotImplementedError(
            f"policy {pol.spec!r}: backend='pallas' is forward-only — "
            f"{kernel} has no VJP (serving/inference); differentiate "
            "through the core backend (or backend='auto', which routes "
            "the backward cotangent GEMMs onto the core path) instead")
    a, b = res
    g64 = g.astype(jnp.float64)
    ga = _ozmm_2d_raw(g64, b.astype(jnp.float64).T, pol.scheme, pol.mode,
                      pol.num_moduli, pol.num_slices)
    gb = _ozmm_2d_raw(a.astype(jnp.float64).T, g64, pol.scheme, pol.mode,
                      pol.num_moduli, pol.num_slices)
    return ga.astype(a.dtype), gb.astype(b.dtype)


_ozmm_pallas_guarded.defvjp(_ozmm_pallas_fwd, _ozmm_pallas_bwd)


def _ozmm_prepared_mixed(a, b, pol: PrecisionPolicy) -> jax.Array:
    """Execute with >= 1 prepared operand, quantizing the raw side on the fly.

    When the policy's backend resolves to ``"pallas"`` the pairing runs on
    the kernel path — the fused MMA+reconstruct kernel by default
    (``ozmm_pallas_fused_prepared``), the phase-split pipeline under
    ``+unfused``; otherwise the core path. Gradients do not flow through
    prepared operands (plans are data, not differentiable inputs); use
    plain ``ozmm`` when you need the VJP.
    """
    anchor = a if isinstance(a, QuantizedMatrix) else b
    ms = anchor.ms
    _record_emulated(_FAMILY_SCHEME[ms.family], anchor.mode, ms.family,
                     ms.n, a.shape, b.shape)
    qa = a if isinstance(a, QuantizedMatrix) else quantize_matrix(
        jnp.asarray(a, jnp.float64), "lhs", ms, mode=anchor.mode)
    qb = b if isinstance(b, QuantizedMatrix) else quantize_matrix(
        jnp.asarray(b, jnp.float64), "rhs", ms, mode=anchor.mode)
    if _resolve_backend(pol) == "pallas":
        from repro.kernels import (ozmm_pallas_fused_prepared,  # lazy
                                   ozmm_pallas_prepared)

        fn = ozmm_pallas_fused_prepared if pol.fused else ozmm_pallas_prepared
        return fn(qa, qb, interpret=pol.interpret)
    return ozmm_prepared(qa, qb)


def plan_source(q: QuantizedMatrix) -> jax.Array:
    """The retained f64 source of a plan, for native-policy fallbacks.
    Slimmed plans (``drop_source``, e.g. serve fast-mode weight caches) have
    none — reaching this under a native policy means the caller's policy
    resolution drifted from the policy the plan was built for."""
    if q.x is None:
        raise ValueError(
            "prepared operand dropped its f64 source (drop_source), so it "
            "cannot run under a native policy; execute it under the "
            f"emulated policy it was quantized for ({q.family}/{q.mode}) or "
            "re-prepare without drop_source")
    return q.x


def backend_matmul(a, b, policy=None,
                   preferred_dtype: jnp.dtype | None = None) -> jax.Array:
    """Matmul router used by every repro.models layer.

    ``policy`` resolves like everywhere else (policy object | spec string |
    None -> context, defaulting to native). native: plain matmul in the layer
    compute dtype (production bf16 path). emulated: inputs are promoted to
    f64, the paper's scheme runs, and the result is returned in f64 (callers
    may cast down). Either side may be a prepared ``QuantizedMatrix``
    (weight-residue caches, panel reuse): the cached phases are skipped.
    """
    pol = resolve_policy(policy)
    a_prepared = isinstance(a, QuantizedMatrix)
    b_prepared = isinstance(b, QuantizedMatrix)
    if a_prepared or b_prepared:
        if not pol.is_emulated:
            # Prepared operands carry their f64 source; fall back to native.
            a = plan_source(a) if a_prepared else a
            b = plan_source(b) if b_prepared else b
            return jnp.matmul(a, b, preferred_element_type=preferred_dtype)
        for q in (a, b):
            if isinstance(q, QuantizedMatrix):
                _check_plan_matches_policy(q, pol)
        out = _ozmm_prepared_mixed(a, b, pol)
        return out if preferred_dtype is None else out.astype(preferred_dtype)
    if not pol.is_emulated:
        return jnp.matmul(a, b, preferred_element_type=preferred_dtype)
    out = ozmm(a, b, pol)
    return out if preferred_dtype is None else out.astype(preferred_dtype)


def default_num_moduli(scheme: str) -> int | None:
    """Paper-default decomposition arity for ``scheme``.

    Ozaki-II schemes return their CRT modulus count; ``"ozaki1-fp8"`` returns
    its slice count (the Ozaki-I analogue, fed to ``num_slices`` rather than
    ``num_moduli``); ``"native"`` returns ``None`` (no decomposition).
    """
    if scheme not in SCHEMES:
        raise ValueError(f"unknown scheme {scheme!r}; expected one of {SCHEMES}")
    return {
        "ozaki2-fp8": DEFAULT_NUM_MODULI["fp8-hybrid"],
        "ozaki2-karatsuba": DEFAULT_NUM_MODULI["fp8-karatsuba"],
        "ozaki2-int8": DEFAULT_NUM_MODULI["int8"],
        "ozaki1-fp8": DEFAULT_NUM_SLICES,
        "native": None,
    }[scheme]
