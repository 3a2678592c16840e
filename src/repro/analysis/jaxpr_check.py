"""Layer 2: jaxpr invariant checker — trace real entry points, walk the
``ClosedJaxpr``, flag the numeric-bug classes that only show up in the
traced dataflow:

* **RPJ001 narrowing downcast** — ``convert_element_type`` f64 -> f32/bf16/f16
  on dataflow that reaches a jaxpr output. Legitimate narrowings exist (the
  quantization pipeline deliberately casts bounded small-integer values down
  to e4m3 via f32); those are baselined with notes. A NEW narrowing on an
  accumulator path is exactly the bug class the emulation cannot survive.
* **RPJ002 int32 overflow chain** — an int32 multiply feeding an int32
  add/reduction without widening (the residue-MMA overflow class; the
  in-tree sites carry < 2^31 magnitude proofs in DESIGN.md and are
  baselined).
* **RPJ003 donation hazards** — declared-donated inputs that are unused
  (silent copy, the donation is a lie) or returned unchanged (aliasing a
  donated buffer into the output without an update).
* **RPJ004 nondeterministic-order reduction** — float scatter-add /
  unordered collectives on entry points under the bitwise contract; those
  make "bitwise-equal to the reference path" backend-dependent.

Findings are keyed by a *signature* (check, primitive, dtypes, shape) and
deduplicated, so the baseline is robust to unrolled-loop repetition and to
equation reordering.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
from jax.extend import core as jax_core

_NARROW_FLOATS = ("float32", "bfloat16", "float16")


@dataclasses.dataclass(frozen=True)
class JaxprFinding:
    entry: str
    check: str
    signature: str
    message: str

    @property
    def key(self) -> str:
        return f"{self.entry}:{self.signature}"

    def render(self) -> str:
        return f"[{self.entry}] {self.check}: {self.message}"


def _subjaxprs(eqn) -> Iterator:
    """Inner jaxprs of a higher-order equation (scan/while/cond/pjit/...)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield v


def iter_jaxprs(jaxpr) -> Iterator:
    """The jaxpr and every nested sub-jaxpr, depth-first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn):
            yield from iter_jaxprs(sub)


def _dtype(v) -> str:
    aval = getattr(v, "aval", None)
    return str(getattr(aval, "dtype", "?"))


def _shape(v) -> str:
    aval = getattr(v, "aval", None)
    return "x".join(str(d) for d in getattr(aval, "shape", ()))


def _output_reaching_vars(jaxpr) -> set:
    """Vars whose dataflow reaches a jaxpr output (backward closure).

    Conservative across higher-order eqns: any equation with sub-jaxprs
    passes liveness through all of its operands.
    """
    live = {v for v in jaxpr.outvars if isinstance(v, jax_core.Var)}
    for eqn in reversed(jaxpr.eqns):
        if any(v in live for v in eqn.outvars):
            live.update(v for v in eqn.invars if isinstance(v, jax_core.Var))
    return live


def _consumers(jaxpr) -> dict:
    out: dict = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                out.setdefault(v, []).append(eqn)
    return out


# ---------------------------------------------------------------------------
# the four checks
# ---------------------------------------------------------------------------
def check_narrowing(entry_name: str, closed) -> list[JaxprFinding]:
    """RPJ001: f64 -> narrower-float conversions on output-reaching paths."""
    found = []
    for jaxpr in iter_jaxprs(closed.jaxpr):
        live = _output_reaching_vars(jaxpr)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src, dst = _dtype(eqn.invars[0]), _dtype(eqn.outvars[0])
            if src != "float64" or dst not in _NARROW_FLOATS:
                continue
            if eqn.outvars[0] not in live:
                continue
            sig = f"RPJ001:convert:{src}->{dst}:{_shape(eqn.invars[0])}"
            found.append(JaxprFinding(
                entry_name, "RPJ001", sig,
                f"float64 -> {dst} downcast of a {_shape(eqn.invars[0])} "
                "value on dataflow reaching an output — precision silently "
                "drops below the emulation target unless the value is "
                "bounded (then baseline with the bound as the note)"))
    return found


def check_int32_chain(entry_name: str, closed) -> list[JaxprFinding]:
    """RPJ002: int32 mul feeding an int32 add/reduction without widening."""
    found = []
    _ACCUM = {"add", "sub", "reduce_sum", "dot_general"}
    for jaxpr in iter_jaxprs(closed.jaxpr):
        consumers = _consumers(jaxpr)
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "mul":
                continue
            if not all(_dtype(v) == "int32" for v in (*eqn.invars, *eqn.outvars)):
                continue
            for consumer in consumers.get(eqn.outvars[0], ()):
                if (consumer.primitive.name in _ACCUM
                        and _dtype(consumer.outvars[0]) == "int32"):
                    sig = (f"RPJ002:mul->{consumer.primitive.name}:"
                           f"int32:{_shape(eqn.outvars[0])}")
                    found.append(JaxprFinding(
                        entry_name, "RPJ002", sig,
                        f"int32 multiply ({_shape(eqn.outvars[0])}) feeds an "
                        f"int32 {consumer.primitive.name} — the residue-MMA "
                        "overflow class; widen to int64 or baseline with the "
                        "magnitude proof"))
                    break
    return found


def check_donation(entry_name: str, closed,
                   donated_invars: set[int]) -> list[JaxprFinding]:
    """RPJ003: declared-donated inputs must be consumed and not aliased out.

    These are the statically checkable proxies for use-after-donation: an
    unused donated input means the donation buys nothing (XLA silently
    copies), and a donated input forwarded unchanged to an output aliases a
    buffer the caller believes is dead.
    """
    found = []
    jaxpr = closed.jaxpr
    used: set = set()
    for sub in iter_jaxprs(jaxpr):
        for eqn in sub.eqns:
            used.update(v for v in eqn.invars if isinstance(v, jax_core.Var))
    outset = {v for v in jaxpr.outvars if isinstance(v, jax_core.Var)}
    for i in sorted(donated_invars):
        var = jaxpr.invars[i]
        if var not in used and var not in outset:
            found.append(JaxprFinding(
                entry_name, "RPJ003", f"RPJ003:unused-donated:{i}",
                f"donated input #{i} ({_dtype(var)} {_shape(var)}) is never "
                "consumed — the donation is a silent copy"))
        elif var in outset:
            found.append(JaxprFinding(
                entry_name, "RPJ003", f"RPJ003:passthrough-donated:{i}",
                f"donated input #{i} ({_dtype(var)} {_shape(var)}) is "
                "returned unchanged — output aliases a buffer the caller "
                "donated away"))
    return found


def check_nondeterministic_reductions(entry_name: str, closed) -> list[JaxprFinding]:
    """RPJ004: unordered float accumulation on bitwise-contract paths."""
    found = []
    _UNORDERED = {"scatter-add", "scatter_add", "psum", "all_reduce_sum"}
    for jaxpr in iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name not in _UNORDERED:
                continue
            dt = _dtype(eqn.outvars[0])
            if not dt.startswith(("float", "bfloat")):
                continue
            sig = f"RPJ004:{eqn.primitive.name}:{dt}:{_shape(eqn.outvars[0])}"
            found.append(JaxprFinding(
                entry_name, "RPJ004", sig,
                f"float {eqn.primitive.name} on a bitwise-contract entry "
                "point: accumulation order is backend-scheduled, so results "
                "are not reproducible across the contract's paths"))
    return found


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def _dedupe(findings: list[JaxprFinding]) -> list[JaxprFinding]:
    seen: dict[str, JaxprFinding] = {}
    for f in findings:
        seen.setdefault(f.key, f)
    return list(seen.values())


def check_fn(name: str, fn, args, *, bitwise: bool = False,
             donate_argnums: tuple[int, ...] = ()) -> list[JaxprFinding]:
    """Trace ``fn(*args)`` and run every invariant check on the jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    donated: set[int] = set()
    if donate_argnums:
        # flat invars are the concatenated leaves of the args pytrees
        offset = 0
        for i, a in enumerate(args):
            n = jax.tree_util.tree_structure(a).num_leaves
            if i in donate_argnums:
                donated.update(range(offset, offset + n))
            offset += n
    findings = []
    findings += check_narrowing(name, closed)
    findings += check_int32_chain(name, closed)
    findings += check_donation(name, closed, donated)
    if bitwise:
        findings += check_nondeterministic_reductions(name, closed)
    return _dedupe(findings)


def check_entry(entry) -> list[JaxprFinding]:
    """Check one :class:`repro.analysis.registry.EntryPoint`."""
    fn, args = entry.build()
    return check_fn(entry.name, fn, args, bitwise=entry.bitwise,
                    donate_argnums=entry.donate)


def check_registry(entries=None) -> tuple[list[JaxprFinding], list[str]]:
    """Check every registered entry point; returns (findings, names)."""
    from .registry import ENTRY_POINTS
    from repro.core.numerics import ensure_x64

    ensure_x64()
    entries = ENTRY_POINTS if entries is None else entries
    findings: list[JaxprFinding] = []
    names: list[str] = []
    for entry in entries:
        findings.extend(check_entry(entry))
        names.append(entry.name)
    return findings, names
