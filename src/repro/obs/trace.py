"""Span tracing: the timing substrate every repro subsystem reports through.

A :class:`Span` measures one named phase of work. Usage, as a context
manager or a decorator::

    with span("dist.lu.panel", step=K) as sp:
        ...            # host work
        sp.fence(out)  # jax.block_until_ready before the end timestamp

    @span("serve.engine.step")
    def step(self): ...

Design constraints (ISSUE 9):

* **Spans always time** (two ``perf_counter`` calls) so call sites can read
  ``sp.elapsed`` for their own accounting — the distributed-LU stats dicts
  keep their exact pre-migration values whether or not tracing is on.
  Recording into the trace buffer happens only while tracing is enabled.
* **Parent linking** is contextvar-scoped: nested spans record their parent's
  id, and the linkage survives threads and (trivially) asyncio tasks. The
  contextvar is touched only when tracing is enabled, so the disabled path
  stays near-zero-cost.
* **On the profiler's clock**: for its whole extent a span also holds a
  ``jax.profiler.TraceAnnotation`` of the same name, with its attributes
  as the annotation's keyword arguments (so the event keeps exactly the
  span's name). It does so whether or not tracing is enabled: the
  annotation costs about a microsecond and records only while a JAX
  profiler session collects, where the span lands on the host plane
  beside the device's operations.
* **Device fencing**: JAX dispatch is asynchronous — a span closing right
  after ``jit_fn(x)`` measures dispatch, not compute. ``sp.fence(value)``
  calls ``jax.block_until_ready`` on the value (any pytree) before the end
  timestamp is taken, so the span covers device time. ``fence`` is explicit
  rather than automatic: host-side spans (schedulers, allocators) must not
  pay a device sync.

The recorder is process-global and thread-safe (append under a lock); export
formats live in :mod:`repro.obs.export`.

Importing this module also installs a ``gc.callbacks`` hook that holds a
``python.gc`` annotation (with the collection's ``generation``) for every
garbage collection, so a profiler trace shows the collector's pauses.
"""
from __future__ import annotations

import contextvars
import functools
import gc
import itertools
import os
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "span", "tracing_enabled", "enable_tracing",
           "disable_tracing", "clear_trace", "trace_events", "TRACE_CLOCK"]

#: Events record microseconds on this clock (perf_counter epoch).
TRACE_CLOCK = "perf_counter_us"

_EVENTS: list[dict] = []
_EVENTS_LOCK = threading.Lock()
_ENABLED = bool(int(os.environ.get("REPRO_OBS_TRACE", "0") or "0"))
_IDS = itertools.count(1)
_CURRENT: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_obs_current_span", default=None)


def tracing_enabled() -> bool:
    return _ENABLED


def enable_tracing() -> None:
    global _ENABLED
    _ENABLED = True


def disable_tracing() -> None:
    global _ENABLED
    _ENABLED = False


def clear_trace() -> None:
    with _EVENTS_LOCK:
        _EVENTS.clear()


def trace_events() -> list[dict]:
    """Snapshot of the recorded span events (copies the list, not the dicts)."""
    with _EVENTS_LOCK:
        return list(_EVENTS)


class Span:
    """One timed phase. Always measures ``elapsed`` and holds a profiler
    annotation of its name; records into the trace buffer (with parent
    linkage) only while tracing is enabled."""

    __slots__ = ("name", "attrs", "_t0", "_t1", "_id", "_parent", "_token",
                 "_recording", "_annotation")

    def __init__(self, name: str, attrs: Optional[dict] = None):
        self.name = name
        self.attrs = attrs
        self._t0 = 0.0
        self._t1 = 0.0
        self._recording = False
        self._token = None

    # -- context manager -------------------------------------------------
    def __enter__(self) -> "Span":
        self._annotation = TraceAnnotation(self.name, **(self.attrs or {}))
        self._annotation.__enter__()
        self._recording = _ENABLED
        if self._recording:
            self._id = next(_IDS)
            self._parent = _CURRENT.get()
            self._token = _CURRENT.set(self._id)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._t1 == 0.0:
            self._t1 = time.perf_counter()
        if self._recording:
            _CURRENT.reset(self._token)
            event = {"name": self.name, "id": self._id, "parent": self._parent,
                     "ts_us": self._t0 * 1e6,
                     "dur_us": (self._t1 - self._t0) * 1e6,
                     "tid": threading.get_ident()}
            if self.attrs:
                event["attrs"] = self.attrs
            if exc_type is not None:
                event["error"] = exc_type.__name__
            with _EVENTS_LOCK:
                _EVENTS.append(event)
        self._annotation.__exit__(exc_type, exc, tb)

    # -- decorator form ---------------------------------------------------
    def __call__(self, fn):
        name = self.name
        attrs = self.attrs

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with Span(name, attrs):
                return fn(*args, **kwargs)
        return wrapper

    # -- explicit device fencing ------------------------------------------
    def fence(self, value: Any) -> Any:
        """Block until ``value``'s arrays are ready, then take the end
        timestamp — the span measures device time, not dispatch time.
        Returns ``value`` so fencing composes with a return expression."""
        import jax

        jax.block_until_ready(value)
        self._t1 = time.perf_counter()
        return value

    @property
    def elapsed(self) -> float:
        """Seconds between enter and exit (or the last fence). Valid after
        ``__exit__``; call sites feed this into legacy stats dicts."""
        return self._t1 - self._t0


def span(name: str, **attrs) -> Span:
    """Create a span — use as ``with span("x"): ...`` or ``@span("x")``."""
    return Span(name, attrs or None)


#: The annotation of the collection in progress (collections do not nest).
_GC_ANNOTATION: Optional[TraceAnnotation] = None


def _gc_span(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``python.gc`` annotation over each collection."""
    global _GC_ANNOTATION
    if phase == "start":
        _GC_ANNOTATION = TraceAnnotation("python.gc", generation=info["generation"])
        _GC_ANNOTATION.__enter__()
    elif _GC_ANNOTATION is not None:
        _GC_ANNOTATION.__exit__(None, None, None)
        _GC_ANNOTATION = None


gc.callbacks.append(_gc_span)
