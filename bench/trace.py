"""Reduce a JAX profiler trace (``.xplane.pb``) of one window to what the
per-layer metrics read: device busy time, kernel time, program launches,
the device's top operations and its idle gaps by what the host was doing.

Planes, lines and the kernel rule were read off a trace of the fused
emulated GEMM on a TPU v5 lite by hand (PERF.md, "Layers"):

- a device is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds
  one event per HLO operation executed, its ``XLA Modules`` line one event
  per program execution (a launch);
- an ``XLA Ops`` event is named by its HLO instruction's text, and a Pallas
  kernel is one whose text calls ``custom_call_target="tpu_custom_call"``
  (the fused kernel reads ``%ozmm_fused_raw.1 = s16[12,8192,8192] ...
  custom-call(...), custom_call_target="tpu_custom_call"``; the other
  custom calls of these programs are XLA's float64 splits, ``X64SplitHigh``
  and the like);
- host spans (``jax.profiler.TraceAnnotation``) are events on the host
  plane ``/host:CPU``, on the line of the thread that opened them. The
  device's clock runs about a millisecond behind the host's, so device
  events are shifted by the largest lead of a host ``tpu::System::Execute``
  over the program execution it started (the host launches a program
  before the device can run it).

The window is the host span ``window_span``; device events are clipped to
it. Busy time is the union of the device's ``XLA Ops`` intervals, so nested
and overlapping operations count once; the ``Async XLA Ops`` line (DMA
copies in flight) is not compute and is left out.

Besides these totals the ``Summary`` carries the window's events
themselves, clipped to it and on the host's clock: each device's ``XLA
Ops``, ``XLA Modules`` and other lines, and every host line's spans. A new
per-layer metric filters them in its own reader (by an operation's text,
a named scope, a collective's opcode, a host span) with no edit here.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
#: Host events that launch one program each, most specific first.
LAUNCH_EVENTS = ("tpu::System::Execute", "PJRT_LoadedExecutable_Execute")
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float          # length of the window span
    busy_s: float            # union of device operation intervals, mean over devices
    kernel_s: float          # Pallas kernel time, mean over devices
    launches: float          # program executions started in the window, mean over devices
    device_ops: list         # [[operation, seconds], ...], the TOP longest in total
    idle_gaps: list          # [[host activity, seconds], ...], idle time by what the host did
    clock_shift_s: float     # added to device times to put them on the host's clock
    #: Per device plane name, per line name (``XLA Ops``, ``XLA Modules``,
    #: ``Async XLA Ops``, ...): the window's events, clipped to it, in ns on
    #: the host's clock. Nested and overlapping events are all kept.
    device_events: dict = dataclasses.field(default_factory=dict)
    #: Per host line (one thread each): its spans in the window, clipped to it.
    host_events: dict = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float    # ns


def _events(line, shift: float = 0.0) -> list[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns) + shift
        out.append(Event(e.name, start, start + float(e.duration_ns)))
    return out


def is_kernel(op: Event) -> bool:
    """A Pallas (Mosaic) kernel: an HLO custom call to ``tpu_custom_call``."""
    return KERNEL_CALL in op.name


def short_name(op: str) -> str:
    """An HLO instruction's name, result shape and opcode, from its text:
    ``%ozmm_fused_raw.1 = s16[12,1024,1024] custom-call``."""
    head, _, rest = op.partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    return f"{head} = {rest.split('{')[0]} {m.group(1) if m else ''}".strip()


def clock_shift(host_launches: list[float], modules: list[float]) -> float:
    """Nanoseconds to add to device times: the largest lead of a host launch
    over the program execution it started, pairing the i-th launch with the
    i-th execution. 0 where the counts differ and no pairing holds."""
    if not modules or len(host_launches) != len(modules):
        return 0.0
    return max(h - d for h, d in zip(sorted(host_launches), sorted(modules)))


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by the intervals (overlaps counted once)."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: list[tuple[float, float]], t0: float, t1: float) -> list[tuple[float, float]]:
    """The sub-intervals of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def _clip(ev: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(e.start, t0), min(e.end, t1)) for e in ev if e.end > t0 and e.start < t1]


def clip_events(ev: list[Event], t0: float, t1: float) -> list[Event]:
    """The events that overlap [t0, t1], cut to it."""
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in ev if e.end > t0 and e.start < t1]


def host_activity(thread: list[Event], times: list[float], outer: set[str]) -> list[str]:
    """What the host thread was doing at each of ``times`` (sorted): the
    innermost span that holds it, after the benchmark's own span (one of
    ``outer``) that holds it. Spans of one thread nest, so one sweep with a
    stack of open spans finds them."""
    events = sorted(thread, key=lambda e: (e.start, -e.end))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i].start <= t:
            while stack and stack[-1].end <= events[i].start:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        own = [e.name for e in stack if e.name in outer]
        where = own[-1] if own else "outside the benchmark's spans"
        inner = stack[-1].name if stack and stack[-1].name not in outer else None
        out.append(f"{where} > {inner}" if inner else where)
    return out


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def reduce_trace(trace_dir: str, window_span: str, step_span: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)), window_span,
                          step_span)


def reduce_profile(profile, window_span: str, step_span: str) -> Summary:
    planes = list(profile.planes)
    host = [p for p in planes if p.name == HOST_PLANE]
    windows, thread = [], []
    host_lines: dict[str, list[Event]] = {}
    launches_by_name: dict[str, list[float]] = {n: [] for n in LAUNCH_EVENTS}
    for p in host:
        for line in p.lines:
            ev = _events(line)
            host_lines[line.name] = ev
            w = [e for e in ev if e.name == window_span]
            if w:
                windows += w
                thread = ev
            for e in ev:
                if e.name in launches_by_name:
                    launches_by_name[e.name].append(e.start)
    if len(windows) != 1:
        raise ValueError(f"expected one {window_span!r} span in the trace, found {len(windows)}")
    t0, t1 = windows[0].start, windows[0].end
    host_events = {name: clip_events(ev, t0, t1) for name, ev in host_lines.items()}

    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy = kernel = launches = shift = 0.0
    op_time: dict[str, float] = {}
    idle: dict[str, float] = {}
    device_events: dict[str, dict[str, list[Event]]] = {}
    outer = {window_span, step_span}
    for i, p in enumerate(devices):
        lines = {line.name: line for line in p.lines}
        mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        starts = [m.start for m in mods]
        d = next((clock_shift(launches_by_name[n], starts) for n in LAUNCH_EVENTS
                  if len(launches_by_name[n]) == len(starts)), 0.0)
        shift += d
        shifted = {name: _events(line, d) for name, line in lines.items()}
        device_events[p.name] = {name: clip_events(ev, t0, t1) for name, ev in shifted.items()}
        mods = shifted.get(MODULES_LINE, [])
        ops = shifted.get(OPS_LINE, [])
        spans = _clip(ops, t0, t1)
        busy += union_length(spans)
        kernel += union_length(_clip([o for o in ops if is_kernel(o)], t0, t1))
        launches += sum(1 for m in mods if t0 <= m.start < t1)
        for o in ops:
            s, e = max(o.start, t0), min(o.end, t1)
            if e > s:
                key = short_name(o.name)
                op_time[key] = op_time.get(key, 0.0) + (e - s)
        if i == 0:  # attribute the first device's idle time to the host
            holes = gaps(spans, t0, t1)
            whats = host_activity(thread, [(s + e) / 2 for s, e in holes], outer)
            for (s, e), what in zip(holes, whats):
                idle[what] = idle.get(what, 0.0) + (e - s)
    nd = len(devices)

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy / nd / 1e9,
                   kernel_s=kernel / nd / 1e9, launches=launches / nd,
                   device_ops=top({k: v / nd for k, v in op_time.items()}),
                   idle_gaps=top(idle), clock_shift_s=shift / nd / 1e9,
                   device_events=device_events, host_events=host_events)
