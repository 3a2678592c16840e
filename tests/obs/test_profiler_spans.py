"""Spans and garbage collections on the JAX profiler's clock: recorded under
``jax.profiler.start_trace`` on the CPU, read back from the ``.xplane.pb``."""
import gc
import glob
import os
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.obs import trace


def _host_events(profile_dir):
    """{name: [(start_ns, end_ns), ...]} over every line of ``/host:CPU``."""
    (path,) = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One profiler session: a nested pair of spans with obs tracing on, the
    same with it off, and a full collection. Returns the host events, the
    obs buffer after each pair and the spans' own timings."""
    was = trace.tracing_enabled()
    d = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    buffers, elapsed = {}, {}
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        for mode in ("on", "off"):
            (trace.enable_tracing if mode == "on" else trace.disable_tracing)()
            trace.clear_trace()
            with trace.span(f"unit.{mode}.outer"):
                with trace.span(f"unit.{mode}.inner", step=3) as sp:
                    time.sleep(0.002)
            elapsed[mode] = sp.elapsed
            buffers[mode] = [ev["name"] for ev in trace.trace_events()]
        gc.collect()
    finally:
        jax.profiler.stop_trace()
        trace.clear_trace()
        (trace.enable_tracing if was else trace.disable_tracing)()
    return _host_events(d), buffers, elapsed


@pytest.mark.parametrize("mode", ["on", "off"])
def test_span_lands_on_the_host_plane_inside_its_parent(recorded, mode):
    events, buffers, elapsed = recorded
    # The exact name: attributes ride as annotation arguments, not in the name.
    ((i0, i1),) = events[f"unit.{mode}.inner"]
    ((o0, o1),) = events[f"unit.{mode}.outer"]
    assert o0 <= i0 < i1 <= o1
    assert i1 - i0 >= 2e6  # the 2 ms sleep, in ns
    assert elapsed[mode] >= 0.002  # spans time with obs off too
    expected = ["unit.on.inner", "unit.on.outer"] if mode == "on" else []
    assert buffers[mode] == expected  # the obs buffer records only when on


def test_garbage_collections_show_as_python_gc(recorded):
    events, _, _ = recorded
    assert events.get("python.gc")
    assert all(e > s for s, e in events["python.gc"])
    assert sum(cb is trace._gc_span for cb in gc.callbacks) == 1
