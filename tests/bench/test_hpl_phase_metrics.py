"""The HPL phase readers (``bench/metrics/*_ms.hpl.py``) on a synthetic
window: each sums its host spans over every host line and divides by the
solves, and finds nothing to read without spans or without solves."""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")


def _load(path):
    name = "test_hpl_phase_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


trace = _load(os.path.join(BENCH, "trace.py"))
MS = 1e6  # ns


def _summary(host_events):
    return trace.Summary(window_s=1.0, busy_s=0.1, kernel_s=0.05, launches=10,
                         device_ops=[], idle_gaps=[], clock_shift_s=0.0,
                         host_events=host_events)


def _window():
    """Two solves on the main thread, a stray panel span on a second line,
    and spans the readers must leave out (the factorization, the harness's)."""
    E = trace.Event
    main = [E("bench.window", 0, 100 * MS), E("bench.solve", 0, 50 * MS),
            E("bench.solve", 50 * MS, 100 * MS), E("linalg.refine_solve", 1 * MS, 49 * MS),
            E("linalg.lu.factor", 1 * MS, 30 * MS)]
    for s0 in (0, 50 * MS):
        main += [E("linalg.lu.panel", s0 + 1 * MS, s0 + 11 * MS),
                 E("linalg.lu.trsm", s0 + 11 * MS, s0 + 13 * MS),
                 E("linalg.lu.update", s0 + 13 * MS, s0 + 20 * MS),
                 E("linalg.lu.panel", s0 + 20 * MS, s0 + 25 * MS),
                 E("linalg.lu.solve", s0 + 30 * MS, s0 + 34 * MS),
                 E("linalg.refine.residual", s0 + 34 * MS, s0 + 37 * MS),
                 E("linalg.lu.solve", s0 + 37 * MS, s0 + 40 * MS),
                 E("linalg.refine.residual", s0 + 40 * MS, s0 + 42.5 * MS),
                 E("PjitFunction(ozmm)", s0 + 14 * MS, s0 + 15 * MS)]
    other = [E("linalg.lu.panel", 60 * MS, 61 * MS)]
    return {"python": main, "worker": other}


# Per solve: panel (10 + 5) * 2 + 1 = 31 ms over 2 solves; trsm 2; update
# 7; epilogue 4 + 3 + 3 + 2.5.
EXPECTED = {"lu_panel_ms.hpl": 15.5, "lu_trsm_ms.hpl": 2.0, "lu_update_ms.hpl": 7.0,
            "solve_epilogue_ms.hpl": 12.5}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_gives_per_solve_ms(metric):
    reader = _load(os.path.join(BENCH, "metrics", metric + ".py"))
    assert reader.read(_summary(_window()), {"solves": 2}) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_finds_nothing_without_spans_or_solves(metric):
    reader = _load(os.path.join(BENCH, "metrics", metric + ".py"))
    E = trace.Event
    bare = {"python": [E("bench.window", 0, 100 * MS), E("bench.solve", 0, 100 * MS)]}
    assert reader.read(_summary(bare), {"solves": 1}) is None  # a program without spans
    assert reader.read(_summary({}), {"solves": 1}) is None
    assert reader.read(_summary(_window()), {"solves": 0}) is None
    assert reader.read(_summary(_window()), {}) is None
