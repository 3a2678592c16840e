"""CPU tests of the chip benchmark (``bench/``): the yardstick, the trace
reduction on a recorded TPU trace, the refusals, the data-driven lookup, and
that the comparison with the plain reference fails on a broken entry and on
the control.

The harness runs here on the CPU with the device check skipped and tiny
configurations written next to a copy of ``bench/``. Their entry is
``ozmm`` / ``refine_solve`` under the ``native`` policy, which compiles in
well under a second; the cells' emulated policies are the same call with
another policy string.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "ozmm1024_v5e.xplane.pb")


def _load(path):
    name = "test_bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


run = _load(os.path.join(BENCH, "run.py"))
work = _load(os.path.join(BENCH, "work.py"))
trace = _load(os.path.join(BENCH, "trace.py"))


# ---------------------------------------------------------------- yardstick

def test_work_counts_match_the_paper_and_the_program():
    from repro.core.perf_model import m_n
    from repro.linalg.hpl import hpl_flop_count

    assert work.m_n(12) == 30 and work.m_n(6) == 12
    assert all(work.m_n(n) == m_n(n) for n in range(1, 20))
    assert work.hpl_flop_count(4096) == 2.0 * 4096 ** 3 / 3.0 + 1.5 * 4096 ** 2
    assert work.hpl_flop_count(4096) == hpl_flop_count(4096)
    mxu, ops = work.dgemm_mma("ozaki2-fp8", 12, 8192, 8192, 8192)
    assert mxu == "bf16" and ops == 2.0 * 8192 ** 3 * 30
    assert work.dgemm_mma("ozaki2-int8", 14, 2, 3, 4) == ("int8", 2.0 * 24 * 14)
    peaks = work.load_peaks("TPU v5 lite")
    least, bound = work.least_seconds(mxu, ops, work.dgemm_kernel_bytes(12, 8192, 8192, 8192),
                                      peaks)
    assert bound == "compute" and least == pytest.approx(ops / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        work.load_peaks("TPU v99")


def test_run_refuses_a_non_tpu_backend(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", "dgemm_fp8acc.square8192", "--seed", "2147483648",
                     "--seconds", "10", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_cache_dir_honours_the_environment():
    assert run.cache_dir("/x/checkout", {}) == os.path.join("/x/checkout", ".jax_cache")
    assert run.cache_dir("/x/checkout", {"JAX_COMPILATION_CACHE_DIR": ""}) == \
        os.path.join("/x/checkout", ".jax_cache")
    assert run.cache_dir("/x/checkout", {"JAX_COMPILATION_CACHE_DIR": "/y/cache"}) == "/y/cache"


def test_benchmark_json_names_existing_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = run.Cell(ROOT, w["name"])
        assert os.path.exists(cell.path("drivers", cell.config["driver"] + ".py"))
        assert os.path.exists(cell.path("reference", cell.config["reference"] + ".py"))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))


# ------------------------------------------------------------------- trace

def test_union_and_gaps():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert trace.union_length(iv) == 4.0
    assert trace.gaps(iv, -1.0, 8.0) == [(-1.0, 0.0), (3.0, 5.0), (6.0, 8.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_host_activity_names_the_innermost_span():
    E = trace.Event
    thread = [E("bench.window", 0, 100), E("bench.call", 10, 50),
              E("PjitFunction(f)", 12, 20), E("inner", 14, 16)]
    got = trace.host_activity(thread, [5.0, 15.0, 18.0, 30.0, 60.0, 200.0],
                              {"bench.window", "bench.call"})
    assert got == ["bench.window", "bench.call > inner", "bench.call > PjitFunction(f)",
                   "bench.call", "bench.window", "outside the benchmark's spans"]


@pytest.fixture(scope="module")
def fixture_summary():
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_file(FIXTURE), "bench.window", "bench.call")


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) <= 1 << 20


def test_reduce_recorded_trace(fixture_summary):
    """Three calls of the fused 1024^3 ozaki2-fp8/accurate program, each
    followed by a row gather and 5 ms of host sleep, on one TPU v5 lite
    (recorded with the profiler's HLO-proto plane removed, to keep it small,
    and its source locations made relative to the checkout).
    The numbers were read off the trace by hand."""
    s = fixture_summary
    assert s.window_s == pytest.approx(0.13428812)
    assert s.busy_s == pytest.approx(0.113119119)  # union of XLA Ops
    assert s.kernel_s == pytest.approx(0.107646653)  # 3 ozmm_fused_raw events
    assert s.launches == 6  # 3 ozmm programs + 3 gathers
    assert s.clock_shift_s == pytest.approx(0.001100495)
    top, t = s.device_ops[0]
    assert top == "%ozmm_fused_raw.1 = s16[12,1024,1024] custom-call" and t == s.kernel_s
    assert len(s.device_ops) == trace.TOP
    assert s.device_ops == sorted(s.device_ops, key=lambda kv: -kv[1])
    gaps = dict(s.idle_gaps)
    assert gaps["bench.window"] == pytest.approx(0.017003109)  # the sleeps
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_summary_carries_the_window_events(fixture_summary):
    """A new reader can take a metric from the events themselves: the
    clipped ``XLA Ops`` and ``XLA Modules`` events give the totals back, and
    the harness's own spans are on the host lines."""
    s = fixture_summary
    (plane, lines), = s.device_events.items()
    assert plane == "/device:TPU:0"
    ops, mods = lines["XLA Ops"], lines["XLA Modules"]
    t0 = min(e.start for e in ops)
    assert all(e.end > e.start for e in ops + mods)
    assert trace.union_length([(e.start, e.end) for e in ops]) / 1e9 == \
        pytest.approx(s.busy_s)
    kernels = [(e.start, e.end) for e in ops if trace.is_kernel(e)]
    assert len(kernels) == 3 and trace.union_length(kernels) / 1e9 == pytest.approx(s.kernel_s)
    assert len(mods) == s.launches
    spans = [e for ev in s.host_events.values() for e in ev]
    window, = [e for e in spans if e.name == "bench.window"]
    assert (window.end - window.start) / 1e9 == pytest.approx(s.window_s)
    assert sum(e.name == "bench.call" for e in spans) == 3
    assert all(window.start <= e.start and e.end <= window.end for e in spans)
    assert t0 >= window.start


# ------------------------------------------------------ harness on the CPU

TINY_DGEMM = {"m": 64, "n": 96, "k": 80, "phi": 0.5, "pairs": 2, "sample_rows": 16,
              "arrivals": "closed loop"}
TINY_HPL = {"pool": 2, "matrix": "uniform", "arrivals": "closed loop"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark with new files only: two configurations, two
    traffic mixes and two workloads that no code names."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(BENCH, root / "bench")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(BENCH, "configs", "dgemm_fp8acc.json")))
    cfg.update(name="dgemm_native_tiny", policy="native")
    json.dump(cfg, open(root / "bench" / "configs" / "dgemm_native_tiny.json", "w"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "hpl_fp8acc.json")))
    cfg.update(name="hpl_native_tiny", policy="native", N=64, NB=32)
    json.dump(cfg, open(root / "bench" / "configs" / "hpl_native_tiny.json", "w"))
    json.dump(TINY_DGEMM, open(root / "bench" / "traffic" / "tiny_dgemm.json", "w"))
    json.dump(TINY_HPL, open(root / "bench" / "traffic" / "tiny_hpl.json", "w"))
    bench["workloads"] += [
        {"name": "dgemm_native_tiny.t", "config": "dgemm_native_tiny", "traffic": "tiny_dgemm",
         "chips": 1, "why": "test"},
        {"name": "hpl_native_tiny.t", "config": "hpl_native_tiny", "traffic": "tiny_hpl",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = m["workloads"][0].split("_")[0]
            m["workloads"].append(f"{kind}_native_tiny.t")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return str(root)


def _run(root, workload, entry=None, seconds=0.2, seed=2 ** 31 + 7):
    cell = run.Cell(root, workload)
    return run.run_cell(cell, seed, seconds, False, jax.devices(),
                        work.load_peaks("TPU v5 lite"), run.Log("cpu test"), entry=entry)


def test_new_cell_is_found_and_runs(tiny_root):
    r = _run(tiny_root, "dgemm_native_tiny.t")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"setup_s", "dgemm_tflops", "dgemm_bits"}
    assert r["metrics"]["dgemm_bits"]["value"] > 45
    assert list(r)[-1] == "checks" and set(r["checks"]) == {"max_err"}
    r = _run(tiny_root, "hpl_native_tiny.t")
    assert r["correct"] and r["attempted"] >= 1
    assert set(r["metrics"]) == {"setup_s", "hpl_gflops"}
    assert r["checks"]["scaled_residual"]["value"] <= 16


def test_hpl_window_never_repeats_a_problem(tiny_root):
    """A window with more solves than the pool holds solves a fresh problem
    each time, and every one is compared."""
    r = _run(tiny_root, "hpl_native_tiny.t", seconds=0.5)
    assert r["correct"] and r["attempted"] > TINY_HPL["pool"]
    cell = run.Cell(tiny_root, "hpl_native_tiny.t")
    drv = run.load_module(cell.path("drivers", "hpl.py"))
    d = drv.Driver(cell.config, cell.traffic, 2 ** 31 + 11, jax.devices(), run.Log("cpu test"),
                   work, entry=lambda a, b: np.zeros(a.shape[0]))
    for _ in range(5):
        d.step()
    assert [i for i, _ in d.solutions] == list(range(5))
    firsts = {float(d.pool[i][0][0, 0]) for i in range(5)}
    assert len(firsts) == 5


def test_same_seed_same_operands(tiny_root):
    drv = run.load_module(os.path.join(tiny_root, "bench", "drivers", "dgemm.py"))
    one = drv.make_operands(drv.seed_key(2 ** 31 + 99), 8, 8, 8, 0.5, 2)
    two = drv.make_operands(drv.seed_key(2 ** 31 + 99), 8, 8, 8, 0.5, 2)
    other = drv.make_operands(drv.seed_key(2 ** 31 + 100), 8, 8, 8, 0.5, 2)
    assert all(np.array_equal(x, y) for p, q in zip(one, two) for x, y in zip(p, q))
    assert not np.array_equal(one[0][0], other[0][0])


def test_altered_answer_is_not_correct(tiny_root):
    """The timed path broken where the answer is produced: one column of
    every product, and every solution, altered."""
    from repro.core import ozmm
    from repro.linalg.solve import refine_solve

    r = _run(tiny_root, "dgemm_native_tiny.t",
             entry=lambda a, b: ozmm(a, b, "native").at[:, 0].add(1.0))
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["checks"]["max_err"]["value"] > r["checks"]["max_err"]["limit"]
    r = _run(tiny_root, "hpl_native_tiny.t",
             entry=lambda a, b: refine_solve(a, b, "native", refine_steps=1, block=32)[0] + 1e-6)
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1


def test_control_is_not_correct(tiny_root):
    """The plain reference in the program's place, computed in float32."""
    for workload, ref in (("dgemm_native_tiny.t", "dgemm"), ("hpl_native_tiny.t", "hpl")):
        control = run.load_module(os.path.join(tiny_root, "bench", "reference", ref + ".py"))
        r = _run(tiny_root, workload, entry=control.control)
        assert not r["correct"], (workload, r["checks"])
        name, = r["checks"]
        assert r["checks"][name]["value"] > r["checks"][name]["limit"]


def test_dgemm_reference_row_errors():
    ref = run.load_module(os.path.join(BENCH, "reference", "dgemm.py"))
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal((4, 6)), rng.standard_normal((6, 5))
    den = np.abs(a) @ np.abs(b)
    assert ref.row_errors(a @ b, a @ b, den) == 0.0
    c = a @ b
    c[0, 0] += den[0, 0] * 2.0 ** -40
    assert ref.row_errors(c, a @ b, den) == pytest.approx(2.0 ** -40)
    c[1, 2] = np.nan
    assert ref.row_errors(c, a @ b, den) == np.inf
    assert jnp.asarray(1.0).dtype == jnp.float64  # x64 is on (conftest)
