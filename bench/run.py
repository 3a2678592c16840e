#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips it asks for, and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It runs from the root of a checkout, with ``src`` on the path, x64 on and
the persistent compilation cache in ``JAX_COMPILATION_CACHE_DIR`` where the
environment sets it, else at ``<checkout>/.jax_cache``. It refuses to
run on anything but a TPU (exit 2, no result), on fewer chips than the cell
asks for, and on a ``device_kind`` missing from ``bench/peaks.json``. Every
line it prints names the platform, the device kind and the device count.

A run: set-up (operands from ``--seed``, every program the cell uses
compiled or loaded from the cache), then a closed loop of calls for
``--seconds`` (a call that starts inside the window runs to its end), then
the comparison with the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window with the JAX profiler
and reports its per-layer metrics, ``device.busy_s``/``window_s`` and a
``breakdown``. The last line of standard output is the result; the last
lines of standard error are the numbers compared, each beside its limit.

Everything a cell needs is found by name; new cells, configurations and
metrics are added as files, with no edit to this harness:

- a cell is an entry of ``workloads`` in ``BENCHMARK.json``, naming a
  configuration and a traffic mix;
- a configuration is ``bench/configs/<config>.json``: the entry, policy,
  sizes, ``source``, ``reduced``, ``assumed``, the ``driver`` that drives it
  (``bench/drivers/<driver>.py``, one closed-loop driver per kind of entry),
  the ``reference`` it is compared with (``bench/reference/<name>.py``) and
  the ``limits`` of the comparison;
- a traffic mix is ``bench/traffic/<traffic>.json``: the parameters its
  configuration's driver reads (shapes, operand family, pool sizes);
- a per-layer metric is ``bench/metrics/<metric>.py`` with
  ``read(trace, counts) -> float | None``: ``trace`` is the window's
  ``Summary`` (``bench/trace.py``), ``counts`` what the driver counted. A reader that
  finds nothing to read returns None and the metric is left out.
- a device is an entry of ``bench/peaks.json``, keyed by ``device_kind``.

Add the metric to ``BENCHMARK.json`` (``end_to_end`` or ``per_layer``,
with the cells that report it under ``workloads``).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: Spans the harness writes into the profiler's trace.
WINDOW_SPAN = "bench.window"
REFERENCE_SPAN = "bench.reference"


class NoChip(RuntimeError):
    """The run found no accelerator, too few chips or an unknown kind."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by path (metric files have dots in
    their names, so they are not importable as packages)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, traffic
    and metric lists, all read from files under ``root``."""

    def __init__(self, root: str, workload: str):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"unknown workload {workload!r} (known: {sorted(by_name)})")
        w = by_name[workload]
        self.root = root
        self.name = workload
        self.chips = int(w["chips"])
        self.config = load_json(os.path.join(root, "bench", "configs", w["config"] + ".json"))
        self.traffic = load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))

        def mine(metrics):
            return [m for m in metrics if workload in m.get("workloads", [workload])]

        self.end_to_end = mine(bench["end_to_end"])
        self.per_layer = mine(bench["per_layer"])

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)


class Log:
    """Writes to standard error, every line tagged with the device."""

    def __init__(self, tag: str = "no device"):
        self.tag = tag

    def __call__(self, msg: str) -> None:
        for line in str(msg).splitlines() or [""]:
            print(f"[{self.tag}] {line}", file=sys.stderr, flush=True)


def find_devices(chips: int):
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    devs = jax.devices()
    kind = f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {kind}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {kind}")
    return devs[:chips]


def cache_dir(root: str, environ=os.environ) -> str:
    """The compilation cache's directory: the one ``JAX_COMPILATION_CACHE_DIR``
    names where the environment sets it, otherwise ``<checkout>/.jax_cache``,
    a fixed path inside the checkout."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")


def enable_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache in ``cache_dir(root)`` and
    return it. The program's ``enable_compile_cache`` is handed the same
    directory."""
    path = cache_dir(root)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, the small ones of the HPL driver too, and evict
    # none, so that only the first run with a cache compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.runtime.cache import enable_compile_cache
    return enable_compile_cache()


class CompileCounter:
    """Counts XLA compilations, through JAX's monitoring events, while
    ``counting`` is set."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counting = False
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.counting and name == self.COMPILE:
            self.compiles += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_window(driver, seconds: float, counter: CompileCounter):
    """Closed loop: one caller, steps back to back while the window is open.
    Returns (steps, window seconds by the host clock)."""
    steps = 0
    counter.counting = True
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t0 = time.perf_counter()
        while steps == 0 or time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation(driver.SPAN):
                driver.step()
            steps += 1
        window = time.perf_counter() - t0
    counter.counting = False
    return steps, window


def start(cell: Cell, log: Log):
    """Find the cell's chips and their peaks, then set up JAX for a run:
    the program's sources on the path, x64, the checkout's compile cache.
    Returns (devices, peaks); raises ``NoChip`` where the run must not go on."""
    src = os.path.join(cell.root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoChip(f"no program sources under {src}")
    sys.path.insert(0, src)
    devices = find_devices(cell.chips)
    try:
        peaks = load_module(cell.path("work.py")).load_peaks(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    log.tag = f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}"
    log(f"start-up (interpreter, JAX import, backend) {time.perf_counter() - T0!r} s")
    jax.config.update("jax_enable_x64", True)
    log(f"compile cache {enable_cache(cell.root)}")
    return devices, peaks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, peaks: dict,
             log: Log, entry=None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``entry`` replaces the program's entry point (the control and the
    fault tests drive the harness with a broken or lower-precision one)."""
    driver_mod = load_module(cell.path("drivers", cell.config["driver"] + ".py"))
    reference = load_module(cell.path("reference", cell.config["reference"] + ".py"))
    yardstick = load_module(cell.path("work.py"))
    counter = CompileCounter()
    driver = driver_mod.Driver(cell.config, cell.traffic, seed, devices, log, yardstick,
                               entry=entry)
    setup_s = time.perf_counter() - T0
    log(f"set-up {setup_s!r} s")
    tdir = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        steps, window = run_window(driver, seconds, counter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window: {steps} steps in {window!r} s; compilations inside the window: "
        f"{counter.compiles}")
    mem = memory_peak(devices)
    driver.release()
    with jax.profiler.TraceAnnotation(REFERENCE_SPAN):
        t = time.perf_counter()
        outcome = reference.compare(driver.answers(), cell.config, log)
        log(f"reference comparison {time.perf_counter() - t!r} s")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": outcome["correct"], "attempted": outcome["attempted"],
              "failed": outcome["failed"], "metrics": {}, "device": device}
    if trace:
        try:
            summary = load_module(cell.path("trace.py")).reduce_trace(
                tdir, WINDOW_SPAN, driver.SPAN)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        counts = driver.counts(steps, peaks)
        for m in cell.per_layer:
            value = load_module(cell.path("metrics", m["name"] + ".py")).read(summary, counts)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
    else:
        values = {"setup_s": setup_s, **driver.rates(steps, window), **outcome["metrics"]}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["checks"] = outcome["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = Log()
    cell = Cell(ROOT, args.workload)
    try:
        devices, peaks = start(cell, log)
    except NoChip as e:
        log(f"refused: {e}")
        return 2
    log(f"cell {cell.name}, seed {args.seed}, {args.seconds} s, trace {args.trace}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, peaks, log)
    for name, c in result["checks"].items():
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])  # JSON has no inf or nan
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
