"""Benchmark harness entry: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV, writes per-figure CSVs under experiments/,
and records every run in experiments/bench_results.json so trajectories are
comparable across policy choices. Run: PYTHONPATH=src python -m
benchmarks.run [--only NAME] [--policy SPEC ...] — e.g. ``--policy
ozaki2-fp8/fast@8 ozaki2-int8/accurate`` replaces the old separate
scheme/mode/moduli flags; benches that sweep policies (fig3, fig456, linalg,
plan_reuse, hpl_dist) use the list, the rest ignore it.

Every row is normalized to the ONE schema-v2 row format
(``repro.perf.rows``: ``schema_version``, ``wall_seconds``, structured
``throughput``/``accuracy``/``accuracy_gate``, resolved ``policy``) by the
shared writer here — benches return either legacy ``(name, us, derived)``
tuples or structured dicts, and the document is validated before it is
written. The run is then appended to the perf-trajectory store
(``experiments/trajectory/``, ``repro.perf.trajectory``) that the
``perf-gate`` CI job compares commits against (docs/perf.md).

``--smoke`` is the CI mode (the ``bench-smoke`` job, docs/ci.md): only the
benches in the smoke registry run, on tiny shapes, so the bench trajectory
accumulates per-commit without eating runner minutes. Membership is
EXPLICIT: every bench module declares ``SMOKE = True/False`` (checked
against its ``run(smoke=)`` signature — a mismatch is an error, so a new
bench cannot silently miss the gate), and ``--list-smoke`` prints the
registry (ci.yml calls it; tests/perf/test_smoke_registry.py pins it).
Smoke keeps the correctness gates armed — bench_hpl_dist raises on an HPL
scaled residual > 16, bench_serve_load raises when continuous batching
falls under 2x sequential tok/s (or its outputs diverge from
single-request decode), and bench_fig456_throughput raises when a
fused/unfused Pallas kernel row diverges bitwise from core; any of these
exits nonzero and fails the job.

``--fused`` / ``--unfused`` restrict the kernel-path comparison rows
(bench_fig456_throughput) to one Pallas route; default runs both.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

BENCHES = ["table2_counts", "fig3_accuracy", "fig12_heatmap",
           "fig456_throughput", "fig78_breakdown", "linalg", "plan_reuse",
           "hpl_dist", "serve_load"]

EXP_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments")
TRAJECTORY_DIR = os.path.join(EXP_DIR, "trajectory")


def _bench_module(bench: str):
    return __import__(f"benchmarks.bench_{bench}", fromlist=["run"])


def smoke_registry() -> dict[str, bool]:
    """``{bench: smoke-capable}`` from the EXPLICIT ``SMOKE`` declarations.

    Every bench module must declare ``SMOKE`` and it must agree with the
    ``run(smoke=)`` signature — the old behavior (deriving membership from
    the signature alone) let a bench miss the CI gate silently.
    """
    registry: dict[str, bool] = {}
    for bench in BENCHES:
        mod = _bench_module(bench)
        if not hasattr(mod, "SMOKE") or not isinstance(mod.SMOKE, bool):
            raise RuntimeError(
                f"bench_{bench} must declare `SMOKE = True/False` (explicit "
                "smoke-registry membership; docs/ci.md)")
        has_param = "smoke" in inspect.signature(mod.run).parameters
        if mod.SMOKE != has_param:
            raise RuntimeError(
                f"bench_{bench}: SMOKE={mod.SMOKE} but run() "
                f"{'has' if has_param else 'lacks'} a smoke= parameter — "
                "the declaration and the signature must agree")
        registry[bench] = mod.SMOKE
    return registry


def list_smoke() -> list[str]:
    """Names of the smoke-capable benches, in harness order."""
    return [b for b, ok in smoke_registry().items() if ok]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--policy", nargs="+", metavar="SPEC", default=None,
                    help="precision-policy specs (e.g. ozaki2-fp8/fast@8); "
                         "recorded verbatim in bench_results.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiny shapes, only smoke-capable "
                         "benches, HPL residual gate armed")
    ap.add_argument("--list-smoke", action="store_true",
                    help="print the smoke registry (one bench per line) and "
                         "exit; validates every bench's SMOKE declaration")
    kp = ap.add_mutually_exclusive_group()
    kp.add_argument("--fused", dest="fused", action="store_true", default=None,
                    help="kernel-path benches: compare core vs the fused "
                         "Pallas route only")
    kp.add_argument("--unfused", dest="fused", action="store_false",
                    help="kernel-path benches: compare core vs the "
                         "phase-split (+unfused) Pallas route only")
    args = ap.parse_args(argv)

    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()

    if args.list_smoke:
        for bench in list_smoke():
            print(bench)
        sys.exit(0)

    if args.policy:  # validate early so typos fail before any bench runs
        from repro.precision import parse_policy
        for spec in args.policy:
            parse_policy(spec)

    from repro.perf import rows as perf_rows
    from repro.perf import trajectory

    os.makedirs(EXP_DIR, exist_ok=True)
    # The whole harness runs with obs on: spans + the GEMM-call counters.
    # The registry is snapshotted PER BENCH (delta via reset) so each bench's
    # rows carry their own metrics + measured roofline fractions.
    import repro.obs as obs
    from benchmarks import roofline
    obs.enable()
    smoke_set = set(list_smoke()) if args.smoke else None
    print("name,us_per_call,derived")
    failed = 0
    results: list[dict] = []
    obs_by_bench: dict[str, dict] = {}

    def record(bench: str, raw_row) -> None:
        row = perf_rows.normalize_row(bench, raw_row)
        print(f"{row['name']},{row['wall_seconds'] * 1e6:.1f},{row['derived']}")
        results.append(row)

    for bench in BENCHES:
        if args.only and args.only not in bench:
            continue
        if smoke_set is not None and bench not in smoke_set:
            continue
        obs.reset_metrics()
        t_bench = time.perf_counter()
        n_before = len(results)
        try:
            mod = _bench_module(bench)
            params = inspect.signature(mod.run).parameters
            kwargs = {}
            if args.policy and "policies" in params:
                kwargs["policies"] = args.policy
            if args.fused is not None and "fused" in params:
                kwargs["fused"] = args.fused
            if args.smoke:
                kwargs["smoke"] = True
            for raw_row in mod.run(**kwargs):
                record(bench, raw_row)
        except Exception as exc:  # noqa: BLE001
            failed += 1
            # A gate failure (e.g. bench_hpl_dist's HPL residual) still
            # carries the rows measured before it fired — keep them in the
            # artifact so the per-commit trajectory has the passing cells.
            try:
                for raw_row in getattr(exc, "rows", []):
                    record(bench, raw_row)
            except perf_rows.RowSchemaError:
                traceback.print_exc(limit=2)
            print(f"bench_{bench},ERROR,{traceback.format_exc(limit=2)!r}")
        snap = obs.global_registry().snapshot()
        wall = time.perf_counter() - t_bench
        fractions = roofline.achieved_fraction(snap, wall)
        obs_by_bench[bench] = {
            "wall_seconds": wall,
            "metrics": snap,
            "roofline": fractions,
        }
        # Counter-derived roofline fractions ride ON EACH ROW too, so a
        # trajectory/store consumer never has to join against the per-bench
        # obs table (the counters are a per-bench delta; rows of one bench
        # share the attribution).
        row_obs = {k: fractions[k] for k in
                   ("achieved_ops_per_s", "roofline_fraction", "hbm_fraction")}
        for row in results[n_before:]:
            row["obs"] = dict(row["obs"] or {}, **row_obs)

    doc = perf_rows.make_results_doc(
        results, policy_specs=args.policy, smoke=args.smoke,
        argv=argv if argv is not None else sys.argv[1:], obs=obs_by_bench)
    with open(os.path.join(EXP_DIR, "bench_results.json"), "w") as f:
        json.dump(doc, f, indent=1)
    # Every run extends the local perf trajectory (experiments/trajectory/);
    # CI chains the store across commits via artifacts (docs/perf.md).
    appended = trajectory.append_results(doc, TRAJECTORY_DIR)
    print(f"trajectory/appended,{appended},{TRAJECTORY_DIR}")
    # Trace artifacts: the full span log (every bench) as Chrome trace JSON
    # + JSONL — the bench-smoke CI job uploads both (docs/observability.md).
    obs.write_chrome_trace(os.path.join(EXP_DIR, "trace.json"))
    obs.write_jsonl(os.path.join(EXP_DIR, "obs_events.jsonl"))
    # roofline table (requires dry-run artifacts; soft dependency)
    try:
        rows = roofline.load_all()
        if rows:
            out_csv = os.path.join(EXP_DIR, "roofline.csv")
            roofline.write_csv(rows, out_csv)
            ok = [r for r in rows if r.get("dominant") != "SKIPPED"]
            print(f"roofline/cells,{len(rows)},ok={len(ok)} -> {out_csv}")
    except Exception:  # noqa: BLE001
        print(f"roofline,SKIPPED,{traceback.format_exc(limit=1)!r}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
