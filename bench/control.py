#!/usr/bin/env python3
"""Readings for a cell's correctness limits: the program's and the control's.

    python bench/control.py --workload <cell> --seconds <s> \\
        [--program SEED ...] [--control SEED ...]

In one process on the chip, runs the cell as ``run.py`` does (set-up,
window, comparison with the plain reference) once per seed: with the
program's entry for each ``--program`` seed, and with the control in its
place for each ``--control`` seed. The control is the configuration's
reference computed one precision below the float64 it states
(``control`` in ``bench/reference/<name>.py``). Prints one JSON line per
run with the numbers compared, and exits non-zero if any program run was
not correct or any control run was. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (bench/run.py)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    log = run.Log()
    cell = run.Cell(run.ROOT, args.workload)
    try:
        devices, peaks = run.start(cell, log)
    except run.NoChip as e:
        log(f"refused: {e}")
        return 2
    control = run.load_module(cell.path("reference", cell.config["reference"] + ".py")).control
    ok = True
    for kind, seeds in (("program", args.program), ("control", args.control)):
        for seed in seeds:
            r = run.run_cell(cell, seed, args.seconds, False, devices, peaks, log,
                             entry=control if kind == "control" else None)
            ok &= r["correct"] == (kind == "program")
            print(json.dumps({"workload": cell.name, "run": kind, "seed": seed,
                              "correct": r["correct"], "attempted": r["attempted"],
                              "failed": r["failed"], "checks": r["checks"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
