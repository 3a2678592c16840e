"""Closed-loop driver of HPL solves: ``repro.linalg.solve.refine_solve`` back to back.

Each solve is the one ``run_hpl`` makes (LU with partial pivoting, one
refinement step) on a fresh netlib HPL problem, A and b uniform in
(-0.5, 0.5), drawn from the seed: solve i of the window solves problem i,
and no problem is solved twice. Set-up runs one warm solve on a problem of
its own (it compiles or loads every program a solve uses) and then makes
the traffic's ``pool`` problems. A window that outruns the pool makes each
further problem in its own step, so its making is then timed with the
solve. The driver keeps A and b on the host, as ``refine_solve`` takes them.
"""
from __future__ import annotations

import time

import jax
import numpy as np

WARM = 2 ** 32  # seed stream of the warm solve's problem, apart from the pool's


def hpl_problem(seed: int, index: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The netlib HPL test problem: A, b ~ uniform(-0.5, 0.5)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    return rng.random((n, n)) - 0.5, rng.random(n) - 0.5


class Driver:
    SPAN = "bench.solve"

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 log, yardstick, entry=None):
        self.yardstick = yardstick
        self.n, nb = int(config["N"]), int(config["NB"])
        policy, steps = config["policy"], int(config["refine_steps"])
        if entry is None:
            from repro.linalg.solve import refine_solve

            def entry(a, b):
                return refine_solve(a, b, policy, factor="lu", refine_steps=steps,
                                    block=nb)[0]

        self.entry = entry
        self.device = devices[0]
        with jax.default_device(self.device):
            a, b = hpl_problem(seed, WARM, self.n)
            t = time.perf_counter()
            entry(a, b)
            warm = time.perf_counter() - t
        t = time.perf_counter()
        self.pool = [hpl_problem(seed, i, self.n) for i in range(int(traffic["pool"]))]
        log(f"hpl n={self.n} NB={nb} {policy}, refine_steps={steps}: warm solve "
            f"{warm!r} s, pool of {len(self.pool)} problems made in "
            f"{time.perf_counter() - t!r} s")
        self.seed = seed
        self.solutions: list[tuple[int, np.ndarray]] = []

    def step(self) -> None:
        i = len(self.solutions)
        if i == len(self.pool):
            self.pool.append(hpl_problem(self.seed, i, self.n))
        a, b = self.pool[i]
        with jax.default_device(self.device):
            x = np.asarray(self.entry(a, b))
        self.solutions.append((i, x))

    def release(self) -> None:
        self.entry = None

    def answers(self) -> dict:
        return {"pool": self.pool, "solutions": self.solutions}

    def rates(self, steps: int, window: float) -> dict:
        return {"hpl_gflops": self.yardstick.hpl_flop_count(self.n) * steps / window / 1e9}

    def counts(self, steps: int, peaks: dict) -> dict:
        return {"solves": steps}
