"""Closed-loop driver of one DGEMM caller: ``repro.core.ozmm`` calls back to back.

Set-up makes ``pairs`` operand pairs on the device in one jitted call from
the seed, with the paper's sec. V-A generator, (rand - 0.5) * exp(phi *
randn), and compiles (or loads from the cache) the entry at the traffic's
shape. It runs no warm call. Calls alternate between the pairs, so no cache
of operands can serve a call; each call ends in ``block_until_ready``, and
the rows sampled from the seed are kept for the comparison.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_STREAM = 1  # seed stream of the sampled output rows


def seed_key(seed: int):
    """A threefry key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def make_operands(key, m: int, n: int, k: int, phi: float, pairs: int):
    """``pairs`` (A, B) float64 pairs, (rand - 0.5) * exp(phi * randn), in
    one jitted call. The uniform draw carries 46 random bits (two float32
    draws), the float64 product all that the device's float64 holds."""
    f64 = jnp.float64

    def mat(key, shape):
        k1, k2, k3 = jax.random.split(key, 3)
        u = (jax.random.uniform(k1, shape, jnp.float32).astype(f64)
             + jax.random.uniform(k2, shape, jnp.float32).astype(f64) * 2.0 ** -23)
        g = jnp.exp(phi * jax.random.normal(k3, shape, jnp.float32)).astype(f64)
        return (u - 0.5) * g

    keys = jax.random.split(key, 2 * pairs)
    return tuple((mat(keys[2 * p], (m, k)), mat(keys[2 * p + 1], (k, n)))
                 for p in range(pairs))


class Driver:
    SPAN = "bench.call"

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 log, yardstick, entry=None):
        self.yardstick = yardstick
        self.m, self.n, self.k = (int(traffic[x]) for x in ("m", "n", "k"))
        self.config = config
        policy = config["policy"]
        if entry is None:
            from repro.core import ozmm

            def entry(a, b):
                return ozmm(a, b, policy)

        t = time.perf_counter()
        with jax.default_device(devices[0]):
            self.pairs = make_operands(seed_key(seed), self.m, self.n, self.k,
                                       float(traffic["phi"]), int(traffic["pairs"]))
            jax.block_until_ready(self.pairs)
            made = time.perf_counter() - t
            rows = min(int(traffic["sample_rows"]), self.m)
            rng = np.random.default_rng(np.random.SeedSequence([seed, SAMPLE_STREAM]))
            self.rows = np.sort(rng.choice(self.m, rows, replace=False))
            self.rows_dev = jnp.asarray(self.rows, jnp.int32)
            a, b = self.pairs[0]
            self.call = jax.jit(entry).lower(a, b).compile()
            c = jax.ShapeDtypeStruct((self.m, self.n), jnp.float64)
            self.take = jax.jit(lambda c, i: c[i]).lower(c, self.rows_dev).compile()
        log(f"dgemm {self.m}x{self.k}x{self.n} {policy}, {len(self.pairs)} operand pairs, "
            f"{rows} sampled rows per call; operands {made!r} s, programs "
            f"{time.perf_counter() - t - made!r} s")
        self.outputs: list[tuple[int, object]] = []

    def step(self) -> None:
        p = len(self.outputs) % len(self.pairs)
        a, b = self.pairs[p]
        c = self.call(a, b)
        c.block_until_ready()
        # The sampled rows stay on the device until the window has closed:
        # copied to the host here, they cost each call ~70 ms (PERF.md).
        self.outputs.append((p, self.take(c, self.rows_dev)))

    def release(self) -> None:
        self.call = self.take = None

    def answers(self) -> dict:
        return {"pairs": self.pairs, "rows": self.rows, "outputs": self.outputs}

    def rates(self, steps: int, window: float) -> dict:
        return {"dgemm_tflops": 2.0 * self.m * self.n * self.k * steps / window / 1e12}

    def counts(self, steps: int, peaks: dict) -> dict:
        """What the per-layer readers divide by: the calls, and the least time
        of one call's residue products by the paper's count (eq. 17), on the
        MXU they run on, or of its least HBM traffic where that is longer."""
        num = int(self.config["num_moduli"])
        mxu, ops = self.yardstick.dgemm_mma(self.config["policy"].split("/")[0], num,
                                            self.m, self.n, self.k)
        nbytes = self.yardstick.dgemm_kernel_bytes(num, self.m, self.n, self.k)
        least, bound = self.yardstick.least_seconds(mxu, ops, nbytes, peaks)
        return {"calls": steps, "least_kernel_s_per_call": least, "bound_by": bound}
