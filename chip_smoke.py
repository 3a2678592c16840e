#!/usr/bin/env python3
"""Smoke test of the emulated DGEMM and HPL on one TPU (or four, as a 2x2
grid, with ``--four-chips``).

    python chip_smoke.py [--seed N] [--four-chips]

Runs in one process, through the normal entry points (``repro.core.ozmm``
under ``backend="auto"``, ``repro.linalg.run_hpl``), and exits non-zero on
the first failed check. It refuses to run anywhere but on a TPU: there is
no CPU or Pallas-interpreter fallback.

Phases (one chip):

1. float64 probe: what XLA:TPU's float64 keeps bit-exact against numpy,
   and whether ``numerics.frexp`` / ``ldexp_wide`` are exact on device-held
   values: checked in [2^-60, 2^60], reported out to 2^+-120.
2. DGEMM at m=n=k=8192 for ozaki2-{fp8,karatsuba,int8} x {fast,accurate}
   on a standard-normal and a lognormal input family: the fused Pallas
   kernel must be in the compiled HLO, a seeded sample of 256 rows must
   meet 2^-49 (accurate) / 2^-40 (fast) against numpy float64, normalised
   by |A||B|; at 4096^3 the fused route must equal ``+core`` bitwise. The
   warm time is that of each program's second call. Reported, not
   checked: ozaki2-fp8/accurate on inputs scaled to ~2^-40 and ~2^60.
3. HPL (n=4096, NB=256) with ozaki2-fp8/accurate and ozaki2-int8/accurate:
   scaled residual <= 16. The trailing-update programs compile on the
   host's cores while phase 2 runs on the device.

With ``--four-chips`` it runs only the distributed paths and what they are
compared with: ``run_hpl_dist`` on a 2x2 mesh (n=512, NB=256: one block
per chip; the block-cyclic LU compiles every GEMM shape once per device,
so larger n costs many more compiles, see PERF.md) and ``ozmm_k_sharded``
over 4 devices against the one-chip fast result, bitwise.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SPECS = [f"{s}/{m}" for s in ("ozaki2-fp8", "ozaki2-karatsuba", "ozaki2-int8")
         for m in ("fast", "accurate")]
BOUND = {"fast": 2.0 ** -40, "accurate": 2.0 ** -49}  # tests/core/test_ozmm_accuracy.py
HPL_SPECS = ("ozaki2-fp8/accurate", "ozaki2-int8/accurate")


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    print(f"  [{'ok' if cond else 'FAIL'}] {what}", flush=True)
    if not cond:
        raise CheckFailed(what)


def same_bits(x, y) -> bool:
    x = np.ascontiguousarray(x, np.float64)
    y = np.ascontiguousarray(y, np.float64)
    return x.shape == y.shape and np.array_equal(x.view(np.uint64), y.view(np.uint64))


def families(rng, n):
    from repro.testing import lognormal_matrix
    return {"stdnormal": (rng.standard_normal((n, n)), rng.standard_normal((n, n))),
            "lognormal": (lognormal_matrix(rng, (n, n), 0.5),
                          lognormal_matrix(rng, (n, n), 0.5))}


def compile_all(pool, jobs):
    """AOT-compile {key: (fn, args)} on ``pool``; XLA compiles release the
    GIL, so the host's cores work on several programs at once."""
    import jax
    futs = {k: pool.submit(lambda f, a: jax.jit(f).lower(*a).compile(), f, a)
            for k, (f, a) in jobs.items()}
    return {k: f.result() for k, f in futs.items()}


def _report(holds: bool, what: str) -> None:
    print(f"  [{'holds' if holds else 'does NOT hold'}] {what}", flush=True)


def phase_probe(jax, jnp):
    """Device float64 against IEEE binary64 (numpy), bitwise. What the
    emulation does not rely on is reported; what it relies on is checked:
    values already held by the device survive a round trip and the
    exponent / power-of-two helpers are exact on them."""
    from repro.core import numerics
    print("phase 1: float64 probe", flush=True)
    rng = np.random.default_rng(0)

    def differ(dev, ref) -> int:
        dev = np.ascontiguousarray(np.asarray(dev), np.float64)
        return int(np.sum(dev.view(np.uint64) != np.asarray(ref, np.float64).view(np.uint64)))

    cases = {"53-bit integers": rng.integers(2 ** 52, 2 ** 53, 64).astype(np.float64),
             "2^+-1000": np.array([2.0 ** 1000, 2.0 ** -1000, -(2.0 ** 1000)]),
             "subnormals": np.array([5e-324, -1e-310, 2.2250738585072009e-308])}
    for name, v in cases.items():
        back = np.asarray(jax.device_put(v))
        bad = differ(back, v)
        _report(bad == 0, f"host -> device -> host round trip of {name}: {bad}/{v.size} "
                f"differ (e.g. {v[0]!r} -> {back[0]!r})")
    a = rng.standard_normal(4096) * np.exp(rng.standard_normal(4096) * 4)
    b = rng.standard_normal(4096)
    ad, bd = np.asarray(jax.device_put(a)), np.asarray(jax.device_put(b))
    for name, dev, ref in [("mul", jax.jit(jnp.multiply)(ad, bd), ad * bd),
                           ("add", jax.jit(jnp.add)(ad, bd), ad + bd)]:
        bad = differ(dev, ref)
        _report(bad == 0, f"IEEE binary64 {name} of device-held values: "
                f"{bad}/{ref.size} results differ")
    ks = np.asarray(jax.device_put(rng.standard_normal(257) * 2.0 ** rng.integers(-40, 40, 257)))
    s = c = 0.0
    for v in ks:
        t = v - c
        s2 = s + t
        c = (s2 - s) - t
        s = s2
    dev = float(numerics.kahan_weighted_sum(jnp.asarray(ks)[:, None], jnp.ones(257))[0])
    _report(same_bits(dev, s), f"IEEE binary64 Kahan sum of 257 terms: device {dev!r}, "
            f"numpy {s!r}")
    # What the emulation relies on, where both float32 halves of a device
    # float64 are normal (|x| >= ~2^-78; below that the low half is a
    # float32 subnormal, which arithmetic flushes).
    x = np.asarray(jax.device_put(rng.standard_normal(4096) * 2.0 ** rng.integers(-60, 60, 4096)))
    check(differ(jax.device_put(x), x) == 0, "device-held float64 values round-trip bit-exactly")
    bad = _frexp_differs(jax, numerics, x)
    check(bad.size == 0, "numerics.frexp is exact on device-held values in "
          f"[2^-60, 2^60]: {bad.size}/{x.size} differ")
    sh = rng.integers(-20, 20, 4096).astype(np.int32)
    check(differ(jax.jit(numerics.ldexp_wide)(x, sh), np.ldexp(x, sh)) == 0,
          "numerics.ldexp_wide is exact on them for results in [2^-80, 2^80]")
    # The edge of that envelope, reported on every run (ozmm's docstring
    # states the range).
    xw = np.asarray(jax.device_put(rng.standard_normal(4096) * 2.0 ** rng.integers(-100, 100, 4096)))
    bad = _frexp_differs(jax, numerics, xw)
    _report(bad.size == 0, f"numerics.frexp on device-held values in [2^-100, 2^100]: "
            f"{bad.size}/{xw.size} differ" + (f", the largest at |x| = 2^{np.log2(np.max(np.abs(xw[bad]))):.1f}"
                                             if bad.size else ""))
    shw = rng.integers(-60, 60, 4096).astype(np.int32)
    want = np.ldexp(x, shw)
    got = np.asarray(jax.jit(numerics.ldexp_wide)(x, shw))
    bad = np.nonzero(got.view(np.uint64) != want.view(np.uint64))[0]
    _report(bad.size == 0, f"numerics.ldexp_wide for results in [2^-120, 2^120]: "
            f"{bad.size}/{x.size} differ" + (f", the largest at |result| = 2^{np.log2(np.max(np.abs(want[bad]))):.1f}"
                                            if bad.size else ""))


def _frexp_differs(jax, numerics, x):
    """Indices where the device's numerics.frexp differs from numpy's."""
    m, e = jax.jit(numerics.frexp)(x)
    mr, er = np.frexp(x)
    return np.nonzero((np.asarray(m) != mr) | (np.asarray(e) != er))[0]


def compile_dgemm(jax, jnp, pool, n=8192, n_cmp=4096):
    """AOT-compile phase 2's programs: every spec at n^3 and n_cmp^3, and its
    ``+core`` route at n_cmp^3."""
    from repro.core import ozmm
    from repro.kernels.common import resolve_interpret
    print(f"phase 2: DGEMM {n}^3 through ozmm (backend=auto)", flush=True)
    check(resolve_interpret(None) is False, "Pallas kernels resolve to compiled (not interpret) mode")
    f64 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float64)
    jobs = {}
    for spec in SPECS:
        fn = (lambda s: lambda a, b: ozmm(a, b, s))(spec)
        jobs[(spec, n)] = (fn, (f64(n, n), f64(n, n)))
        jobs[(spec, n_cmp)] = (fn, (f64(n_cmp, n_cmp), f64(n_cmp, n_cmp)))
        core = (lambda s: lambda a, b: ozmm(a, b, s + "+core"))(spec)
        jobs[(spec + "+core", n_cmp)] = (core, (f64(n_cmp, n_cmp), f64(n_cmp, n_cmp)))
    t0 = time.perf_counter()
    exe = compile_all(pool, jobs)
    print(f"  compiled {len(exe)} programs in {time.perf_counter() - t0:.1f} s", flush=True)
    for spec in SPECS:
        hlo = exe[(spec, n)].as_text()
        check("tpu_custom_call" in hlo, f"{spec}: compiled HLO runs the fused Pallas kernel")
        mem = exe[(spec, n)].memory_analysis()
        print(f"    memory at {n}^3: args {mem.argument_size_in_bytes} B, "
              f"temp {mem.temp_size_in_bytes} B, out {mem.output_size_in_bytes} B", flush=True)
    return exe


def phase_dgemm(jax, exe, seed, before_timing, n=8192, n_cmp=4096, rows=256):
    """Accuracy of every spec on both input families, fused == ``+core`` on
    the standard-normal one, and the warm time: each executable runs the
    standard-normal family first, so the lognormal call is its second.
    ``before_timing`` is called before the first timed call."""
    kind = jax.devices()[0].device_kind
    rng = np.random.default_rng(seed)
    sample = np.sort(rng.choice(n, rows, replace=False))
    for call, (fam, (a, b)) in enumerate(families(rng, n).items()):
        if call:
            before_timing()
        ref = a[sample] @ b
        denom = np.abs(a[sample]) @ np.abs(b)
        da, db = jax.device_put(a), jax.device_put(b)
        for spec in SPECS:
            t = time.perf_counter()
            c = exe[(spec, n)](da, db).block_until_ready()
            dt = time.perf_counter() - t
            err = float(np.max(np.abs(np.asarray(c[sample]) - ref) / denom))
            del c
            mode = spec.split("/")[1]
            if call:
                print(f"    {spec} {fam}: warm (second call) {dt!r} s, "
                      f"{2.0 * n ** 3 / dt / 1e12!r} dgemm TFLOP/s on {kind}", flush=True)
            check(err <= BOUND[mode],
                  f"{spec} {fam}: max err / (|A||B|) = {err!r} = 2^{np.log2(max(err, 1e-300)):.2f} "
                  f"<= 2^{int(np.log2(BOUND[mode]))} on {rows} sampled rows")
        if not call:
            sa, sb = jax.device_put(a[:n_cmp, :n_cmp]), jax.device_put(b[:n_cmp, :n_cmp])
            for spec in SPECS:
                fused = np.asarray(exe[(spec, n_cmp)](sa, sb))
                core = np.asarray(exe[(spec + "+core", n_cmp)](sa, sb))
                check(same_bits(fused, core), f"{spec}: fused == +core bitwise at {n_cmp}^3")
            del sa, sb
            _dgemm_edge(jax, exe, a[:n_cmp, :n_cmp], b[:n_cmp, :n_cmp], rows, "ozaki2-fp8/accurate")
        del da, db


def _dgemm_edge(jax, exe, a, b, rows, spec):
    """Reported, not checked: ``spec`` on inputs scaled towards the edges of
    the device float64's range (a float32 pair): magnitudes ~2^-40 give
    products ~2^-74, where low halves turn subnormal; ~2^60 gives products
    ~2^126, near float32's largest."""
    n = a.shape[0]
    for scale in (-40, 60):
        da = jax.device_put(a * 2.0 ** scale)
        db = jax.device_put(b * 2.0 ** scale)
        ha, hb = np.asarray(da), np.asarray(db)  # as the device holds them
        c = np.asarray(exe[(spec, n)](da, db))[:rows]
        ref = ha[:rows] @ hb
        err = np.abs(c - ref) / (np.abs(ha[:rows]) @ np.abs(hb))
        bad = int(np.sum(~np.isfinite(c)))
        worst = float(np.max(np.where(np.isfinite(err), err, np.inf)))
        _report(bad == 0 and worst <= BOUND[spec.split("/")[1]],
                f"{spec} at {n}^3 on inputs ~2^{scale}: {bad} non-finite of {c.size}, "
                f"max err / (|A||B|) = {worst!r} on {rows} rows")


def warm_hpl(pool, n=4096, block=256):
    """Start compiling ``lu_factor``'s trailing-update programs (one per
    trailing size n - k1 and policy, by running them on zeros) on ``pool``,
    so that they compile while phase 2 keeps the device busy."""
    from repro.linalg.blas3 import device_matmul, prepare

    def warm(spec, rows):
        lhs = prepare(np.zeros((rows, block)), "lhs", spec)
        device_matmul(lhs, np.zeros((block, rows)), spec).block_until_ready()

    return [pool.submit(warm, s, n - k1) for s in HPL_SPECS for k1 in range(block, n, block)]


def wait_all(futures) -> None:
    for f in futures:
        f.result()


def phase_hpl(warm, n=4096, block=256, seed=0):
    from repro.linalg import run_hpl
    print(f"phase 3: HPL n={n} NB={block}", flush=True)
    t = time.perf_counter()
    wait_all(warm)
    print(f"  {len(warm)} trailing-update programs compiled (waited {time.perf_counter() - t:.1f} s "
          "more after phase 2)", flush=True)
    # The two solves share the chip side by side: the wall time below is
    # not a time per solve.
    t = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(HPL_SPECS)) as pool:
        futs = {s: pool.submit(run_hpl, n, s, block=block, seed=seed) for s in HPL_SPECS}
        for spec, fut in futs.items():
            r = fut.result()["scaled_residual"]
            check(np.isfinite(r) and r <= 16.0, f"{spec}: HPL scaled residual {r!r} <= 16")
    print(f"  both solves, side by side: {time.perf_counter() - t:.1f} s", flush=True)


def phase_four_chips(jax, jnp, seed, n_hpl=512, block=256, n=4096):
    from repro.core import ozmm
    from repro.core.distributed import ozmm_k_sharded
    from repro.launch.mesh import make_mesh
    from repro.linalg.dist import run_hpl_dist
    from repro.linalg.dist.grid import ProcessGrid
    print("four-chip phase", flush=True)
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices visible")
    rng = np.random.default_rng(seed)
    a, b = rng.standard_normal((n, n)), rng.standard_normal((n, n))

    def k_sharded():
        mesh = make_mesh((1, 4), ("data", "model"))
        ck = np.asarray(ozmm_k_sharded(jnp.asarray(a), jnp.asarray(b), mesh, mode="fast"))
        return ck, np.asarray(ozmm(jnp.asarray(a), jnp.asarray(b), "ozaki2-fp8/fast"))

    # The two checks are independent: run them side by side so their
    # compiles overlap.
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        ks = pool.submit(_timed, k_sharded)
        hpl = pool.submit(_timed, run_hpl_dist, n_hpl, "ozaki2-fp8/accurate",
                          grid=ProcessGrid(2, 2, collectives="mesh"), block=block, seed=seed)
        (ck, one), dt = ks.result()
        check(same_bits(ck, one), f"ozmm_k_sharded over 4 devices == one-chip "
              f"ozaki2-fp8/fast bitwise at {n}^3 ({dt:.1f} s incl. compile)")
        res, dt = hpl.result()
    check(res["mesh_collectives"], "run_hpl_dist used the device mesh collectives")
    r = res["scaled_residual"]
    check(np.isfinite(r) and r <= 16.0, f"run_hpl_dist 2x2 n={n_hpl} NB={block}: scaled "
          f"residual {r!r} <= 16 ({dt:.1f} s incl. compile)")


def _timed(fn, *args, **kw):
    t = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 distributed HPL and the k-sharded GEMM")
    args = ap.parse_args(argv)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    import jax.numpy as jnp
    from repro.runtime.cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(jax, jnp, args.seed)
        else:
            with concurrent.futures.ThreadPoolExecutor(len(os.sched_getaffinity(0))) as pool:
                phase_probe(jax, jnp)
                exe = compile_dgemm(jax, jnp, pool)
                warm = warm_hpl(pool)
                phase_dgemm(jax, exe, args.seed, before_timing=lambda: wait_all(warm))
                phase_hpl(warm, seed=args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
