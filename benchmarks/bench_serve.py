"""Serving-layer throughput/latency benchmark: batched ensemble solves
through :mod:`repro.serve` vs the naive one-request-one-launch loop.

All requests arrive at t=0 (closed-loop burst): the naive baseline
answers them one ``solve_until`` at a time, so request k's latency
includes the k-1 solves ahead of it; the server packs them into
``max_batch``-wide batches whose per-sample convergence masking keeps
every lane busy (converged samples freeze and free their slot for
refill). Reported per mode: aggregate solves/s and the p50/p99
request-completion latency of the burst. The headline claim — batched
beats one-by-one on solves/s at >= 8 concurrent requests — is what CI's
``--quick`` run re-checks.

Results land in ``BENCH_serve_*.json`` (stamped via ``_meta.py``) and
are guarded by ``benchmarks/compare.py``.

    PYTHONPATH=src python benchmarks/bench_serve.py [--quick]
        [--n 16] [--requests 16] [--max-batch 8] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from repro.core import fd3d, init_parallel_stencil
from repro.core import iterate
from repro.serve import ServePolicy, SimulationServer, SolveRequest

from _meta import bench_meta


def diffusion_kernel():
    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions={"err": "max_abs_diff(T2, T)"})
    def kern(T2, T, dt):
        return {"T2": fd3d.inn(T) + dt * (
            fd3d.d2_xi(T) + fd3d.d2_yi(T) + fd3d.d2_zi(T))}

    return kern


def make_requests(n: int, count: int, tol: float, max_iters: int):
    """``count`` independent ICs/scalars on one grid bucket — a spike of
    varying amplitude and a per-request stable dt."""
    reqs = []
    for i in range(count):
        T = np.zeros((n, n, n), np.float32)
        T[n // 2, n // 2, n // 2] = 1.0 + 0.1 * i
        dt = 0.06 + 0.002 * (i % 5)
        reqs.append(SolveRequest(
            fields={"T": T, "T2": T}, scalars={"dt": dt},
            tol=tol, max_iters=max_iters))
    return reqs


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_one_by_one(kernel, n, count, tol, max_iters):
    """The naive baseline: a fresh solve_until launch per request."""
    reqs = make_requests(n, count, tol, max_iters)
    # warm the jit outside the timed region, as the server does
    r0 = reqs[0]
    iterate.solve_until(kernel, dict(r0.fields), dict(r0.scalars),
                        tol=tol, max_iters=max_iters, check_every=4)
    lat = []
    t0 = time.perf_counter()
    for r in reqs:
        res = iterate.solve_until(kernel, dict(r.fields), dict(r.scalars),
                                  tol=tol, max_iters=max_iters,
                                  check_every=4)
        np.asarray(res.fields["T"])          # request is done when host-visible
        lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    return wall, lat


def run_batched(kernel, n, count, tol, max_iters, max_batch):
    """The serving path: burst-submit, continuous batching drains it."""
    pol = ServePolicy(max_batch=max_batch, chunk_steps=64, check_every=4,
                      collect_window_s=0.005,
                      queue_capacity=max(64, 2 * count))
    with SimulationServer(kernel, pol) as srv:
        # warm the jit (one throwaway request) before the timed burst
        warm = make_requests(n, 1, tol, max_iters)[0]
        srv.solve(warm, timeout=120.0)
        reqs = make_requests(n, count, tol, max_iters)
        t0 = time.perf_counter()
        tickets = [srv.submit(r) for r in reqs]
        lat = []
        for t in tickets:
            t.result(timeout=300.0)
            lat.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t0
    return wall, lat


def bench(n: int, count: int, max_batch: int, tol: float = 1e-5,
          max_iters: int = 500):
    kernel = diffusion_kernel()
    rows = []
    for name, runner in (
            ("one_by_one", lambda: run_one_by_one(
                kernel, n, count, tol, max_iters)),
            ("batched", lambda: run_batched(
                kernel, n, count, tol, max_iters, max_batch))):
        wall, lat = runner()
        rows.append({
            "name": name, "n": n, "requests": count,
            "max_batch": max_batch if name == "batched" else 1,
            "wall_s": wall,
            "solves_per_s": count / wall,
            "per_solve_s": wall / count,
            "p50_s": percentile(lat, 50),
            "p99_s": percentile(lat, 99),
        })
        print(f"{name:12s} n={n} requests={count}: "
              f"{count / wall:7.2f} solves/s  "
              f"p50 {percentile(lat, 50)*1e3:7.1f} ms  "
              f"p99 {percentile(lat, 99)*1e3:7.1f} ms")
    base = next(r for r in rows if r["name"] == "one_by_one")
    bat = next(r for r in rows if r["name"] == "batched")
    speedup = bat["solves_per_s"] / base["solves_per_s"]
    print(f"batched/one_by_one throughput: {speedup:.2f}x")
    return rows, speedup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small burst for CI: n=12, 8 requests")
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_serve record here")
    args = ap.parse_args(argv)
    if args.quick:
        args.n, args.requests = 12, max(8, args.max_batch)

    rows, speedup = bench(args.n, args.requests, args.max_batch)
    record = {"kind": "serve", "rows": rows,
              "speedup_batched": speedup, "meta": bench_meta()}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"wrote {args.json}")
    # the acceptance claim: batching wins at >= 8 concurrent requests
    if args.requests >= 8 and speedup <= 1.0:
        print("FAIL: batched serving did not beat one-request-one-launch")
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
