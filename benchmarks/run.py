"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV lines per the harness contract:
  * Fig. 2  — T_eff of 3-D diffusion, fused kernel vs array broadcasting
  * §3      — solver-translation efficiency (diffusion + Gross-Pitaevskii)
  * §3      — weak scaling, sequential vs hidden-communication halo steps
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-scaling", action="store_true")
    args = ap.parse_args()

    from benchmarks import bench_teff, bench_solvers

    print("# --- Fig. 2: T_eff, kernel vs broadcast ---")
    if args.quick:
        from repro.configs.diffusion3d import BENCH_128
        rows = bench_teff.bench(BENCH_128, iters=5)
        for r in rows:
            print(f"teff_{r['name']}_{r['n']},{r['median_s']*1e6:.1f},"
                  f"T_eff={r['t_eff_GBs']:.2f}GB/s")
    else:
        bench_teff.main()

    print("# --- paper S3: solver translation efficiency ---")
    bench_solvers.main(["--skip-coupled"] if args.quick else [])

    print("# --- paper C5: SoA vs AoS data layout ---")
    from benchmarks import bench_layout
    bench_layout.main()

    if not args.skip_scaling:
        print("# --- paper S3: weak scaling w/ hidden communication ---")
        from benchmarks import bench_scaling
        bench_scaling.main(["--quick"] if args.quick else [])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
