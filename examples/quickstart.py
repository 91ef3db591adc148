"""Quickstart: the paper's Fig. 1 — 3-D heat diffusion, math-close notation.

    PYTHONPATH=src python examples/quickstart.py [--n 64] [--nt 50] \
        [--backend pallas|jnp]

One kernel source runs on every backend (the xPU property): `pallas` is the
TPU kernel (interpret-mode on CPU), `jnp` is the XLA-fused path.
"""
import argparse
import sys

import jax.numpy as jnp

sys.path.insert(0, "src")

from repro.core import Grid, FieldSet, fd3d as fd, init_parallel_stencil, \
    solve_until
from repro.core.teff import a_eff, measure, t_eff
from repro.data.physics import gaussian_hotspot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--nt", type=int, default=50)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    args = ap.parse_args()

    # Physics (paper Fig. 1 lines 14-18)
    lam, c0 = 1.0, 2.0
    grid = Grid((args.n,) * 3, (1.0, 1.0, 1.0))

    # Initial conditions (lines 27-31)
    fs = FieldSet(grid)
    T = fs.from_fn(lambda x, y, z: 1.7 + gaussian_hotspot(grid) * 0)
    T = T + gaussian_hotspot(grid, amplitude=1.0, width=0.1)
    T2 = T.copy()
    Ci = fs.ones() / c0

    # Time step (line 33)
    dt = grid.stable_diffusion_dt(lam / c0)
    _dx, _dy, _dz = grid.inv_spacing

    ps = init_parallel_stencil(backend=args.backend, dtype="float32", ndims=3)

    # the paper's @parallel macro (line 5); rotations name the T2->T
    # double buffer so fused multi-step / convergence drivers can rotate
    @ps.parallel(outputs=("T2",), rotations={"T2": "T"})
    def step(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2 +
            fd.d2_zi(T) * _dz ** 2))}

    # Time loop (lines 34-37)
    for it in range(args.nt):
        T2 = step(T2=T2, T=T, Ci=Ci, lam=lam, dt=dt, _dx=_dx, _dy=_dy, _dz=_dz)
        T, T2 = T2, T

    print(f"done: {args.nt} steps on {grid.shape} [{args.backend}] "
          f"T in [{float(T.min()):.4f}, {float(T.max()):.4f}]")

    # T_eff (paper's metric): 2 reads + 1 write per step
    m = measure(lambda: step(T2=T2, T=T, Ci=Ci, lam=lam, dt=dt,
                             _dx=_dx, _dy=_dy, _dz=_dz), iters=5, warmup=2)
    A = a_eff(grid.n_points, 2, 1, 4)
    print(f"T_eff = {t_eff(A, m.median_s)/1e9:.2f} GB/s "
          f"(median {m.median_s*1e3:.2f} ms)")

    # Convergence-driven: the SAME kernel with a fused error epilogue —
    # max|T2-T| folds inside the launch (no second pass) and the whole
    # iteration runs on device in one lax.while_loop (no host syncs).
    conv = step.with_reductions({"err": "max_abs_diff(T2, T)"})
    res = solve_until(conv, dict(T2=T2, T=T, Ci=Ci),
                      dict(lam=lam, dt=dt, _dx=_dx, _dy=_dy, _dz=_dz),
                      tol=1e-7, max_iters=10 * args.nt, check_every=10)
    print(f"solve_until: steady in {int(res.iters)} steps "
          f"(max|dT| = {float(res.err):.2e})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
