"""Paper §3 "solver translation" table: solvers written through the
framework's @parallel engine vs hand-fused direct-jax implementations,
plus the coupled-engine solver benchmarks.

The paper reports its translated CUDA-C solvers reach 90%/98% of the
originals; here the "original" is a hand-written jax.jit step and the
"translation" is the same physics through repro.core.parallel — the ratio
measures the abstraction's overhead (expected ~1.0: both lower to XLA).

The coupled benches time the two example solvers (reactive porosity
waves, Gross-Pitaevskii) end-to-end through the coupled multi-output
engine: pallas-vs-jnp backend ratio (on CPU hosts pallas runs in
interpret mode — the ratio is a correctness-path record, not a speed
claim) and fused k-step temporal blocking vs k sequential launches.
Results land in ``BENCH_solvers_*.json``.

    PYTHONPATH=src python benchmarks/bench_solvers.py [--quick]
        [--n-porosity 64] [--n-gp 32] [--nsteps 4] [--iters 10] [--json P]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
for _p in (_ROOT, os.path.join(_ROOT, "src")):  # examples + repro importable
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax
import jax.numpy as jnp

from repro.core import Grid, fd3d as fd, init_parallel_stencil, teff
from repro.kernels import ref


def bench_diffusion_translation(n: int = 96, iters: int = 10):
    g = Grid((n,) * 3)
    key = jax.random.PRNGKey(0)
    T = jax.random.uniform(key, g.shape, jnp.float32)
    Ci = jnp.full(g.shape, 0.5, jnp.float32)
    dt = g.stable_diffusion_dt(2.0)
    inv = g.inv_spacing

    hand = jax.jit(lambda T2, T: ref.diffusion3d_step(T2, T, Ci, 1.0, dt, *inv))

    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",))
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2 +
            fd.d2_zi(T) * _dz ** 2))}

    framework = jax.jit(lambda T2, T: kern(T2=T2, T=T, Ci=Ci, lam=1.0, dt=dt,
                                           _dx=inv[0], _dy=inv[1], _dz=inv[2]))

    mh = teff.measure(lambda: hand(T, T), iters=iters)
    mf = teff.measure(lambda: framework(T, T), iters=iters)
    return {
        "hand_us": mh.median_s * 1e6,
        "framework_us": mf.median_s * 1e6,
        "translation_efficiency": mh.median_s / mf.median_s,
    }


def bench_gp_translation(n: int = 48, iters: int = 10):
    g = Grid((n,) * 3, (8.0,) * 3)
    key = jax.random.PRNGKey(1)
    re = jax.random.uniform(key, g.shape, jnp.float32) * 0.1
    im = jnp.zeros_like(re)
    V = jnp.zeros_like(re)
    inv2 = tuple(1.0 / d ** 2 for d in g.spacing)
    dt = 0.2 * min(g.spacing) ** 2

    def H_direct(f, re, im):
        lap = ((f[2:, 1:-1, 1:-1] - 2 * f[1:-1, 1:-1, 1:-1] + f[:-2, 1:-1, 1:-1]) * inv2[0]
               + (f[1:-1, 2:, 1:-1] - 2 * f[1:-1, 1:-1, 1:-1] + f[1:-1, :-2, 1:-1]) * inv2[1]
               + (f[1:-1, 1:-1, 2:] - 2 * f[1:-1, 1:-1, 1:-1] + f[1:-1, 1:-1, :-2]) * inv2[2])
        dens = re[1:-1, 1:-1, 1:-1] ** 2 + im[1:-1, 1:-1, 1:-1] ** 2
        return -0.5 * lap + (V[1:-1, 1:-1, 1:-1] + 0.5 * dens) * f[1:-1, 1:-1, 1:-1]

    @jax.jit
    def hand(re, im):
        re = re.at[1:-1, 1:-1, 1:-1].add(dt * H_direct(im, re, im))
        im = im.at[1:-1, 1:-1, 1:-1].add(-dt * H_direct(re, re, im))
        return re, im

    ps = init_parallel_stencil(backend="jnp", ndims=3)

    # V enters as a field argument: the stencil IR traces the kernel, so
    # every array it reads must be visible as an argument (closures over
    # full arrays are untraceable by design).
    def H(f, re, im, V, _dx2, _dy2, _dz2):
        lap = fd.d2_xi(f) * _dx2 + fd.d2_yi(f) * _dy2 + fd.d2_zi(f) * _dz2
        dens = fd.inn(re) ** 2 + fd.inn(im) ** 2
        return -0.5 * lap + (fd.inn(V) + 0.5 * dens) * fd.inn(f)

    @ps.parallel(outputs=("re2",))
    def step_re(re2, re, im, V, dt, _dx2, _dy2, _dz2):
        return {"re2": fd.inn(re) + dt * H(im, re, im, V, _dx2, _dy2, _dz2)}

    @ps.parallel(outputs=("im2",))
    def step_im(im2, re, im, V, dt, _dx2, _dy2, _dz2):
        return {"im2": fd.inn(im) - dt * H(re, re, im, V, _dx2, _dy2, _dz2)}

    @jax.jit
    def framework(re, im):
        re = step_re(re2=re, re=re, im=im, V=V, dt=dt, _dx2=inv2[0],
                     _dy2=inv2[1], _dz2=inv2[2])
        im = step_im(im2=im, re=re, im=im, V=V, dt=dt, _dx2=inv2[0],
                     _dy2=inv2[1], _dz2=inv2[2])
        return re, im

    mh = teff.measure(lambda: hand(re, im), iters=iters)
    mf = teff.measure(lambda: framework(re, im), iters=iters)
    return {
        "hand_us": mh.median_s * 1e6,
        "framework_us": mf.median_s * 1e6,
        "translation_efficiency": mh.median_s / mf.median_s,
    }


# --------------------------------------------------------------------------
# coupled-engine solver benches (pallas-vs-jnp, fused-vs-sequential)
# --------------------------------------------------------------------------
def _measure_backends(make_step_fn, iters):
    """Per-step median seconds per backend for a ``step()`` closure maker."""
    out = {}
    for backend in ("jnp", "pallas"):
        fn = make_step_fn(backend)
        m = teff.measure(fn, iters=iters, warmup=2)
        out[backend] = m.median_s
    out["pallas_over_jnp"] = out["pallas"] / out["jnp"]
    return out


def _fused_vs_sequential(kern, fields, scalars, nsteps, iters):
    """run_steps(k) — ONE temporally-blocked launch — vs k sequential
    rotated calls, per-step seconds. ``kern`` should be a pallas-backend
    kernel: on the jnp backend run_steps IS an unrolled sequential chain,
    so the comparison would measure jit noise. Field arrays are passed as
    jit *arguments* (a zero-arg closure would let XLA constant-fold the
    whole chain and time a no-op)."""
    rot = kern.rotations
    names = tuple(fields)

    def seq_chain(*arrs):
        cur = dict(zip(names, arrs))
        for _ in range(nsteps):
            outs = kern(**cur, **scalars)
            for o, tgt in rot.items():
                cur[o], cur[tgt] = cur[tgt], outs[o]
        return tuple(cur[tgt] for tgt in rot.values())

    def fused_chain(*arrs):
        outs = kern.run_steps(nsteps, **dict(zip(names, arrs)), **scalars)
        return tuple(outs[o] for o in kern.outputs)

    arrs = tuple(fields[n] for n in names)
    ms = teff.measure(lambda: jax.jit(seq_chain)(*arrs), iters=iters, warmup=2)
    mf = teff.measure(lambda: jax.jit(fused_chain)(*arrs), iters=iters,
                      warmup=2)
    return {
        "nsteps": nsteps,
        "backend": kern.ps.backend,
        "sequential_per_step_us": ms.median_s / nsteps * 1e6,
        "fused_per_step_us": mf.median_s / nsteps * 1e6,
        "fused_speedup": ms.median_s / mf.median_s,
    }


def _march_rows(kern, fields, scalars, march_axis: int, iters: int):
    """Streamed-vs-all-parallel record for one coupled kernel: per-step
    medians on both backends through ``kern.marched`` plus a parity check
    against the all-parallel jnp step (CI compiles the streamed path for
    every solver this way)."""
    import numpy as np

    out = {"axis": march_axis}
    ref = kern.marched(None)(**fields, **scalars)
    names = tuple(fields)
    arrs = tuple(fields[n] for n in names)
    for backend in ("jnp", "pallas"):
        k = kern if kern.ps.backend == backend else None
        if k is None:
            from repro.core import init_parallel_stencil
            ps = init_parallel_stencil(backend=backend, ndims=kern.ps.ndims,
                                       dtype=kern.ps.dtype)
            k = ps.parallel(outputs=kern.outputs, tile=kern.tile,
                            rotations=kern.rotations, bc=kern.bc)(kern.fn)
        m = k.marched(march_axis)
        # field arrays as jit *arguments* — a zero-arg closure would let
        # XLA constant-fold the whole chain and time a no-op
        step = jax.jit(lambda *a, m=m: m(**dict(zip(names, a)), **scalars))
        meas = teff.measure(lambda: step(*arrs), iters=iters, warmup=2)
        out[f"{backend}_us"] = meas.median_s * 1e6
        got = step(*arrs)
        for o in kern.outputs:
            np.testing.assert_allclose(np.asarray(got[o]), np.asarray(ref[o]),
                                       atol=1e-5)
    return out


def bench_porosity_coupled(n: int = 64, iters: int = 10, nsteps: int = 4,
                           march_axis: int | None = None):
    """Reactive porosity waves through the coupled (phi, Pe) engine."""
    from examples import porosity_waves as pw

    rows = {"n": n}

    def make(backend):
        cfg = pw.PorosityConfig(n=n, backend=backend)
        grid, phi, Pe = pw.init_state(cfg)
        dtau = pw.timestep(cfg, grid)
        step = jax.jit(pw.make_step(grid, cfg))
        return lambda: step(phi, Pe, dtau)

    b = _measure_backends(make, iters)
    rows["jnp_us"] = b["jnp"] * 1e6
    rows["pallas_us"] = b["pallas"] * 1e6
    rows["pallas_over_jnp"] = b["pallas_over_jnp"]

    cfg = pw.PorosityConfig(n=n, backend="pallas")
    grid, phi, Pe = pw.init_state(cfg)
    kern = pw.make_step(grid, cfg).kernels[0]
    rows["temporal"] = _fused_vs_sequential(
        kern, dict(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe),
        dict(dtau=pw.timestep(cfg, grid)), nsteps, iters)
    if march_axis is not None:
        rows["march"] = _march_rows(
            kern, dict(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe),
            dict(dtau=pw.timestep(cfg, grid)), march_axis, iters)
    return rows


def bench_gp_coupled(n: int = 32, iters: int = 10, nsteps: int = 2,
                     march_axis: int | None = None):
    """Gross-Pitaevskii through the fused coupled radius-2 kernel, plus
    the one-fused-launch vs two-launch comparison."""
    from examples import gross_pitaevskii as gp

    rows = {"n": n}

    def make(backend, fused=True):
        cfg = gp.GPConfig(n=n, backend=backend, fused=fused)
        grid, re, im, V = gp.init_state(cfg)
        dt = gp.timestep(grid)
        step = jax.jit(gp.make_step(grid, cfg))
        return lambda: step(re, im, dt, V)

    b = _measure_backends(make, iters)
    rows["jnp_us"] = b["jnp"] * 1e6
    rows["pallas_us"] = b["pallas"] * 1e6
    rows["pallas_over_jnp"] = b["pallas_over_jnp"]

    m2 = teff.measure(make("jnp", fused=False), iters=iters, warmup=2)
    rows["two_launch_us"] = m2.median_s * 1e6
    rows["fused_over_two_launch"] = rows["jnp_us"] / rows["two_launch_us"]

    cfg = gp.GPConfig(n=n, backend="pallas")
    grid, re, im, V = gp.init_state(cfg)
    dt = gp.timestep(grid)
    kern = gp.make_step(grid, cfg).kernels[0]
    inv2 = tuple(1.0 / d ** 2 for d in grid.spacing)
    rows["temporal"] = _fused_vs_sequential(
        kern, dict(re2=re, im2=im, re=re, im=im, V=V),
        dict(g=cfg.g, dt=dt, _dx2=inv2[0], _dy2=inv2[1], _dz2=inv2[2]),
        nsteps, iters)
    if march_axis is not None:
        rows["march"] = _march_rows(
            kern, dict(re2=re, im2=im, re=re, im=im, V=V),
            dict(g=cfg.g, dt=dt, _dx2=inv2[0], _dy2=inv2[1], _dz2=inv2[2]),
            march_axis, iters)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small grids / few iters (CI smoke)")
    ap.add_argument("--n-porosity", type=int, default=64)
    ap.add_argument("--n-gp", type=int, default=32)
    ap.add_argument("--nsteps", type=int, default=4)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json", default=None,
                    help="output path (default BENCH_solvers_p{N}_g{N}.json)")
    ap.add_argument("--skip-coupled", action="store_true",
                    help="translation-efficiency table only, no JSON")
    ap.add_argument("--march-axis", type=int, default=None,
                    help="also time the streamed (marching) coupled step "
                         "on both backends and check parity")
    args = ap.parse_args(argv)
    n_diff, n_gp_tr, tr_iters = 96, 48, 10
    if args.quick:
        args.n_porosity = min(args.n_porosity, 32)
        args.n_gp = min(args.n_gp, 16)
        args.iters = min(args.iters, 3)
        n_diff, n_gp_tr, tr_iters = 48, 24, 3

    d = bench_diffusion_translation(n=n_diff, iters=tr_iters)
    print(f"solvers_diffusion_translation,{d['framework_us']:.1f},"
          f"eff={d['translation_efficiency']:.3f}")
    g = bench_gp_translation(n=n_gp_tr, iters=tr_iters)
    print(f"solvers_gp_translation,{g['framework_us']:.1f},"
          f"eff={g['translation_efficiency']:.3f}")
    record = {"diffusion": d, "gp": g}
    if args.skip_coupled:
        return record

    p = bench_porosity_coupled(args.n_porosity, args.iters, args.nsteps,
                               march_axis=args.march_axis)
    print(f"solvers_porosity_coupled_{p['n']},{p['jnp_us']:.1f},"
          f"pallas/jnp={p['pallas_over_jnp']:.2f}")
    print(f"solvers_porosity_fused_k{p['temporal']['nsteps']},"
          f"{p['temporal']['fused_per_step_us']:.1f},"
          f"speedup={p['temporal']['fused_speedup']:.2f}")
    if "march" in p:
        print(f"solvers_porosity_march{p['march']['axis']},"
              f"{p['march']['jnp_us']:.1f},us")
    gc = bench_gp_coupled(args.n_gp, args.iters, max(2, args.nsteps // 2),
                          march_axis=args.march_axis)
    print(f"solvers_gp_coupled_{gc['n']},{gc['jnp_us']:.1f},"
          f"pallas/jnp={gc['pallas_over_jnp']:.2f}")
    print(f"solvers_gp_fused_vs_two_launch,{gc['jnp_us']:.1f},"
          f"ratio={gc['fused_over_two_launch']:.2f}")
    if "march" in gc:
        print(f"solvers_gp_march{gc['march']['axis']},"
              f"{gc['march']['jnp_us']:.1f},us")
    record["porosity_coupled"] = p
    record["gp_coupled"] = gc

    path = args.json or f"BENCH_solvers_p{p['n']}_g{gc['n']}.json"
    try:
        from ._meta import bench_meta
    except ImportError:
        from _meta import bench_meta
    with open(path, "w") as f:
        json.dump({"rows": record,
                   "backend": jax.default_backend(),
                   "note": ("pallas interpret-mode on non-TPU hosts; "
                            "ratios are correctness-path records there"),
                   "meta": bench_meta()},
                  f, indent=1)
    print(f"# wrote {path}")
    return record


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
