#!/usr/bin/env python3
"""On-chip smoke test: drive the stencil engine's main path once on a TPU
and check every result against a plain reference run on the same chip.

    python3 chip_smoke.py              # one chip, phases (a)-(d)
    python3 chip_smoke.py --chips 4    # four chips: elastic 2x2x1 solve
                                       # (512^3 interior per chip) against
                                       # the same grid on one chip

One-chip phases, all through the public API (``init_parallel_stencil``
with ``backend="pallas", interpret=False``, ``@parallel``, ``run_steps``,
``solve_until``, ``serve.SimulationServer``):

  (a) the paper's Fig. 1 diffusion at 512^3 f32 for 100 steps, against
      the plain-jnp reference ``kernels.ref.diffusion3d_step``;
  (b) ``solve_until`` with the fused ``max_abs_diff(T2, T)`` check at
      512^3, against a host loop of single steps and a separate norm;
  (c) one ``run_steps(4)`` launch against 4 single steps;
  (d) a few requests through ``SimulationServer`` at a 128^3 bucket (the
      served path runs the kernel's jnp twin), against ``solve_until``.

Each phase prints one JSON line (shapes, max error and its bound, compile
and wall seconds, whether the compiled text holds a ``tpu_custom_call``).
The last line is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed on a TPU. Without a TPU the script exits non-zero
before any work. ``--rehearse N`` runs the same phases at N^3 on whatever
backend JAX has (Pallas in interpret mode off the TPU) and never prints
the ``ok`` line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def fail(msg: str, code: int = 1) -> "NoReturn":  # noqa: F821
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def fig1_kernel(ps, reductions=None):
    from repro.core import fd3d as fd

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions=reductions)
    def step(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2
            + fd.d2_zi(T) * _dz ** 2))}

    return step


def fig1_state(shape, seed: int):
    """Fig. 1's initial state (T = 1.7 plus a Gaussian hot spot of width
    0.1, Ci = 1/c0) on the unit cube, the spot's centre and amplitude
    drawn from ``seed``, and its scalars."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Grid

    lam, c0 = 1.0, 2.0
    grid = Grid(tuple(shape), (1.0, 1.0, 1.0))
    rng = np.random.default_rng(seed)
    centre = rng.uniform(0.4, 0.6, 3)
    amp = rng.uniform(0.5, 1.5)

    def hot_spot():   # jitted: no full-size coordinate arrays on the chip
        r2 = sum((x - c) ** 2 for x, c in zip(grid.meshgrid(), centre))
        return 1.7 + amp * jnp.exp(-r2 / (2 * 0.1 ** 2))

    T = jax.jit(hot_spot)()
    Ci = jnp.full(grid.shape, 1.0 / c0, jnp.float32)
    _dx, _dy, _dz = grid.inv_spacing
    scalars = dict(lam=lam, dt=grid.stable_diffusion_dt(lam / c0),
                   _dx=_dx, _dy=_dy, _dz=_dz)
    return {"T2": T, "T": T, "Ci": Ci}, scalars


def compile_timed(fn, *args):
    """(compiled, compile seconds, whether a Pallas TPU kernel is in it)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return (compiled, time.perf_counter() - t0,
            "tpu_custom_call" in compiled.as_text())


def run_timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def max_err(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def check(phase: str, rec: dict, err: float, bound: float,
          kernel_expected: bool, why: str | None = None) -> None:
    """Print the phase's line; exit non-zero unless it passed. ``why``
    names a failure found before the error comparison."""
    import numpy as np

    if why is None and kernel_expected and not rec.get("tpu_custom_call"):
        why = "no tpu_custom_call in the compiled text"
    if why is None and not (np.isfinite(err) and err <= bound):
        why = f"max error {err} over the bound {bound}"
    rec.update(phase=phase, max_err=err, bound=bound, ok=why is None)
    if why is not None:
        rec["why"] = why
    emit(rec)
    if why is not None:
        fail(f"phase {phase}: {why}")


# f32 agreement bound between the kernel and its references: the same
# expression, reassociated by a different compiler, over <= 100 steps
ATOL = 2e-5


def phase_a(ps, n: int, nt: int, seed: int, on_tpu: bool) -> None:
    import jax

    from repro.kernels import ref

    step = fig1_kernel(ps)
    f, s = fig1_state((n,) * 3, seed)

    def march(stepper):
        def run(T2, T, Ci):
            def body(_, c):
                T2, T = c
                return T, stepper(T2, T, Ci)
            return jax.lax.fori_loop(0, nt, body, (T2, T))[1]
        return run

    kern = march(lambda T2, T, Ci: step(T2=T2, T=T, Ci=Ci, **s))
    plain = march(lambda T2, T, Ci: ref.diffusion3d_step(
        T2, T, Ci, s["lam"], s["dt"], s["_dx"], s["_dy"], s["_dz"]))
    args = (f["T2"], f["T"], f["Ci"])
    ck, c_s, has_kernel = compile_timed(kern, *args)
    cr, cr_s, _ = compile_timed(plain, *args)
    out, w_s = run_timed(ck, *args)
    want, wr_s = run_timed(cr, *args)
    check("a", {"what": f"Fig. 1 diffusion, {nt} steps, pallas vs "
                        "kernels/ref.py diffusion3d_step",
                "shape": [n] * 3, "dtype": "float32", "compile_s": c_s,
                "wall_s": w_s, "ref_compile_s": cr_s, "ref_wall_s": wr_s,
                "tpu_custom_call": has_kernel, "block": list(
                    next(iter(step.launch_info.values()))["block"])},
          max_err(out, want), ATOL, on_tpu)


def phase_b(ps, n: int, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import iterate, solve_until

    every, max_iters = 10, 40
    conv = fig1_kernel(ps, {"err": "max_abs_diff(T2, T)"})
    plain = conv.with_reductions(None)
    f, s = fig1_state((n,) * 3, seed)

    # host loop: single steps, a separate norm at every check
    one = jax.jit(lambda T2, T, Ci: plain(T2=T2, T=T, Ci=Ci, **s))
    norm = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))
    T2, T, errs, host = f["T2"], f["T"], {}, {}
    for it in range(1, max_iters + 1):
        T2, T = T, one(T2, T, f["Ci"])
        if it % every == 0:
            errs[it] = float(norm(T, T2))
            host[it] = T
    # a tolerance between two checks' errors: the solve must stop at 30
    tol = 0.5 * (errs[20] + errs[30])

    solver = iterate.make_solver(conv, s, check_every=every)
    _, c_s, has_kernel = compile_timed(solver, f, jnp.float32(tol),
                                       jnp.int32(max_iters))
    t0 = time.perf_counter()
    res = solve_until(conv, f, s, tol=tol, max_iters=max_iters,
                      check_every=every)
    iters = int(res.iters)   # the solve's one host sync
    w_s = time.perf_counter() - t0
    rec = {"what": "solve_until, fused max_abs_diff(T2, T) every "
                   f"{every} steps, tol between the errors at steps 20 and "
                   "30, vs a host loop of single steps + separate norm",
           "shape": [n] * 3, "iters": iters, "err": float(res.err),
           "host_err": errs.get(iters), "compile_s": c_s,
           "wall_s_first_call": w_s, "tpu_custom_call": has_kernel}
    if iters != 30:
        check("b", rec, float("inf"), ATOL, on_tpu,
              why=f"stopped at {iters} steps, expected 30")
    err = max(max_err(res.output(conv), host[30]),
              abs(float(res.err) - errs[30]))
    check("b", rec, err, ATOL, on_tpu)


def phase_c(ps, n: int, seed: int, on_tpu: bool) -> None:
    import jax

    k = 4
    step = fig1_kernel(ps)
    f, s = fig1_state((n,) * 3, seed)

    def fused(T2, T, Ci):
        return step.run_steps(k, T2=T2, T=T, Ci=Ci, **s)

    def seq(T2, T, Ci):
        for _ in range(k):
            T2, T = T, step(T2=T2, T=T, Ci=Ci, **s)
        return T

    args = (f["T2"], f["T"], f["Ci"])
    cf, c_s, has_kernel = compile_timed(fused, *args)
    cs, cs_s, _ = compile_timed(seq, *args)
    out, w_s = run_timed(cf, *args)
    want, ws_s = run_timed(cs, *args)
    check("c", {"what": f"one run_steps({k}) launch vs {k} single steps",
                "shape": [n] * 3, "compile_s": c_s, "wall_s": w_s,
                "seq_compile_s": cs_s, "seq_wall_s": ws_s,
                "tpu_custom_call": has_kernel},
          max_err(out, want), ATOL, on_tpu)


def phase_d(ps, n: int, seed: int) -> None:
    import numpy as np

    from repro.core import solve_until
    from repro.serve import ServePolicy, SimulationServer, SolveRequest

    every = 10
    conv = fig1_kernel(ps, {"err": "max_abs_diff(T2, T)"})
    f, s = fig1_state((n,) * 3, seed)
    pol = ServePolicy(max_batch=4, chunk_steps=4 * every, check_every=every)
    reqs = []
    for i in range(4):
        sc = {**s, "dt": s["dt"] * (0.5 + 0.1 * i)}
        # a tolerance between the errors at steps 10 and 20: each request
        # converges at its second check
        e = [float(solve_until(conv, f, sc, tol=0.0, max_iters=k,
                               check_every=every).err) for k in (10, 20)]
        reqs.append((sc, 0.5 * (e[0] + e[1])))
    host = {k: np.asarray(v) for k, v in f.items()}
    t0 = time.perf_counter()
    with SimulationServer(conv, pol) as server:
        tickets = [server.submit(SolveRequest(
            fields=host, scalars=sc, tol=tol, max_iters=400))
            for sc, tol in reqs]
        outs = [t.result(timeout=600.0) for t in tickets]
    w_s = time.perf_counter() - t0
    err, iters, ref_iters = 0.0, [], []
    for (sc, tol), o in zip(reqs, outs):
        want = solve_until(conv, f, sc, tol=tol, max_iters=400,
                           check_every=every)
        iters.append(o["iters"])
        ref_iters.append(int(want.iters))
        err = max(err, max_err(o["fields"]["T"], want.output(conv)))
    check("d", {"what": f"{len(reqs)} requests through SimulationServer; "
                        "the served batch runs the kernel's jnp twin "
                        "(core/iterate.py batchable_kernel), compared with "
                        "pallas solve_until",
                "shape": [n] * 3, "requests": len(reqs), "iters": iters,
                "wall_s": w_s, "served_path": "jnp twin"},
          err, ATOL, kernel_expected=False,
          why=None if iters == ref_iters else
          f"served {iters} steps, solve_until {ref_iters}")


def four_chips(ps, n: int, seed: int, on_tpu: bool) -> None:
    """elastic_solve_until on a 2x2x1 mesh, an n^3 interior per chip,
    against the plain-jnp Fig. 1 step (``kernels/ref.py``) over the same
    global grid on one chip.

    The reference is a host loop of single steps, each donating its write
    buffer: 9.48 GB of HBM at n=512 (compiled for a v5e). ``solve_until``
    over that grid would need 19.45 GB, over the chip's 15.75 GB."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed import elastic
    from repro.kernels import ref
    from repro.launch.mesh import make_mesh

    factors, every, max_iters = (2, 2, 1), 10, 20
    axes = elastic.DEFAULT_AXES[:len(factors)]
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs four devices, JAX has {len(devs)}")
    shape = (2 * n + 2, 2 * n + 2, n + 2)
    conv = fig1_kernel(ps, {"err": "max_abs_diff(T2, T)"})
    f, s = fig1_state(shape, seed)
    host = {k: np.asarray(v) for k, v in f.items()}
    del f

    step = jax.jit(lambda T2, T, Ci: ref.diffusion3d_step(
        T2, T, Ci, s["lam"], s["dt"], s["_dx"], s["_dy"], s["_dz"]),
        donate_argnums=0)
    t0 = time.perf_counter()
    T2, T, Ci = (jnp.asarray(host[k]) for k in ("T2", "T", "Ci"))
    for _ in range(max_iters):
        T2, T = T, step(T2, T, Ci)
    want, want_err = np.asarray(T), float(jnp.max(jnp.abs(T - T2)))
    one_s = time.perf_counter() - t0
    del T2, T, Ci

    # the decomposed state: one rank block per chip
    mesh = make_mesh(factors, axes)
    stacked = elastic.remesh(
        elastic.decompose_fields(host, factors, 1), mesh,
        {k: P(*axes, None, None, None) for k in host})
    shard_devs = sorted(str(sh.device)
                        for sh in stacked["T"].addressable_shards)
    local = list(stacked["T"].addressable_shards[0].data.shape[len(factors):])
    distinct = len(set(shard_devs)) == 4
    platforms = sorted({d.platform for d in mesh.devices.flat})
    # the chunk driver elastic_solve_until runs, compiled for this mesh
    solver = elastic.make_elastic_solver(conv, s, mesh, factors, axes,
                                         ("T",), check_every=every)
    _, c_s, has_kernel = compile_timed(solver, stacked, jnp.float32(0.0),
                                       jnp.int32(max_iters))
    del stacked

    t0 = time.perf_counter()
    res = elastic.elastic_solve_until(
        conv, host, s, factors=factors, tol=0.0, max_iters=max_iters,
        exchange=("T",), check_every=every, radius=1)
    got = np.asarray(res.output(conv))
    el_s = time.perf_counter() - t0
    rec = {"what": "elastic_solve_until on a 2x2x1 mesh vs kernels/ref.py "
                   "diffusion3d_step over the same global grid on one chip",
           "global_shape": list(shape), "local_shape": local,
           "iters": [int(res.iters), max_iters], "err": float(res.err),
           "ref_err": want_err, "shard_devices": shard_devs,
           "mesh_platforms": platforms, "compile_s": c_s,
           "tpu_custom_call": has_kernel, "elastic_wall_s": el_s,
           "one_chip_wall_s": one_s}
    why = None
    if not distinct or (on_tpu and platforms != ["tpu"]):
        why = "shards do not sit on four distinct TPU devices"
    elif int(res.iters) != max_iters:
        why = "iteration count differs"
    check("4chips", rec,
          max(max_err(got, want), abs(float(res.err) - want_err)), ATOL,
          on_tpu, why=why)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip elastic solve and its "
                         "one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=None, metavar="N",
                    help="run the phases at N^3 on any backend (interpret "
                         "mode off the TPU); never prints the ok line")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.rehearse is None:
        fail(f"JAX found no TPU (devices()[0].platform = {dev.platform!r}); "
             "this smoke test runs on the chip only", code=2)

    from repro.compile_cache import enable_compile_cache
    from repro.core import init_parallel_stencil

    cache = enable_compile_cache()
    emit({"device": {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())},
          "jax": jax.__version__, "compile_cache": cache})
    ps = init_parallel_stencil(backend="pallas", dtype="float32", ndims=3,
                               interpret=not on_tpu)
    n = 512 if args.rehearse is None else args.rehearse
    if args.chips == 4:
        four_chips(ps, n, args.seed, on_tpu)
    else:
        phase_a(ps, n, 100, args.seed, on_tpu)
        phase_b(ps, n, args.seed, on_tpu)
        phase_c(ps, n, args.seed, on_tpu)
        phase_d(ps, 128 if args.rehearse is None else args.rehearse,
                args.seed)
    if args.rehearse is not None:
        print("chip_smoke: rehearsal passed (no result: not a chip run)",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
