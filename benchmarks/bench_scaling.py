"""Paper §3 scaling claim (95% parallel efficiency at 1024 GPUs via hidden
communication) plus the checkpointing fault-tolerance cost model,
measured on fake CPU devices through the real engine. Every child forces
``JAX_PLATFORMS=cpu`` with fake host devices, so this measures nothing
on a TPU, even on a machine that has one; the multi-chip path on the
chip is ``chip_smoke.py --chips 4``:

* **Weak scaling** (``scale_seq_r{R}`` / ``scale_ovl_r{R}`` rows): fixed
  local block per rank, domain grows with the rank count; one
  ``overlap.sequential_step`` vs ``overlap.overlapped_step`` timing per
  mesh size via ``shard_map`` — the same code path the distributed tests
  and ``elastic_solve_until`` drive.
* **Checkpoint overhead** (``ckpt_m{M}`` rows): the chunked
  ``solve_until`` driver with async checkpointing at save-every-M checks
  (M in {10, 100}) vs the uninterrupted single-``while_loop`` solve
  (``ckpt_minf``).  Per-step times are the difference of a LONG and a
  SHORT run, so one-off jit compile cost cancels and the rows measure
  pure steady-state step+save cost.  The PR-6 acceptance bar: the
  ``ckpt_m100`` row must sit within 5% of ``ckpt_minf``
  (``--check-overhead`` turns that into a hard exit code).

Each measurement runs in a subprocess so the parent keeps one device and
the XLA device-count flag can vary per row.  Rows carry ``name`` / ``n``
/ ``nsteps`` / ``per_step_s`` so ``benchmarks/compare.py`` guards them
like any other teff-family record (``BENCH_scaling*.json``).

    PYTHONPATH=src python benchmarks/bench_scaling.py [--quick] [--json]
        [--check-overhead]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

try:
    from ._meta import bench_meta   # imported as benchmarks.bench_scaling
except ImportError:
    from _meta import bench_meta    # run as a script

_SCALE_CHILD = r"""
import json, os, numpy as np, jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core import init_parallel_stencil, fd3d as fd
from repro.distributed import overlap
from repro.launch.mesh import make_mesh
import repro.core.teff as teff

n_dev = int(jax.device_count())
LOC = int(os.environ["BENCH_LOC"])
ITERS = int(os.environ["BENCH_ITERS"])
mesh = make_mesh((n_dev,), ("x",))
ps = init_parallel_stencil(backend="jnp", ndims=3)

@ps.parallel(outputs=("T2",))
def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}

sc = dict(lam=1.0, dt=1e-4, _dx=1.0, _dy=1.0, _dz=1.0)
rng = np.random.RandomState(0)
# weak scaling: fixed local block (planes of a 3-D bar), domain grows
# with devices
shape = (n_dev, LOC + 2, 64, 64)
T = jnp.asarray(rng.rand(*shape), jnp.float32)
Ci = jnp.ones_like(T)

def make(step_fn):
    def local(Tl, Cl):
        Tl, Cl = Tl[0], Cl[0]
        out, _ = step_fn(kern, dict(T2=Tl, T=Tl, Ci=Cl), sc, ("T",), ("x",))
        return out[None]
    f = shard_map(local, mesh=mesh, in_specs=(P("x"), P("x")),
                  out_specs=P("x"), check_vma=False)
    return jax.jit(f)

res = {}
for name, fn in [("sequential", overlap.sequential_step),
                 ("overlapped", overlap.overlapped_step)]:
    step = make(fn)
    m = teff.measure(lambda: step(T, Ci), iters=ITERS, warmup=3)
    res[name] = m.median_s
print("RESULT " + json.dumps(res))
"""

_CKPT_CHILD = r"""
import json, os, shutil, tempfile, time
import jax.numpy as jnp
from repro.core import init_parallel_stencil, fd3d as fd, iterate

N = int(os.environ["BENCH_N"])
SHORT = int(os.environ["BENCH_SHORT"])
LONG = int(os.environ["BENCH_LONG"])
M = int(os.environ["BENCH_M"])          # <= 0: no checkpointing

ps = init_parallel_stencil(backend="jnp", ndims=3)

@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def kern(T2, T, dt):
    return {"T2": fd.inn(T) + dt * (fd.d2_xi(T) + fd.d2_yi(T)
                                    + fd.d2_zi(T))}

T0 = jnp.zeros((N, N, N), jnp.float32).at[N // 2, N // 2, N // 2].set(1.0)

def run(iters):
    ck, tmp = None, None
    if M > 0:
        tmp = tempfile.mkdtemp(prefix="bench_ck_")
        ck = iterate.Checkpointing(tmp, save_every=M, resume=False,
                                   blocking=False)
    t0 = time.perf_counter()
    res = iterate.solve_until(kern, dict(T2=T0, T=T0), dict(dt=1e-4),
                              tol=0.0, max_iters=iters, check_every=1,
                              checkpoint=ck)
    n_done = int(res.iters)          # block: the plain path is async
    dt = time.perf_counter() - t0
    assert n_done == iters, (n_done, iters)
    if tmp is not None:
        shutil.rmtree(tmp, ignore_errors=True)
    return dt

# LONG - SHORT cancels the (identical) jit compile of the two runs,
# leaving LONG-SHORT steps of steady-state step + amortized save cost.
t_short = run(SHORT)
t_long = run(LONG)
per_step = (t_long - t_short) / (LONG - SHORT)
print("RESULT " + json.dumps({"per_step_s": per_step}))
"""


def _run_child(code: str, n_dev: int, env_extra: dict) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    env.update({k: str(v) for k, v in env_extra.items()})
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if p.returncode != 0:
        raise RuntimeError(p.stderr[-2000:])
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError("no RESULT line in child output")


def weak_scaling_rows(devices, loc: int, iters: int) -> list[dict]:
    rows, base = [], None
    for n in devices:
        r = _run_child(_SCALE_CHILD, n,
                       {"BENCH_LOC": loc, "BENCH_ITERS": iters})
        seq, ovl = r["sequential"], r["overlapped"]
        if base is None:
            base = ovl
        eff = base / ovl  # weak scaling: perfect = 1.0
        rows.append({"name": f"scale_seq_r{n}", "n": loc, "nsteps": iters,
                     "per_step_s": seq, "ranks": n})
        rows.append({"name": f"scale_ovl_r{n}", "n": loc, "nsteps": iters,
                     "per_step_s": ovl, "ranks": n,
                     "weak_efficiency": eff, "overlap_gain": seq / ovl})
        print(f"scale r={n}: seq {seq*1e6:.0f}us ovl {ovl*1e6:.0f}us "
              f"eff={eff:.3f} overlap_gain={seq/ovl:.3f}")
    return rows


def checkpoint_rows(n: int, short: int, long_: int,
                    save_everys=(10, 100), repeats: int = 3) -> list[dict]:
    """``ckpt_m{M}`` rows vs the ``ckpt_minf`` no-checkpoint baseline;
    min of ``repeats`` child runs per configuration (the noise floor —
    medians still carry scheduler jitter comparable to the 5% gate)."""

    def measure(m):
        vals = [_run_child(_CKPT_CHILD, 1,
                           {"BENCH_N": n, "BENCH_SHORT": short,
                            "BENCH_LONG": long_, "BENCH_M": m})["per_step_s"]
                for _ in range(repeats)]
        return min(vals)

    base = measure(0)
    rows = [{"name": "ckpt_minf", "n": n, "nsteps": long_ - short,
             "per_step_s": base}]
    print(f"ckpt m=inf: {base*1e6:.0f}us/step (no checkpointing)")
    for m in save_everys:
        t = measure(m)
        frac = t / base - 1.0
        rows.append({"name": f"ckpt_m{m}", "n": n,
                     "nsteps": long_ - short, "per_step_s": t,
                     "save_every": m, "overhead_frac": frac})
        print(f"ckpt m={m}: {t*1e6:.0f}us/step "
              f"(overhead {frac:+.1%} vs no-checkpoint)")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Weak scaling and checkpoint overhead on fake CPU "
                    "devices (every child runs with JAX_PLATFORMS=cpu: "
                    "nothing here runs on a TPU; the on-chip multi-chip "
                    "check is chip_smoke.py --chips 4)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 1-2 ranks, short runs, 1 repeat")
    ap.add_argument("--json", action="store_true",
                    help="record rows to BENCH_scaling_r{max}.json")
    ap.add_argument("--check-overhead", action="store_true",
                    help="exit 1 unless the ckpt_m100 row is within 5%% "
                         "of the no-checkpoint baseline")
    args = ap.parse_args(argv)

    if args.quick:
        devices, loc, iters = (1, 2), 32, 5
        n, short, long_, repeats = 32, 50, 250, 1
    else:
        devices, loc, iters = (1, 2, 4, 8), 64, 10
        n, short, long_, repeats = 64, 100, 500, 3

    rows = weak_scaling_rows(devices, loc, iters)
    rows += checkpoint_rows(n, short, long_, repeats=repeats)

    if args.json:
        path = f"BENCH_scaling_r{max(devices)}.json"
        with open(path, "w") as f:
            json.dump({"rows": rows, "meta": bench_meta()}, f, indent=1)
        print(f"wrote {path}")

    if args.check_overhead:
        m100 = next(r for r in rows if r["name"] == "ckpt_m100")
        if m100["overhead_frac"] >= 0.05:
            print(f"FAIL: save-every-100 checkpoint overhead "
                  f"{m100['overhead_frac']:.1%} >= 5%")
            return 1
        print(f"checkpoint overhead gate OK: "
              f"{m100['overhead_frac']:+.1%} < 5%")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
