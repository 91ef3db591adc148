"""The program's Fig. 1 solver, driven through its public entry points.

Each entry takes the run's context and returns what a driver needs: the
initial program state and ``advance`` (a number of steps per call) or
``solve`` (one solve to tolerance, without its host sync, which the
driver makes). Both are the timed path itself and trace under
``jax.jit``, so the rehearsal compiles them as they are for described
devices. ``outputs`` maps a program state to the arrays compared with the
reference, in the reference's layout.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp


def kernel(ctx, reductions=None):
    """The Fig. 1 ``@parallel`` kernel as users write it
    (``examples/quickstart.py``)."""
    from repro.core import fd3d as fd, init_parallel_stencil

    ps = init_parallel_stencil(backend="pallas", dtype=ctx.cfg["dtype"],
                               ndims=3, interpret=ctx.interpret)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions=reductions)
    def step(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2
            + fd.d2_zi(T) * _dz ** 2))}

    return step


def scalars(p) -> dict:
    """Python-float scalars, as the listing passes them."""
    _dx, _dy, _dz = p["inv_spacing"]
    return dict(lam=p["lam"], dt=p["dt"], _dx=_dx, _dy=_dy, _dz=_dz)


def _whole(state):
    return {"T": state["T"], "T2": state["T2"]}


def eager_step(ctx):
    """One eager ``@parallel`` call per step with Python-float scalars and
    the T/T2 swap: the Fig. 1 time loop."""
    step, sc = kernel(ctx), scalars(ctx.p)

    def advance(s):
        T2 = step(T2=s["T2"], T=s["T"], Ci=s["Ci"], **sc)
        return {"T": T2, "T2": s["T"], "Ci": s["Ci"]}

    return types.SimpleNamespace(
        state=ctx.initial(), advance=advance, steps_per_call=1,
        outputs=_whole)


def solve_until(ctx):
    """``core.iterate.solve_until`` with the fused ``max_abs_diff(T2, T)``
    check, every solve from the same initial state."""
    from repro.core import solve_until as solve

    conv = kernel(ctx, {"err": "max_abs_diff(T2, T)"})
    sc, t = scalars(ctx.p), ctx.traffic

    def run(s):
        res = solve(conv, s, sc, tol=t["tol"], max_iters=t["max_iters"],
                    check_every=t["check_every"])
        return res.fields, res.iters, res.err

    return types.SimpleNamespace(state=ctx.initial(), solve=run,
                                 outputs=_whole)


def elastic_chunk(ctx):
    """``distributed.elastic``'s chunk driver on a mesh of the chips,
    ``chunk_steps`` steps per call with tol 0, on per-rank state built on
    the devices."""
    from jax.sharding import AxisType

    cfg, t = ctx.cfg, ctx.traffic
    factors = tuple(cfg["mesh"])
    mesh = jax.make_mesh(factors, _AXES[:len(factors)],
                         (AxisType.Auto,) * len(factors),
                         devices=ctx.devices)
    solver, spec = _elastic_solver(ctx, mesh)
    state = ctx.initial_stacked(factors, spec)
    tol, block = jnp.float32(0.0), jnp.int32(t["chunk_steps"])

    def advance(s):
        out, _, _, _ = solver(s, tol, block)
        return out

    def outputs(s):
        return {k: _interior_global(s[k]) for k in ("T", "T2")}

    return types.SimpleNamespace(
        state=state, advance=advance, steps_per_call=t["chunk_steps"],
        outputs=outputs, mesh=mesh, interior_only=True)


_AXES = ("x", "y", "z")


def _elastic_solver(ctx, mesh):
    """The jitted chunk driver and the sharding of its stacked fields."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import elastic

    factors = tuple(ctx.cfg["mesh"])
    axes = mesh.axis_names
    conv = kernel(ctx, {"err": "max_abs_diff(T2, T)"})
    solver = elastic.make_elastic_solver(
        conv, scalars(ctx.p), mesh, factors, axes,
        tuple(ctx.cfg["exchange"]), check_every=ctx.traffic["check_every"])
    return solver, NamedSharding(mesh, P(*axes, None, None, None))


@jax.jit
def _interior_global(a):
    """Stacked rank blocks ``(fx, fy, fz, *local)`` to the global interior:
    each rank's owned cells, stitched in rank order."""
    f = a.shape[:3]
    inner = a[:, :, :, 1:-1, 1:-1, 1:-1]
    n = inner.shape[3:]
    return inner.transpose(0, 3, 1, 4, 2, 5).reshape(
        f[0] * n[0], f[1] * n[1], f[2] * n[2])
