"""The program's Stokes3D solver, driven as ``examples/stokes3d.py`` runs it:
its four ``@parallel`` launches (``compute_P``, ``compute_tau``,
``compute_dV``, ``compute_V``) as one iteration of
``core.iterate.solve_until``, the residual norms fused into the launches
that write them.

The state the reference builds from the seed holds every field in the low
corner of one face-extent array; the program's holds each at its own
staggered extent, cut from it once at set-up. A solve returns what one
more iteration reads, so the reference can take that step from it
(``yardstick/faults.extra_step``): the velocities, the pressure and the
stresses it computed, and the material and pseudo-time steps, which no
launch writes, as the solve was given them. It leaves out the fields an
iteration writes before it reads them (the divergence, residuals and
increments): holding all 21 fields of the last solve across the next one
does not fit in a v5e's 16 GB. ``outputs`` puts the compared fields back
in the reference's layout.
"""
from __future__ import annotations

import os
import sys
import types


def _example():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, "examples")
    if path not in sys.path:
        sys.path.insert(0, path)
    import stokes3d

    return stokes3d


def _config(ctx):
    """The example's configuration for the cell's: the same box, physics
    and cells (the inclusion's place is in the data)."""
    st, cfg = _example(), ctx.cfg
    if cfg["length"] != [cfg["length"][0]] * 3:
        raise ValueError("the example builds cubic boxes only")
    return st.StokesConfig(
        n=ctx.ref.cells(cfg), length=cfg["length"][0], mu0=cfg["mu0"],
        mui=cfg["mui"], rho_gi=cfg["rho_gi"],
        radius=cfg["inclusion"]["radius"], re=cfg["re"], r=cfg["r"],
        cfl=cfg["cfl"], smooth=cfg["smooth_steps"], backend="pallas",
        interpret=ctx.interpret)


def _program_state(ctx):
    """Each field of the seed's state at its own extent."""
    import jax

    ext = ctx.ref.extents(ctx.ref.cells(ctx.cfg))

    def own(full):
        return {k: full[k][tuple(slice(0, e) for e in ext[k])]
                for k in ctx.ref.STATE}

    if not ctx.abstract:
        return jax.jit(own)(ctx.initial())
    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
            for k, v in jax.eval_shape(own, ctx.initial()).items()}


def solve_until(ctx):
    """``core.iterate.solve_until`` over the four launches, every solve
    from the same initial state."""
    import jax
    import jax.numpy as jnp

    from repro.core import solve_until as solve

    st = _example()
    scfg, t = _config(ctx), ctx.traffic
    if list(st.spacing(scfg)) != list(ctx.p["spacing"]):
        raise ValueError(f"the example's spacing {st.spacing(scfg)} is not "
                         f"the reference's {ctx.p['spacing']}")
    kernels = st.make_kernels(scfg)
    err = st.residual_error(scfg.n)
    sc, keep, ref = st.scalars(scfg), ctx.ref.OUTPUTS, ctx.ref
    carried = [k for k in ref.STATE
               if k not in ref.FRESH and k not in ref.MATERIAL]
    shape = ctx.ref.layout(scfg.n)

    def run(s):
        res = solve(kernels, s, sc, tol=t["tol"], max_iters=t["max_iters"],
                    check_every=t["check_every"], error=err)
        out = {k: res.fields[k] for k in carried}
        out.update((k, s[k]) for k in ref.MATERIAL)
        return out, res.iters, res.err

    @jax.jit
    def outputs(s):
        return {k: jnp.pad(s[k], [(0, g - e)
                                  for g, e in zip(shape, s[k].shape)])
                for k in keep}

    return types.SimpleNamespace(state=_program_state(ctx), solve=run,
                                 outputs=outputs)
