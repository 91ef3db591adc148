"""Reactive porosity waves — the paper §3's second translated solver family.

Pseudo-transient two-field compaction model (Raess et al. 2022 [5], 2-D):

    q         = -k(phi) (grad(Pe) - rho_g)      Darcy flux (staggered)
    dPe/dtau  = -(div q + Pe/eta)               effective pressure
    dphi/dtau = -(1 - phi) Pe/eta               porosity

A buoyant porosity anomaly focuses into an ascending wave. The coupled
(phi, Pe) update runs as ONE fused stencil launch through ``@parallel``
on either backend; staggered-grid fluxes use the ``d_xa``/``av_xa``
operators. Two equivalent formulations are provided:

  * ``flux_split=False`` (default): the face fluxes are intermediates
    inside the single coupled kernel — one launch per time step.
  * ``flux_split=True``: the fluxes are explicit *face-centered fields*
    (``qx``: (nx-1, ny), ``qy``: (nx, ny-1)) produced by a staggered
    ``@all``-write kernel and consumed, mixed-shape, by the cell update —
    the two-launch scheme that exercises the engine's staggered-field
    support end-to-end. Both produce identical physics.

Stencil geometry is *inferred*: no ``radius`` is declared anywhere — the
engine traces the update once and derives the (phi, Pe) footprint and
the staggered flux offsets itself. Boundary conditions are declared per
output (``--bc``) and fused into the engine's step (bitwise-equal to the
seed's explicit ``neumann0`` post-pass).

Convergence-driven mode (``--tol``): the pseudo-transient iteration runs
to *steady state* instead of a fixed step count — the coupled kernel
gains a fused ``max_abs_diff(Pe2, Pe)`` reduction epilogue (the residual
folds inside the same launch as the update; no separate norm pass) and
``core.iterate.solve_until`` drives the loop on device with a
``lax.while_loop``: zero host syncs between checks, ``--nt`` becomes the
iteration cap.

    PYTHONPATH=src python examples/porosity_waves.py [--n 128] [--nt 500]
        [--backend jnp|pallas] [--flux-split]
        [--bc neumann|dirichlet|periodic]
        [--tol 1e-6] [--check-every 10]
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import Grid, fd2d as fd, init_parallel_stencil, iterate
from repro.ir import BoundaryCondition


@dataclasses.dataclass(frozen=True)
class PorosityConfig:
    n: int = 128
    nt: int = 500
    npow: float = 3.0          # permeability exponent, k ~ phi^n
    phi0: float = 0.01         # background porosity
    dphi: float = 0.1          # relative anomaly amplitude
    eta: float = 1.0           # compaction viscosity
    rho_g: float = 30.0        # buoyancy contrast
    backend: str = "jnp"
    dtype: str = "float32"     # field STORAGE dtype; compute stays f32
    flux_split: bool = False
    bc: str = "neumann"        # neumann | dirichlet | periodic | none
    interpret: bool | None = None
    tol: float | None = None   # steady-state residual (None: fixed nt)
    check_every: int = 10      # residual cadence in --tol mode
    checkpoint_dir: str | None = None  # survivable --tol solves
    save_every: int = 10       # checks between checkpoints
    resume: bool = True        # restore from LATEST when present


def boundary_conditions(cfg: PorosityConfig) -> dict | None:
    """Per-output BC specs routed through the engine's fused path.

    ``neumann`` reproduces the seed's zero-flux post-pass; ``dirichlet``
    pins the faces to the far-field state (phi0, zero overpressure);
    ``none`` freezes the initial boundary ring (raw ``@inn`` semantics,
    the reference the parity tests post-process by hand).
    """
    if cfg.bc == "none":
        return None
    if cfg.bc == "neumann":
        return {"phi2": BoundaryCondition("neumann0"),
                "Pe2": BoundaryCondition("neumann0")}
    if cfg.bc == "dirichlet":
        return {"phi2": BoundaryCondition("dirichlet", value=cfg.phi0),
                "Pe2": BoundaryCondition("dirichlet", value=0.0)}
    if cfg.bc == "periodic":
        return {"phi2": BoundaryCondition("periodic"),
                "Pe2": BoundaryCondition("periodic")}
    raise ValueError(f"unknown bc {cfg.bc!r}")


def make_grid(cfg: PorosityConfig) -> Grid:
    return Grid((cfg.n, cfg.n), (10.0, 10.0))


def init_state(cfg: PorosityConfig):
    """Gaussian porosity anomaly low in the domain, zero overpressure."""
    grid = make_grid(cfg)
    x, y = grid.meshgrid()
    phi = cfg.phi0 + cfg.dphi * cfg.phi0 * jnp.exp(
        -((x - 5.0) ** 2 + (y - 2.0) ** 2) / 0.5)
    # storage rounding happens once, here — every later step computes in
    # f32 and rounds only on store (see README "Mixed precision")
    phi = phi.astype(jnp.dtype(cfg.dtype))
    Pe = jnp.zeros_like(phi)
    return grid, phi, Pe


def timestep(cfg: PorosityConfig, grid: Grid) -> float:
    dx, dy = grid.spacing
    return 0.1 * min(dx, dy) ** 2 / (cfg.phi0 ** cfg.npow * 4) * cfg.phi0 ** cfg.npow


def make_step(grid: Grid, cfg: PorosityConfig):
    """Build ``step(phi, Pe, dtau) -> (phi, Pe)``.

    The returned callable advances one pseudo-time step: the coupled
    stencil launch(es) followed by zero-flux boundaries. Its ``kernels``
    attribute exposes the underlying :class:`StencilKernel`s (the fused
    variant supports ``run_steps`` temporal blocking with the
    ``{phi2: phi, Pe2: Pe}`` double-buffer rotation).
    """
    dx, dy = grid.spacing
    phi0, npow, eta, rho_g = cfg.phi0, cfg.npow, cfg.eta, cfg.rho_g
    bc = boundary_conditions(cfg)
    ps = init_parallel_stencil(backend=cfg.backend, dtype=cfg.dtype,
                               ndims=2, interpret=cfg.interpret)

    if not cfg.flux_split:
        @ps.parallel(outputs=("phi2", "Pe2"),
                     rotations={"phi2": "phi", "Pe2": "Pe"}, bc=bc)
        def update(phi2, Pe2, phi, Pe, dtau):
            k = (phi / phi0) ** npow
            # staggered Darcy fluxes (x-faces / y-faces), in-kernel
            qx = -fd.av_xa(k) * fd.d_xa(Pe) / dx
            qy = -fd.av_ya(k) * (fd.d_ya(Pe) / dy
                                 - rho_g * (fd.av_ya(phi) - phi0))
            div_q = fd.d_xa(qx[:, 1:-1]) / dx + fd.d_ya(qy[1:-1, :]) / dy
            Pe_new = fd.inn(Pe) + dtau * (-(div_q + fd.inn(Pe) / eta))
            phi_new = fd.inn(phi) + dtau * (-(1.0 - fd.inn(phi)) * Pe_new / eta)
            return {"phi2": phi_new, "Pe2": Pe_new}

        def step(phi, Pe, dtau):
            out = update(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe, dtau=dtau)
            return out["phi2"], out["Pe2"]

        step.kernels = (update,)
        return step

    # Flux-split scheme: explicit face-centered flux fields. `fluxes`
    # writes its staggered outputs at full extent (`@all` semantics);
    # `update` consumes them mixed-shape next to the cell fields.
    @ps.parallel(outputs=("qx", "qy"))
    def fluxes(qx, qy, phi, Pe):
        k = (phi / phi0) ** npow
        return {"qx": -fd.av_xa(k) * fd.d_xa(Pe) / dx,
                "qy": -fd.av_ya(k) * (fd.d_ya(Pe) / dy
                                      - rho_g * (fd.av_ya(phi) - phi0))}

    @ps.parallel(outputs=("phi2", "Pe2"), bc=bc)
    def update(phi2, Pe2, phi, Pe, qx, qy, dtau):
        div_q = fd.d_xa(qx[:, 1:-1]) / dx + fd.d_ya(qy[1:-1, :]) / dy
        Pe_new = fd.inn(Pe) + dtau * (-(div_q + fd.inn(Pe) / eta))
        phi_new = fd.inn(phi) + dtau * (-(1.0 - fd.inn(phi)) * Pe_new / eta)
        return {"phi2": phi_new, "Pe2": Pe_new}

    nx, ny = grid.shape
    qx0 = jnp.zeros((nx - 1, ny), jnp.dtype(cfg.dtype))
    qy0 = jnp.zeros((nx, ny - 1), jnp.dtype(cfg.dtype))

    def step(phi, Pe, dtau):
        q = fluxes(qx=qx0, qy=qy0, phi=phi, Pe=Pe)
        out = update(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe,
                     qx=q["qx"], qy=q["qy"], dtau=dtau)
        return out["phi2"], out["Pe2"]

    step.kernels = (fluxes, update)
    return step


def solve_steady(cfg: PorosityConfig, grid: Grid, phi, Pe) -> tuple:
    """Device-resident steady-state drive from the given initial state:
    iterate the coupled kernel until ``max|Pe2 - Pe| < cfg.tol``
    (checked every ``cfg.check_every`` sweeps through the fused
    reduction epilogue — the residual never costs a second whole-array
    pass or a host round-trip), capped at ``cfg.nt`` sweeps. Returns
    (phi, Pe, iters, err)."""
    if cfg.flux_split:
        raise ValueError(
            "--tol drives the fused coupled kernel; the flux-split scheme "
            "splits the update over two launches and has no single kernel "
            "to attach the residual to — drop --flux-split"
        )
    if cfg.bc == "periodic":
        raise ValueError(
            "--tol needs the fused residual epilogue, which cannot ride a "
            "periodic-bc launch (the wrap scatter runs after it); use "
            "--bc neumann or dirichlet"
        )
    dtau = timestep(cfg, grid)
    kern = make_step(grid, cfg).kernels[0]
    rkern = kern.with_reductions({"err": "max_abs_diff(Pe2, Pe)"})
    ckpt = None
    if cfg.checkpoint_dir is not None:
        # survivable solve: async atomic checkpoints of the carry every
        # save_every checks; a killed run restarted with the same flags
        # resumes from LATEST (see README "Fault tolerance")
        ckpt = iterate.Checkpointing(cfg.checkpoint_dir,
                                     save_every=cfg.save_every,
                                     resume=cfg.resume)
    res = iterate.solve_until(
        rkern, dict(phi2=phi, Pe2=Pe, phi=phi, Pe=Pe), dict(dtau=dtau),
        tol=cfg.tol, max_iters=cfg.nt, check_every=cfg.check_every,
        checkpoint=ckpt)
    if res.resumed_from is not None:
        print(f"porosity wave: resumed from checkpoint step "
              f"{res.resumed_from} in {cfg.checkpoint_dir}")
    # rotation targets hold the newest state after the in-loop rotation
    return res.fields["phi"], res.fields["Pe"], int(res.iters), \
        float(res.err)


def solve(cfg: PorosityConfig = PorosityConfig()) -> dict:
    """Run ``cfg.nt`` pseudo-time steps (or, with ``cfg.tol``, iterate on
    device until steady state); returns fields + diagnostics."""
    iters, err = cfg.nt, None
    grid, phi, Pe = init_state(cfg)
    peak0_y = float(jnp.argmax(jnp.max(phi, axis=0)))
    if cfg.tol is not None:
        phi, Pe, iters, err = solve_steady(cfg, grid, phi, Pe)
    else:
        dtau = timestep(cfg, grid)
        step = jax.jit(make_step(grid, cfg))
        for it in range(cfg.nt):
            phi, Pe = step(phi, Pe, dtau)
            if (it + 1) % 50 == 0 and not bool(jnp.isfinite(phi).all()):
                raise FloatingPointError(f"diverged at step {it}")
    if not bool(jnp.isfinite(phi).all()):
        raise FloatingPointError(f"diverged by step {cfg.nt}")
    dy = grid.spacing[1]
    peak_y = float(jnp.argmax(jnp.max(phi, axis=0)))
    return {
        "grid": grid,
        "phi": phi,
        "Pe": Pe,
        "phi_min": float(phi.min()),
        "phi_max": float(phi.max()),
        "pe_absmax": float(jnp.abs(Pe).max()),
        "peak0_y": peak0_y * dy,
        "peak_y": peak_y * dy,
        "iters": iters,
        "residual": err,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--nt", type=int, default=500)
    ap.add_argument("--npow", type=float, default=3.0, help="k ~ phi^n")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"],
                    help="field storage dtype (stencil arithmetic stays "
                         "f32; bf16/f16 halve the bytes every sweep moves)")
    ap.add_argument("--flux-split", action="store_true",
                    help="explicit staggered flux fields (two launches)")
    ap.add_argument("--bc", default="neumann",
                    choices=["neumann", "dirichlet", "periodic"],
                    help="boundary condition fused into the engine step")
    ap.add_argument("--tol", type=float, default=None,
                    help="steady-state residual: iterate on device until "
                         "max|dPe| < tol (fused check, zero host syncs); "
                         "--nt becomes the iteration cap")
    ap.add_argument("--check-every", type=int, default=10,
                    help="residual cadence (steps per check) in --tol mode")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for atomic async checkpoints of the "
                         "--tol solve (restartable: see --resume)")
    ap.add_argument("--save-every", type=int, default=10,
                    help="checkpoint cadence in CHECKS (default 10: one "
                         "save per 10 residual checks)")
    ap.add_argument("--resume", dest="resume", action="store_true",
                    default=True,
                    help="resume from the LATEST checkpoint when one "
                         "exists (default)")
    ap.add_argument("--no-resume", dest="resume", action="store_false",
                    help="ignore existing checkpoints; start fresh")
    args = ap.parse_args(argv)
    if args.checkpoint_dir is not None and args.tol is None:
        ap.error("--checkpoint-dir requires --tol (checkpoints ride the "
                 "convergence-driven solve loop)")
    cfg = PorosityConfig(n=args.n, nt=args.nt, npow=args.npow,
                         backend=args.backend, dtype=args.dtype,
                         flux_split=args.flux_split,
                         bc=args.bc, tol=args.tol,
                         check_every=args.check_every,
                         checkpoint_dir=args.checkpoint_dir,
                         save_every=args.save_every, resume=args.resume)
    r = solve(cfg)
    steps = (f"{r['iters']} steps (tol={cfg.tol:g}, "
             f"residual={r['residual']:.2e})" if cfg.tol is not None
             else f"{cfg.nt} steps")
    print(f"porosity wave: {steps} on {r['grid'].shape} "
          f"[{cfg.backend}{'/flux-split' if cfg.flux_split else ''}"
          f"/bc={cfg.bc}]; "
          f"phi in [{r['phi_min']:.4f}, {r['phi_max']:.4f}]; "
          f"anomaly y: {r['peak0_y']:.2f} -> {r['peak_y']:.2f} (ascending)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
