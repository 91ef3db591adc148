"""3-D incompressible Stokes flow by the accelerated pseudo-transient method.

ParallelStencil.jl's miniapp ``Stokes3D`` (Räss, Utkin, Duretz, Omlin &
Podladchikov 2022, "Assessing the robustness and scalability of the
accelerated pseudo-transient method", GMD 15, 5757): viscous flow around a
weak, buoyant spherical inclusion in a box with free-slip walls,

    div(tau) - grad(P) + rho g = 0,    div(V) = 0,    tau = 2 mu eps(V),

iterated to steady state in pseudo-time. The fields live on a staggered
grid of ``n`` cells per axis: pressure, divergence, normal stresses and
the material fields on cells ``(n, n, n)``, the velocities on faces
(``Vx`` is ``(n+1, n, n)``), the shear stresses on edges (``txy`` is
``(n-1, n-1, n-2)``) and the residuals on the inner faces (``Rx`` is
``(n-1, n-2, n-2)``).

One pseudo-transient iteration is four dependent ``@parallel`` launches,
``compute_P -> compute_tau -> compute_dV -> compute_V``, each reading what
the launches before it wrote; ``compute_V`` fuses the free-slip walls
(``neumann0`` on the tangential axes of each velocity component). The
whole solve runs through ``core.iterate.solve_until`` with the four
launches as one iteration: the squared sums of the residuals and the
divergence ride the launches that write them as fused ``sum_sq``
reductions, and

    err = max(|Rx|/len(Rx), |Ry|/len(Ry), |Rz|/len(Rz), |divV|/len(divV))

(``|.|`` the 2-norm) is checked every ``--check-every`` iterations on
the device, with one host sync at the end.

    PYTHONPATH=src python examples/stokes3d.py [--n 31] [--tol 1e-6]
        [--max-iters 2000] [--check-every 10] [--backend jnp|pallas]
"""
from __future__ import annotations

import argparse
import dataclasses
import math

import jax.numpy as jnp

from repro.core import fd3d as fd, init_parallel_stencil, iterate
from repro.ir import BoundaryCondition


@dataclasses.dataclass(frozen=True)
class StokesConfig:
    n: int = 31                   # cells per axis, boundary cells included
    length: float = 10.0          # box edge, lx = ly = lz
    mu0: float = 1.0              # matrix shear viscosity
    mui: float = 1e-3             # inclusion shear viscosity
    rho_gi: float = 1.0           # inclusion buoyancy (rho g)
    radius: float = 1.0           # inclusion radius
    centre: tuple = (0.0, 0.0, 0.0)   # inclusion centre less the box centre
    re: float = 5 * math.pi       # numerical Reynolds number
    r: float = 1.0                # bulk-to-shear modulus ratio
    cfl: float = 0.8 / math.sqrt(3)
    smooth: int = 10              # diffusion steps smoothing the viscosity
    backend: str = "jnp"
    interpret: bool | None = None
    tol: float = 1e-6
    max_iters: int = 2000
    check_every: int = 10


def spacing(cfg: StokesConfig) -> tuple[float, float, float]:
    """Cell spacing: ``n`` cell centres span the box, ``l / (n - 1)``."""
    return (cfg.length / (cfg.n - 1),) * 3


def shapes(n: int) -> dict[str, tuple[int, int, int]]:
    """Every field's extent on the staggered grid of ``n`` cells."""
    cell = (n, n, n)
    out = {k: cell for k in ("Pt", "divV", "txx", "tyy", "tzz", "Mus",
                             "Rog", "Gdt", "dt_Rho")}
    for a, v in enumerate("xyz"):
        out["V" + v] = tuple(n + 1 if b == a else n for b in range(3))
        out["R" + v] = out["dV" + v] = tuple(n - 1 if b == a else n - 2
                                             for b in range(3))
    for a, b, name in ((0, 1, "txy"), (0, 2, "txz"), (1, 2, "tyz")):
        out[name] = tuple(n - 1 if c in (a, b) else n - 2 for c in range(3))
    return out


def _stencil(cfg: StokesConfig):
    return init_parallel_stencil(backend=cfg.backend, ndims=3,
                                 interpret=cfg.interpret)


def make_kernels(cfg: StokesConfig):
    """The four launches of one pseudo-transient iteration, in order."""
    ps = _stencil(cfg)

    @ps.parallel(outputs=("divV", "Pt"), reductions={"ss_divV": "sum_sq(divV)"})
    def compute_P(divV, Pt, Vx, Vy, Vz, Gdt, r, dx, dy, dz):
        div = fd.d_xa(Vx) / dx + fd.d_ya(Vy) / dy + fd.d_za(Vz) / dz
        return {"divV": div, "Pt": Pt - r * Gdt * div}

    @ps.parallel(outputs=("txx", "tyy", "tzz", "txy", "txz", "tyz"))
    def compute_tau(txx, tyy, tzz, txy, txz, tyz, Vx, Vy, Vz, divV, Mus, Gdt,
                    dx, dy, dz):
        den = Gdt / Mus + 1.0

        def normal(t, dv):
            return (t + 2.0 * Gdt * (dv - divV / 3.0)) / den

        def shear(t, av, dv):
            g = av(Gdt)
            return (t + 2.0 * g * (0.5 * dv)) / (g / av(Mus) + 1.0)

        return {
            "txx": normal(txx, fd.d_xa(Vx) / dx),
            "tyy": normal(tyy, fd.d_ya(Vy) / dy),
            "tzz": normal(tzz, fd.d_za(Vz) / dz),
            "txy": shear(txy, fd.av_xyi, fd.d_yi(Vx) / dy + fd.d_xi(Vy) / dx),
            "txz": shear(txz, fd.av_xzi, fd.d_zi(Vx) / dz + fd.d_xi(Vz) / dx),
            "tyz": shear(tyz, fd.av_yzi, fd.d_zi(Vy) / dz + fd.d_yi(Vz) / dy),
        }

    @ps.parallel(outputs=("Rx", "Ry", "Rz", "dVx", "dVy", "dVz"),
                 reductions={"ss_Rx": "sum_sq(Rx)", "ss_Ry": "sum_sq(Ry)",
                             "ss_Rz": "sum_sq(Rz)"})
    def compute_dV(Rx, Ry, Rz, dVx, dVy, dVz, Pt, Rog, txx, tyy, tzz, txy, txz,
                   tyz, dt_Rho, dx, dy, dz):
        rx = (fd.d_xi(txx) / dx + fd.d_ya(txy) / dy + fd.d_za(txz) / dz
              - fd.d_xi(Pt) / dx)
        ry = (fd.d_yi(tyy) / dy + fd.d_xa(txy) / dx + fd.d_za(tyz) / dz
              - fd.d_yi(Pt) / dy)
        rz = (fd.d_zi(tzz) / dz + fd.d_xa(txz) / dx + fd.d_ya(tyz) / dy
              - fd.d_zi(Pt) / dz + fd.av_zi(Rog))
        return {"Rx": rx, "Ry": ry, "Rz": rz,
                "dVx": fd.av_xi(dt_Rho) * rx, "dVy": fd.av_yi(dt_Rho) * ry,
                "dVz": fd.av_zi(dt_Rho) * rz}

    # free slip: the normal velocity stays 0 on the walls (the boundary
    # faces are never written) and the tangential gradients vanish
    free_slip = {v: BoundaryCondition("neumann0", axes=axes) for v, axes in
                 (("Vx", (1, 2)), ("Vy", (0, 2)), ("Vz", (0, 1)))}

    @ps.parallel(outputs=("Vx", "Vy", "Vz"), bc=free_slip)
    def compute_V(Vx, Vy, Vz, dVx, dVy, dVz):
        return {"Vx": fd.inn(Vx) + dVx, "Vy": fd.inn(Vy) + dVy,
                "Vz": fd.inn(Vz) + dVz}

    return compute_P, compute_tau, compute_dV, compute_V


def residual_error(n: int):
    """The convergence scalar over the merged reductions of one checked
    iteration: the largest of the residuals' and the divergence's 2-norms,
    each over its own number of cells."""
    sizes = {k: math.prod(s) for k, s in shapes(n).items()}

    def err(reds):
        return jnp.maximum(
            jnp.maximum(jnp.sqrt(reds["ss_Rx"]) / sizes["Rx"],
                        jnp.sqrt(reds["ss_Ry"]) / sizes["Ry"]),
            jnp.maximum(jnp.sqrt(reds["ss_Rz"]) / sizes["Rz"],
                        jnp.sqrt(reds["ss_divV"]) / sizes["divV"]))

    return err


def scalars(cfg: StokesConfig) -> dict:
    dx, dy, dz = spacing(cfg)
    return dict(r=cfg.r, dx=dx, dy=dy, dz=dz)


def material(cfg: StokesConfig) -> tuple:
    """Viscosity ``Mus`` and buoyancy ``Rog`` of the inclusion set-up: the
    sphere of ``cfg.radius`` about the box centre plus ``cfg.centre``, its
    viscosity jump smoothed by ``cfg.smooth`` explicit diffusion steps
    (a sharp jump would leave neighbouring cells' pseudo-time steps
    hundreds of times apart)."""
    n, d = cfg.n, spacing(cfg)[0]
    i = jnp.arange(n, dtype=jnp.float32) * jnp.float32(d)
    c = [cfg.length / 2 + o for o in cfg.centre]
    r2 = ((i[:, None, None] - jnp.float32(c[0])) ** 2
          + (i[None, :, None] - jnp.float32(c[1])) ** 2
          + (i[None, None, :] - jnp.float32(c[2])) ** 2)
    inside = r2 < jnp.float32(cfg.radius ** 2)
    Mus = jnp.where(inside, jnp.float32(cfg.mui), jnp.float32(cfg.mu0))
    Rog = jnp.where(inside, jnp.float32(cfg.rho_gi), jnp.float32(0.0))
    ps = _stencil(cfg)

    @ps.parallel(outputs=("M2",))
    def smooth(M2, M):
        return {"M2": fd.inn(M) + 1.0 / 6.1 * (
            fd.d2_xi(M) + fd.d2_yi(M) + fd.d2_zi(M))}

    M2 = Mus
    for _ in range(cfg.smooth):
        Mus, M2 = smooth(M2=M2, M=Mus), Mus
    return Mus, Rog


def pt_coefficients(cfg: StokesConfig, Mus):
    """The solve's set-up: the pseudo-time steps ``dt_Rho`` and ``Gdt``
    from the local viscosity maximum ``Must`` (``maxloc`` on the interior,
    its boundary copied from the first interior cell)."""
    ps = _stencil(cfg)

    @ps.parallel(outputs=("Must",), radius=1, bc={"Must": "neumann0"})
    def compute_maxloc(Must, Mus):
        return {"Must": fd.maxloc(Mus)}

    Must = compute_maxloc(Must=Mus, Mus=Mus)
    vpdt = cfg.cfl * min(spacing(cfg))
    dt_Rho = (vpdt * cfg.length / cfg.re) / Must
    Gdt = (vpdt ** 2 / (cfg.r + 2.0)) / dt_Rho
    return Gdt, dt_Rho


def init_state(cfg: StokesConfig) -> dict:
    """Every field the iteration carries: velocities, pressure, stresses,
    residuals and increments at 0, the material and the set-up's
    pseudo-time steps."""
    Mus, Rog = material(cfg)
    Gdt, dt_Rho = pt_coefficients(cfg, Mus)
    state = {k: jnp.zeros(s, jnp.float32) for k, s in shapes(cfg.n).items()}
    state.update(Mus=Mus, Rog=Rog, Gdt=Gdt, dt_Rho=dt_Rho)
    return state


def solve(cfg: StokesConfig = StokesConfig(), state: dict | None = None):
    """The pseudo-transient solve to ``cfg.tol`` through ``solve_until``,
    the four launches as one iteration; returns the ``SolveResult``."""
    return iterate.solve_until(
        make_kernels(cfg), init_state(cfg) if state is None else state,
        scalars(cfg), tol=cfg.tol, max_iters=cfg.max_iters,
        check_every=cfg.check_every, error=residual_error(cfg.n))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=31,
                    help="cells per axis (the miniapp's rule: 32k - 1)")
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--max-iters", type=int, default=2000)
    ap.add_argument("--check-every", type=int, default=10)
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    args = ap.parse_args(argv)
    cfg = StokesConfig(n=args.n, tol=args.tol, max_iters=args.max_iters,
                       check_every=args.check_every, backend=args.backend)
    res = solve(cfg)
    it, err = int(res.iters), float(res.err)
    vz = float(jnp.max(jnp.abs(res.fields["Vz"])))
    print(f"Stokes3D: {it} iterations on {cfg.n}^3 cells [{cfg.backend}], "
          f"err {err:.3e} (tol {cfg.tol:g}), max |Vz| {vz:.4e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
