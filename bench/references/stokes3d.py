"""Plain reference for the accelerated pseudo-transient Stokes3D solver:
3-D incompressible viscous flow around a buoyant spherical inclusion,
iterated in pseudo-time (ParallelStencil.jl's miniapp ``Stokes3D``; Raess,
Utkin, Duretz, Omlin & Podladchikov 2022, GMD 15, 5757).

Straight ``jax.numpy`` over whole arrays; it imports nothing of the
program under test. On ``n`` cells per axis (spacing ``l / (n - 1)``)
the fields are staggered: cells ``(n, n, n)`` (``Pt``, ``divV``, the
normal stresses and the material), faces (``Vx`` is ``(n+1, n, n)``),
edges (``txy`` is ``(n-1, n-1, n-2)``) and inner faces (``Rx`` and ``dVx``
are ``(n-1, n-2, n-2)``). The configuration's ``grid`` is the cells,
``n`` per axis. The state lays every field in the low corner of one
``(n+1)^3`` array, the face extent, and zero elsewhere, so that all of it
shares one shape; ``extents`` gives each field's own. ``step`` and
``check_value`` also take a state whose fields are at their own extents
(the program's), and ``step`` returns the reference's layout.

One step is one pseudo-transient iteration: pressure and divergence,
stresses, residuals and velocity increments, velocities with free-slip
walls. The same module makes the initial state from the seed: the
inclusion's viscosity and buoyancy, and the set-up's pseudo-time steps
``dt_Rho`` and ``Gdt`` from the local viscosity maximum.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STATE = ("Vx", "Vy", "Vz", "Pt", "divV", "txx", "tyy", "tzz", "txy", "txz",
         "tyz", "Rx", "Ry", "Rz", "dVx", "dVy", "dVz", "Mus", "Rog", "Gdt",
         "dt_Rho")
OUTPUTS = ("Vx", "Vy", "Vz", "Pt")
RING = 1
INCREMENTS = ()
RESIDUALS = ("Rx", "Ry", "Rz", "divV")   # what the convergence check reads
# written by every iteration before it reads them: a step needs none
FRESH = ("divV", "Rx", "Ry", "Rz", "dVx", "dVy", "dVz")
# the material and the pseudo-time steps, written only at set-up
MATERIAL = ("Mus", "Rog", "Gdt", "dt_Rho")


def extents(n: int) -> dict:
    """Each field's own extent on ``n`` cells per axis."""
    c = (n, n, n)
    return {
        "Pt": c, "divV": c, "txx": c, "tyy": c, "tzz": c, "Mus": c,
        "Rog": c, "Gdt": c, "dt_Rho": c,
        "Vx": (n + 1, n, n), "Vy": (n, n + 1, n), "Vz": (n, n, n + 1),
        "txy": (n - 1, n - 1, n - 2), "txz": (n - 1, n - 2, n - 1),
        "tyz": (n - 2, n - 1, n - 1),
        "Rx": (n - 1, n - 2, n - 2), "Ry": (n - 2, n - 1, n - 2),
        "Rz": (n - 2, n - 2, n - 1),
        "dVx": (n - 1, n - 2, n - 2), "dVy": (n - 2, n - 1, n - 2),
        "dVz": (n - 2, n - 2, n - 1),
    }


def cells(cfg: dict) -> int:
    """Cells per axis, the configuration's ``grid``."""
    g = cfg["grid"]
    if g != [g[0]] * 3:
        raise ValueError(f"the Stokes3D set-up is cubic; grid {g}")
    return g[0]


def layout(n: int) -> tuple:
    """The shape every field of the state is laid in: the face extent."""
    return (n + 1,) * 3


def params(cfg: dict, seed: int) -> dict:
    """Host scalars of one run: the physics of ``cfg`` and the inclusion's
    centre, moved from the box centre by up to ``centre_offset_max`` per
    axis as ``seed`` draws (the data changes, the work does not)."""
    rng = np.random.default_rng(seed)
    n, inc = cells(cfg), cfg["inclusion"]
    spacing = [l / (n - 1) for l in cfg["length"]]
    off = rng.uniform(-inc["centre_offset_max"], inc["centre_offset_max"], 3)
    vpdt = cfg["cfl"] * min(spacing)
    return {"n": n, "spacing": spacing,
            "centre": [l / 2 + float(o) for l, o in zip(cfg["length"], off)],
            "radius": inc["radius"], "mu0": cfg["mu0"], "mui": cfg["mui"],
            "rho_gi": cfg["rho_gi"], "r": cfg["r"],
            "smooth_steps": cfg["smooth_steps"],
            # dt_Rho = Vpdt max(l) / Re / Must, Gdt = Vpdt^2 / dt_Rho / (r + 2)
            "dt_rho_num": vpdt * max(cfg["length"]) / cfg["re"],
            "gdt_num": vpdt ** 2 / (cfg["r"] + 2.0)}


def _corner(a, shape):
    """``a`` in the low corner of a zero array of ``shape``."""
    return jnp.pad(a, [(0, s - e) for s, e in zip(shape, a.shape)])


def _own(state: dict, n: int) -> dict:
    ext = extents(n)
    return {k: state[k][tuple(slice(0, e) for e in ext[k])] for k in STATE
            if k in state}


def _maxloc_copied(A):
    """The maximum over each interior cell's 3x3x3 neighbourhood, the
    boundary layer then copied from the first interior layer (axis by
    axis)."""
    n = A.shape
    out = None
    for i in range(3):
        for j in range(3):
            for k in range(3):
                v = A[i:n[0] - 2 + i, j:n[1] - 2 + j, k:n[2] - 2 + k]
                out = v if out is None else jnp.maximum(out, v)
    M = A.at[1:-1, 1:-1, 1:-1].set(out)
    return _free_slip(M, (0, 1, 2))


def _smoothed(M, steps):
    """``steps`` explicit diffusion steps of ``M`` on the interior (the
    boundary layer kept), each ``M + (d2x M + d2y M + d2z M) / 6.1``."""
    def one(_, M):
        c = M[1:-1, 1:-1, 1:-1]
        d2 = [M[_sl(a, 2, None, True)] - 2.0 * c + M[_sl(a, None, -2, True)]
              for a in range(3)]
        return M.at[1:-1, 1:-1, 1:-1].set(
            c + 1.0 / 6.1 * (d2[0] + d2[1] + d2[2]))

    return jax.lax.fori_loop(0, steps, one, M)


def initial(cfg: dict, p: dict, shape, sharding=None) -> dict:
    """The whole initial state on the device, built under jit: V, P, the
    stresses, residuals and increments 0; ``Mus`` ``mui`` and ``Rog``
    ``rho_gi`` inside the sphere, ``mu0`` and 0 outside, the viscosity
    jump smoothed by ``smooth_steps`` diffusion steps; the set-up's
    pseudo-time steps. ``shape`` is the ``grid``, the cells; the state is
    laid in the face extent around them (``layout``)."""
    n = p["n"]
    if tuple(shape) != (n,) * 3:
        raise ValueError(f"grid {tuple(shape)} is not {n} cells per axis")
    shape = layout(n)

    def build():
        xs = [jax.lax.broadcasted_iota(jnp.int32, (n, n, n), a)
              .astype(jnp.float32) * jnp.float32(d)
              for a, d in enumerate(p["spacing"])]
        r2 = sum((x - jnp.float32(c)) ** 2 for x, c in zip(xs, p["centre"]))
        inside = r2 < jnp.float32(p["radius"] ** 2)
        Mus = _smoothed(jnp.where(inside, jnp.float32(p["mui"]),
                                  jnp.float32(p["mu0"])), p["smooth_steps"])
        Rog = jnp.where(inside, jnp.float32(p["rho_gi"]), jnp.float32(0.0))
        dt_Rho = jnp.float32(p["dt_rho_num"]) / _maxloc_copied(Mus)
        Gdt = jnp.float32(p["gdt_num"]) / dt_Rho
        own = dict(Mus=Mus, Rog=Rog, dt_Rho=dt_Rho, Gdt=Gdt)
        return {k: _corner(own[k], shape) if k in own
                else jnp.zeros(shape, jnp.float32) for k in STATE}

    out = None if sharding is None else {k: sharding for k in STATE}
    return jax.jit(build, out_shardings=out)()


# differences and averages along one axis; ``i`` takes the other axes'
# interior
def _sl(axis, lo, hi, inner):
    return tuple(slice(lo, hi) if a == axis
                 else (slice(1, -1) if inner else slice(None))
                 for a in range(3))


def _d(A, axis, inner):
    return A[_sl(axis, 1, None, inner)] - A[_sl(axis, None, -1, inner)]


def _av(A, axis, inner):
    return 0.5 * (A[_sl(axis, 1, None, inner)] + A[_sl(axis, None, -1, inner)])


def _av2(A, a, b):
    """Mean of the 4 cells around each edge along axes ``a`` and ``b``,
    the third axis's interior."""
    def s(da, db):
        return tuple(slice(da, None if da else -1) if ax == a
                     else slice(db, None if db else -1) if ax == b
                     else slice(1, -1) for ax in range(3))

    return (A[s(0, 0)] + A[s(1, 0)] + A[s(0, 1)] + A[s(1, 1)]) * 0.25


def _free_slip(V, axes):
    """Zero gradient across the walls of ``axes``, axis by axis."""
    for a in axes:
        lo, hi = _sl(a, 0, 1, False), _sl(a, -1, None, False)
        V = V.at[lo].set(V[_sl(a, 1, 2, False)])
        V = V.at[hi].set(V[_sl(a, -2, -1, False)])
    return V


def step(state: dict, p: dict) -> dict:
    """One pseudo-transient iteration at the dtype of ``state``, at the
    highest matmul precision (the iteration has no contraction; f32 ones
    would otherwise run in lower precision on a TPU). ``state`` may leave
    out the ``FRESH`` fields; the result holds every field."""
    with jax.default_matmul_precision("highest"):
        return _step(state, p)


def _step(state: dict, p: dict) -> dict:
    n = state["Vx"].shape[0] - 1
    f = _own(state, n)
    dt_ = f["Vx"].dtype
    dx, dy, dz = (jnp.asarray(v, dt_) for v in p["spacing"])
    r = jnp.asarray(p["r"], dt_)
    Vx, Vy, Vz = f["Vx"], f["Vy"], f["Vz"]
    Gdt, Mus = f["Gdt"], f["Mus"]

    # pressure and divergence
    divV = _d(Vx, 0, False) / dx + _d(Vy, 1, False) / dy + _d(Vz, 2, False) / dz
    Pt = f["Pt"] - r * Gdt * divV

    # stresses
    den = Gdt / Mus + 1.0

    def normal(t, dv):
        return (t + 2.0 * Gdt * (dv - divV / 3.0)) / den

    def shear(t, a, b, dv):
        g = _av2(Gdt, a, b)
        return (t + 2.0 * g * (0.5 * dv)) / (g / _av2(Mus, a, b) + 1.0)

    txx = normal(f["txx"], _d(Vx, 0, False) / dx)
    tyy = normal(f["tyy"], _d(Vy, 1, False) / dy)
    tzz = normal(f["tzz"], _d(Vz, 2, False) / dz)
    txy = shear(f["txy"], 0, 1, _d(Vx, 1, True) / dy + _d(Vy, 0, True) / dx)
    txz = shear(f["txz"], 0, 2, _d(Vx, 2, True) / dz + _d(Vz, 0, True) / dx)
    tyz = shear(f["tyz"], 1, 2, _d(Vy, 2, True) / dz + _d(Vz, 1, True) / dy)

    # residuals and velocity increments
    Rx = (_d(txx, 0, True) / dx + _d(txy, 1, False) / dy
          + _d(txz, 2, False) / dz - _d(Pt, 0, True) / dx)
    Ry = (_d(tyy, 1, True) / dy + _d(txy, 0, False) / dx
          + _d(tyz, 2, False) / dz - _d(Pt, 1, True) / dy)
    Rz = (_d(tzz, 2, True) / dz + _d(txz, 0, False) / dx
          + _d(tyz, 1, False) / dy - _d(Pt, 2, True) / dz
          + _av(f["Rog"], 2, True))
    dt_Rho = f["dt_Rho"]
    dVx = _av(dt_Rho, 0, True) * Rx
    dVy = _av(dt_Rho, 1, True) * Ry
    dVz = _av(dt_Rho, 2, True) * Rz

    # velocities, free slip on the tangential walls
    Vx = _free_slip(Vx.at[1:-1, 1:-1, 1:-1].add(dVx), (1, 2))
    Vy = _free_slip(Vy.at[1:-1, 1:-1, 1:-1].add(dVy), (0, 2))
    Vz = _free_slip(Vz.at[1:-1, 1:-1, 1:-1].add(dVz), (0, 1))

    new = dict(f, Vx=Vx, Vy=Vy, Vz=Vz, Pt=Pt, divV=divV, txx=txx, tyy=tyy,
               tzz=tzz, txy=txy, txz=txz, tyz=tyz, Rx=Rx, Ry=Ry, Rz=Rz,
               dVx=dVx, dVy=dVy, dVz=dVz)
    return {k: _corner(new[k].astype(dt_), layout(n)) for k in STATE}


def check_value(state: dict):
    """The solve's check, in f32: the largest of the residuals' and the
    divergence's 2-norms, each over its own number of cells."""
    n = state["Vx"].shape[0] - 1
    ext = extents(n)
    err = None
    for k in RESIDUALS:
        v = state[k][tuple(slice(0, e) for e in ext[k])].astype(jnp.float32)
        e = jnp.sqrt(jnp.sum(v * v)) / math.prod(ext[k])
        err = e if err is None else jnp.maximum(err, e)
    return err


def launch_bytes(cfg: dict) -> dict:
    """HBM bytes of each of the program's launches, operands and results
    each counted once, at the extents the launch reads and stores: every
    field operand at its own extent, every result at the launch's base
    extent (the per-axis maximum of its fields), at which the engine
    stores staggered results before cropping them, and each scalar as one
    f32 in SMEM. The count the device trace makes from a launch's HLO text
    (``yardstick/trace.launch_bytes``); a checked launch adds its
    reductions' partials, one (8, 128) f32 tile per block (under 0.1%)."""
    ext = extents(cells(cfg))
    launches = {   # (field operands, results, scalars)
        "compute_P": (("divV", "Pt", "Vx", "Vy", "Vz", "Gdt"),
                      ("divV", "Pt"), 4),
        "compute_tau": (("txx", "tyy", "tzz", "txy", "txz", "tyz", "Vx",
                         "Vy", "Vz", "divV", "Mus", "Gdt"),
                        ("txx", "tyy", "tzz", "txy", "txz", "tyz"), 3),
        "compute_dV": (("Rx", "Ry", "Rz", "dVx", "dVy", "dVz", "Pt", "Rog",
                        "txx", "tyy", "tzz", "txy", "txz", "tyz", "dt_Rho"),
                       ("Rx", "Ry", "Rz", "dVx", "dVy", "dVz"), 3),
        "compute_V": (("Vx", "Vy", "Vz", "dVx", "dVy", "dVz"),
                      ("Vx", "Vy", "Vz"), 0),
    }
    out = {}
    for name, (operands, results, n_scalars) in launches.items():
        base = math.prod(max(ext[k][a] for k in operands) for a in range(3))
        out[name] = 4 * (sum(math.prod(ext[k]) for k in operands)
                         + base * len(results) + n_scalars)
    return out
