"""Plain reference for the paper's Fig. 1 solver: 3-D heat diffusion,
explicit Euler, ``dT/dt = lam * Ci * lap(T)`` on the interior, the
boundary ring held at its initial value.

Straight ``jax.numpy`` over whole arrays; it imports nothing of the
program under test. The same module makes the initial state from the seed
(the benchmark's data, handed to the program and rebuilt here for the
comparison), so both sides start from identical bits.

Source: Omlin & Raess, "High-performance xPU Stencil Computations in
Julia", arXiv:2211.15634, Fig. 1 (T = 1.7 plus a Gaussian of width 0.1,
Ci = 1/c0, dt = min(dx)^2 / lam / max(Ci) / 6.1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STATE = ("T", "T2", "Ci")     # the fields the solver carries
OUTPUTS = ("T", "T2")         # what a run's answer is: the newest two steps
RING = 1                      # boundary planes the step never writes
INCREMENTS = (("T", "T2"),)   # (newest, previous) time levels


def params(cfg: dict, seed: int) -> dict:
    """Host scalars of one run: the physics of ``cfg`` and the hot spot's
    centre, drawn from ``seed`` (the spot's size and amplitude are fixed,
    so every seed does the same work)."""
    rng = np.random.default_rng(seed)
    lo, hi = cfg["hot_spot"]["centre_range"]
    shape = cfg["grid"]
    spacing = [l / (n - 1) for l, n in zip(cfg["length"], shape)]
    lam, c0 = cfg["lam"], cfg["c0"]
    # Fig. 1 line 33: dt = min(dx, dy, dz)^2 / lam / maximum(Ci) / 6.1
    dt = min(spacing) ** 2 / lam / (1.0 / c0) / cfg["dt_safety"]
    return {"centre": [float(c) for c in rng.uniform(lo, hi, 3)],
            "spacing": spacing, "lam": lam, "c0": c0, "dt": dt,
            "inv_spacing": [1.0 / d for d in spacing]}


def fields_at(cfg: dict, p: dict, idx) -> dict:
    """Initial fields at integer global grid indices ``idx`` (one
    broadcastable int array per axis)."""
    hs = cfg["hot_spot"]
    r2 = sum((i.astype(jnp.float32) * jnp.float32(d) - jnp.float32(c)) ** 2
             for i, d, c in zip(idx, p["spacing"], p["centre"]))
    T = cfg["init_temp"] + hs["amplitude"] * jnp.exp(
        -r2 / jnp.float32(2 * hs["width"] ** 2))
    shape = jnp.broadcast_shapes(*(i.shape for i in idx))
    T = jnp.broadcast_to(T, shape).astype(jnp.float32)
    return {"T": T, "T2": T, "Ci": jnp.full(shape, 1.0 / p["c0"],
                                             jnp.float32)}


def initial(cfg: dict, p: dict, shape, sharding=None) -> dict:
    """The whole initial state on the device, built under jit."""
    def build():
        idx = [jax.lax.broadcasted_iota(jnp.int32, shape, a)
               for a in range(len(shape))]
        return fields_at(cfg, p, idx)

    out = None if sharding is None else {k: sharding for k in STATE}
    return jax.jit(build, out_shardings=out)()


def _interior(shape, ring):
    m = None
    for a, n in enumerate(shape):
        i = jax.lax.broadcasted_iota(jnp.int32, shape, a)
        ma = (i >= ring) & (i < n - ring)
        m = ma if m is None else m & ma
    return m


_C = (slice(1, -1),) * 3


def _d2(T, axis):
    """Second difference along ``axis`` on the interior points."""
    hi = tuple(slice(2, None) if a == axis else slice(1, -1) for a in range(3))
    lo = tuple(slice(None, -2) if a == axis else slice(1, -1)
               for a in range(3))
    return T[hi] - 2.0 * T[_C] + T[lo]


def step(state: dict, p: dict) -> dict:
    """One Fig. 1 step and the T/T2 swap, at the dtype of ``state``: the
    interior update, padded back to the whole grid, where the boundary
    keeps T2's values."""
    T, T2, Ci = state["T"], state["T2"], state["Ci"]
    dt_ = T.dtype
    ix, iy, iz = (jnp.asarray(v, dt_) for v in p["inv_spacing"])
    lam, dt = jnp.asarray(p["lam"], dt_), jnp.asarray(p["dt"], dt_)
    upd = T[_C] + dt * (lam * Ci[_C] * (_d2(T, 0) * ix ** 2
                                        + _d2(T, 1) * iy ** 2
                                        + _d2(T, 2) * iz ** 2))
    new = jnp.where(_interior(T.shape, RING), jnp.pad(upd.astype(dt_), 1),
                    T2)
    return {"T": new, "T2": T, "Ci": Ci}


def check_value(state: dict):
    """The solve's check: ``max |T2 - T|`` between the newest two steps, in
    f32."""
    return jnp.max(jnp.abs(state["T"].astype(jnp.float32)
                           - state["T2"].astype(jnp.float32)))
