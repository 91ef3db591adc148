"""Plain reference for the Gross-Pitaevskii solver:
``i dpsi/dt = [-1/2 lap + V + g |psi|^2] psi`` with ``psi = re + i im``,
advanced by symplectic Euler (re with the current im, then im with the
new re).

Straight ``jax.numpy`` over whole arrays; it imports nothing of the
program under test. The coupled step updates re on the planes one in from
the faces, uses that new re for im's update two planes in, and writes
both fields two planes in: the program's fused radius-2 step, whose outer
two planes keep their initial values.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STATE = ("re", "im", "V")
OUTPUTS = ("re", "im")
RING = 2
INCREMENTS = ()               # the step keeps one time level


def params(cfg: dict, seed: int) -> dict:
    """Host scalars: the physics of ``cfg`` and, from ``seed``, the blob's
    offset from the trap centre and a plane-wave phase (so im is not zero
    at the start); the amount of work does not depend on the seed."""
    rng = np.random.default_rng(seed)
    shape = cfg["grid"]
    spacing = [l / (n - 1) for l, n in zip(cfg["length"], shape)]
    init = cfg["initial"]
    return {"offset": [float(v) for v in rng.uniform(
                -init["offset_max"], init["offset_max"], 3)],
            "k": [float(v) for v in rng.uniform(-init["k_max"],
                                                init["k_max"], 3)],
            "spacing": spacing,
            "inv2": [1.0 / d ** 2 for d in spacing],
            "dt": cfg["dt_factor"] * min(spacing) ** 2,
            "g": cfg["g"], "trap": cfg["trap"], "width2": init["width2"]}


def fields_at(cfg: dict, p: dict, idx) -> dict:
    """Initial fields at integer grid indices: V = trap * r^2 about the box
    centre, psi = A exp(-|x - x0|^2 / width2) exp(i k.x) with
    A = (pi width2 / 2)^(-3/4), so that the integral of |psi|^2 is 1."""
    xs = [i.astype(jnp.float32) * jnp.float32(d)
          for i, d in zip(idx, p["spacing"])]
    centre = [l / 2 for l in cfg["length"]]
    r2 = sum((x - jnp.float32(c)) ** 2 for x, c in zip(xs, centre))
    b2 = sum((x - jnp.float32(c + o)) ** 2
             for x, c, o in zip(xs, centre, p["offset"]))
    amp = (math.pi * p["width2"] / 2) ** -0.75
    env = jnp.float32(amp) * jnp.exp(-b2 / jnp.float32(p["width2"]))
    phase = sum(x * jnp.float32(k) for x, k in zip(xs, p["k"]))
    shape = jnp.broadcast_shapes(*(i.shape for i in idx))
    f32 = jnp.float32

    def full(a):
        return jnp.broadcast_to(a, shape).astype(f32)

    return {"re": full(env * jnp.cos(phase)), "im": full(env * jnp.sin(phase)),
            "V": full(jnp.float32(p["trap"]) * r2)}


def initial(cfg: dict, p: dict, shape, sharding=None) -> dict:
    def build():
        idx = [jax.lax.broadcasted_iota(jnp.int32, shape, a)
               for a in range(len(shape))]
        return fields_at(cfg, p, idx)

    out = None if sharding is None else {k: sharding for k in STATE}
    return jax.jit(build, out_shardings=out)()


def _inside(shape, ring):
    m = None
    for a, n in enumerate(shape):
        i = jax.lax.broadcasted_iota(jnp.int32, shape, a)
        ma = (i >= ring) & (i < n - ring)
        m = ma if m is None else m & ma
    return m


_C = (slice(1, -1),) * 3


def _lap(f, inv2):
    """The Laplacian on the interior points of ``f``'s frame."""
    out = 0.0
    for axis in range(3):
        hi = tuple(slice(2, None) if a == axis else slice(1, -1)
                   for a in range(3))
        lo = tuple(slice(None, -2) if a == axis else slice(1, -1)
                   for a in range(3))
        out = out + (f[hi] - 2.0 * f[_C] + f[lo]) * inv2[axis]
    return out


def _H(f, re, im, V, g, inv2):
    """``(-1/2 lap + V + g |psi|^2) f`` on the interior points."""
    return -0.5 * _lap(f, inv2) + (V[_C] + g * (re[_C] * re[_C]
                                                + im[_C] * im[_C])) * f[_C]


def step(state: dict, p: dict) -> dict:
    """One symplectic Euler step at the dtype of ``state``."""
    re, im, V = state["re"], state["im"], state["V"]
    dt_ = re.dtype
    inv2 = [jnp.asarray(v, dt_) for v in p["inv2"]]
    g, dt = jnp.asarray(p["g"], dt_), jnp.asarray(p["dt"], dt_)
    in1, in2 = _inside(re.shape, 1), _inside(re.shape, RING)
    re1 = jnp.where(in1, jnp.pad(
        (re[_C] + dt * _H(im, re, im, V, g, inv2)).astype(dt_), 1), re)
    im_new = jnp.pad((im[_C] - dt * _H(re1, re1, im, V, g, inv2)).astype(dt_),
                     1)
    return {"re": jnp.where(in2, re1, re), "im": jnp.where(in2, im_new, im),
            "V": V}
