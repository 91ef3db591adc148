"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the (slower) inter-pod links; gradient all-reduce and
(optionally int8-compressed) collectives run there.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh with the same axis-type convention (tests, examples)."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_mesh_kwargs(len(axes)))


def make_host_mesh(max_devices: int | None = None, axes=("data", "model")):
    """Best-effort mesh over whatever local devices exist (CPU tests)."""
    n = len(jax.devices()) if max_devices is None else max_devices
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), axes)
