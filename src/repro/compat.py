"""The one jax API the repo wraps: ``shard_map`` with the mesh and specs
passed positionally (``jax.shard_map`` takes them as keywords only)."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, **kwargs):
    """``jax.shard_map(f, mesh=mesh, in_specs=..., out_specs=...)``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
