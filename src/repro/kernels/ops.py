"""Public wrapper around the hand-written Fig. 1 kernel.

``impl="pallas"`` runs the Pallas kernel (``kernels/diffusion3d.py``;
interpret mode on CPU); ``impl="ref"`` runs its jnp oracle in ref.py.
"""
from __future__ import annotations

from . import ref as _ref
from . import diffusion3d as _diff_kernel


# =====================================================================
# 3-D diffusion step (paper Fig. 1)
# =====================================================================
def diffusion3d_step(T2, T, Ci, lam, dt, inv_dx, inv_dy, inv_dz,
                     impl: str = "pallas", tile=None):
    if impl == "pallas":
        return _diff_kernel.diffusion3d_step(T2, T, Ci, lam, dt, inv_dx, inv_dy,
                                             inv_dz, tile=tile)
    return _ref.diffusion3d_step(T2, T, Ci, lam, dt, inv_dx, inv_dy, inv_dz)
