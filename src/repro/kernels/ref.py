"""Pure-jnp oracle of the Fig. 1 diffusion step: the ground truth of the
hand-written kernel (``kernels/diffusion3d.py``) and of the generated
launches in tests, and the paper's "array programming" baseline in
benchmarks.
"""
from __future__ import annotations


# -- 3-D heat diffusion (paper Fig. 1) ---------------------------------------
def diffusion3d_step(T2, T, Ci, lam, dt, inv_dx, inv_dy, inv_dz):
    """One explicit Euler step of ``dT/dt = lam/c * lap(T)`` on the interior.

    Returns the new T2 (boundary kept from the input T2).
    """
    d2x = (T[2:, 1:-1, 1:-1] - 2 * T[1:-1, 1:-1, 1:-1] + T[:-2, 1:-1, 1:-1]) * inv_dx**2
    d2y = (T[1:-1, 2:, 1:-1] - 2 * T[1:-1, 1:-1, 1:-1] + T[1:-1, :-2, 1:-1]) * inv_dy**2
    d2z = (T[1:-1, 1:-1, 2:] - 2 * T[1:-1, 1:-1, 1:-1] + T[1:-1, 1:-1, :-2]) * inv_dz**2
    upd = T[1:-1, 1:-1, 1:-1] + dt * (lam * Ci[1:-1, 1:-1, 1:-1] * (d2x + d2y + d2z))
    return T2.at[1:-1, 1:-1, 1:-1].set(upd.astype(T2.dtype))
