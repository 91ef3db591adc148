"""Pallas TPU kernels: the stencil launch builder (``stencil``), its
autotuner (``autotune``), the hand-written Fig. 1 kernel
(``diffusion3d``, wrapped in ``ops``) and its jnp oracle (``ref``)."""
from . import autotune, ref, stencil

__all__ = ["autotune", "ref", "stencil"]
