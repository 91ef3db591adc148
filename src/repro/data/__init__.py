"""Initial and boundary conditions for the PDE solvers (``physics``)."""
from . import physics

__all__ = ["physics"]
