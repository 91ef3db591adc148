"""repro — xPU stencil computations in JAX (ParallelStencil.jl reproduction)."""
__version__ = "0.1.0"
