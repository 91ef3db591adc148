"""Stencil IR — symbolic footprint inference, cost models, boundary specs.

The user's math-close update function is traced ONCE with symbolic window
objects (:mod:`.sym`) that implement the same relative-slice protocol as
the ``core.fd`` operators. The resulting per-output expression graph
(:class:`.trace.StencilIR`) carries everything the rest of the stack used
to take on faith from a hand-declared ``radius`` and hand-counted
``n_read``/``n_write``:

  * **footprints** — per-field, per-axis, per-side halo depths
    (``StencilIR.field_halo``) and the coupled system's window halo
    (``StencilIR.halo``), consumed by ``kernels.stencil`` (VMEM window
    geometry), ``distributed.halo`` (exchange depths) and
    ``distributed.overlap`` (face-slab widths);
  * **boundary conditions** (:mod:`.bc`) — declared per output field and
    realized inside the fused launch, bitwise-equal to the
    ``core.boundary`` post-pass;
  * **cost models** (:mod:`.cost`) — exact flop/byte counts per output
    feeding ``core.teff``, the autotuner's pre-compile candidate pruning
    and the roofline position of a launch (``cost.stencil_roofline``).
"""
from .sym import SymArray, TraceError, field as sym_field
from .trace import StencilIR, trace_stencil
from .cost import FlopCount, StencilCostModel, count_flops
from .bc import BoundaryCondition
from .reductions import Reduction, normalize_reductions

__all__ = [
    "SymArray", "TraceError", "sym_field",
    "StencilIR", "trace_stencil",
    "FlopCount", "StencilCostModel", "count_flops",
    "BoundaryCondition",
    "Reduction", "normalize_reductions",
]
