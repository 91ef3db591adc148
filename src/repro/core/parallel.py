"""`@parallel` — single-source xPU stencil kernels (the paper's C1/C2/C3).

Usage, mirroring Fig. 1 of the paper::

    from repro.core import parallel as P
    from repro.core.fd import fd3d as fd

    ps = P.init_parallel_stencil(backend="pallas", dtype="float32", ndims=3)

    @ps.parallel(outputs=("T2",))
    def step(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
            fd.d2_xi(T) * _dx**2 + fd.d2_yi(T) * _dy**2 + fd.d2_zi(T) * _dz**2))}

    T2 = step(T2=T2, T=T, Ci=Ci, lam=lam, dt=dt, _dx=_dx, _dy=_dy, _dz=_dz)

The same kernel source runs on every backend (the xPU property):

  * ``backend="jnp"``    — the update is traced on full arrays and scattered
    into the interior; XLA fuses the chain. This doubles as the paper's
    "array programming" comparison baseline when called op-by-op unjitted.
  * ``backend="pallas"`` — the update is traced on halo-extended VMEM
    windows inside a fused Pallas TPU kernel with derived launch parameters
    (kernels/stencil.py). On non-TPU hosts it validates via interpret mode.

Arguments are classified by value: arrays of the kernel's dimensionality
are *fields*, everything else is a *scalar*. Every name in ``outputs``
must be a field argument; its previous contents provide the boundary
values (the paper's ``@inn(T2) = ...`` semantics).

Footprint inference (the stencil IR): before anything runs, the update
function is traced ONCE with symbolic window objects (``repro.ir``) that
implement the same relative-slice protocol as the ``fd`` operators. The
trace yields per-field, per-axis halo depths — ``radius`` no longer needs
declaring. A declared ``radius`` is kept as a cross-check: a mismatch
against the inferred footprint raises a pointed ``ValueError``; if the
update uses constructs the tracer cannot analyze (``jnp.*`` calls,
integer indexing), a declared ``radius`` selects the legacy symmetric
geometry instead, and an undeclared one reports why inference failed.

Boundary conditions: ``bc={"T2": BoundaryCondition("neumann0"), ...}``
(or bare kind strings) declares each output's condition, realized by the
engine itself — inside the fused Pallas launch (dirichlet/neumann0, also
between the sweeps of ``run_steps(k)``) or as a face-slab scatter fused
into the surrounding jit (periodic) — bitwise-equal to applying the
``core.boundary`` post-pass after every step.

Coupled systems: ``outputs`` may name several fields — the whole coupled
update runs as ONE fused Pallas launch. Fields may be staggered: a field
up to the footprint band shorter than the (per-axis maximal) base shape
lives on cell faces, e.g. the Darcy flux ``qx`` of shape ``(nx-1, ny)``
next to cell-centered ``phi``/``Pe`` of shape ``(nx, ny)``. Per-output
write semantics follow the shape of the returned update along each axis:
a symmetric interior margin writes the interior (``@inn``, boundary ring
preserved; on a staggered axis the inferred halo reaches past the
stagger), full-field extent writes everything (``@all``). See
kernels/stencil.py for the window geometry.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import stencil as _stencil
from .. import ir as _ir
from .. import telemetry as _telemetry

_BACKENDS = ("jnp", "pallas")


def _bf16_load_f32(x):
    """bf16 -> f32 as integer bit movement (widen + shift): exact, and
    — unlike the ``convert`` HLO, which LLVM scalarizes to a libcall on
    CPUs without native bf16 — it vectorizes inside fused loops."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(u << 16, jnp.float32)


def _f32_store_bf16(x):
    """f32 -> bf16 round-to-nearest-even as integer bit arithmetic.
    Bit-identical to ``astype(bfloat16)`` for finite values and Inf
    (ties-to-even via the odd-bit bias); quiet-NaN payloads survive,
    signaling NaNs with sub-0x8000 payloads are not preserved."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bias = jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(
        ((u + bias) >> 16).astype(jnp.uint16), jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class ParallelStencil:
    """Backend/dtype/ndims context (the paper's ``@init_parallel_stencil``).

    ``dtype`` is the *storage* dtype — what fields occupy in HBM and what
    every kernel call returns. ``compute_dtype`` (default: f32 for
    sub-f32 float storage, else the storage dtype itself — see
    ``kernels.stencil.default_compute_dtype``) is what the stencil
    arithmetic runs at: fields are cast up on load and back down on
    store, on both backends, so bf16/f16 storage halves bytes moved
    while derivatives keep f32 precision."""

    backend: str = "jnp"
    dtype: Any = jnp.float32
    ndims: int = 3
    interpret: bool | None = None  # None -> auto (True unless on real TPU)
    compute_dtype: Any = None      # None -> default_compute_dtype(dtype)

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}")
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))
        cd = self.compute_dtype
        if cd is None:
            cd = _stencil.default_compute_dtype(self.dtype)
        object.__setattr__(self, "compute_dtype", jnp.dtype(cd))

    @property
    def acc_dtype(self) -> jnp.dtype:
        """Reduction-accumulation dtype (never narrower than f32)."""
        return _stencil.accum_dtype(self.compute_dtype)

    def parallel(
        self,
        outputs: Sequence[str],
        radius: int | None = None,
        tile: Sequence[int] | None = None,
        vmem_budget: int = _stencil.DEFAULT_VMEM_BUDGET,
        rotations: Mapping[str, str] | None = None,
        bc: Mapping[str, Any] | None = None,
        march_axis: int | None = None,
        reductions: Mapping[str, Any] | None = None,
    ) -> Callable[[Callable], "StencilKernel"]:
        """``radius`` is optional: the stencil IR infers per-field,
        per-axis footprints from the update function itself; declaring it
        adds a cross-check (ValueError on mismatch) and a fallback
        geometry for untraceable updates. ``rotations`` maps each output
        field to the input field it becomes on the next time step (e.g.
        ``{"T2": "T"}``) — required for the temporally-blocked
        ``run_steps(k>1)`` path. ``bc`` declares per-output boundary
        conditions fused into the engine's step. ``march_axis`` turns one
        grid axis into a sequential *streaming* dimension: the pallas
        backend slides per-field VMEM plane queues along it instead of
        refetching overlapping halo windows, the jnp backend realizes the
        same marching order as a scan over plane slabs (cache-resident
        working set). Streamed results equal the all-parallel path.

        ``reductions`` declares named in-launch reduction epilogues
        (``{"err": "max_abs_diff(T2, T)"}``-style, or ``ir.Reduction``
        objects): the kernel call then returns ``(outputs, {name:
        scalar})`` with the reductions folded inside the same launch as
        the update — no second whole-array pass, no host sync (the
        scalars stay on device; ``core.iterate.solve_until`` consumes
        them inside a ``lax.while_loop``). Reductions reassociate:
        cross-program comparisons (jnp vs pallas, fused vs post-pass)
        are ``allclose``, never bitwise."""
        if march_axis is not None and not 0 <= int(march_axis) < self.ndims:
            raise ValueError(
                f"march_axis {march_axis} out of range for ndims={self.ndims}")

        def deco(fn: Callable) -> StencilKernel:
            return StencilKernel(self, fn, tuple(outputs), radius, tile,
                                 vmem_budget, rotations, bc, march_axis,
                                 reductions)

        return deco


def init_parallel_stencil(
    backend: str = "jnp", dtype: Any = jnp.float32, ndims: int = 3,
    interpret: bool | None = None, compute_dtype: Any = None,
) -> ParallelStencil:
    return ParallelStencil(backend=backend, dtype=dtype, ndims=ndims,
                           interpret=interpret, compute_dtype=compute_dtype)


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """Resolved launch geometry of one kernel instance (per field-shape
    set): the traced IR (None when the legacy declared-radius fallback is
    active), the staggering band, and the per-axis window halo."""

    ir: _ir.StencilIR | None
    band: int                                  # largest staggering offset
    halos: tuple[tuple[int, int], ...] | None  # per-axis (lo, hi) or None

    @property
    def inferred(self) -> bool:
        return self.ir is not None


class StencilKernel:
    """A compiled-on-first-use, shape-polymorphic stencil kernel."""

    def __init__(self, ps: ParallelStencil, fn: Callable, outputs: tuple[str, ...],
                 radius: int | None, tile, vmem_budget: int,
                 rotations: Mapping[str, str] | None = None,
                 bc: Mapping[str, Any] | None = None,
                 march_axis: int | None = None,
                 reductions: Mapping[str, Any] | None = None):
        self.ps = ps
        self.fn = fn
        self.outputs = outputs
        self.radius = radius
        self.tile = tile
        self.vmem_budget = vmem_budget
        self.rotations = dict(rotations or {})
        self.bc = _ir.bc.normalize_bcs(bc, outputs, ps.ndims)
        self.march_axis = None if march_axis is None else int(march_axis)
        self.reductions = _ir.normalize_reductions(reductions)
        if self.reductions and any(c.kind == "periodic"
                                   for c in self.bc.values()):
            raise ValueError(
                "fused reductions cannot be declared next to a periodic "
                "boundary condition: the wrap scatter runs after the "
                "launch, so the in-launch fold would see pre-wrap face "
                "values"
            )
        self._cache: dict = {}
        self._geom_cache: dict = {}
        self._march_variants: dict = {}
        self._red_variants: dict = {}
        functools.update_wrapper(self, fn)

    def marched(self, march_axis: int | None) -> "StencilKernel":
        """A variant of this kernel streaming along ``march_axis``
        (``None`` returns the all-parallel variant). Variants are
        memoized on the parent so repeated calls — e.g. the distributed
        overlap path marching its interior every step — reuse one
        compile cache."""
        if march_axis is not None and not 0 <= int(march_axis) < self.ps.ndims:
            raise ValueError(
                f"march_axis {march_axis} out of range for "
                f"ndims={self.ps.ndims}")
        if march_axis == self.march_axis:
            return self
        v = self._march_variants.get(march_axis)
        if v is None:
            v = StencilKernel(self.ps, self.fn, self.outputs, self.radius,
                              self.tile, self.vmem_budget, self.rotations,
                              self.bc, march_axis, self.reductions)
            self._march_variants[march_axis] = v
        return v

    def with_reductions(self, reductions: Mapping[str, Any] | None
                        ) -> "StencilKernel":
        """A variant of this kernel with a different fused-reduction set
        (``None``/``{}`` strips them — the plain step a convergence
        driver runs between checks). Memoized on the parent so the
        checked and unchecked variants each compile once."""
        reds = _ir.normalize_reductions(reductions)
        if reds == self.reductions:
            return self
        key = tuple(sorted(reds.items()))
        v = self._red_variants.get(key)
        if v is None:
            v = StencilKernel(self.ps, self.fn, self.outputs, self.radius,
                              self.tile, self.vmem_budget, self.rotations,
                              self.bc, self.march_axis, reds)
            self._red_variants[key] = v
        return v

    def apply_reductions(self, outs: Mapping[str, Any],
                         fields: Mapping[str, Any],
                         mask=None) -> dict[str, Any]:
        """The post-pass reference realization of this kernel's
        reductions: whole-array folds over the final outputs (``outs``)
        and the pre-step fields — exactly what a separate norm pass
        computes — restricted to the cells of ``mask`` when given. The
        fused epilogue is tested ``allclose`` against this (bitwise only
        holds within one compiled program)."""
        reds = {}
        acc = self.ps.acc_dtype
        for name, r in self.reductions.items():
            # lift operands to the accumulation dtype first: bf16 storage
            # must not fold a 256^3 sum in bf16 (it plateaus after ~256
            # increments and the convergence signal is gone)
            ops = [(outs[op] if op in outs else fields[op]).astype(acc)
                   for op in r.operands]
            reds[name] = r.fold(r.map_element(*ops), mask)
        return reds

    # -- argument classification ------------------------------------------
    def _split(self, kwargs: Mapping[str, Any]):
        fields, scalars = {}, {}
        for name, v in kwargs.items():
            if hasattr(v, "ndim") and getattr(v, "ndim", 0) == self.ps.ndims:
                fields[name] = v
            else:
                scalars[name] = v
        if not fields:
            raise ValueError("no field arguments found")
        # Fields live at the context's storage dtype: callers may hand in
        # f32 (or f64 host) arrays to a bf16-storage kernel and get the
        # same carry dtype a chained solve would — cast once at the rim
        # (a no-op asarray for device arrays already at storage dtype).
        fields = {n: jnp.asarray(v, self.ps.dtype)
                  for n, v in fields.items()}
        shapes = {n: tuple(np.shape(v)) for n, v in fields.items()}
        base = tuple(
            max(s[a] for s in shapes.values()) for a in range(self.ps.ndims)
        )
        for o in self.outputs:
            if o not in fields:
                raise ValueError(f"output {o!r} is not a field argument")
        return fields, scalars, base, shapes

    # -- footprint inference ------------------------------------------------
    def _geometry(self, base, shapes: Mapping[str, tuple],
                  scalar_names: Sequence[str]) -> KernelGeometry:
        """Trace the update once per field-shape set; derive and validate
        the launch geometry (footprint halos, staggering band, bc fit)."""
        key = (base, tuple(sorted(shapes.items())), tuple(sorted(scalar_names)))
        geom = self._geom_cache.get(key)
        if geom is not None:
            return geom

        def update(fdict, sdict):
            return self.fn(**fdict, **sdict)

        try:
            ir = _ir.trace_stencil(update, shapes, self.outputs, scalar_names,
                                   reductions=self.reductions)
        except _ir.TraceError as e:
            if self.radius is None:
                raise ValueError(
                    f"footprint inference failed for kernel "
                    f"{getattr(self.fn, '__name__', '?')!r} and no radius "
                    f"was declared — declare radius= on @parallel to use "
                    f"the legacy symmetric geometry. Trace error: {e}"
                ) from e
            ir = None
            # The legacy fallback skips the trace, so the reduction
            # operands must be validated here instead.
            for name, r in self.reductions.items():
                for op in r.operands:
                    if op not in shapes:
                        raise ValueError(
                            f"reduction {name!r} = {r.describe()} reads "
                            f"{op!r}, which is not a field of this kernel"
                        )
                _ir.reductions.check_operand_shapes(name, r, shapes)

        if ir is not None and self.radius is not None \
                and ir.inferred_radius != self.radius:
            raise ValueError(
                f"declared radius={self.radius} does not match the inferred "
                f"footprint of kernel {getattr(self.fn, '__name__', '?')!r}: "
                f"per-axis window halo {ir.halo} and write rings "
                f"{tuple(ir.write_rings.values())} imply radius "
                f"{ir.inferred_radius} (drop radius= to use the inferred "
                "geometry, or fix the declaration)"
            )

        # The staggering band: how much shorter than the base a field of
        # the system may be. On the legacy path the declared radius; with
        # the inferred footprint of radius r, a face (the base) down to the
        # cell interior r deep on each side, 2r + 1 (ParallelStencil's
        # Stokes residual Rx, (n-1, n-2, n-2), beside the face field Vx,
        # (n+1, n, n), at r = 1). With an inferred footprint the band only
        # validates: the windows follow the traced halos.
        band = self.radius if self.radius is not None \
            else 2 * max(ir.inferred_radius, 1) + 1
        for n, s in shapes.items():
            off = tuple(b - x for b, x in zip(base, s))
            if any(o < 0 or o > band for o in off):
                raise ValueError(
                    f"field {n!r} shape {s} is inconsistent with the coupled "
                    f"system's base shape {base}: per-axis offsets {off} "
                    f"exceed the staggering band [0, {band}] — fields "
                    "of one system must agree up to face/cell staggering"
                )
        # bc face depths must fit the actual field extents
        _ir.bc.normalize_bcs(self.bc, self.outputs, self.ps.ndims,
                             field_shapes=shapes)
        geom = KernelGeometry(ir=ir, band=band,
                              halos=None if ir is None else ir.halo)
        self._geom_cache[key] = geom
        return geom

    def stencil_ir(self, **kwargs) -> _ir.StencilIR:
        """The kernel's traced IR for a given field set. Accepts the same
        keyword arguments as a call — arrays, or bare shape tuples for
        the fields (scalars may be omitted or given any value)."""
        shapes, scalar_names = {}, []
        for name, v in kwargs.items():
            if isinstance(v, (tuple, list)) and all(
                    isinstance(x, (int, np.integer)) for x in v):
                if len(v) == self.ps.ndims:
                    shapes[name] = tuple(int(x) for x in v)
                else:
                    scalar_names.append(name)
            elif hasattr(v, "ndim") and getattr(v, "ndim", 0) == self.ps.ndims:
                shapes[name] = tuple(np.shape(v))
            else:
                scalar_names.append(name)
        if not shapes:
            raise ValueError("no field shapes given")
        base = tuple(max(s[a] for s in shapes.values())
                     for a in range(self.ps.ndims))
        geom = self._geometry(base, shapes, tuple(scalar_names))
        if geom.ir is None:
            raise ValueError(
                "kernel is running on the legacy declared-radius fallback; "
                "no IR is available"
            )
        return geom.ir

    def cost_model(self, **kwargs) -> _ir.StencilCostModel:
        """Analytic flop/byte cost model for a given field set. Byte
        counts use the *storage* itemsize (what actually crosses HBM
        under mixed precision); reduction partials are accounted at the
        accumulation width."""
        ir = self.stencil_ir(**kwargs)
        isz = self.ps.dtype.itemsize
        return _ir.StencilCostModel.from_ir(
            ir, isz,
            field_itemsizes=tuple(isz for _ in ir.field_shapes),
            partials_itemsize=self.ps.acc_dtype.itemsize)

    # -- backends -----------------------------------------------------------
    # Every backend runner returns ``(outs, reds)`` — ``reds`` is None for
    # kernels without declared reductions. The jnp realizations fold the
    # reductions inline (whole-array jnp ops in the SAME jit trace as the
    # update, so XLA fuses the check into the step instead of paying a
    # second HBM pass); the pallas realization folds per-tile partials
    # inside the launch itself.
    def _compute_fields(self, fields):
        """Storage -> compute cast on load (no-op when the dtypes agree):
        the jnp-backend twin of the pallas kernel's in-window cast."""
        cd = self.ps.compute_dtype
        if cd == self.ps.dtype:
            return fields
        if self._bittrick:
            return {n: _bf16_load_f32(v) for n, v in fields.items()}
        return {n: v.astype(cd) for n, v in fields.items()}

    @property
    def _bittrick(self):
        """bf16 storage with f32 compute takes the integer-bit-twiddle
        conversion path: LLVM has no vector lowering for bf16<->f32
        ``convert`` on most CPUs (it emits a per-element libcall once
        XLA's float normalization injects converts mid-loop), but the
        same conversion written as shift/add on uint16/uint32 words
        vectorizes like any integer code. The bit path IS round-to-
        nearest-even, so results are identical to ``astype``."""
        return (self.ps.dtype == jnp.bfloat16
                and self.ps.compute_dtype == jnp.float32)

    @staticmethod
    def _opaque_true(v):
        """A data-dependent, always-true predicate XLA cannot fold away
        (the popcount of any machine word is at most 64). Used to pin a
        computation boundary via ``lax.cond`` — see
        :meth:`_fenced_updates`."""
        if v.dtype.itemsize == 8:
            bits = jax.lax.bitcast_convert_type(
                v.ravel()[0], jnp.uint32)[0]
        else:
            bits = jax.lax.bitcast_convert_type(
                v.ravel()[0],
                jnp.uint16 if v.dtype.itemsize == 2 else jnp.uint32)
        return jax.lax.population_count(
            bits.astype(jnp.uint32)) <= jnp.uint32(64)

    def _fenced_updates(self, fields, scalars):
        """Run ``self.fn`` (cast to compute dtype on load, back to
        storage on store) behind a fusion fence, for sub-f32 storage.

        XLA:CPU loop-fuses the storage-dtype boundary scatter into the
        update computation, producing one mega-loop in which every
        narrow-float load/store converts element-wise — 2-3x slower
        than memory bandwidth. ``optimization_barrier`` is expanded
        away before fusion runs, so the only reliable fence is a
        computation boundary: a ``lax.cond`` whose predicate is
        data-dependent (always true at runtime, never constant-foldable,
        so the conditional cannot be inlined). Only the *fields* enter
        the branch — keeping the output arrays out of the conditional
        avoids full-array copy insertion around it. f32 storage skips
        the fence: there the single fused loop IS the fast path."""
        names = list(fields)

        def compute(vals):
            ups = self.fn(**self._compute_fields(dict(zip(names, vals))),
                          **scalars)
            return tuple(self._store(ups[o]) for o in self.outputs)

        vals = tuple(fields.values())
        shapes = jax.eval_shape(compute, vals)
        updates = jax.lax.cond(
            self._opaque_true(vals[0]), compute,
            lambda _: tuple(jnp.zeros(s.shape, s.dtype) for s in shapes),
            vals)
        return dict(zip(self.outputs, updates))

    @staticmethod
    def _dus_bits(prev, idx, upd):
        """Interior scatter as a raw ``dynamic_update_slice`` on the
        bit-identical unsigned-int view: no oob-guard select, nothing
        for float normalization to rewrite."""
        starts = tuple(0 if s.start is None else int(s.start) for s in idx)
        uint = jnp.dtype(f"uint{8 * upd.dtype.itemsize}")
        p = jax.lax.bitcast_convert_type(prev, uint)
        u = jax.lax.bitcast_convert_type(upd, uint)
        return jax.lax.bitcast_convert_type(
            jax.lax.dynamic_update_slice(p, u, starts), prev.dtype)

    def _store(self, upd):
        """Compute -> storage cast on store, the inverse of
        :meth:`_compute_fields` (no-op when the dtypes agree)."""
        if upd.dtype == self.ps.dtype:
            return upd
        if self._bittrick and upd.dtype == jnp.float32:
            return _f32_store_bf16(upd)
        return upd.astype(self.ps.dtype)

    def _run_jnp(self, fields, scalars, base, geom: KernelGeometry):
        mixed = self.ps.compute_dtype != self.ps.dtype
        if mixed:
            # Sub-f32 storage: fence the update computation away from
            # the boundary scatter (see _fenced_updates — one fused
            # loop with a DUS/pad/concat root drops out of XLA:CPU's
            # vectorized path and runs 1.4-2x slower than the two-pass).
            updates = self._fenced_updates(fields, scalars)
        else:
            updates = self.fn(**fields, **scalars)
        ring = self.radius if geom.ir is None else None
        out = {}
        for name in self.outputs:
            prev = fields[name]
            upd = self._store(updates[name])
            # Per-axis write semantics from the update's shape — the SAME
            # derivation the pallas backend applies to windows (including
            # the staggered-axes-must-be-`all` rule), so a kernel that
            # traces on one backend traces on both.
            off = tuple(b - s for b, s in zip(base, prev.shape))
            modes, rings = _stencil.write_geometry(
                upd.shape, prev.shape, off, name, ring)
            idx = tuple(
                slice(None) if m == "all" else slice(w, prev.shape[a] - w)
                for a, (m, w) in enumerate(zip(modes, rings))
            )
            if mixed:
                # Guard-free DUS on the bit-identical unsigned-int view:
                # jnp's .at[].set would add an oob-guard select that XLA
                # float-normalizes into convert/f32-select/convert loops
                # over the FULL narrow-float array.
                res = self._dus_bits(prev, idx, upd)
            else:
                res = prev.at[idx].set(upd)
            cond = self.bc.get(name)
            if cond is not None:
                res = cond.apply(res)
            out[name] = res
        reds = (self.apply_reductions(out, fields)
                if self.reductions else None)
        return out, reds

    def _march_write_geometry(self, fields, scalars, base, geom):
        """Per-output (modes, rings, off) from an abstract trace (no
        compute), plus the staggered-march validation shared with the
        pallas path."""
        march = self.march_axis
        upd_shapes = jax.eval_shape(
            lambda f, s: self.fn(**f, **s), dict(fields), dict(scalars))
        ring_pin = self.radius if geom.ir is None else None
        out = {}
        for o in self.outputs:
            prev_shape = tuple(fields[o].shape)
            off = tuple(b - s for b, s in zip(base, prev_shape))
            modes, rings = _stencil.write_geometry(
                tuple(upd_shapes[o].shape), prev_shape, off, o, ring_pin)
            out[o] = (modes, rings, off)
        for n, v in fields.items():
            if base[march] - v.shape[march]:
                raise ValueError(
                    f"march_axis {march} points at a staggered axis: field "
                    f"{n!r} has offset {base[march] - v.shape[march]} there "
                    "— streaming slides collocated planes; stagger a "
                    "non-marching axis or drop march_axis"
                )
        return out

    def _run_jnp_march(self, fields, scalars, base, geom: KernelGeometry):
        """Marching realization of the jnp backend: a ``lax.scan`` slides
        plane slabs along ``march_axis`` in block steps, so the working
        set per step is a few planes per field (cache-resident — the CPU
        analogue of the pallas path's VMEM plane queue) instead of the
        whole arrays. Results equal :meth:`_run_jnp` (1-ulp across the
        two separately compiled programs)."""
        march = self.march_axis
        nd = self.ps.ndims
        geometry = self._march_write_geometry(fields, scalars, base, geom)
        n_march = base[march]
        halos = geom.halos if geom.halos is not None \
            else ((self.radius, self.radius),) * nd
        lo_m, hi_m = halos[march]
        ring_max = max(rings[march] for _, rings, _ in geometry.values())
        e_lo, e_hi = max(lo_m, ring_max), max(hi_m, ring_max)
        bm = max((d for d in range(1, min(4, n_march) + 1)
                  if n_march % d == 0), default=1)
        slab = bm + e_lo + e_hi
        if slab > n_march:
            # march extent smaller than one slab: marching degenerates —
            # run the all-parallel realization (identical semantics).
            return self._run_jnp(fields, scalars, base, geom)
        nb = n_march // bm

        def block_at(i):
            sc = jnp.clip(i * bm - e_lo, 0, n_march - slab)
            slabs = {n: jax.lax.dynamic_slice_in_dim(v, sc, slab, axis=march)
                     for n, v in fields.items()}
            updates = self.fn(**self._compute_fields(slabs), **scalars)
            outs = []
            for o in self.outputs:
                modes, rings, off = geometry[o]
                upd = self._store(updates[o])
                w_m = rings[march]
                # Update index u holds the update of global plane
                # sc + u + w_m; block positions g in [i*bm, i*bm + bm)
                # live at u = g - sc - w_m. Out-of-range u (zero pad)
                # only lands on march-ring planes, masked below.
                # Tight placement pad: slice start (i*bm - sc) ranges over
                # [0, e_lo + e_hi] on an update of extent
                # bm + e_lo + e_hi - 2*w_m, so w_m zeros per side cover
                # every clamped position (zeros land only on ring planes,
                # blended below).
                pad = [(0, 0)] * nd
                pad[march] = (w_m, w_m)
                blk = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(upd, pad) if w_m else upd, i * bm - sc, bm,
                    axis=march)
                prev = jax.lax.dynamic_slice_in_dim(
                    fields[o], i * bm, bm, axis=march)
                # Wrap each `inn` axis with the prev block's thin boundary
                # strips (concat assembly — the one block-sized
                # materialization per output; a copy-then-scatter or a
                # post-scan patch both cost extra whole-array passes):
                # when wrapping axis a, axes already wrapped are at full
                # extent, axes still pending stay at their interior
                # extents.
                done = set()
                for a in range(nd - 1, -1, -1):
                    if a == march or modes[a] == "all" or not rings[a]:
                        done.add(a)
                        continue
                    w = rings[a]

                    def strip(side, a=a, w=w, done=frozenset(done)):
                        idx = []
                        for b_ax in range(nd):
                            n_b = prev.shape[b_ax]
                            if b_ax == a:
                                idx.append(slice(0, w) if side == 0
                                           else slice(n_b - w, n_b))
                            elif b_ax in done or b_ax == march:
                                idx.append(slice(None))
                            else:
                                wb = rings[b_ax]
                                idx.append(slice(wb, n_b - wb))
                        return prev[tuple(idx)]

                    blk = jnp.concatenate([strip(0), blk, strip(1)], axis=a)
                    done.add(a)
                if modes[march] == "inn" and w_m:
                    g = i * bm + jnp.arange(bm)
                    keep = (g < w_m) | (g >= n_march - w_m)
                    keep = keep.reshape(tuple(bm if a == march else 1
                                              for a in range(nd)))
                    blk = jnp.where(keep, prev, blk)
                outs.append(blk)
            return tuple(outs)

        _, stacked = jax.lax.scan(lambda c, i: (c, block_at(i)), 0,
                                  jnp.arange(nb))
        out = {}
        for o, ys in zip(self.outputs, stacked):
            arr = jnp.moveaxis(ys, 0, march)
            arr = arr.reshape(fields[o].shape)
            cond = self.bc.get(o)
            if cond is not None:
                arr = cond.apply(arr)
            out[o] = arr
        reds = (self.apply_reductions(out, fields)
                if self.reductions else None)
        return out, reds

    def _run_pallas(self, fields, scalars, base, shapes,
                    geom: KernelGeometry, nsteps: int = 1,
                    col=_telemetry.NULL):
        key = (base, tuple(sorted(shapes.items())), tuple(sorted(scalars)),
               nsteps, self.march_axis)
        run = self._cache.get(key)
        if run is None:
            field_names = tuple(fields)
            scalar_names = tuple(scalars)

            def update(fdict, sdict):
                return self.fn(**fdict, **sdict)

            run = _stencil.build_stencil_call(
                update,
                name=getattr(self.fn, "__name__", None),
                field_names=field_names,
                out_names=self.outputs,
                scalar_names=scalar_names,
                shape=base,
                radius=geom.band,
                dtype=self.ps.dtype,
                tile=self.tile,
                vmem_budget=self.vmem_budget,
                compute_dtype=self.ps.compute_dtype,
                interpret=self.ps.interpret,
                nsteps=nsteps,
                rotations=self.rotations,
                field_shapes=shapes,
                halos=geom.halos,
                bc=self.bc,
                march_axis=self.march_axis,
                write_rings=None if geom.ir is None else tuple(
                    max(rings[a] for rings in geom.ir.write_rings.values())
                    for a in range(self.ps.ndims)
                ),
                reductions=self.reductions,
            )
            self._cache[key] = run
        res = run(fields, scalars, col)
        return res if self.reductions else (res, None)

    def __call__(self, **kwargs):
        # ``stencil.dispatch``: from entry to the return after the launch
        # is enqueued (under a jit trace: the trace of this call)
        col = _telemetry.get()
        with col.span("stencil.dispatch"):
            fields, scalars, base, shapes = self._split(kwargs)
            geom = self._geometry(base, shapes, tuple(scalars))
            if self.ps.backend == "pallas":
                outs, reds = self._run_pallas(fields, scalars, base, shapes,
                                              geom, col=col)
            elif self.march_axis is not None:
                outs, reds = self._run_jnp_march(fields, scalars, base, geom)
            else:
                outs, reds = self._run_jnp(fields, scalars, base, geom)
        res = outs[self.outputs[0]] if len(self.outputs) == 1 else outs
        return (res, reds) if self.reductions else res

    def _check_rotations(self):
        if not self.rotations or set(self.outputs) - set(self.rotations):
            raise ValueError(
                "run_steps(nsteps>1) requires rotations covering every output "
                "(pass rotations={'T2': 'T'}-style mapping to @parallel)"
            )

    def run_steps(self, nsteps: int, **kwargs):
        """Advance ``nsteps`` fused time steps; returns the *final* outputs
        (same structure as ``__call__``).

        The pallas backend runs one temporally-blocked kernel launch
        (k stacked halo margins, k in-kernel sweeps — each field crosses
        HBM once per k steps), with declared boundary conditions applied
        between sweeps exactly like the post-pass between sequential
        steps. The jnp backend realizes the identical semantics as k
        unrolled single steps with the ``rotations`` double-buffer
        rotation; under ``jax.jit`` XLA fuses the chain and elides the
        intermediate buffers. Both are bitwise-consistent with k
        sequential ``__call__``s when the rotation buffers agree on their
        boundary rings.

        Periodic conditions wrap across the whole domain and cannot run
        inside local windows; the pallas path then falls back to k
        sequential fused launches (bitwise-identical, k HBM round trips).
        """
        nsteps = int(nsteps)
        if nsteps < 1:
            raise ValueError(f"nsteps must be >= 1, got {nsteps}")
        if nsteps == 1:
            return self(**kwargs)
        self._check_rotations()
        col = _telemetry.get()
        with col.span("stencil.dispatch"):
            outs, reds = self._steps(nsteps, kwargs, col)
        res = outs[self.outputs[0]] if len(self.outputs) == 1 else outs
        return (res, reds) if self.reductions else res

    def _steps(self, nsteps, kwargs, col):
        fields, scalars, base, shapes = self._split(kwargs)
        geom = self._geometry(base, shapes, tuple(scalars))
        periodic = any(c.kind == "periodic" for c in self.bc.values())
        if self.ps.backend == "pallas" and not periodic:
            outs, reds = self._run_pallas(fields, scalars, base, shapes,
                                          geom, nsteps, col)
        else:
            # True double-buffer rotation, unrolled: sweep s scatters into
            # the stale buffer of the (out, target) pair, which is dead two
            # sweeps later — under jit XLA turns those scatters into
            # in-place updates instead of per-launch copies. (Also the
            # pallas realization when a periodic bc forbids in-window
            # temporal blocking.) A marching jnp kernel unrolls marched
            # single steps — each sweep streams its slabs in order.
            if self.ps.backend == "jnp":
                step = (self._run_jnp_march if self.march_axis is not None
                        else self._run_jnp)
            else:
                step = lambda f, s, b, g: self._run_pallas(  # noqa: E731
                    f, s, b, shapes, g, col=col)
            cur = dict(fields)
            for s in range(nsteps):
                # Intermediate sweeps' reductions are dead values — XLA's
                # DCE drops them under jit; only the final sweep's check
                # (the k-step value, matching the fused launch) survives.
                outs, reds = step(cur, scalars, base, geom)
                if s < nsteps - 1:
                    for o, tgt in self.rotations.items():
                        cur[o], cur[tgt] = cur[tgt], outs[o]
        return outs, reds

    @property
    def launch_info(self) -> dict:
        """Derived launch parameters of compiled instances (for inspection)."""
        return {
            k: {"grid": v.grid, "block": v.block, "march_axis": v.march_axis,
                "window_bytes": v.window_bytes, "vmem_bytes": v.vmem_bytes}
            for k, v in self._cache.items()
        }
