"""Communication/computation overlap (the paper's ``@hide_communication``).

ParallelStencil + ImplicitGlobalGrid hide the halo exchange behind the
stencil update of the interior: the boundary-adjacent cells are computed
in separate kernels once the halos arrive, while the bulk of the domain is
updated concurrently with communication. That is what gave the paper >95%
parallel efficiency on 1024 GPUs.

On TPU/XLA the overlap is *dataflow-structured* rather than stream-
structured: we build the program so that

    bulk update      — depends only on stale-halo local data
    halo ppermutes   — depend only on interior slabs
    shell re-update  — depends on both

and XLA's async collective-permute (start/done pairs) lets the bulk update
execute between start and done. ``overlapped_step`` implements the generic
pattern for any `StencilKernel`; tests assert bit-equality with the
sequential exchange-then-update reference.

The shell is recomputed per face from a slab of thickness ``3r`` (ghost r +
shell r + support r): face slabs span the full extent of the other axes, so
edge/corner cells are recomputed consistently by every adjacent face (the
kernel is pure — last write wins with identical values).
"""
from __future__ import annotations

import time
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..core.parallel import StencilKernel
from . import fault as _fault
from . import halo as _halo


def _face_slab(arr, axis: int, side: int, thickness: int, off: int = 0):
    """Face slab covering base positions [0, thickness) / [N-thickness, N).
    A field staggered by ``off`` along ``axis`` (extent ``N - off``) yields
    a ``thickness - off`` slab over the same physical region, so slab sets
    keep the coupled system's staggering intact for the kernel's shape
    contract."""
    t = thickness - off
    idx = [slice(None)] * arr.ndim
    idx[axis] = slice(0, t) if side == 0 else slice(arr.shape[axis] - t, None)
    return arr[tuple(idx)]


def _paste_shell(dst, src, axis: int, side: int, radius: int):
    """Paste the ghost + shell layers ([0, 2r) from the face) of src into
    dst. Including the ghost ring keeps overlapped_step bit-equal to the
    sequential reference for `@all`-write outputs too (whose ghost cells
    are computed from exchanged values, not just carried)."""
    r = radius
    di = [slice(None)] * dst.ndim
    si = [slice(None)] * dst.ndim
    di[axis] = slice(0, 2 * r) if side == 0 else slice(-2 * r, None)
    si[axis] = slice(0, 2 * r) if side == 0 else slice(-2 * r, None)
    return dst.at[tuple(di)].set(src[tuple(si)])


def _kernel_geometry(kernel: StencilKernel, fields, scalars,
                     exchange: Sequence[str], mesh_axes: Sequence[str]):
    """(effective radius, per-exchanged-field exchange depths, ir) for
    this field set. Footprint-inferred kernels tighten each field's ghost
    refresh to its actual per-axis/per-side read depth on the decomposed
    (leading) axes; the legacy declared-radius fallback exchanges the
    full ring (depths=None, ir=None)."""
    try:
        ir = kernel.stencil_ir(**fields, **scalars)
    except ValueError:
        if kernel.radius is None:
            raise  # untraceable AND undeclared: the kernel call would fail
        return kernel.radius, None, None
    r = kernel.radius if kernel.radius is not None \
        else max(ir.inferred_radius, 1)
    n_dec = len(mesh_axes)
    depths = {f: ir.field_halo[f][:n_dec]
              for f in exchange if f in ir.field_halo}
    return r, depths, ir


def finish_reductions(kernel: StencilKernel, reds: Mapping[str, jax.Array],
                      mesh_axes: Sequence[str]) -> dict[str, jax.Array]:
    """Finish a kernel's fused reductions across ranks: ONE ``pmax`` /
    ``psum`` per reduction over the rank-local fused values (which are
    valid partials — the combines are associative). Rank-local (inside
    ``shard_map``).

    A kernel's fused values fold its whole local array, ghost rings
    included, and an output's ghost ring still holds the previous step
    (the update writes the interior only): combined this way, a check
    mixes in a stale step and sums count the overlap twice.
    :func:`owned_reductions` folds the owned cells only."""
    return {n: kernel.reductions[n].all_reduce(v, mesh_axes)
            for n, v in reds.items()}


def owned_mask(shape: Sequence[int], mesh_axes: Sequence[str],
               ghost: int) -> jax.Array:
    """The cells of a rank-local array this rank owns — those
    :func:`..halo.local_to_global` stitches into the global array: a
    ``ghost``-deep ring is dropped on every side that faces another rank
    along a decomposed axis, the physical boundary ring is kept.
    Rank-local (inside ``shard_map``)."""
    mask = jnp.ones(tuple(shape), bool)
    for a, ax in enumerate(mesh_axes):
        n, i = shape[a], jax.lax.axis_index(ax)
        last = jax.lax.axis_size(ax) - 1
        g = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), a)
        mask &= ((g >= jnp.where(i == 0, 0, ghost))
                 & (g < jnp.where(i == last, n, n - ghost)))
    return mask


def owned_reductions(kernel: StencilKernel, outs: Mapping[str, jax.Array],
                     fields: Mapping[str, jax.Array],
                     mesh_axes: Sequence[str], ghost: int
                     ) -> dict[str, jax.Array]:
    """The kernel's reductions over the cells each rank owns
    (:func:`owned_mask`), each combined across ranks by ONE ``pmax`` /
    ``psum``: the value the whole-array fold of the gathered global
    arrays gives. ``outs`` are the step's outputs by name, ``fields`` its
    inputs with fresh ghost rings. Rank-local (inside ``shard_map``)."""
    op = next(iter(kernel.reductions.values())).operands[0]
    shape = (outs[op] if op in outs else fields[op]).shape
    reds = kernel.apply_reductions(outs, fields,
                                   owned_mask(shape, mesh_axes, ghost))
    return finish_reductions(kernel, reds, mesh_axes)


class MonitoredStepper:
    """Rank-failure detection wired around the distributed step drivers.

    Wraps the *compiled host-level* step callable (a jitted
    ``shard_map`` around :func:`sequential_step` / :func:`multi_step` /
    :func:`overlapped_step` — those themselves are traced, so timing
    belongs out here): every call blocks on the result, records the
    wall time with the :class:`~repro.distributed.fault.StepMonitor`
    (which bumps this host's heartbeat file), and polls peer
    heartbeats. A stale peer raises
    :class:`~repro.distributed.fault.RankFailure` so the launcher can
    checkpoint-restore on the surviving mesh; stragglers are surfaced
    on ``.last_health`` without interrupting the run."""

    def __init__(self, step: Callable, monitor: "_fault.StepMonitor",
                 nsteps_per_call: int = 1, check_peers_every: int = 1):
        self.step = step
        self.monitor = monitor
        self.nsteps_per_call = max(int(nsteps_per_call), 1)
        self.check_peers_every = max(int(check_peers_every), 1)
        self.calls = 0
        self.last_health = {"dead": [], "stragglers": [], "healthy": 1}

    def __call__(self, *args, **kwargs):
        col = _telemetry.get()
        t0 = time.perf_counter()
        with col.span("distributed.step") as span:
            out = self.step(*args, **kwargs)
            out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            self.calls += 1
            if col.enabled:
                span.set(call=self.calls, steps=self.nsteps_per_call,
                         per_step_s=dt / self.nsteps_per_call)
        self.monitor.record(self.calls * self.nsteps_per_call,
                            dt / self.nsteps_per_call)
        if col.enabled:
            col.count("distributed.steps", self.nsteps_per_call)
        if self.calls % self.check_peers_every == 0:
            self.last_health = self.monitor.check_peers()
            if self.last_health["dead"]:
                raise _fault.RankFailure(self.last_health["dead"])
        return out


def monitored(step: Callable, monitor: "_fault.StepMonitor",
              **kwargs) -> MonitoredStepper:
    """Convenience wrapper: ``monitored(jax.jit(shard_mapped_step),
    StepMonitor(...))`` — see :class:`MonitoredStepper`."""
    return MonitoredStepper(step, monitor, **kwargs)


def sequential_step(
    kernel: StencilKernel,
    fields: Mapping[str, jax.Array],
    scalars: Mapping[str, object],
    exchange: Sequence[str],
    mesh_axes: Sequence[str],
    periodic=False,
    halo_compress: str | None = None,
):
    """Reference: exchange halos, then update. No overlap. A kernel with
    fused reductions returns ``((outs, reds), fresh)`` with the rank
    reductions folded over the cells each rank owns and combined across
    ranks (:func:`owned_reductions`) — one collective scalar each.
    ``halo_compress`` selects the ghost wire format (``"bf16"``/
    ``"int8"`` — see :func:`..halo.halo_exchange`)."""
    r, depths, _ = _kernel_geometry(kernel, fields, scalars, exchange,
                                    mesh_axes)
    fresh = _halo.exchange_many(fields, exchange, mesh_axes, radius=r,
                                periodic=periodic, depths=depths,
                                compress=halo_compress)
    if not kernel.reductions:
        return kernel(**fresh, **scalars), fresh
    outs = kernel.with_reductions(None)(**fresh, **scalars)
    named = {kernel.outputs[0]: outs} if len(kernel.outputs) == 1 else outs
    return (outs, owned_reductions(kernel, named, fresh, mesh_axes, r)), fresh


def multi_step(
    kernel: StencilKernel,
    fields: Mapping[str, jax.Array],
    scalars: Mapping[str, object],
    exchange: Sequence[str],
    mesh_axes: Sequence[str],
    nsteps: int,
    periodic=False,
    halo_compress: str | None = None,
):
    """Temporal blocking across ranks: ONE deep halo exchange feeds k fused
    local steps — k× fewer messages (each k·r wide instead of r).

    Local arrays must carry ``nsteps * r`` ghost layers (r: declared or
    inferred radius). After the k local sweeps the owned interior (depth
    >= k·r from the local edge) is exact: sweep s only needs
    time-s-correct values at depth >= s·d, which the deep exchange
    provides — footprint-inferred kernels refresh only ``k * depth(F)``
    per field, axis and side instead of the full ``k*r``. The ghost ring
    is stale afterwards and must be re-exchanged before the next k-step
    block. Rank-local (inside shard_map). Returns (final outputs, fresh
    fields). Fused reductions are combined by :func:`finish_reductions`,
    so they fold the stale ghost ring too: not the global check.
    """
    r, depths, _ = _kernel_geometry(kernel, fields, scalars, exchange,
                                    mesh_axes)
    if depths is not None:
        depths = {
            f: tuple((nsteps * lo, nsteps * hi) for lo, hi in d)
            for f, d in depths.items()
        }
    fresh = _halo.exchange_many(fields, exchange, mesh_axes,
                                radius=nsteps * r, periodic=periodic,
                                depths=depths, compress=halo_compress)
    res = kernel.run_steps(nsteps, **fresh, **scalars)
    if kernel.reductions:
        outs, reds = res
        res = (outs, finish_reductions(kernel, reds, mesh_axes))
    return res, fresh


def overlapped_step(
    kernel: StencilKernel,
    fields: Mapping[str, jax.Array],
    scalars: Mapping[str, object],
    exchange: Sequence[str],
    mesh_axes: Sequence[str],
    periodic=False,
    march_axis: int | None = None,
    halo_compress: str | None = None,
):
    """@hide_communication: bulk update overlaps the halo ppermutes.

    Returns (updated_outputs, fresh_fields). Rank-local (inside
    shard_map). Coupled multi-output kernels update all their outputs in
    the same overlapped pass (the halo group travels in one round-trip);
    the return mirrors the kernel's call convention — a bare array for
    single-output kernels, an out-name dict for coupled systems.

    ``march_axis`` streams the *interior* (bulk) update — the big launch
    whose windows dominate the rank's HBM traffic — through the engine's
    marching mode (``kernel.marched``); the per-face shell re-updates
    stay all-parallel: their slabs are a few cells thick, thinner than a
    plane queue, so the streamed builder would fall back anyway.

    Fused reductions: the bulk launch's partials would fold stale-halo
    shell cells that the face re-updates are about to overwrite, so the
    overlapped path runs the reduction-free kernel variants and folds
    the reductions over the *pasted* outputs' owned cells instead
    (:func:`owned_reductions` — jnp folds fused into the surrounding jit,
    then one collective); returns ``((outs, reds), fresh)`` like
    :func:`sequential_step`.
    """
    r, _, ir = _kernel_geometry(kernel, fields, scalars, exchange,
                                mesh_axes)
    plain = kernel.with_reductions(None)
    nd = fields[kernel.outputs[0]].ndim
    single = len(kernel.outputs) == 1
    # Per-axis base extent of the coupled set: staggered fields (shorter by
    # their offset) get matching shorter face slabs so the slab set keeps
    # the system's staggering. Outputs staggered along a decomposed axis
    # would need offset-aware shell pastes across the shared rank face —
    # exchange the cell fields and recompute fluxes locally instead.
    base = tuple(max(v.shape[a] for v in fields.values()) for a in range(nd))
    for axis in range(min(len(mesh_axes), nd)):
        for o in kernel.outputs:
            if fields[o].shape[axis] != base[axis]:
                raise NotImplementedError(
                    f"output {o!r} is staggered along decomposed axis "
                    f"{axis}; overlapped_step supports staggered inputs "
                    "only — keep face fields rank-local (recompute from "
                    "exchanged cell fields)"
                )

    def as_dict(res):
        return {kernel.outputs[0]: res} if single else dict(res)

    # 1) launch grouped halo exchange (independent subgraph, one
    #    round-trip for the whole coupled field set)
    fresh = _halo.exchange_many(fields, exchange, mesh_axes, radius=r,
                                periodic=periodic, compress=halo_compress)

    # 2) bulk update with stale halos — correct except the shell ring
    #    (streamed along march_axis when requested: the interior tiles
    #    reuse their plane queues instead of refetching halo windows)
    bulk_kernel = plain if march_axis is None else plain.marched(march_axis)
    bulk = as_dict(bulk_kernel(**fields, **scalars))

    # 3) recompute the shell per face from fresh slabs and paste. The
    #    slab must contain the shell's reads (support) and its writes
    #    (ring): ghost r + shell r + max(support, ring) per face — the
    #    inferred footprint trims the legacy 3r when the kernel reads
    #    shallower than r toward that face.
    if ir is not None:
        w_max = tuple(max(rings[a] for rings in ir.write_rings.values())
                      for a in range(nd))
        thick = tuple(
            (2 * r + max(ir.halo[a][1], w_max[a]),   # low face reads "up"
             2 * r + max(ir.halo[a][0], w_max[a]))   # high face reads "down"
            for a in range(nd)
        )
    else:
        thick = ((3 * r, 3 * r),) * nd
    for axis in range(min(len(mesh_axes), nd)):
        for side in (0, 1):
            slab_fields = {
                n: _face_slab(v, axis, side, thick[axis][side],
                              off=base[axis] - v.shape[axis])
                for n, v in fresh.items()
            }
            slab_out = as_dict(plain(**slab_fields, **scalars))
            for o in kernel.outputs:
                bulk[o] = _paste_shell(bulk[o], slab_out[o], axis, side, r)
    res = bulk[kernel.outputs[0]] if single else bulk
    if kernel.reductions:
        res = (res, owned_reductions(kernel, bulk, fresh, mesh_axes, r))
    return res, fresh
