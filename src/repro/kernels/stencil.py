"""Generic Pallas stencil kernel builder (the paper's C3/C4 on TPU).

ParallelStencil's ``@parallel loopopt=true`` generates a CUDA kernel where a
thread block stages a halo-extended tile of the input fields in shared
memory/registers and sweeps it. The TPU-native equivalent built here:

  * the Pallas *grid* tiles the array; every input field gets a
    **halo-extended VMEM window** (element-indexed ``pl.Element`` dims,
    overlapping windows) — this is the BlockSpec realization of
    shared-memory blocking. Mosaic (the TPU compiler) accepts only
    unpadded windows whose two minor dims are (8, 128)-aligned or span
    the array, so the derived launch tiles the *leading* axes and keeps
    the two minor axes whole; a tiled window's offset is clamped into
    the array at the domain ends and the kernel copies it into a VMEM
    frame at the matching shift, so the update always sees the nominal
    ``[pid*block - halo, pid*block + block + halo)`` frame (cells beyond
    the domain hold stale values and are masked);
  * the kernel body evaluates the *same math-close update function* the
    ``jnp`` backend uses, on the window, producing the block-interior
    update;
  * a per-block interior mask blends the update with the output field's
    previous (boundary) values, so one fused pass writes the full output
    array — boundary handling costs no extra kernel;
  * scalars ride in SMEM;
  * launch parameters (grid + block shapes) are **derived automatically**
    from the array bounds, stencil radius and a VMEM budget, mirroring
    ParallelStencil's automatic launch-parameter derivation; where the
    derived blocks would tile the leading axis and refetch each other's
    halo planes, the launch instead *streams* that axis through a VMEM
    plane queue (``march_axis``, see :func:`build_stencil_call`), so each
    plane crosses HBM once;
  * **temporal blocking** (``nsteps=k``): the VMEM window halo grows to
    ``k*radius`` and the update function is swept ``k`` times inside one
    launch, the valid region shrinking by ``radius`` per sweep. Each field
    then crosses HBM once per *k* steps instead of once per step, cutting
    A_eff by ~k at the cost of redundant halo-cone recompute per block.

Coupled multi-field systems
---------------------------
One launch may carry several simultaneous output fields (``out_names``)
and **mixed-shape staggered fields**: a field whose extent along axis ``a``
is ``shape[a] - off`` with ``0 <= off <= radius`` (the staggering band)
lives on cell faces (``off = 1`` is the classic face-centered flux next
to cell-centered scalars; with an inferred footprint of radius r the band
is 2r + 1, down to ParallelStencil's inner-face residuals). Per-field
halo windows are derived from the field's staggering: a field with
offset ``off`` gets a VMEM window of ``block + 2*halo - off`` per axis,
which is exactly what makes the *relative slice* fd operators
(``d_xa``, ``av_xa``, ``inn``, ...) consume shapes on windows the same way
they do on full arrays — the single-source shape contract.

Per-output write semantics are likewise *derived from the update's shape*
along each axis (the engine's analogue of ParallelStencil's ``@inn(T2)``
vs ``@all(qx)`` left-hand sides):

  * update extent == window extent - 2*radius  ->  ``inn``: interior
    write; the output's boundary ring keeps its previous values.
  * update extent == window extent             ->  ``all``: every
    in-domain cell is written (no boundary ring). On staggered axes
    (``off > 0``) an interior-style write needs the inferred footprint,
    whose window halo reaches ``off`` cells further (see
    :func:`write_geometry`).

Multi-output temporal blocking: with ``nsteps=k`` each sweep's outputs
rotate into their ``rotations[out]`` partner windows (the in-kernel
analogue of ``phi, phi2 = phi2, phi; Pe, Pe2 = Pe2, Pe``), so whole
coupled systems (porosity waves, Gross-Pitaevskii) advance k steps per
HBM round-trip.

Caveats (documented): the update function must not read an *output* field's
halo ring (its window is only used as the boundary-copy source). All paper
solvers satisfy this — e.g. Fig. 1's ``T2`` is write-only. With ``nsteps>1``
the k-step result is bitwise-identical to k rotated single-step calls
provided the rotation buffers agree on their boundary rings (true for all
solvers here: both buffers start as copies and boundaries are never
updated).
"""
from __future__ import annotations

import functools
import math
import os
import sys
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _telemetry
from ..ir.reductions import (check_operand_shapes as _check_operand_shapes,
                             normalize_reductions as _normalize_reductions)

# Per-grid-step block of a reduction-partials output: the smallest f32
# tile Mosaic stores (one value, broadcast; ``run`` reads [..., 0, 0]).
_PARTIAL_TILE = (8, 128)

# Scoped-VMEM limit handed to Mosaic (``vmem_limit_bytes``) and the wall
# the preflight admission check holds every launch to. A TPU v5e core has
# 128 MiB of VMEM; without an explicit limit Mosaic enforces 16 MiB. The
# 8 MiB kept back is Mosaic's own internal scratch. Override per
# deployment with REPRO_VMEM_LIMIT_BYTES.
DEFAULT_VMEM_LIMIT = 120 << 20

# Soft budget steering automatic tile derivation: the launch's whole
# accounted footprint (see :func:`launch_footprint_bytes`) stays under it
# where the array allows. It admits the 2-plane f32 blocks of 512-wide
# grids — (2, 512, 512) at 44 MiB, and (2, 514, 514), a 512^3 rank with
# its ghost ring, at 55.9 MiB once lanes are padded — not 4-plane ones.
DEFAULT_VMEM_BUDGET = 56 << 20


class UnrealizableTile(ValueError):
    """A tile this launch cannot realize by construction (it does not
    divide the array, or its windows cannot fit VMEM) — the one error
    class a tile search may skip."""


class TileNotCompilable(UnrealizableTile, NotImplementedError):
    """A tile Mosaic cannot compile by construction: it splits one of the
    two minor axes, which a compiled launch keeps whole."""


class LaunchFootprintError(UnrealizableTile):
    """The derived launch's VMEM footprint exceeds the device limit —
    raised at derivation time (preflight), not as an opaque backend
    allocation failure at compile/run time."""


def _vmem_limit(vmem_limit: int | None) -> int:
    if vmem_limit is not None:
        return int(vmem_limit)
    env = os.environ.get("REPRO_VMEM_LIMIT_BYTES", "")
    return int(env) if env else DEFAULT_VMEM_LIMIT


def preflight_vmem(block: Sequence[int], window_bytes: int,
                   vmem_limit: int | None = None, *,
                   explicit_tile: bool) -> None:
    """Admission check: refuse a launch whose halo-extended window set
    cannot fit device VMEM. Names the tile, the footprint and the limit,
    and says what to do about it."""
    limit = _vmem_limit(vmem_limit)
    if window_bytes <= limit:
        return
    source = ("explicit tile" if explicit_tile
              else "derived block (grid too small to shrink further)")
    raise LaunchFootprintError(
        f"launch preflight: {source} {tuple(block)} needs "
        f"{window_bytes / 2**20:.1f} MiB of VMEM (double-buffered windows "
        "and output blocks, scratch and the body's intermediates), over the "
        "device "
        f"limit of {limit / 2**20:.1f} MiB — pass a smaller tile=, raise "
        "march_axis streaming, or (if the device really has more VMEM) "
        "set REPRO_VMEM_LIMIT_BYTES")


def default_compute_dtype(dtype) -> jnp.dtype:
    """The compute dtype a storage dtype implies: sub-f32 floats (bf16,
    f16, f8) widen to float32 — fields are *stored* narrow but all
    stencil arithmetic happens at f32 inside the VMEM window (cast on
    load, cast on store) — while f32/f64/int storage computes in its own
    precision. The engine-wide storage-vs-compute rule; override with
    ``compute_dtype=`` on ``init_parallel_stencil``/``build_stencil_call``."""
    st = jnp.dtype(dtype)
    if jnp.issubdtype(st, jnp.floating) and st.itemsize < 4:
        return jnp.dtype(jnp.float32)
    return st


def accum_dtype(compute_dtype) -> jnp.dtype:
    """Accumulation dtype for reduction epilogues: never narrower than
    f32 (bf16 partial sums saturate after ~256 increments — a 256^3
    ``sum`` would plateau at a tiny fraction of its value and a
    convergence check would silently lose its signal), and f64 compute
    keeps f64 accumulation."""
    return jnp.promote_types(jnp.float32, jnp.dtype(compute_dtype))


def _divisors_leq(n: int, cap: int) -> list[int]:
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def _pick_block(n: int, cap: int, align: int) -> int:
    """Largest divisor of n that is <= cap, preferring multiples of align."""
    divs = _divisors_leq(n, cap)
    aligned = [d for d in divs if d % align == 0]
    return (aligned or divs)[-1]


def _halo_pairs(halo, nd: int) -> tuple[tuple[int, int], ...]:
    """Normalize a halo spec — an int (symmetric, every axis) or a
    per-axis sequence of ints/(lo, hi) pairs — to per-axis pairs."""
    if isinstance(halo, int):
        return ((halo, halo),) * nd
    out = []
    for h in halo:
        if isinstance(h, int):
            out.append((h, h))
        else:
            lo, hi = h
            out.append((int(lo), int(hi)))
    return tuple(out)


def _total_halo(radius: int, halos, nsteps: int, nd: int
                ) -> tuple[tuple[int, int], ...]:
    """Per-axis (lo, hi) window halo of all ``nsteps`` sweeps: the
    symmetric ``radius`` or the per-axis single-sweep ``halos``, times k."""
    k = max(int(nsteps), 1)
    return tuple((k * lo, k * hi)
                 for lo, hi in _halo_pairs(radius if halos is None else halos,
                                           nd))


def window_footprint_bytes(
    block: Sequence[int],
    halo,
    field_offsets: Sequence[Sequence[int]],
    itemsize: int,
) -> int:
    """VMEM bytes of a coupled field set's halo-extended windows: each
    field occupies ``prod(block + halo_lo + halo_hi - off)`` elements
    (``halo``: int or per-axis (lo, hi) pairs — footprint-inferred halos
    are per-axis and possibly asymmetric). The single shared accounting
    used by launch derivation, the autotuner's candidate filter and
    ``run.window_bytes`` — keep them consistent."""
    pairs = _halo_pairs(halo, len(tuple(block)))
    return sum(
        math.prod(b + lo + hi - o
                  for b, (lo, hi), o in zip(block, pairs, off))
        for off in field_offsets
    ) * itemsize


def march_queue_blocks(block_m: int, halo_m: tuple[int, int]) -> tuple[int, int, int]:
    """Rolling plane-queue geometry of a streamed (marching) launch along
    one axis: ``halo_m`` is the *total* (lo, hi) window halo along the
    march axis (single-sweep depths times ``nsteps``), ``block_m`` the
    march-axis block extent.  Returns ``(Q, Llo, Lhi)``: the queue depth
    in blocks and the low/high lookbehind/lookahead in blocks.  The queue
    holds ``Q * block_m`` planes — ``2*halo + support`` rounded up to
    block multiples — and the output lags the fetch by ``Lhi`` blocks
    (the drain/priming offset of the software pipeline)."""
    k_lo, k_hi = int(halo_m[0]), int(halo_m[1])
    bm = int(block_m)
    llo = -(-k_lo // bm)
    lhi = -(-k_hi // bm)
    return llo + 1 + lhi, llo, lhi


def streamed_footprint_bytes(
    block: Sequence[int],
    halo,
    field_offsets: Sequence[Sequence[int]],
    itemsize: int,
    march_axis: int,
    queue: bool = True,
) -> int:
    """VMEM bytes of a *streamed* launch: per field, the fetch window
    carries no halo along the march axis (new planes only — the reuse
    that kills the refetch) plus (``queue``) the rolling plane queue of
    ``Q * block_m`` planes carried in scratch across grid steps."""
    block = tuple(int(b) for b in block)
    nd = len(block)
    pairs = _halo_pairs(halo, nd)
    m = march_axis
    q, _, _ = march_queue_blocks(block[m], pairs[m])
    total = 0
    for off in field_offsets:
        other = [block[a] + pairs[a][0] + pairs[a][1] - off[a]
                 for a in range(nd) if a != m]
        area = math.prod(other) if other else 1
        total += (block[m] - off[m]) * area          # fetch window
        if queue:
            total += q * block[m] * area             # scratch plane queue
    return total * itemsize


def effective_halo(shape: Sequence[int], block: Sequence[int], halo
                   ) -> tuple[tuple[int, int], ...]:
    """Per-axis window halo of a launch: an axis the block spans whole
    (one grid step) has no neighbouring block to read from, so it carries
    no halo — the update sees the whole domain along it."""
    pairs = _halo_pairs(halo, len(tuple(block)))
    return tuple((0, 0) if b == s else p
                 for s, b, p in zip(shape, block, pairs))


def launch_footprint_bytes(
    shape: Sequence[int],
    block: Sequence[int],
    halo,
    field_offsets: Sequence[Sequence[int]],
    itemsize: int,
    n_out: int,
    march_axis: int | None = None,
    nsteps: int = 1,
    compute_itemsize: int | None = None,
) -> int:
    """VMEM a launch holds, as Mosaic allocates it: the pipelined input
    windows and output blocks twice over (double buffering), the frame
    buffer of each output (its stage when a tiled window's origin is
    clamped, and its rotated field's buffer between temporal sweeps),
    — streamed — the plane queues, and the body's intermediate values.
    ``halo`` is the total (all sweeps) per-axis depth; axes the block
    spans whole carry none (:func:`effective_halo`).

    Each buffer is counted as Mosaic lays it out: its two minor dims
    rounded up to whole (sublane, 128-lane) tiles of 32-bit words (8
    sublanes for f32, 16 for bf16), so a 514-wide axis costs 640 lanes.
    The intermediates are estimated as ``1 + n_out + nsteps`` frames at
    the compute dtype, a bound fitted to Mosaic's scoped allocations for
    the diffusion step at 128³-514³ (f32 and bf16, 1-4 sweeps, streamed)
    that it exceeded by at most 1.5 MiB."""
    shape = tuple(int(s) for s in shape)
    block = tuple(int(b) for b in block)
    nd = len(block)
    pairs = effective_halo(shape, block, halo)

    def vmem(extent, size=itemsize):
        ext = list(extent)
        ext[-1] = -(-ext[-1] // 128) * 128
        if nd > 1:
            sub = 8 * max(4 // size, 1)
            ext[-2] = -(-ext[-2] // sub) * sub
        return math.prod(ext) * size

    windows = queues = 0
    for off in field_offsets:
        ext = [b + lo + hi - o for b, (lo, hi), o in zip(block, pairs, off)]
        if march_axis is not None:
            # the fetch window carries no march-axis halo; the plane
            # queue holds Q blocks of planes
            m = march_axis
            q, _, _ = march_queue_blocks(block[m], pairs[m])
            ext[m] = block[m] - off[m]
            queues += vmem(ext[:m] + [q * block[m]] + ext[m + 1:])
        windows += vmem(ext)
    frame = [min(b + lo + hi, s) for s, b, (lo, hi) in zip(shape, block, pairs)]
    frames = 0
    if nsteps > 1 or any(a != march_axis and sum(pairs[a])
                         for a in range(nd)):
        frames = n_out * vmem(frame)
    outs = n_out * vmem(block)
    body = ((1 + n_out + int(nsteps))
            * vmem(frame, max(itemsize, compute_itemsize or itemsize)))
    return 2 * (windows + outs) + frames + queues + body


def tpu_whole_axes(nd: int, march_axis: int | None = None) -> tuple[int, ...]:
    """The axes a TPU launch keeps at full extent: the two minor axes
    (Mosaic refuses windows whose minor dims are neither (8, 128)-aligned
    nor whole, and padded windows), less a march axis."""
    return tuple(a for a in range(max(nd - 2, 0), nd) if a != march_axis)


def derive_launch(
    shape: Sequence[int],
    radius: int,
    n_fields: int,
    itemsize: int,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    tile: Sequence[int] | None = None,
    nsteps: int = 1,
    field_offsets: Sequence[Sequence[int]] | None = None,
    halos: Sequence[tuple[int, int]] | None = None,
    march_axis: int | None = None,
    march_min_block: int = 1,
    vmem_limit: int | None = None,
    tpu_layout: bool = False,
    compiled: bool = False,
    n_out: int = 1,
    compute_itemsize: int | None = None,
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Derive (grid, block_shape) from array bounds — ParallelStencil's
    automatic launch-parameter derivation, with TPU tiling constraints.

    Every launch is costed with :func:`launch_footprint_bytes` (the VMEM
    Mosaic allocates for it: double-buffered windows of all fields and
    ``n_out`` output blocks, realignment frames, plane queues) and passes
    a preflight admission check against the limit the compiler enforces
    (``vmem_limit``, default :data:`DEFAULT_VMEM_LIMIT` or
    ``REPRO_VMEM_LIMIT_BYTES``): an explicit ``tile`` that cannot fit
    raises a pointed :class:`LaunchFootprintError` here, before compile,
    instead of an opaque allocation failure later.

    ``tpu_layout`` derives the layout the Pallas kernels run in: the
    :func:`tpu_whole_axes` stay at full extent and only leading axes are
    tiled. ``compiled`` (a launch Mosaic will compile, not interpret)
    also refuses what Mosaic cannot compile: an explicit ``tile`` that
    splits a minor axis (:class:`TileNotCompilable`) and a march axis on
    a minor axis (``NotImplementedError``); interpret mode honours both.
    Without ``tpu_layout`` the minor (last) axis prefers 128-lane
    multiples, the next-to-minor 8-sublane multiples. Blocks must
    divide the array extents (the caller pads otherwise). The block set is
    shrunk until the footprint fits ``vmem_budget``. With temporal
    blocking (``nsteps > 1``) the window halo is ``nsteps * radius`` per
    side, so the same budget yields smaller blocks.

    ``halos`` overrides the symmetric ``radius`` halo with per-axis
    (lo, hi) single-sweep depths (the footprint-inferred geometry): the
    window extension becomes ``nsteps * (lo, hi)`` per axis, so an axis
    the kernel never differences costs no VMEM halo at all.

    ``field_offsets`` gives the per-field staggering offsets of the whole
    coupled field set (one tuple per field, entries subtracted from the
    base window extent); the VMEM footprint is the *sum of the per-field
    windows*, so a system with many fields gets smaller blocks than a
    single-field problem under the same budget.

    ``march_axis`` switches the VMEM accounting to the streamed launch
    geometry: the march axis carries no window halo (blocks fetch new
    planes only) but each field adds a rolling plane queue of
    ``Q * block_m`` planes held in scratch across grid steps.
    """
    shape = tuple(int(s) for s in shape)
    nd = len(shape)
    k = max(int(nsteps), 1)
    halo = _total_halo(radius, halos, k, nd)
    if field_offsets is None:
        field_offsets = [(0,) * nd] * int(n_fields)
    field_offsets = [tuple(int(o) for o in off) for off in field_offsets]
    minor = tuple(range(max(nd - 2, 0), nd))
    if compiled:
        if march_axis is not None and march_axis in minor:
            raise NotImplementedError(
                f"march_axis={march_axis} streams a minor axis of a {nd}-d "
                "stencil; compiled for the TPU a launch keeps its two minor "
                "axes whole (Mosaic windows must span them), so only a "
                "leading axis can stream — march axis 0 of a 3-d stencil "
                "or drop march_axis")
        if tile is not None and any(int(tile[a]) != shape[a] for a in minor):
            raise TileNotCompilable(
                f"tile {tuple(tile)} splits a minor axis of shape {shape}; "
                "compiled for the TPU a launch keeps its two minor axes "
                "whole (Mosaic refuses windows whose minor dims are neither "
                f"(8, 128)-aligned nor whole) — pass a tile spanning axes "
                f"{minor} or leave tile=None")
    whole = (set(tpu_whole_axes(nd, march_axis)) if tpu_layout or compiled
             else set())

    def footprint(blk):
        return launch_footprint_bytes(shape, blk, halo, field_offsets,
                                      itemsize, n_out, march_axis, k,
                                      compute_itemsize)

    if tile is not None:
        block = tuple(int(b) for b in tile)
        if len(block) != nd or any(s % b for s, b in zip(shape, block)):
            raise UnrealizableTile(f"tile {block} must divide shape {shape}")
        preflight_vmem(block, footprint(block), vmem_limit,
                       explicit_tile=True)
    else:
        caps = [256 if a == nd - 1 else (64 if a == nd - 2 else 16) for a in range(nd)]
        aligns = [128 if a == nd - 1 else (8 if a == nd - 2 else 1) for a in range(nd)]
        block = [
            s if a in whole else _pick_block(s, c, al)
            for a, (s, c, al) in enumerate(zip(shape, caps, aligns))
        ]
        if march_axis is not None:
            # The march block should be *small*: each sequential grid
            # step fetches bm fresh planes, and the pipeline's drain
            # refetches up to one block per column — so bm beyond the
            # halo depth only inflates the queue and the drain traffic,
            # while a halo-sized bm keeps both at O(halo). The innermost
            # two axes keep their lane/sublane-aligned tiles.
            m = march_axis
            need = max(halo[m][0], halo[m][1], 1, int(march_min_block))
            fit = [d for d in _divisors_leq(shape[m], shape[m]) if d >= need]
            block[m] = fit[0] if fit else shape[m]

        # Shrink the largest non-minor axis first; keep lane alignment
        # longest; whole axes never shrink, nor a march block below
        # ``march_min_block`` (a fused neumann0 shift needs its cells).
        while footprint(block) > vmem_budget:
            cands = sorted((a for a in range(nd) if a not in whole),
                           key=lambda a: (a == nd - 1, -block[a]))
            for a in cands:
                floor = march_min_block if a == march_axis else 1
                smaller = [d for d in _divisors_leq(shape[a], block[a] - 1)
                           if d >= floor]
                if smaller:
                    block[a] = smaller[-1]
                    break
            else:
                break  # cannot shrink further; let it ride
        block = tuple(block)
        # "let it ride" can still exceed the soft budget — but never the
        # hard device capacity
        preflight_vmem(block, footprint(block), vmem_limit,
                       explicit_tile=False)
    grid = tuple(s // b for s, b in zip(shape, block))
    return grid, block


def derive_default_launch(
    shape: Sequence[int],
    radius: int,
    n_fields: int,
    itemsize: int,
    *,
    nsteps: int = 1,
    field_offsets: Sequence[Sequence[int]] | None = None,
    halos: Sequence[tuple[int, int]] | None = None,
    march_min_block: int = 1,
    vmem_limit: int | None = None,
    **kw,
) -> tuple[tuple[int, ...], tuple[int, ...], int | None]:
    """The launch of a caller that fixed neither ``tile`` nor
    ``march_axis``: ``(grid, block, march_axis)``.

    The all-parallel :func:`derive_launch` geometry, unless it tiles axis
    0 and carries a halo along it — then every block would refetch its
    neighbours' halo planes, and the launch streams along axis 0 instead
    (each plane crosses HBM once per launch), provided no field is
    staggered along axis 0, axis 0 fills the plane queue, and the
    streamed footprint passes the VMEM preflight. ``kw`` goes to
    :func:`derive_launch` (never ``tile`` or ``march_axis``);
    ``march_min_block`` applies to the streamed derivation only."""
    shape = tuple(int(s) for s in shape)

    def derive(**extra):
        return derive_launch(shape, radius, n_fields, itemsize, nsteps=nsteps,
                             field_offsets=field_offsets, halos=halos,
                             **kw, **extra)

    def all_parallel():
        return derive(vmem_limit=vmem_limit) + (None,)

    # the all-parallel geometry; the launch kept is admitted below
    grid, _ = derive(vmem_limit=sys.maxsize)
    lo, hi = _total_halo(radius, halos, nsteps, len(shape))[0]
    if grid[0] == 1 or not lo + hi or any(
            off[0] for off in field_offsets or ()):
        return all_parallel()
    try:
        s_grid, s_block = derive(march_axis=0, vmem_limit=vmem_limit,
                                 march_min_block=march_min_block)
    except LaunchFootprintError:
        return all_parallel()
    q_blocks, _, _ = march_queue_blocks(s_block[0], (lo, hi))
    if shape[0] < q_blocks * s_block[0]:
        return all_parallel()
    return s_grid, s_block, 0


def window_spec(extent: Sequence[int], index_map: Callable) -> pl.BlockSpec:
    """BlockSpec for an overlapping VMEM window of ``extent`` elements per
    axis; ``index_map`` returns the window's *element* offsets, which must
    keep it inside the array (Mosaic supports no padded windows)."""
    return pl.BlockSpec(tuple(pl.Element(int(e)) for e in extent), index_map)


def window_start(pid, block: int, lo: int, hi: int, n: int):
    """Element offset of a tiled axis's window: the nominal frame origin
    ``pid*block - lo`` clamped so the window (``block + lo + hi`` cells,
    or the whole axis when that is shorter) stays inside an axis of ``n``
    cells (the base extent; staggered fields clamp identically)."""
    return jnp.clip(pid * block - lo, 0, max(n - block - lo - hi, 0))


def frame_origin(pids: Sequence, block: Sequence[int],
                 whalo: Sequence[tuple[int, int]], shape: Sequence[int]
                 ) -> tuple:
    """Global index of cell 0 of a block's VMEM frame, per axis: the
    window's element offset (what the index map returns). A literal 0 on
    an axis the block spans whole (Mosaic must prove a minor-axis offset
    tile-aligned, which it cannot for a traced expression), the clamped
    :func:`window_start` on an axis whose windows overlap their
    neighbours', the block start otherwise."""
    return tuple(0 if b == s else window_start(p, b, lo, hi, s) if lo + hi
                 else p * b
                 for p, b, (lo, hi), s in zip(pids, block, whalo, shape))


def compiler_params(nd: int, march: bool = False,
                    vmem_limit: int | None = None):
    """Mosaic parameters for an nd stencil grid: ``dimension_semantics``
    all-parallel by default (every block independent, letting Mosaic
    pipeline block revisits); with ``march=True`` the innermost (last)
    grid dimension is ``"arbitrary"`` — executed sequentially so the
    scratch plane queue carries state from one grid step to the next —
    while the leading tile dimensions stay ``"parallel"``. The scoped-VMEM
    limit is the one the preflight admitted the launch against."""
    sem = ("parallel",) * nd if not march else \
        ("parallel",) * (nd - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_vmem_limit(vmem_limit))


def field_geometry(
    shape: Sequence[int],
    field_names: Sequence[str],
    field_shapes: Mapping[str, Sequence[int]] | None,
    radius: int,
) -> tuple[dict[str, tuple[int, ...]], dict[str, tuple[int, ...]]]:
    """Resolve per-field shapes and staggering offsets against the base
    (cell-centered) ``shape``; offsets must lie in ``[0, radius]``."""
    base = tuple(int(s) for s in shape)
    field_shapes = dict(field_shapes or {})
    shapes, offsets = {}, {}
    for n in field_names:
        s = tuple(int(x) for x in field_shapes.get(n, base))
        if len(s) != len(base):
            raise ValueError(
                f"field {n!r} shape {s} has rank {len(s)}, expected {len(base)}"
            )
        off = tuple(b - x for b, x in zip(base, s))
        if any(o < 0 or o > radius for o in off):
            raise ValueError(
                f"field {n!r} shape {s} is not within the staggering band of "
                f"base shape {base}: per-axis offsets {off} must lie in "
                f"[0, radius={radius}] (face-centered fields are at most "
                "`radius` shorter than the cell-centered base per axis)"
            )
        shapes[n] = s
        offsets[n] = off
    return shapes, offsets


def write_geometry(
    update_shape: Sequence[int],
    window_shape: Sequence[int],
    off: Sequence[int],
    name: str,
    ring: int | None = None,
) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """Per-axis write semantics + interior-ring depth derived from the
    update's traced shape.

    ``all``: the update spans the field's whole window (ParallelStencil's
    ``@all(qx) = ...`` left-hand side — every in-domain cell is written;
    ring 0). ``inn``: it spans a symmetric window interior (``@inn(T2) =
    ...`` — a ``w``-cell boundary ring keeps its previous values).

    ``ring`` pins the accepted ``inn`` depth (the legacy declared-radius
    contract); ``None`` accepts any symmetric margin (the inferred-
    footprint engine, where the ring is whatever the kernel's own slicing
    produced). An ``inn`` write on a staggered axis (ParallelStencil's
    ``@inn(Vx)`` on a face field) needs a window that reaches ``off + w``
    cells past each block, which the inferred halo provides
    (``ir.trace_stencil``) and the legacy symmetric ``radius`` halo does
    not: with a pinned ring such a write would leave the faces straddling
    block boundaries written by no block, and is refused.
    """
    modes, rings = [], []
    for a, (u, w, o) in enumerate(zip(update_shape, window_shape, off)):
        if u == w:
            modes.append("all")
            rings.append(0)
            continue
        margin = w - u
        if margin > 0 and margin % 2 == 0 and (ring is None or margin == 2 * ring):
            if o > 0 and ring is not None:
                raise ValueError(
                    f"output {name!r} is staggered along axis {a} (offset "
                    f"{o}) but its update covers only the interior there; "
                    "staggered axes must be written at full extent "
                    "(`all` semantics, e.g. qx = -k_face * d_xa(Pe)/dx)"
                )
            modes.append("inn")
            rings.append(margin // 2)
            continue
        want = (f"{w - 2 * ring} (`inn` write) for window extent {w} at "
                f"radius {ring}" if ring is not None else
                f"an even interior margin (`inn` write) of window extent {w}")
        raise ValueError(
            f"output {name!r} update has extent {u} along axis {a}; "
            f"expected {w} (`all` write) or {want}"
        )
    return tuple(modes), tuple(rings)


def _valid_mask(origin, frame_shape, field_shape, rings, modes):
    """Mask of the cells one output field may take from its update, on a
    frame whose cell ``j`` is global cell ``origin + j`` per axis.

    ``inn`` axes accept the field's global interior at that axis's ring
    depth; ``all`` axes accept every in-domain cell (cells beyond a
    staggered field's extent stay masked and are never stored).
    """
    m = None
    for a in range(len(frame_shape)):
        g = origin[a] + jax.lax.broadcasted_iota(jnp.int32, frame_shape, a)
        if modes[a] == "inn":
            ma = (g >= rings[a]) & (g < field_shape[a] - rings[a])
        else:
            ma = (g >= 0) & (g < field_shape[a])
        m = ma if m is None else (m & ma)
    return m


def _embed(a, frame: Sequence[int], starts: Sequence[int]):
    """Place ``a`` on a frame so element ``u`` lands at ``u + start`` per
    axis: negative starts crop the front, overhang crops the back, and
    shortfall zero-pads (padded cells are always masked out by the
    caller's validity mask). For the legacy symmetric geometry this
    reduces to the plain interior/`all` slices (no padding)."""
    sl, pads, need_pad = [], [], False
    for ext, st, d in zip(frame, starts, a.shape):
        lo_crop = max(0, -st)
        place = max(st, 0)
        take = min(d - lo_crop, ext - place)
        sl.append(slice(lo_crop, lo_crop + take))
        pads.append((place, ext - place - take))
        need_pad = need_pad or place > 0 or ext - place - take > 0
    a = a[tuple(sl)]
    if need_pad:
        a = jnp.pad(a, pads)
    return a


def _shift(a, axis: int, d: int):
    """``out[j] = a[j + d]`` along ``axis`` (zero-fill at the vacated
    end; only consumed under face predicates that never select fill)."""
    idx = [slice(None)] * a.ndim
    pad = [(0, 0)] * a.ndim
    if d > 0:
        idx[axis] = slice(d, None)
        pad[axis] = (0, d)
    else:
        idx[axis] = slice(0, a.shape[axis] + d)
        pad[axis] = (-d, 0)
    return jnp.pad(a[tuple(idx)], pad)


def _apply_bc_frame(arr, bc, field_shape, origin, dtype):
    """Realize one output's dirichlet/neumann0 condition on a frame whose
    cell ``j`` is global cell ``origin + j`` per axis, bitwise-equal to
    the ``core.boundary`` post-pass.

    Face cells are located by global-index iotas; neumann0 copies travel
    through frame-local static shifts, applied axis-by-axis in the same
    sequential order as the post-pass (which is what defines the corner
    values). Periodic conditions cannot be realized from local windows
    (their sources live across the domain) and are handled by the caller
    as a face-slab scatter on the assembled output.
    """
    if bc is None or bc.kind == "periodic":
        return arr
    nd = arr.ndim
    d = bc.depth

    def giota(a):
        return origin[a] + jax.lax.broadcasted_iota(jnp.int32, arr.shape, a)

    if bc.kind == "dirichlet":
        val = jnp.asarray(bc.value, dtype)
        face = None
        for a in bc.resolved_axes(nd):
            g = giota(a)
            n = field_shape[a]
            fa = ((g >= 0) & (g < d)) | ((g >= n - d) & (g < n))
            face = fa if face is None else (face | fa)
        return arr if face is None else jnp.where(face, val, arr)

    # neumann0
    for a in bc.resolved_axes(nd):
        g = giota(a)
        n = field_shape[a]
        arr = jnp.where((g >= 0) & (g < d), _shift(arr, a, d), arr)
        arr = jnp.where((g >= n - d) & (g < n), _shift(arr, a, -d), arr)
    return arr


def build_stencil_call(
    update_fn: Callable[[Mapping[str, jax.Array], Mapping[str, jax.Array]], Mapping[str, jax.Array]],
    *,
    field_names: Sequence[str],
    out_names: Sequence[str],
    scalar_names: Sequence[str],
    shape: Sequence[int],
    radius: int,
    dtype,
    compute_dtype=None,
    tile: Sequence[int] | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    interpret: bool | None = None,
    nsteps: int = 1,
    rotations: Mapping[str, str] | None = None,
    field_shapes: Mapping[str, Sequence[int]] | None = None,
    halos: Sequence[tuple[int, int]] | None = None,
    bc: Mapping[str, object] | None = None,
    march_axis: int | None = None,
    write_rings: Sequence[int] | None = None,
    reductions: Mapping[str, object] | None = None,
    name: str | None = None,
) -> Callable[..., dict[str, jax.Array]]:
    """Build a fused Pallas stencil step (or a k-step temporal block).

    ``update_fn(fields, scalars) -> {out_name: update}`` is traced on
    halo-extended VMEM windows. Returns ``run(fields, scalars)`` mapping
    full arrays -> dict of full output arrays.

    Mixed precision: ``dtype`` is the *storage* dtype — what the fields,
    VMEM windows, scratch plane queues and outputs hold, and what sizes
    the launch derivation's VMEM accounting. ``compute_dtype`` (default:
    :func:`default_compute_dtype` — f32 for sub-f32 float storage) is
    what the update arithmetic runs in: windows are cast on load right
    before ``update_fn`` sees them, updates cast back to storage on
    store, and scalars ride in SMEM at compute precision. Between
    temporal sweeps the rotated values round through storage dtype, so a
    k-fused launch stays bitwise-consistent with k sequential launches.
    Reduction partials always accumulate at :func:`accum_dtype` (>= f32)
    regardless of storage.

    ``shape`` is the *base* (cell-centered) extent; ``field_shapes`` may
    give smaller per-field extents for staggered fields (``shape - off``
    per axis, ``0 <= off <= radius``) — each field's window and write mask
    are derived from its own geometry (see module docstring).

    ``halos`` switches the window geometry from the legacy symmetric
    ``radius`` to footprint-inferred per-axis (lo, hi) depths (the
    stencil-IR path): windows extend ``nsteps * (lo, hi)`` per axis, and
    per-output interior rings are whatever the update's own slicing
    produced rather than being pinned to ``radius``. ``radius`` then only
    bounds the staggering band.

    ``bc`` maps output names to ``ir.BoundaryCondition``s, realized
    *inside* the launch (dirichlet/neumann0 — including between temporal
    sweeps) or as a face-slab scatter on the assembled output (periodic),
    bitwise-equal to applying the ``core.boundary`` post-pass after every
    step.

    With ``nsteps=k > 1`` the update is swept k times inside the kernel:
    the windows carry a ``k``-sweep halo, each sweep shrinks them by one
    sweep's depth per side, and ``rotations[out_name]`` names the input
    field the sweep's output becomes for the next sweep (the in-kernel
    analogue of the solver's ``T, T2 = T2, T`` double-buffer rotation) —
    for coupled systems every output rotates simultaneously.

    Streaming (``march_axis=a``): axis ``a`` becomes a *sequential* grid
    dimension (innermost, ``dimension_semantics`` "arbitrary") that the
    launch marches block-by-block. Each grid step fetches only the NEW
    planes of every field (the march-axis window carries no halo) and
    pushes them into a rolling plane queue held in VMEM scratch across
    grid steps; the halo-extended march window is then assembled from
    the queue, so each input element crosses HBM ~once per sweep instead
    of once per overlapping tile. The output lags the fetch by ``Lhi``
    blocks (priming steps write block 0 and are overwritten; ``Lhi``
    drain steps flush the tail), which is transparent to the caller.
    Fields staggered along the march axis are unsupported (ValueError);
    a march extent smaller than the queue falls back to the all-parallel
    path (``run.march_fallback``). Without ``tile`` and ``march_axis``
    the launch streams along axis 0 wherever the all-parallel blocks
    would tile it and refetch its halo planes
    (:func:`derive_default_launch`); an explicit ``tile`` or
    ``march_axis`` builds exactly the launch it names. Each build counts
    ``stencil.builds{fn, path}`` (``path``: ``streamed``, ``refetched``
    — axis 0 tiled by overlapping windows — or ``whole``, axis 0 in one
    block) and ``stencil.own_extent_outputs{fn}``, its outputs staggered
    against the base extent. Each output is stored at its own extent: its
    block is the launch's on a tiled or marched axis and its own extent on
    an axis the block spans whole, its block index is clamped to its last
    block, and a block wholly past its extent stores nothing.

    Reductions (``reductions={name: ir.Reduction | "kind(field[, other])"}``):
    named convergence/conservation checks (``max_abs``, ``max_abs_diff``,
    ``sum``, ``sum_sq``) computed INSIDE the launch. Each grid tile folds
    its domain-masked partial over the out-block frame — output operands
    see the freshly blended values, input operands the current window —
    into a tiny per-tile partials output (one scalar per tile, written
    through the same lagged index map on the streamed path, so sequential
    march steps land their partials per written block and the drain
    flushes the tail), and ``run`` finishes with a scalar combine over
    the partials: no operand crosses HBM a second time. ``run`` then
    returns ``(outputs, reductions)``. With ``nsteps=k`` only the final
    sweep reduces (the k-step value — what a sequential checker sees).
    A staggered operand reduces over its own extent: its per-tile mask
    bounds the block's cells by the operand's shape, so no cell of the
    base frame past it enters the fold; the operands of one reduction
    share one shape. Periodic BCs are incompatible (their wrap scatter
    happens after the launch, so an in-launch fold would see pre-wrap face
    values).

    ``name`` (the ``@parallel`` function's name) names the launch: the
    custom call's instruction and its ``kernel_metadata`` attribute
    (``{"parallel": name}``) carry it into the compiled HLO, so a device
    trace finds the launch by name.
    """
    shape = tuple(int(s) for s in shape)
    nd = len(shape)
    dtype = jnp.dtype(dtype)
    compute_dtype = (default_compute_dtype(dtype) if compute_dtype is None
                     else jnp.dtype(compute_dtype))
    acc_dtype = accum_dtype(compute_dtype)
    cast_compute = compute_dtype != dtype

    def call_update(windows, scalars):
        if cast_compute:
            windows = {n: w.astype(compute_dtype) for n, w in windows.items()}
        return update_fn(windows, scalars)

    field_names = tuple(field_names)
    out_names = tuple(out_names)
    scalar_names = tuple(scalar_names)
    nsteps = int(nsteps)
    if nsteps < 1:
        raise ValueError(f"nsteps must be >= 1, got {nsteps}")
    for o in out_names:
        if o not in field_names:
            raise ValueError(
                f"output {o!r} must also be an input field (boundary-copy source)"
            )
    shapes, offsets = field_geometry(shape, field_names, field_shapes, radius)
    reductions = _normalize_reductions(reductions, field_names)
    red_names = tuple(reductions)
    for rn, r in reductions.items():
        _check_operand_shapes(rn, r, shapes)
    bc = dict(bc or {})
    inkernel_bc = {o: c for o, c in bc.items() if c.kind != "periodic"}
    post_bc = {o: c for o, c in bc.items() if c.kind == "periodic"}
    if reductions and post_bc:
        raise ValueError(
            "fused reductions cannot ride a launch with periodic boundary "
            "conditions: the wrap scatter runs after the launch, so the "
            "in-kernel fold would see pre-wrap face values — apply the "
            "reduction as a post-pass or use dirichlet/neumann0"
        )
    if post_bc and nsteps > 1:
        raise ValueError(
            "periodic boundary conditions cannot run inside a temporally-"
            "blocked launch (their wrap sources live outside every local "
            "window); the caller must realize nsteps>1 as sequential "
            "single-step launches"
        )
    if nsteps > 1:
        rotations = dict(rotations or {})
        missing = set(out_names) - set(rotations)
        if missing:
            raise ValueError(
                f"nsteps={nsteps} needs rotations for outputs {sorted(missing)} "
                "(e.g. rotations={'T2': 'T'}: each sweep's T2 becomes next sweep's T)"
            )
        for o, tgt in rotations.items():
            if tgt not in field_names:
                raise ValueError(f"rotation target {tgt!r} is not a field")
            if tgt in out_names:
                raise ValueError(
                    f"rotation target {tgt!r} is an output; outputs only "
                    "provide boundary values and cannot receive sweep results"
                )
            if o in shapes and shapes[o] != shapes[tgt]:
                raise ValueError(
                    f"rotation {o!r} -> {tgt!r} joins fields of different "
                    f"shapes {shapes[o]} vs {shapes[tgt]}; double-buffer "
                    "partners must share one staggering"
                )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # Per-axis single-sweep halo depths: the declared radius (symmetric)
    # or the inferred footprint (possibly asymmetric / zero per axis).
    sweep_halo = _halo_pairs(radius if halos is None else halos, nd)
    if write_rings is not None:
        # The window must cover every block cell *structurally*, not just
        # its data footprint: an `inn`-written output's update expression
        # spans window - 2*ring, placed ring cells in — so a side whose
        # read halo is shallower than the write ring (one-sided/upwind
        # taps under inn-style slicing) would leave the seam cells of
        # interior blocks unreachable by any block. Extend each side to
        # at least the deepest output ring on that axis.
        sweep_halo = tuple(
            (max(lo, int(r)), max(hi, int(r)))
            for (lo, hi), r in zip(sweep_halo, write_rings)
        )
    ring = radius if halos is None else None  # legacy pins `inn` to radius
    march = march_axis
    march_fallback = False
    if march is not None:
        march = int(march)
        if not 0 <= march < nd:
            raise ValueError(
                f"march_axis {march} out of range for a {nd}-d stencil")
        for n in field_names:
            if offsets[n][march]:
                raise ValueError(
                    f"march_axis {march} points at a staggered axis: field "
                    f"{n!r} has offset {offsets[n][march]} there — streaming "
                    "slides collocated planes; stagger a non-marching axis "
                    "or drop march_axis"
                )

    def _min_block(m):
        # Fused neumann0 conditions copy through frame-local shifts, so
        # the (small, halo-sized) march block must still hold 2*depth
        # cells along the marched axis.
        return max([1] + [2 * c.depth + offsets[o][m]
                          for o, c in inkernel_bc.items()
                          if c.kind == "neumann0" and m in c.resolved_axes(nd)])

    # The TPU layout (derive_launch): the two minor axes stay whole and
    # leading axes are tiled with clamped windows; interpret mode also
    # honours explicit tiles across any axis (the CPU test path for the
    # general multi-axis geometry).
    derive_kw = dict(
        vmem_budget=vmem_budget, nsteps=nsteps,
        field_offsets=[offsets[n] for n in field_names],
        halos=None if halos is None else sweep_halo,
        tpu_layout=True, compiled=not interpret, n_out=len(out_names),
        compute_itemsize=compute_dtype.itemsize)

    def _derive(m):
        return derive_launch(
            shape, radius, len(field_names), dtype.itemsize, tile=tile,
            march_axis=m, march_min_block=_min_block(m), **derive_kw)

    if tile is None and march is None:
        # The caller chose no launch: stream axis 0 where the
        # all-parallel blocks would refetch its halo planes.
        grid, block, march = derive_default_launch(
            shape, radius, len(field_names), dtype.itemsize,
            march_min_block=_min_block(0), **derive_kw)
    else:
        grid, block = _derive(march)
    if march is not None:
        q_blocks, llo_b, lhi_b = march_queue_blocks(
            block[march], (nsteps * sweep_halo[march][0],
                           nsteps * sweep_halo[march][1]))
        if shape[march] < q_blocks * block[march]:
            # The march extent cannot even fill the plane queue:
            # streaming would fetch mostly duplicate planes. Fall back to
            # the all-parallel launch (identical results, refetched halos).
            march, march_fallback = None, True
            grid, block = _derive(None)
    # Axes the block spans whole carry no halo: the update sees the whole
    # domain along them and every sweep keeps their full extent.
    ehalo = effective_halo(shape, block, sweep_halo)
    whalo = tuple((nsteps * lo, nsteps * hi) for lo, hi in ehalo)
    fext = tuple(min(b + lo + hi, s)
                 for s, b, (lo, hi) in zip(shape, block, whalo))
    for o, c in inkernel_bc.items():
        if c.kind == "neumann0":
            for a in c.resolved_axes(nd):
                # the frame-local shift copies a face from the cells next
                # to it: a one-sweep all-parallel frame (block plus window
                # halo) must hold them, a streamed or multi-sweep block too
                need = 2 * c.depth + offsets[o][a]
                have = fext[a] if a != march and nsteps == 1 else block[a]
                if have < need:
                    raise ValueError(
                        f"fused neumann0 depth {c.depth} on axis {a} needs "
                        f"a block frame >= {need}, got {have} (pass a "
                        "larger tile)"
                    )
    # Tiled axes whose windows overlap their neighbours' ("realigned"):
    # the window offset is clamped into the array at the domain ends, so
    # the frame's origin moves with it and each output block is cut from
    # its frame at a per-block shift. The update never shrinks the frame:
    # every sweep is embedded back into it, and cells a sweep could not
    # compute exactly (within the consumed halo of a non-domain frame
    # edge) never reach the block.
    realign = tuple(a != march and whalo[a][0] + whalo[a][1] > 0
                    for a in range(nd))
    win = {n: tuple(e - o for e, o in zip(fext, offsets[n]))
           for n in field_names}
    fetch = {n: tuple(block[a] if a == march else w
                      for a, w in enumerate(win[n]))
             for n in field_names}

    if march is None:
        launch_grid = grid

        def logical_pids(*pids):
            return pids

        def out_index_map(*pids):
            return pids
    else:
        # Streamed launch: the march axis becomes the innermost (fastest
        # varying, sequential) grid dimension so consecutive grid steps
        # walk one tile column plane-block by plane-block and the scratch
        # queue stays column-coherent. The fetch leads the write by Lhi
        # blocks (lookahead); `Lhi` extra drain steps flush the tail, and
        # the fetch map clamps there (duplicate planes stand in for the
        # planes beyond the domain — both only ever reach masked
        # boundary-ring cells).
        others = tuple(a for a in range(nd) if a != march)
        launch_grid = tuple(grid[a] for a in others) + (grid[march] + lhi_b,)

        def logical_pids(*pids):
            return tuple(pids[-1] if a == march else pids[others.index(a)]
                         for a in range(nd))

        def out_index_map(*pids):
            i = pids[-1]
            return tuple(
                jnp.maximum(i - lhi_b, 0) if a == march
                else pids[others.index(a)]
                for a in range(nd)
            )

    def in_index_map(*pids):
        # the streamed march axis fetches new planes only (clamped in the
        # drain steps); every other axis fetches its frame
        lp = logical_pids(*pids)
        return tuple(
            jnp.minimum(lp[a], grid[a] - 1) * block[a]
            if a == march and block[a] != shape[a] else o
            for a, o in enumerate(frame_origin(lp, block, whalo, shape)))

    n_s, n_f = len(scalar_names), len(field_names)

    def _check_updates(updates):
        missing = set(out_names) - set(updates)
        if missing:
            raise ValueError(f"update_fn did not produce outputs {missing}")

    n_out = len(out_names)
    stage = any(realign)
    use_queue = march is not None and q_blocks > 1
    frame_bufs = stage or nsteps > 1
    sweep_win = {n: tuple(block[a] + whalo[a][0] + whalo[a][1]
                          if a == march else w for a, w in enumerate(win[n]))
                 for n in field_names}
    # Each output is stored at its own extent. Its block keeps the base
    # block on a tiled or marched axis and takes the output's extent on an
    # axis the block spans whole. A staggered field is shorter at the high
    # end only, so its block i holds the base block i's cells cut at its
    # extent; the blocks wholly past it (the last along an axis) map to
    # the last block that holds cells and store nothing.
    oblock = {o: tuple(shapes[o][a] if block[a] == shape[a] else block[a]
                       for a in range(nd)) for o in out_names}
    olast = {o: tuple(-(-shapes[o][a] // block[a]) - 1 for a in range(nd))
             for o in out_names}
    short = {o: tuple(a for a in range(nd) if olast[o][a] < grid[a] - 1)
             for o in out_names}
    # the block's place in the output's frame where it is static
    static_cut = {o: tuple(
        slice(whalo[a][0], whalo[a][0] + block[a]) if a == march
        else slice(0, oblock[o][a]) for a in range(nd)) for o in out_names}

    def blend(o, updates, windows, tgt, origin):
        """The output ``o``'s update placed on the frame of ``tgt``,
        blended with its previous values under the write mask, with the
        output's fused boundary condition applied."""
        fshape = windows[tgt].shape
        modes, rings = write_geometry(
            updates[o].shape, windows[o].shape, offsets[o], o, ring)
        upd = _embed(updates[o].astype(dtype), fshape, rings)
        mask = _valid_mask(origin, fshape, shapes[o], rings, modes)
        blended = jnp.where(mask, upd, windows[tgt])
        return _apply_bc_frame(blended, inkernel_bc.get(o), shapes[o],
                               origin, dtype)

    def body(*refs):
        scal_refs = refs[:n_s]
        in_refs = refs[n_s : n_s + n_f]
        rest = refs[n_s + n_f:]
        out_refs = rest[:n_out]
        red_refs = rest[n_out : n_out + len(red_names)]
        rest = rest[n_out + len(red_names):]
        frame_refs = rest[:n_out] if frame_bufs else ()
        stage_refs = frame_refs if stage else (None,) * n_out
        q_refs = rest[len(frame_refs):]
        # each rotated field sweeps in its output's frame buffer (the
        # final stage write comes after the last sweep has been read)
        rot_refs = {rotations[o]: r.at[tuple(slice(0, w)
                                             for w in sweep_win[rotations[o]])]
                    for o, r in zip(out_names, frame_refs)} \
            if nsteps > 1 else {}
        scalars = {n: ref[0] for n, ref in zip(scalar_names, scal_refs)}
        lp = logical_pids(*(pl.program_id(g)
                            for g in range(len(launch_grid))))
        # logical block ids: the streamed march axis writes block i - Lhi
        # while it fetches block i
        pids = tuple(jnp.maximum(lp[a] - lhi_b, 0) if a == march else lp[a]
                     for a in range(nd))
        # global index of each frame's cell 0, per axis (the streamed march
        # window is assembled from the queue around the written block)
        origin = tuple(
            pids[a] * block[a] - whalo[a][0]
            if a == march and block[a] != shape[a] else o
            for a, o in enumerate(frame_origin(lp, block, whalo, shape)))
        windows = {n: ref[...] for n, ref in zip(field_names, in_refs)}
        if use_queue:
            # Roll each field's plane queue by one block and append the
            # newly fetched planes; the halo-extended march window of the
            # *written* block (o = i - Lhi) is a static slice of the
            # queue: queue plane q holds global plane (i - Q + 1)*bm + q.
            bm = block[march]
            tail = tuple(slice(bm, None) if a == march else slice(None)
                         for a in range(nd))
            qs = llo_b * bm - whalo[march][0]
            wsl = tuple(
                slice(qs, qs + bm + whalo[march][0] + whalo[march][1])
                if a == march else slice(None)
                for a in range(nd)
            )
            for n, q_ref in zip(field_names, q_refs):
                q = jnp.concatenate([q_ref[tail], windows[n]], axis=march)
                q_ref[...] = q
                windows[n] = q[wsl]
        if rot_refs:
            # Sweeps 1..k-1 run as one loop whose rotated fields live in
            # VMEM scratch, so the sweep is emitted once (an unrolled
            # chain grows Mosaic's code and compile time with k); the
            # other windows are re-read from their refs each sweep.
            queue_of = dict(zip(field_names, q_refs))
            ref_of = dict(zip(field_names, in_refs))

            def current():
                return {n: rot_refs[n][...] if n in rot_refs
                        else queue_of[n][wsl] if use_queue
                        else ref_of[n][...] for n in field_names}

            for t, r in rot_refs.items():
                r[...] = windows[t]

            def sweep(_, carry):
                cur = current()
                updates = call_update(cur, scalars)
                _check_updates(updates)
                # Cells outside the mask (boundary ring of `inn` axes)
                # keep carrying their previous values; a fused bc then
                # rewrites that ring exactly like the post-pass would
                # between steps.
                new = {rotations[o]: blend(o, updates, cur, rotations[o],
                                           origin) for o in out_names}
                for t, v in new.items():
                    rot_refs[t][...] = v
                return carry

            jax.lax.fori_loop(0, nsteps - 1, sweep, 0)
            windows = current()
        updates = call_update(windows, scalars)
        _check_updates(updates)
        blendeds = {}
        for o, oref, sref in zip(out_names, out_refs, stage_refs):
            blendeds[o] = blend(o, updates, windows, o, origin)

            def store(o=o, oref=oref, sref=sref):
                # the frame is the output's own window; the block's cells
                # past its extent are dropped by the (partial) block store
                own = blendeds[o]
                if sref is None:
                    oref[...] = own[static_cut[o]]
                    return
                if own.shape == sref.shape:   # collocated: the whole stage
                    sref[...] = own
                else:
                    sref[tuple(slice(0, e) for e in own.shape)] = own
                oref[...] = sref[tuple(
                    pl.ds(pids[a] * block[a] - origin[a], block[a])
                    if realign[a] else static_cut[o][a] for a in range(nd))]

            if short[o]:
                pl.when(functools.reduce(
                    jnp.logical_and, [pids[a] * block[a] < shapes[o][a]
                                      for a in short[o]]))(store)
            else:
                store()
        # Fused reduction epilogue: fold each named check over the frame
        # cells of this block — output operands are the blended values
        # still live in VMEM, input operands the window (the value the
        # boundary copy reads) — masked to the block's cells inside the
        # operand's own extent (each of its cells lies in exactly one
        # block, so the per-tile partials tile the whole-array reduction
        # without overlap; a staggered operand's frame is its window,
        # shorter than the base frame, and no pad enters).
        doms = {}

        def domain(frame, extent):
            if (frame, extent) not in doms:
                dom = None
                for a in range(nd):
                    g = origin[a] + jax.lax.broadcasted_iota(jnp.int32,
                                                             frame, a)
                    b0 = pids[a] * block[a]
                    ma = (g >= b0) & (g < b0 + block[a]) & (g < extent[a])
                    dom = ma if dom is None else (dom & ma)
                doms[frame, extent] = dom
            return doms[frame, extent]

        for rn, rref in zip(red_names, red_refs):
            r = reductions[rn]
            # operands lift to the accumulation dtype BEFORE the
            # elementwise map: |T2 - T| and T*T happen at >= f32
            # even when the blended storage values are bf16
            mapped = r.map_element(*[
                blendeds.get(op, windows[op]).astype(acc_dtype)
                for op in r.operands])
            dom = domain(mapped.shape, shapes[r.operands[0]])
            # one (8, 128) tile per grid step: the smallest block
            # Mosaic stores; run() reads element [0, 0]
            rref[...] = jnp.full(rref.shape, r.fold(mapped, dom),
                                 acc_dtype)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in scalar_names]
    in_specs += [window_spec(fetch[n], in_index_map) for n in field_names]
    # Outputs are stored at their own extents: a staggered output's block
    # index is clamped to its last block on the axes where it is short.
    # Reduction partials ride as one (8, 128) tile per grid tile through
    # the same (lagged, on the streamed path) block index map.
    def own_index_map(o):
        def index_map(*pids):
            return tuple(jnp.minimum(i, olast[o][a]) if a in short[o] else i
                         for a, i in enumerate(out_index_map(*pids)))
        return index_map

    out_specs = [pl.BlockSpec(oblock[o], own_index_map(o)) for o in out_names]
    out_shape = [jax.ShapeDtypeStruct(shapes[o], dtype) for o in out_names]
    out_specs += [pl.BlockSpec((1,) * nd + _PARTIAL_TILE,
                               lambda *p: tuple(out_index_map(*p)) + (0, 0))
                  for _ in red_names]
    # partials stay at the accumulation dtype all the way to finish():
    # rounding them through a bf16 output would undo the f32 folds
    out_shape += [jax.ShapeDtypeStruct(grid + _PARTIAL_TILE, acc_dtype)
                  for _ in red_names]

    # One frame buffer per output: the stage its block is cut from at a
    # per-block shift, and the rotated field's sweep-to-sweep buffer.
    scratch = [pltpu.VMEM(fext, dtype) for _ in out_names] \
        if frame_bufs else []
    if use_queue:
        # One rolling plane queue per field, persisted across grid steps
        # (the march dimension is sequential, so the previous step's
        # planes are still live when the next block arrives).
        scratch += [
            pltpu.VMEM(tuple(q_blocks * block[march] if a == march else w
                             for a, w in enumerate(win[n])), dtype)
            for n in field_names
        ]
    kwargs = {}
    if scratch:
        kwargs["scratch_shapes"] = scratch
    if not interpret:
        kwargs["compiler_params"] = compiler_params(
            len(launch_grid), march=march is not None)
    if name is not None:
        kwargs.update(name=name, metadata={"parallel": name})
    call = pl.pallas_call(
        body,
        grid=launch_grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if len(out_specs) == 1 else out_specs,
        out_shape=out_shape[0] if len(out_shape) == 1 else out_shape,
        interpret=interpret,
        **kwargs,
    )

    def run(fields: Mapping[str, jax.Array], scalars: Mapping[str, jax.Array],
            col=_telemetry.NULL):
        # scalars ride in SMEM at compute precision: dt/lam quantized to
        # bf16 would perturb every update even though the fields are the
        # only thing the mixed-precision trade wants narrowed
        with col.span("stencil.scalars"):
            ordered_scal = [
                jnp.asarray(scalars[n], dtype=compute_dtype).reshape((1,))
                for n in scalar_names
            ]
        ordered_fields = [jnp.asarray(fields[n], dtype=dtype) for n in field_names]
        for n, f in zip(field_names, ordered_fields):
            if f.shape != shapes[n]:
                raise ValueError(
                    f"field {n!r} has shape {f.shape}, expected {shapes[n]}"
                )
        outs = call(*ordered_scal, *ordered_fields)
        if n_out + len(red_names) == 1:
            outs = [outs]
        outs = list(outs)
        partials = [p[..., 0, 0] for p in outs[n_out:]]
        outs = dict(zip(out_names, outs[:n_out]))
        # Periodic faces wrap across the whole domain — realized as a
        # face-slab scatter fused into the surrounding jit (touches
        # O(N^(d-1) * depth) cells; no extra whole-array HBM round-trip).
        for o, c in post_bc.items():
            outs[o] = c.apply(outs[o])
        if not red_names:
            return outs
        # Finish each reduction with a scalar combine over its per-tile
        # partials (O(n_blocks) values — fused into the surrounding jit).
        reds = {rn: reductions[rn].finish(p)
                for rn, p in zip(red_names, partials)}
        return outs, reds

    # once per built launch (never per call): which geometry axis 0 got,
    # and how many outputs are stored at an extent shorter than the base
    _telemetry.count("stencil.builds", fn=name,
                     path="streamed" if march is not None
                     else "whole" if grid[0] == 1 else "refetched")
    _telemetry.count("stencil.own_extent_outputs",
                     sum(shapes[n] != shape for n in out_names), fn=name)
    run.grid = grid
    run.block = block
    run.nsteps = nsteps
    run.dtype = dtype
    run.compute_dtype = compute_dtype
    run.reductions = dict(reductions)
    run.field_shapes = dict(shapes)
    run.halo = sweep_halo
    run.march_axis = march
    run.march_fallback = march_fallback
    run.queue_planes = 0 if march is None else q_blocks * block[march]
    offs = [offsets[n] for n in field_names]
    if march is None:
        run.window_bytes = window_footprint_bytes(block, whalo, offs,
                                                  dtype.itemsize)
    else:
        run.window_bytes = streamed_footprint_bytes(
            block, whalo, offs, dtype.itemsize, march)
    run.vmem_bytes = launch_footprint_bytes(
        shape, block, whalo, offs, dtype.itemsize, n_out, march, nsteps,
        compute_dtype.itemsize)
    return run
