"""Empirical launch autotuner: sweep (tile, nsteps) and keep the winner.

ParallelStencil derives launch parameters analytically (stencil.derive_launch);
this module closes the loop empirically, the way production stencil
frameworks (and XLA's own autotuner) do: run each candidate configuration
through ``teff.measure`` and cache the fastest per (shape, dtype, radius,
n_fields) — so the search cost is paid once per problem class per process
(and optionally persisted to JSON across processes).

The candidate space is deliberately small and deterministic:

  * tiles — the analytically-derived block of the TPU layout plus a few
    divisor-preserving perturbations of the tiled leading axes (the two
    minor axes stay whole, as Mosaic needs them);
  * nsteps — temporal-blocking depths; per-step time is what is compared,
    so a k-fused candidate wins only when its redundant halo compute is
    cheaper than the HBM traffic it saves.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..core import teff
from . import stencil as _stencil


@dataclasses.dataclass(frozen=True)
class TuneResult:
    tile: tuple[int, ...]
    nsteps: int
    per_step_s: float
    candidates_tried: int
    candidates_pruned: int = 0   # dropped by the analytic model pre-compile
    march_axis: int | None = None  # winning streaming axis (None: all-parallel)

    def to_json(self) -> dict:
        return {"tile": list(self.tile), "nsteps": self.nsteps,
                "per_step_s": self.per_step_s,
                "candidates_tried": self.candidates_tried,
                "candidates_pruned": self.candidates_pruned,
                "march_axis": self.march_axis}

    @classmethod
    def from_json(cls, d: dict) -> "TuneResult":
        march = d.get("march_axis")
        return cls(tuple(d["tile"]), int(d["nsteps"]), float(d["per_step_s"]),
                   int(d.get("candidates_tried", 0)),
                   int(d.get("candidates_pruned", 0)),
                   None if march is None else int(march))


_CACHE: dict[tuple, TuneResult] = {}

# Persistent-cache schema version. v2 added the engine-geometry fields
# (march axis candidates, per-axis halos) to the key; v3 adds the check
# workload (fused reduction set + cadence); v4 adds the (storage,
# compute) dtype pair — a bf16-storage run must never inherit an
# f32-tuned winner whose VMEM window footprints are 2x its own (or vice
# versa). Launches cached by older binaries carry shorter keys that can
# never match, so files without a matching version are IGNORED
# (re-tuned), never trusted.
CACHE_VERSION = 4


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _footprint(shape, tile, radius, field_offsets, itemsize, nsteps=1,
               march_axis=None):
    """VMEM the launch allocates (one output), as the preflight counts it."""
    return _stencil.launch_footprint_bytes(
        shape, tile, radius * nsteps, field_offsets, itemsize, 1,
        march_axis, nsteps)


def tile_candidates(
    shape: Sequence[int],
    radius: int,
    n_fields: int,
    itemsize: int,
    vmem_budget: int = _stencil.DEFAULT_VMEM_BUDGET,
    max_candidates: int = 4,
    field_offsets: Sequence[Sequence[int]] | None = None,
) -> list[tuple[int, ...]]:
    """The derived block of the TPU layout (the two minor axes whole) plus
    divisor-preserving halvings/doublings of the tiled leading axes, each
    within the VMEM budget. The budget is checked against the launch's
    whole footprint for the *full coupled field set* (``field_offsets``:
    one staggering tuple per field; defaults to ``n_fields`` collocated
    fields)."""
    shape = tuple(int(s) for s in shape)
    nd = len(shape)
    if field_offsets is None:
        field_offsets = [(0,) * nd] * n_fields
    _, base = _stencil.derive_launch(shape, radius, n_fields, itemsize,
                                     vmem_budget,
                                     field_offsets=field_offsets,
                                     tpu_layout=True)

    def fits(blk):
        return _footprint(shape, blk, radius, field_offsets,
                          itemsize) <= vmem_budget

    whole = _stencil.tpu_whole_axes(nd)
    cands = [base]
    for axis in (a for a in range(nd) if a not in whole):
        for factor in (2, 0.5):
            b = int(base[axis] * factor)
            if b < 1 or b > shape[axis] or shape[axis] % b:
                continue
            cand = tuple(b if a == axis else base[a] for a in range(nd))
            if fits(cand) and cand not in cands:
                cands.append(cand)
    return cands[:max_candidates]


def launch_candidates(
    shape: Sequence[int],
    radius: int,
    n_fields: int,
    itemsize: int,
    nsteps_candidates: Sequence[int],
    tiles: Sequence[Sequence[int]] | None = None,
    vmem_budget: int = _stencil.DEFAULT_VMEM_BUDGET,
    field_offsets: Sequence[Sequence[int]] | None = None,
    march_candidates: Sequence[int | None] | None = None,
) -> list[tuple[tuple[int, ...], int, int | None]]:
    """The (tile, nsteps, march_axis) configurations :func:`autotune`
    measures before pruning: ``tiles`` (default :func:`tile_candidates`)
    crossed with the depths and march axes. Derived tiles are kept only
    where the launch at that depth fits ``vmem_budget`` — temporal
    blocking widens the halo to k*radius, summed over the full coupled
    field set, and streamed candidates are costed with their plane
    queues instead of march-axis halos. Explicitly-passed tiles bypass
    the budget: the caller may be tuning a backend where it is
    irrelevant, e.g. jnp."""
    nd = len(tuple(shape))
    offs = (field_offsets if field_offsets is not None
            else [(0,) * nd] * n_fields)
    derived_tiles = tiles is None
    if derived_tiles:
        tiles = tile_candidates(shape, radius, n_fields, itemsize, vmem_budget,
                                field_offsets=field_offsets)
    marches = (None,) if march_candidates is None else tuple(march_candidates)
    return [(tuple(int(b) for b in tile), int(k), march)
            for tile in tiles for k in nsteps_candidates for march in marches
            if not derived_tiles or _footprint(
                shape, tile, radius, offs, itemsize, int(k),
                march) <= vmem_budget]


def cache_key(shape, dtype, radius: int, n_fields: int, tag: str = "",
              nsteps_candidates: Sequence[int] = (),
              tiles=None, vmem_budget: int = 0,
              field_offsets: Sequence[Sequence[int]] | None = None,
              prune: tuple | None = None,
              march_candidates: Sequence[int | None] | None = None,
              halos: Sequence[tuple[int, int]] | None = None,
              reductions: Sequence[str] | None = None,
              check_every: int | None = None,
              dtypes: Sequence[str] | None = None) -> tuple:
    """Memo key covers the full search space: a call with a different
    candidate set must re-tune, not inherit another sweep's winner. The
    coupled field set's staggering (``field_offsets``) is part of the key:
    two systems with the same field count but different VMEM footprints
    tune independently. ``prune`` tags an analytic-pruning configuration
    (hardware name + ratio) — a pruned search must not inherit an
    unpruned sweep's winner or vice versa. The engine-geometry fields —
    ``march_candidates`` (streaming axes in the search space) and
    ``halos`` (per-axis (lo, hi) window depths) — key the launch
    geometry itself: a winner tuned for refetched halo windows must not
    be handed to a streamed-queue launch or vice versa. ``reductions``
    (the fused epilogue set, e.g. ``r.describe()`` strings) and
    ``check_every`` key the check workload: a winner tuned for a plain
    sweep must not be handed to a checked solver whose epilogue shifts
    the tile economics. ``dtypes`` — the (storage, compute) dtype-name
    pair — keys mixed precision: bf16 storage halves every window
    footprint, so an f32-tuned tile is wrong for it in both directions."""
    return (tag, tuple(int(s) for s in shape), jnp.dtype(dtype).name,
            int(radius), int(n_fields),
            tuple(int(k) for k in nsteps_candidates),
            None if tiles is None else tuple(tuple(int(b) for b in t)
                                             for t in tiles),
            int(vmem_budget),
            None if field_offsets is None else tuple(
                tuple(int(o) for o in off) for off in field_offsets),
            prune,
            None if march_candidates is None else tuple(
                None if m is None else int(m) for m in march_candidates),
            None if halos is None else tuple(
                (int(lo), int(hi)) for lo, hi in halos),
            None if reductions is None else tuple(sorted(
                str(r) for r in reductions)),
            None if check_every is None else int(check_every),
            None if dtypes is None else tuple(str(d) for d in dtypes))


def autotune(
    make_step: Callable[[tuple[int, ...], int], Callable[[], object]],
    *,
    shape: Sequence[int],
    dtype,
    radius: int = 1,
    n_fields: int = 3,
    itemsize: int | None = None,
    nsteps_candidates: Sequence[int] = (1, 2, 4),
    tiles: Sequence[Sequence[int]] | None = None,
    vmem_budget: int = _stencil.DEFAULT_VMEM_BUDGET,
    iters: int = 5,
    tag: str = "",
    cache_path: str | None = None,
    field_offsets: Sequence[Sequence[int]] | None = None,
    cost_model=None,
    hw=None,
    prune_ratio: float = 2.0,
    march_candidates: Sequence[int | None] | None = None,
    halos: Sequence[tuple[int, int]] | None = None,
    reductions: Sequence[str] | None = None,
    check_every: int | None = None,
    compute_dtype=None,
) -> TuneResult:
    """Find the fastest (tile, nsteps[, march_axis]) for a stencil
    problem class.

    ``reductions`` (epilogue descriptions, e.g.
    ``[r.describe() for r in kernel.reductions.values()]``) and
    ``check_every`` key the cached winner to the check workload, and the
    analytic pruner prices the check's amortized flops and traffic
    (``cost_model.predict_per_step_s(..., check_every=)``) so a checked
    solver never inherits a plain sweep's winner.

    ``make_step(tile, nsteps)`` must return a zero-arg callable advancing
    ``nsteps`` time steps with that configuration (typically a jit'd
    ``StencilKernel.run_steps`` closure). Per-step median wall time decides.
    Results are memoized per (shape, dtype, radius, field set, tag) in
    process memory and, when ``cache_path`` is given, in a JSON file.

    ``march_candidates`` adds the streaming axis to the search space
    (e.g. ``(None, 0)``: all-parallel vs marching the leading axis);
    ``make_step`` is then called as ``make_step(tile, nsteps,
    march_axis)``. ``halos`` (per-axis (lo, hi) depths, e.g. the traced
    ``ir.halo``) keys the cached winner to the launch geometry.

    For coupled systems pass ``field_offsets`` (one per-axis staggering
    tuple per field): the candidate filter and derived tiles then budget
    VMEM for the *sum* of all the system's windows, not a single field.

    Analytic pruning: with a ``cost_model`` (``ir.StencilCostModel``, e.g.
    ``kernel.cost_model(...)``) and ``hw`` (``teff.HardwareSpec``), every
    candidate gets a predicted per-step time from the kernel's exact
    flop/byte footprint — fetched-window traffic vs halo-cone recompute —
    and candidates slower than ``prune_ratio`` times the best prediction
    are dropped *before anything is built or compiled*. Only the
    survivors are measured; ``TuneResult.candidates_pruned`` records how
    many configs never paid a compile.

    A candidate whose tile the launch cannot realize by construction
    (:class:`~repro.kernels.stencil.UnrealizableTile`) is skipped. Any
    other failure is re-raised — a kernel the compiler refuses is a
    fault, not a slow candidate.
    """
    prune_tag = (None if cost_model is None or hw is None
                 else (getattr(hw, "name", "hw"), float(prune_ratio)))
    # Every tune keys the FULL (storage, compute) dtype pair — the v4
    # fix for the stale-cache bug where a bf16 run silently reused
    # f32-tuned tiles with half-wrong VMEM footprints.
    st = jnp.dtype(dtype)
    cd = (_stencil.default_compute_dtype(st) if compute_dtype is None
          else jnp.dtype(compute_dtype))
    key = cache_key(shape, dtype, radius, n_fields, tag, nsteps_candidates,
                    tiles, vmem_budget, field_offsets, prune_tag,
                    march_candidates, halos, reductions, check_every,
                    dtypes=(st.name, cd.name))
    col = _telemetry.get()
    if key in _CACHE:
        hit = _CACHE[key]
        if col.enabled:
            col.event("autotune.decision", tag=tag, cache="memory_hit",
                      tile=hit.tile, nsteps=hit.nsteps,
                      march_axis=hit.march_axis,
                      per_step_s=hit.per_step_s)
            col.count("autotune.cache_hits", 1)
        return hit
    if cache_path and os.path.exists(cache_path):
        disk = _load_cache(cache_path)
        hit = disk.get(_key_str(key))
        if hit is not None:
            _CACHE[key] = hit
            if col.enabled:
                col.event("autotune.decision", tag=tag, cache="disk_hit",
                          tile=hit.tile, nsteps=hit.nsteps,
                          march_axis=hit.march_axis,
                          per_step_s=hit.per_step_s)
                col.count("autotune.cache_hits", 1)
            return hit

    itemsize = jnp.dtype(dtype).itemsize if itemsize is None else itemsize
    pass_march = march_candidates is not None
    cands = launch_candidates(shape, radius, n_fields, itemsize,
                              nsteps_candidates, tiles, vmem_budget,
                              field_offsets, march_candidates)
    pruned = 0
    if prune_tag is not None and len(cands) > 1:
        preds = {c: cost_model.predict_per_step_s(c[0], c[1], hw, c[2],
                                                  check_every=check_every)
                 for c in cands}
        best_pred = min(preds.values())
        survivors = [c for c in cands if preds[c] <= prune_ratio * best_pred]
        pruned = len(cands) - len(survivors)
        cands = survivors
    best: TuneResult | None = None
    tried = 0
    for tile, k, march in cands:
        try:
            fn = make_step(tile, k, march) if pass_march else \
                make_step(tile, k)
            m = teff.measure(fn, iters=iters, warmup=1)
        except _stencil.UnrealizableTile:
            continue  # tile the launch cannot realize by construction
        tried += 1
        per_step = m.median_s / k
        if best is None or per_step < best.per_step_s:
            best = TuneResult(tile, k, per_step, tried, march_axis=march)
    if best is None:
        raise RuntimeError("no autotune candidate was runnable")
    best = dataclasses.replace(best, candidates_tried=tried,
                               candidates_pruned=pruned)
    if col.enabled:
        col.event("autotune.decision", tag=tag, cache="miss",
                  tile=best.tile, nsteps=best.nsteps,
                  march_axis=best.march_axis, per_step_s=best.per_step_s,
                  candidates_tried=tried, candidates_pruned=pruned)
        col.count("autotune.cache_misses", 1)
        col.count("autotune.candidates_pruned", pruned)
        col.count("autotune.candidates_tried", tried)
    _CACHE[key] = best
    if cache_path:
        disk = _load_cache(cache_path) if os.path.exists(cache_path) else {}
        disk[_key_str(key)] = best
        _save_cache(cache_path, disk)
    return best


def autotune_diffusion3d(
    shape: Sequence[int],
    dtype="float32",
    backend: str = "jnp",
    nsteps_candidates: Sequence[int] = (1, 2, 4),
    iters: int = 5,
    cache_path: str | None = None,
    hw=None,
    prune_ratio: float = 2.0,
    march_candidates: Sequence[int | None] | None = None,
) -> TuneResult:
    """Tune the Fig. 1 diffusion solver on this host.

    Uses the ``StencilKernel`` path (jit'd ``run_steps``) so the measured
    configuration is exactly what the solver runs. The jnp backend is the
    performance path on CPU hosts; on TPU pass ``backend="pallas"``.
    With ``hw`` (a ``teff.HardwareSpec``) the kernel's inferred cost model
    prunes the candidate grid analytically before anything compiles.
    ``march_candidates`` (e.g. ``(None, 0)``) adds streamed execution to
    the search space.
    """
    import numpy as np

    from ..core import init_parallel_stencil, fd3d as fd

    shape = tuple(int(s) for s in shape)
    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    T = jnp.asarray(rng.rand(*shape), dtype)
    T2 = T.copy()  # distinct write buffer, as the solvers allocate
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, dtype)
    sc = dict(lam=1.0, dt=1e-6, _dx=float(shape[0] - 1),
              _dy=float(shape[1] - 1), _dz=float(shape[2] - 1))

    # The jnp backend has no tiling knob — only sweep nsteps there.
    tiles = None
    if backend == "jnp":
        _, base = _stencil.derive_launch(shape, 1, 3, dtype.itemsize)
        tiles = [base]

    def _kernel(ps, tile=None, march=None):
        @ps.parallel(outputs=("T2",), tile=tile, rotations={"T2": "T"},
                     march_axis=march)
        def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
            return {"T2": fd.inn(T) + dt * (lam * fd.inn(Ci) * (
                fd.d2_xi(T) * _dx ** 2 + fd.d2_yi(T) * _dy ** 2 +
                fd.d2_zi(T) * _dz ** 2))}
        return kern

    probe = _kernel(init_parallel_stencil(backend=backend, dtype=dtype,
                                          ndims=3))
    halos = probe.stencil_ir(T2=shape, T=shape, Ci=shape, **sc).halo
    cost_model = None
    if hw is not None:
        cost_model = probe.cost_model(T2=shape, T=shape, Ci=shape, **sc)

    def make_step(tile, k, march=None):
        ps = init_parallel_stencil(backend=backend, dtype=dtype, ndims=3)
        kern = _kernel(ps, tile, march)
        step = jax.jit(lambda T2, T: kern.run_steps(k, T2=T2, T=T, Ci=Ci, **sc))
        return lambda: step(T2, T)

    return autotune(
        make_step, shape=shape, dtype=dtype, radius=1, n_fields=3,
        nsteps_candidates=nsteps_candidates, tiles=tiles, iters=iters,
        tag=f"diffusion3d/{backend}", cache_path=cache_path,
        cost_model=cost_model, hw=hw, prune_ratio=prune_ratio,
        march_candidates=march_candidates, halos=halos,
    )


# ---------------- JSON persistence ----------------
def _key_str(key: tuple) -> str:
    return json.dumps(key, separators=(",", ":"))


def _load_cache(path: str) -> dict[str, TuneResult]:
    """Load a persistent cache, IGNORING (not crashing on) files written
    by older schema versions: PR 1–3 binaries cached launches without the
    march/halos geometry in the key, so their winners may be invalid for
    the streamed engine — a version mismatch simply re-tunes. Transient
    read failures (shared filesystems hiccup) are retried with backoff
    before giving up on the cache."""
    from ..distributed import fault

    def read():
        fault.FaultPlan.active_on_io(path)
        with open(path) as f:
            return json.load(f)

    try:
        raw = fault.retry(read, exceptions=(OSError,))
        if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
            return {}
        return {k: TuneResult.from_json(v)
                for k, v in raw.get("entries", {}).items()}
    except (OSError, ValueError, KeyError, TypeError):
        return {}


def _save_cache(path: str, cache: dict[str, TuneResult]) -> None:
    from ..distributed import fault

    def write():
        fault.FaultPlan.active_on_io(path)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION,
                       "entries": {k: v.to_json() for k, v in cache.items()}},
                      f, indent=1)
        os.replace(tmp, path)

    fault.retry(write, exceptions=(OSError,))
