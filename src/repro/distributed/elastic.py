"""Elastic scaling: re-laying out solver state onto a different mesh.

A checkpoint written on one mesh must restore onto another (node failure
shrinks the pool; scale-up grows it). Checkpoints store *global* logical
tensors (shard files + a manifest, see checkpoint/manager.py), so restoring
is: rebuild the sharding for the new mesh from the same logical rules, then
``jax.device_put`` each tensor with its new NamedSharding. No tensor ever
needs all-to-all resharding on device — the host stream feeds each device
only its shard (jax.make_array_from_callback).

For the stencil engine this module adds the full elastic solve loop:

  * :func:`decompose_fields` / :func:`gather_fields` — global <-> per-rank
    ghost-ring layout (the ImplicitGlobalGrid decomposition, stacked with
    leading mesh-factor axes and placed through :func:`remesh`);
  * :func:`elastic_solve_until` — the distributed, checkpointing analogue
    of :func:`repro.core.iterate.solve_until`: ONE jitted
    ``shard_map``-ed ``lax.while_loop`` per chunk whose body runs
    ``overlap.sequential_step`` (grouped halo ppermutes + fused kernel +
    one ``pmax``/``psum`` per reduction), chunked at reduction-check
    boundaries for async checkpointing of the *global* carry. Because the
    checkpoint is mesh-agnostic, a run killed on an N-rank mesh resumes
    on an M-rank mesh: same iteration trajectory, allclose fields
    (reduction scalars reassociate across decompositions — never compare
    them bitwise);
  * :func:`plan_factors` / :func:`validate_stencil_factors` — shrink or
    regrow remeshing: pick a decomposition for the surviving world size
    and verify the interior still divides;
  * :func:`supervise` — the restart policy a launcher loops over
    (attempt -> exit code; planned kills re-plan the mesh and go again).

Also provides `remesh` for live resharding (device_put with a new sharding)
used when a run continues after swapping the mesh in-process.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from ..core.iterate import SolveResult, _crossed, _resolve_error
from . import fault, halo as _halo, overlap

DEFAULT_AXES = ("x", "y", "z")


def remesh(tree, mesh: Mesh, spec_tree) -> object:
    """Reshard a pytree of arrays onto ``mesh`` with matching PartitionSpecs."""
    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, tree, spec_tree)


def from_host_callback(shape_dtype, spec: P, mesh: Mesh, read: Callable[[tuple], np.ndarray]):
    """Build a sharded array where each device's block is fetched on demand
    (``read(index)`` returns the numpy block for a global index tuple).
    This is the restore path that scales to 1000+ nodes: every host reads
    only the bytes its devices own."""
    sharding = NamedSharding(mesh, spec)

    def cb(index):
        return read(index)

    return jax.make_array_from_callback(shape_dtype.shape, sharding, cb)


def validate_divisibility(tree_specs, tree_shapes, mesh: Mesh) -> list[str]:
    """Return human-readable problems where a spec no longer divides a dim
    on the new mesh (elastic scale-down can break divisibility)."""
    problems = []

    def check(path, spec, shape):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if shape[i] % size:
                problems.append(f"{path}: dim {i} ({shape[i]}) % mesh {axes} ({size}) != 0")

    def walk(prefix, specs, shapes):
        if isinstance(specs, P):
            check(prefix, specs, shapes)
            return
        if isinstance(specs, dict):
            for k in specs:
                walk(f"{prefix}/{k}", specs[k], shapes[k])
            return
        if isinstance(specs, (list, tuple)):
            for i, (sp, sh) in enumerate(zip(specs, shapes)):
                walk(f"{prefix}[{i}]", sp, sh)
            return

    walk("", tree_specs, tree_shapes)
    return problems


# ---------------------------------------------------------------------------
# stencil-field decomposition (global <-> stacked rank-local ghost layout)
# ---------------------------------------------------------------------------
def plan_factors(n_ranks: int, ndims: int = 1) -> tuple[int, ...]:
    """A near-balanced mesh decomposition for ``n_ranks`` over the leading
    ``ndims`` grid axes (largest factors first — row-major rank order).
    This is the shrink/regrow policy: after losing a rank, call it with
    the surviving world size."""
    if n_ranks < 1:
        raise ValueError(f"n_ranks must be >= 1, got {n_ranks}")
    factors = [1] * ndims
    rem = n_ranks
    for i in range(ndims - 1):
        f = 1
        for cand in range(int(math.isqrt(rem)), 0, -1):
            if rem % cand == 0:
                f = cand
                break
        factors[i] = max(rem // f, f) if ndims - i == 2 else f
        rem //= factors[i]
    factors[-1] = rem
    return tuple(sorted(factors, reverse=True))


def plan_compatible(shape: Sequence[int], radius: int, world: int,
                    ndims: int = 1) -> tuple[int, tuple[int, ...]]:
    """The supervisor's replan policy: the largest world size ``<= world``
    whose :func:`plan_factors` decomposition passes
    :func:`validate_stencil_factors` on this grid. After losing a rank, a
    4-rank world on an interior-16 grid must step down to 2, not 3 — 3
    does not divide. Returns ``(world, factors)``; raises a pointed error
    when not even a single rank fits (grid thinner than the ghost ring)."""
    for w in range(int(world), 0, -1):
        factors = plan_factors(w, ndims)
        try:
            validate_stencil_factors(shape, factors, radius)
        except ValueError:
            continue
        return w, factors
    raise ValueError(
        f"no world size in [1, {world}] decomposes grid {tuple(shape)} "
        f"(radius {radius}) over {ndims} axis/axes — the grid interior is "
        "thinner than one ghost ring")


def validate_stencil_factors(shape: Sequence[int], factors: Sequence[int],
                             radius: int) -> None:
    """The ghost-ring decomposition contract: every decomposed axis'
    interior (extent minus the 2r physical boundary ring) must divide by
    its factor, and each rank block must be at least one ghost ring
    wide. Raises a pointed ValueError naming the failing axis."""
    for ax, f in enumerate(factors):
        inner = shape[ax] - 2 * radius
        if inner <= 0 or inner % f:
            raise ValueError(
                f"axis {ax}: interior extent {inner} (= {shape[ax]} - 2*r, "
                f"r={radius}) does not divide over {f} ranks — choose a "
                f"mesh from divisors of {inner} (plan_factors of a "
                "compatible world size)")
        if inner // f < radius:
            raise ValueError(
                f"axis {ax}: rank block {inner // f} thinner than the "
                f"ghost ring (r={radius}) — fewer ranks needed")


def decompose_fields(fields: Mapping[str, np.ndarray],
                     factors: Sequence[int], radius: int) -> dict:
    """Split global arrays into the stacked rank-local ghost layout: each
    field becomes shape ``(*factors, *local_shape)`` — the layout
    ``shard_map`` splits one rank-block per device (host-side)."""
    out = {}
    for name, g in fields.items():
        locals_ = _halo.global_to_local(np.asarray(g), factors, radius=radius)
        out[name] = np.stack(locals_).reshape(
            tuple(factors) + locals_[0].shape)
    return out


def gather_fields(stacked: Mapping[str, np.ndarray],
                  factors: Sequence[int], radius: int) -> dict:
    """Inverse of :func:`decompose_fields` (interior stitching, host-side).
    This is what checkpoints store: the mesh-agnostic global arrays."""
    out = {}
    nrank = int(np.prod(factors))
    for name, st in stacked.items():
        a = np.asarray(st)
        locals_ = list(a.reshape((nrank,) + a.shape[len(factors):]))
        out[name] = _halo.local_to_global(locals_, factors, radius=radius)
    return out


def _field_specs(factors: Sequence[int], axes: Sequence[str], ndim: int) -> P:
    return P(*axes, *([None] * (ndim - len(factors))))


def fetch_global(stacked: Mapping[str, object], mesh: Mesh) -> dict:
    """``device_get`` for a dict of sharded arrays that also works when
    ``mesh`` spans OS processes. A process-spanning ``jax.Array`` cannot
    be fetched directly (its shards live in other processes' address
    spaces — ``jax.device_get`` raises); route those through a jitted
    identity re-sharded to fully-replicated, then read the local copy.
    Every participating process must call this (it runs a collective)."""
    spanning = {k: v for k, v in stacked.items()
                if isinstance(v, jax.Array) and not v.is_fully_addressable}
    out: dict = {}
    if spanning:
        rep = NamedSharding(mesh, P())
        replicated = jax.jit(
            lambda t: t,
            out_shardings={k: rep for k in spanning})(spanning)
        for k, v in replicated.items():
            out[k] = np.asarray(v.addressable_data(0))
    for k, v in stacked.items():
        if k not in out:
            out[k] = jax.device_get(v)
    return out


# ---------------------------------------------------------------------------
# the elastic solve loop
# ---------------------------------------------------------------------------
def make_elastic_solver(kernel, scalars: Mapping[str, object], mesh: Mesh,
                        factors: Sequence[int], axes: Sequence[str],
                        exchange: Sequence[str], *, check_every: int = 1,
                        error=None, until: str = "below",
                        periodic: bool = False):
    """Build the chunk driver ``solver(stacked_fields, tol, block)
    -> (stacked_fields, reds, err, iters)``: a jitted program, each call
    an ``elastic.chunk`` telemetry span (entry to enqueue).

    One ``shard_map`` over the whole chunk: the rank-local body is the
    same m-steps-per-check ``lax.while_loop`` as
    :func:`repro.core.iterate.make_solver`, except every step runs
    ``overlap.sequential_step`` (grouped halo exchange + fused kernel)
    and the check's reductions arrive pre-combined across ranks (ONE
    ``pmax``/``psum``), so the loop condition is rank-uniform and the
    whole chunk needs zero host round-trips."""
    err_fn = _resolve_error(kernel, error)
    scalars = dict(scalars or {})
    plain = kernel.with_reductions(None)
    single = len(kernel.outputs) == 1
    rot = kernel.rotations
    if not rot or set(kernel.outputs) - set(rot):
        raise ValueError("elastic_solve_until needs rotations covering "
                         "every output (like solve_until)")
    nfac = len(factors)
    lead = (0,) * nfac

    def as_dict(res):
        return {kernel.outputs[0]: res} if single else dict(res)

    def rotate(cur, outs):
        cur = dict(cur)
        for o, tgt in rot.items():
            cur[o], cur[tgt] = cur[tgt], outs[o]
        return cur

    def rank_solver(cur0, tol, block):
        reds0 = {n: jnp.zeros((), jnp.float32) for n in kernel.reductions}
        err0 = jnp.float32(jnp.inf if until == "below" else -jnp.inf)

        def cond(state):
            _, _, err, it = state
            keep = err > tol if until == "below" else err <= tol
            return keep & (it < block)

        def body(state):
            cur, _, _, it = state
            for _ in range(check_every - 1):
                outs, fresh = overlap.sequential_step(
                    plain, cur, scalars, exchange, axes, periodic=periodic)
                cur = rotate(fresh, as_dict(outs))
            with jax.named_scope("solve.check"):
                (outs, reds), fresh = overlap.sequential_step(
                    kernel, cur, scalars, exchange, axes, periodic=periodic)
                reds = {n: jnp.asarray(v, jnp.float32)
                        for n, v in reds.items()}
                err = jnp.asarray(err_fn(reds), jnp.float32)
            cur = rotate(fresh, as_dict(outs))
            return cur, reds, err, it + check_every

        return jax.lax.while_loop(
            cond, body, (cur0, reds0, err0, jnp.int32(0)))

    def local_chunk(stacked, tol, block):
        cur = {k: v[lead] for k, v in stacked.items()}
        cur, reds, err, it = rank_solver(cur, tol, block)
        cur = {k: v[(np.newaxis,) * nfac] for k, v in cur.items()}
        return cur, reds, err, it

    def solver(stacked, tol, block):
        field_spec = {k: _field_specs(factors, axes, stacked[k].ndim)
                      for k in stacked}
        f = jax.shard_map(
            local_chunk, mesh=mesh,
            in_specs=(field_spec, P(), P()),
            out_specs=(field_spec,
                       {n: P() for n in kernel.reductions}, P(), P()),
            check_vma=False,
        )
        return f(stacked, tol, block)

    jitted = jax.jit(solver)

    def chunk(stacked, tol, block):
        with _telemetry.get().span("elastic.chunk"):
            return jitted(stacked, tol, block)

    return chunk


def elastic_solve_until(
    kernel,
    fields: Mapping[str, np.ndarray],
    scalars: Mapping[str, object] | None = None,
    *,
    factors: Sequence[int],
    tol: float,
    max_iters: int,
    exchange: Sequence[str],
    check_every: int = 1,
    error=None,
    until: str = "below",
    periodic: bool = False,
    checkpoint=None,
    mesh_axes: Sequence[str] | None = None,
    radius: int | None = None,
) -> SolveResult:
    """Distributed, survivable ``solve_until``: iterate ``kernel`` over a
    ``factors``-decomposed mesh until the rank-combined fused error
    scalar crosses ``tol``.

    ``fields`` are GLOBAL arrays (physical boundary ring included);
    ``exchange`` names the fields whose ghost rings each check-step
    refreshes. ``checkpoint`` (path or
    :class:`~repro.core.iterate.Checkpointing`) chunks the loop at
    check boundaries and checkpoints the gathered *global* carry, so a
    killed run resumes on ANY compatible mesh — ``factors`` at resume
    time may differ from the mesh the checkpoint was written on
    (shrink after a rank failure, regrow after scale-up). Returned
    ``fields`` are global arrays again."""
    from ..core.iterate import Checkpointing

    scalars = dict(scalars or {})
    factors = tuple(int(f) for f in factors)
    axes = tuple(mesh_axes or DEFAULT_AXES[: len(factors)])
    n_ranks = int(np.prod(factors))
    if n_ranks > len(jax.devices()):
        raise ValueError(f"factors {factors} need {n_ranks} devices, have "
                         f"{len(jax.devices())}")
    field_arrays = {k: np.asarray(v) for k, v in fields.items()}
    if radius is None:
        radius, _, _ = overlap._kernel_geometry(
            kernel, {k: jnp.asarray(v) for k, v in field_arrays.items()},
            scalars, exchange, axes)
    sample = next(iter(field_arrays.values()))
    validate_stencil_factors(sample.shape, factors, radius)

    from ..launch.mesh import make_mesh

    mesh = make_mesh(factors, axes)
    ckpt = (Checkpointing(checkpoint) if isinstance(checkpoint, str)
            else checkpoint)
    mgr = ckpt.manager() if ckpt is not None else None
    save_every = int(ckpt.save_every) if ckpt is not None else 1
    block = (save_every * check_every if ckpt is not None
             else max_iters + check_every)

    err_host = np.float32(np.inf if until == "below" else -np.inf)
    reds_host = {n: np.float32(0) for n in kernel.reductions}
    done, resumed_from = 0, None
    if mgr is not None and ckpt.resume and mgr.latest_step() is not None:
        like = {"fields": field_arrays, "reds": reds_host,
                "err": err_host}
        tree, extra = mgr.restore(like)
        field_arrays = {k: np.asarray(v) for k, v in tree["fields"].items()}
        reds_host = tree["reds"]
        err_host = np.float32(tree["err"])
        done = int(extra.get("iters", extra["step"]))
        resumed_from = done

    # decompose onto THIS mesh (possibly not the checkpoint's) and place
    # each stacked field through the new mesh's NamedSharding
    stacked = decompose_fields(field_arrays, factors, radius)
    specs = {k: _field_specs(factors, axes, v.ndim)
             for k, v in stacked.items()}
    stacked = remesh(stacked, mesh, specs)

    solver = make_elastic_solver(
        kernel, scalars, mesh, factors, axes, exchange,
        check_every=check_every, error=error, until=until,
        periodic=periodic)

    plan = fault.FaultPlan.active()
    monitor = ckpt.monitor if ckpt is not None else None
    saved: list[int] = []
    err = jnp.float32(err_host)
    reds = {n: jnp.float32(v) for n, v in reds_host.items()}
    converged = done > 0 and _crossed(float(err), tol, until)
    while not converged and done < max_iters:
        take = min(block, max_iters - done)
        t0 = time.perf_counter()
        stacked, reds, err, it = solver(stacked, jnp.float32(tol),
                                        jnp.int32(take))
        n = int(it)                      # chunk-boundary host sync
        dt = time.perf_counter() - t0
        done += n
        converged = _crossed(float(err), tol, until)
        if monitor is not None:
            monitor.record(done, dt / max(n, 1))
            health = monitor.check_peers()
            if health["dead"]:
                if mgr is not None:
                    mgr.wait()
                raise fault.RankFailure(health["dead"])
        if mgr is not None:
            # the replicate-fetch is a collective: every process runs it,
            # but only process 0 writes (one writer per shared ckpt dir)
            global_now = gather_fields(fetch_global(stacked, mesh),
                                       factors, radius)
            if jax.process_index() == 0:
                mgr.save(done,
                         {"fields": global_now, "reds": reds, "err": err},
                         blocking=ckpt.blocking,
                         extra={"iters": done, "err": float(err),
                                "tol": float(tol),
                                "check_every": int(check_every),
                                "save_every": save_every, "until": until,
                                "factors": list(factors),
                                "radius": int(radius),
                                "converged": converged})
            saved.append(done)
        if plan is not None:
            plan.on_step(done)   # a kill lands between save and next chunk
    if mgr is not None:
        mgr.wait()
    final = gather_fields(fetch_global(stacked, mesh), factors, radius)
    return SolveResult(
        fields={k: jnp.asarray(v) for k, v in final.items()},
        reds=reds, err=err, iters=jnp.int32(done),
        resumed_from=resumed_from, saved_steps=tuple(saved))


def supervise(run_attempt: Callable[[int, int], int], world: int, *,
              replan: Callable[[int, int], int] | None = None,
              max_restarts: int = 3) -> tuple[int, int, list[int]]:
    """Launcher restart loop: call ``run_attempt(attempt, world)`` until it
    returns 0.

    On a nonzero exit (a planned :data:`~repro.distributed.fault.
    KILL_EXIT_CODE` death or a real crash) the world is re-planned —
    ``replan(world, rc)``, default: lose one rank, floor 1 — and the
    next attempt launches; the attempt's own checkpoint/resume logic
    carries the state across. Returns ``(attempts_used, final_world,
    exit_codes)``; raises after ``max_restarts`` failed restarts."""
    codes: list[int] = []
    attempt = 0
    while True:
        rc = int(run_attempt(attempt, world))
        codes.append(rc)
        if rc == 0:
            return attempt, world, codes
        if attempt >= max_restarts:
            raise RuntimeError(
                f"gave up after {attempt} restarts (exit codes {codes})")
        world = (replan(world, rc) if replan is not None
                 else max(world - 1, 1))
        attempt += 1
