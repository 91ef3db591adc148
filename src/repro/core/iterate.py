"""Device-resident convergence-driven iteration (``solve_until``).

The paper's pseudo-transient solvers iterate until ``err = max|dT|``
drops under a tolerance. The classic host loop

    while err > tol: err = float(norm(step(...)))   # host sync per check

serializes the step stream on a device->host transfer every check. With
the engine's fused reduction epilogues the error is a device scalar that
costs no extra HBM pass — so the WHOLE iteration can live on device: a
``lax.while_loop`` whose body advances ``check_every`` steps (the first
``m-1`` through the reduction-free kernel variant, the last through the
checked one), rotates the double buffers in place (the carry is donated
— XLA updates the field buffers without copies), and whose condition
reads the fused error scalar. Zero host transfers from the first step to
convergence; one compiled program regardless of iteration count.

One iteration may be several dependent launches (``kernel`` a sequence):
each reads the carry as the launches before it left it, and the check
block's last iteration runs every launch that declares reductions in its
checked variant, so a convergence scalar may combine reductions folded
in different launches (the Stokes residuals and the divergence).

``until="below"`` runs while ``err > tol`` (convergence: stop once the
residual drops under tol); ``until="above"`` runs while ``err <= tol``
(drift guard: stop once a conserved-quantity error exceeds tol).

Caveat: a ``while_loop`` has data-dependent trip count, so the program
cannot be reverse-differentiated and steps are taken in multiples of
``check_every`` (``iters`` may overshoot ``max_iters`` by at most
``check_every - 1``).

Fault tolerance (``checkpoint=``): the paper's headline workloads run
for days, and at that scale runs die to preemption, not math. The
checkpointing driver chunks the same jitted ``while_loop`` at
reduction-check boundaries — each chunk is ``save_every`` checks — and
hands the double-buffer carry (field buffers + iteration counter +
error scalar + last reductions) to an async
:class:`~repro.checkpoint.manager.CheckpointManager` between chunks.
The loop only stalls for the device->host copy; the filesystem write
runs behind the next chunk. Checkpoints are atomic (``step_X.tmp`` +
``os.replace`` + ``LATEST`` swap) with keep-k retention, and a killed
run resumes from ``LATEST`` bit-identically to the uninterrupted run
on the same machine (per-step math never sees the chunk boundary; only
cross-mesh/cross-program comparisons degrade to allclose — reductions
reassociate).

Telemetry (``telemetry=`` / ``REPRO_TELEMETRY=1``): the solve is the
subsystem's flagship instrumentation site, and it obeys the zero-host-
sync rule — device-derived metrics (step counts, the error trajectory,
reduction values) are harvested ONLY at host syncs that already exist:
the chunk boundary of the checkpointing driver (which reads ``iters`` /
``err`` anyway) and the final carry of the plain path. The traced
program is identical with telemetry on or off; the disabled path costs
one attribute check.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
import weakref
from typing import Any, Callable, Mapping, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _telemetry

__all__ = [
    "Checkpointing", "SolveResult", "make_solver", "solve_until",
    "BatchCarry", "BatchedSolveResult", "make_batched_solver", "solve_batch",
]

# jitted-solver reuse across solve_until calls: make_solver builds a new
# closure per call, so a bare jax.jit would retrace AND recompile every
# solve of the same kernel — death by compile for iterative callers (and
# it would bury the telemetry-overhead measurement under compile noise).
# Keyed weakly on the kernel; entries hold strong refs to any jax.Array
# scalars so their id()s can't be recycled under the key.
_SOLVER_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _scalars_cache_key(scalars) -> Optional[tuple]:
    """A hashable identity for a scalars dict (the values are baked into
    the traced program as constants): plain Python numbers key by value,
    immutable jax arrays by object identity. Anything else (e.g. a
    mutable numpy buffer) returns None — no caching for that call."""
    items = []
    for k in sorted(scalars or {}):
        v = scalars[k]
        if isinstance(v, (bool, int, float)):
            items.append((k, type(v).__name__, v))
        elif isinstance(v, jax.Array):
            items.append((k, "jax", id(v)))
        else:
            return None
    return tuple(items)


def _jitted_solver(kernel, scalars, *, check_every, error, until):
    """The jitted driver for (kernel or launch sequence, scalars, policy),
    memoized on the first launch."""
    def build():
        return jax.jit(make_solver(kernel, scalars, check_every=check_every,
                                   error=error, until=until))

    skey = _scalars_cache_key(scalars)
    if skey is None:
        return build()
    launches = _launches(kernel)
    err_key = error if (error is None or isinstance(error, str)) \
        else id(error)
    key = (int(check_every), err_key, until, skey,
           tuple(id(k) for k in launches[1:]))
    try:
        cache = _SOLVER_CACHE.setdefault(launches[0], {})
    except TypeError:                      # kernel not weak-referenceable
        return build()
    if key not in cache:
        keep = [v for v in (scalars or {}).values()
                if isinstance(v, jax.Array)] + list(launches[1:])
        cache[key] = (build(), keep)
    return cache[key][0]


@dataclasses.dataclass
class Checkpointing:
    """Checkpoint policy for :func:`solve_until`.

    ``path`` is the checkpoint root directory (or an existing
    :class:`~repro.checkpoint.manager.CheckpointManager`). ``save_every``
    counts reduction CHECKS between saves — the snapshot piggybacks on a
    check boundary, so it never costs an extra HBM pass; per-step
    overhead is the device->host copy amortized over
    ``save_every * check_every`` steps. ``resume=True`` restores from
    ``LATEST`` when one exists (a fresh directory starts from the given
    initial fields). ``blocking=False`` writes on a background thread.
    ``monitor`` (a :class:`~repro.distributed.fault.StepMonitor`) bumps
    a heartbeat file per chunk and raises
    :class:`~repro.distributed.fault.RankFailure` when a peer's
    heartbeat goes stale."""

    path: Union[str, Any]          # root dir or CheckpointManager
    save_every: int = 1            # checks between saves
    keep: int = 3
    resume: bool = True
    blocking: bool = False
    monitor: Optional[Any] = None  # fault.StepMonitor

    def manager(self):
        from ..checkpoint import CheckpointManager

        if isinstance(self.path, str):
            return CheckpointManager(self.path, keep=self.keep)
        return self.path


@dataclasses.dataclass
class SolveResult:
    """Final state of a convergence-driven solve. Everything is a device
    value — reading ``.err``/``.iters`` as Python numbers is the caller's
    (single, final) host sync."""

    fields: dict[str, jax.Array]   # all field buffers, rotated in place
    reds: dict[str, jax.Array]     # the last check's fused reductions
    err: jax.Array                 # last error scalar (float32)
    iters: jax.Array               # steps taken (int32)
    resumed_from: Optional[int] = None   # checkpoint step a resume started at
    saved_steps: tuple[int, ...] = ()    # steps checkpointed this run
    # per-rank EWMA step stats from the run's StepMonitor (own rank plus
    # every peer heartbeat), {rank: {"ewma_s", "last_s", "n"}} — None when
    # the solve ran without a monitor
    step_stats: Optional[dict[int, dict[str, float]]] = None

    def output(self, kernel) -> Any:
        """The solver's answer: the rotation target of each output holds
        the newest value after the final in-loop rotation."""
        tgts = {o: self.fields[t] for o, t in kernel.rotations.items()}
        if len(kernel.outputs) == 1:
            return tgts[kernel.outputs[0]]
        return tgts


def _launches(kernel) -> tuple:
    """The launches of one iteration: a ``StencilKernel``, or a sequence
    of them run in order."""
    launches = tuple(kernel) if isinstance(kernel, (tuple, list)) \
        else (kernel,)
    if not launches:
        raise ValueError("an iteration needs at least one kernel")
    return launches


def _declared(launches) -> dict:
    """The fused reductions of every launch of the iteration, by name."""
    reds = {}
    for k in launches:
        for name, r in k.reductions.items():
            if name in reds:
                raise ValueError(
                    f"reduction {name!r} is declared by two launches of the "
                    "iteration; give each its own name")
            reds[name] = r
    return reds


def _resolve_error(kernel, error) -> Callable[[Mapping[str, Any]], Any]:
    declared = _declared(_launches(kernel))
    if error is None:
        if len(declared) != 1:
            raise ValueError(
                f"kernel declares reductions {tuple(declared)}; "
                "pass error=<name> (or a callable over the reduction dict) "
                "to pick the convergence scalar"
            )
        error = next(iter(declared))
    if isinstance(error, str):
        if error not in declared:
            raise ValueError(
                f"error={error!r} is not a declared reduction "
                f"(have {tuple(declared)})"
            )
        name = error
        return lambda reds: reds[name]
    return error


def _arguments(kernel):
    """The argument names ``kernel`` takes (None: it takes any)."""
    params = inspect.signature(kernel.fn).parameters.values()
    if any(p.kind is p.VAR_KEYWORD for p in params):
        return None
    return {p.name for p in params}


def make_solver(
    kernel,
    scalars: Mapping[str, Any] | None = None,
    *,
    check_every: int = 1,
    error: str | Callable | None = None,
    until: str = "below",
):
    """Build the un-jitted driver ``solver(fields, tol, max_iters) ->
    (fields, reds, err, iters)`` for :func:`solve_until`.

    ``kernel`` is one ``@parallel`` kernel or a sequence of them, one
    iteration's launches in order. The carry holds every field by name;
    each launch takes its own arguments from it (and from ``scalars``),
    and its outputs rebind the carry's names for the launches after it:
    a rotated output swaps with its partner (``T2 -> T``), any other
    takes its own name (written fresh, or updated in place). Every
    launch that declares
    reductions runs its checked variant in the last iteration of each
    check block, its reduction-free variant otherwise; ``error`` reads
    the reductions of all of them, merged by name.

    Exposed separately so callers (and the zero-host-sync test) can
    inspect the traced program: ``jax.make_jaxpr(solver)(...)`` is ONE
    ``while`` — no transfers, no callbacks between checks.
    """
    launches = _launches(kernel)
    declared = _declared(launches)
    if not declared:
        raise ValueError(
            "solve_until needs a kernel with fused reductions "
            "(declare reductions={'err': 'max_abs_diff(T2, T)'}-style on "
            "@parallel)"
        )
    check_every = int(check_every)
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    if until not in ("below", "above"):
        raise ValueError(f"until must be 'below' or 'above', got {until!r}")
    err_fn = _resolve_error(launches, error)
    scalars = dict(scalars or {})
    plains = tuple(k.with_reductions(None) for k in launches)
    takes = [_arguments(k) for k in launches]
    _telemetry.count("solve.launches", len(launches),
                     fn=_kernel_label(launches))

    def iteration(cur, kernels):
        """One pass of the launches; returns the carry and the merged
        reductions."""
        cur, reds = dict(cur), {}
        for k, names in zip(kernels, takes):
            args = {n: v for n, v in cur.items()
                    if names is None or n in names}
            args.update((n, v) for n, v in scalars.items()
                        if names is None or n in names)
            res = k(**args)
            if k.reductions:
                res, r = res
                reds.update(r)
            outs = {k.outputs[0]: res} if len(k.outputs) == 1 else res
            for o in k.outputs:
                tgt = k.rotations.get(o)
                if tgt is None:
                    cur[o] = outs[o]
                else:
                    cur[o], cur[tgt] = cur[tgt], outs[o]
        return cur, reds

    def solver(fields, tol, max_iters):
        tol = jnp.asarray(tol, jnp.float32)
        max_iters = jnp.asarray(max_iters, jnp.int32)
        # Carry fields at the kernel's STORAGE dtype: a bf16-storage
        # kernel returns bf16 buffers, so f32 initial fields would make
        # the while_loop carry type-unstable after the first rotation.
        st = launches[0].ps.dtype
        cur0 = {n: jnp.asarray(v, st) for n, v in fields.items()}
        reds0 = {n: jnp.zeros((), jnp.float32) for n in declared}
        err0 = jnp.float32(jnp.inf if until == "below" else -jnp.inf)

        def cond(state):
            _, _, err, it = state
            keep = err > tol if until == "below" else err <= tol
            return keep & (it < max_iters)

        def body(state):
            cur, _, _, it = state
            for _ in range(check_every - 1):
                cur, _ = iteration(cur, plains)
            with jax.named_scope("solve.check"):
                cur, reds = iteration(cur, launches)
                reds = {n: jnp.asarray(v, jnp.float32)
                        for n, v in reds.items()}
                err = jnp.asarray(err_fn(reds), jnp.float32)
            return cur, reds, err, it + check_every

        return jax.lax.while_loop(cond, body, (cur0, reds0, err0,
                                               jnp.int32(0)))

    return solver


def _crossed(err: float, tol: float, until: str) -> bool:
    """Host-side mirror of the while_loop's stop test."""
    return err <= tol if until == "below" else err > tol


def _kernel_label(kernel) -> str:
    """The launches' names, ``+``-joined for a sequence."""
    return "+".join(getattr(k.fn, "__name__", "kernel")
                    for k in _launches(kernel))


def _solve_checkpointed(
    kernel, fields, scalars, *, tol, max_iters, check_every, error, until,
    ckpt: Checkpointing, col=_telemetry.NULL,
) -> SolveResult:
    """The chunked driver behind ``solve_until(checkpoint=...)``.

    Each chunk is the SAME jitted while_loop as the plain path, capped
    at ``save_every`` checks — per-step math never sees the chunk
    boundary, so a run killed between chunks resumes from ``LATEST``
    bit-identically to the uninterrupted run. Between chunks the carry
    is handed to the (async) checkpoint writer and the FaultPlan /
    heartbeat hooks fire; those are the run's only host syncs."""
    from ..distributed import fault

    mgr = ckpt.manager()
    save_every = int(ckpt.save_every)
    if save_every < 1:
        raise ValueError(f"save_every must be >= 1, got {save_every}")
    solver = _jitted_solver(kernel, scalars, check_every=check_every,
                            error=error, until=until)
    block = save_every * check_every
    # storage-dtype carry (same rationale as make_solver): resume-vs-
    # fresh stay bitwise because checkpoints then hold storage dtype too
    launches = _launches(kernel)
    cur = {n: jnp.asarray(v, launches[0].ps.dtype) for n, v in fields.items()}
    reds = {n: jnp.zeros((), jnp.float32) for n in _declared(launches)}
    err = jnp.float32(jnp.inf if until == "below" else -jnp.inf)
    done, resumed_from = 0, None

    if ckpt.resume and mgr.latest_step() is not None:
        like = {"fields": cur, "reds": reds, "err": err}
        tree, extra = mgr.restore(like)
        cur, reds, err = tree["fields"], tree["reds"], tree["err"]
        done = int(extra.get("iters", extra["step"]))
        resumed_from = done
        if col.enabled:
            ev = {"step": done, "err": float(err)}
            if extra.get("skipped_corrupt"):
                # torn steps the fallback walked past (step, reason)
                ev["skipped_corrupt"] = [s for s, _ in
                                         extra["skipped_corrupt"]]
            col.event("solve.resume", **ev)

    plan = fault.FaultPlan.active()
    monitor = ckpt.monitor
    saved: list[int] = []
    converged = done > 0 and _crossed(float(err), tol, until)
    while not converged and done < max_iters:
        take = min(block, max_iters - done)
        t0 = time.perf_counter()
        with col.span("solve.chunk") as span:
            cur, reds, err, it = solver(cur, tol, take)
            n = int(it)                  # chunk-boundary host sync
            dt = time.perf_counter() - t0
            done += n
            converged = _crossed(float(err), tol, until)
            per = dt / max(n, 1)
            if col.enabled:
                # harvest ONLY what this boundary already syncs: iters +
                # err (+ the reduction scalars the checkpoint ships anyway)
                span.set(steps=n, iters=done, err=float(err),
                         per_step_s=per, cold=len(saved) == 0)
        if col.enabled:
            col.count("solve.steps", n)
            col.event("solve.trajectory", iters=done, err=float(err),
                      per_step_s=per,
                      reds={k: float(v) for k, v in reds.items()})
        if monitor is not None:
            monitor.record(done, per)
            health = monitor.check_peers()
            if health["dead"]:
                mgr.wait()
                raise fault.RankFailure(health["dead"])
        # async: stalls only for the device->host snapshot; the write
        # overlaps the next chunk's device work
        mgr.save(done, {"fields": cur, "reds": reds, "err": err},
                 blocking=ckpt.blocking,
                 extra={"iters": done, "err": float(err), "tol": float(tol),
                        "check_every": int(check_every),
                        "save_every": save_every, "until": until,
                        "converged": converged})
        saved.append(done)
        if plan is not None:
            plan.on_step(done)   # a kill lands between save and next chunk
    mgr.wait()                           # surface async write failures
    stats = monitor.snapshot() if monitor is not None else None
    if col.enabled:
        col.gauge("solve.iters", done)
        col.gauge("solve.err", float(err))
    return SolveResult(fields=cur, reds=reds, err=err,
                       iters=jnp.int32(done), resumed_from=resumed_from,
                       saved_steps=tuple(saved), step_stats=stats)


def solve_until(
    kernel,
    fields: Mapping[str, Any],
    scalars: Mapping[str, Any] | None = None,
    *,
    tol: float,
    max_iters: int,
    check_every: int = 1,
    error: str | Callable | None = None,
    until: str = "below",
    checkpoint: Union[Checkpointing, str, None] = None,
    telemetry: Any = None,
) -> SolveResult:
    """Iterate ``kernel`` on device until its fused error scalar crosses
    ``tol`` (or ``max_iters`` steps), checking every ``check_every``
    steps — zero host transfers between checks.

    ``kernel`` is a :class:`~repro.core.parallel.StencilKernel` with
    ``reductions=`` and ``rotations=`` declared, or a sequence of
    kernels that together make one iteration (e.g. the pseudo-transient
    Stokes solver's pressure, stress, residual and velocity launches;
    see :func:`make_solver` for how their outputs rebind the carry).
    ``fields`` maps every field argument of every launch to its initial
    array; ``scalars`` the non-field arguments. ``error`` picks the
    convergence scalar: a reduction name (default: the single declared
    reduction) or a callable over the reduction dict (merged over the
    launches; e.g. a relative-drift formula); it must be cheap — it runs
    inside the loop condition's body on device.

    ``checkpoint`` (a directory path or :class:`Checkpointing`) makes
    the solve survivable: the loop is chunked at check boundaries, the
    carry is checkpointed asynchronously every ``save_every`` checks,
    and an interrupted run restarted with the same arguments resumes
    from the last atomic checkpoint (see :class:`Checkpointing`).

    ``telemetry`` selects a collector: ``None`` inherits the process
    singleton (env ``REPRO_TELEMETRY``), ``False`` forces it off,
    ``True``/a ``Collector`` forces it on. With telemetry off this
    function is byte-identical to the uninstrumented solve; with it on,
    device metrics are read only at already-existing host syncs (chunk
    boundaries / the final carry) — never inside the while_loop.
    """
    col = _telemetry.resolve(telemetry)
    if checkpoint is not None:
        if isinstance(checkpoint, str):
            checkpoint = Checkpointing(checkpoint)
        return _solve_checkpointed(
            kernel, dict(fields), scalars, tol=tol, max_iters=max_iters,
            check_every=check_every, error=error, until=until,
            ckpt=checkpoint, col=col)
    solver = _jitted_solver(kernel, scalars, check_every=check_every,
                            error=error, until=until)
    if not col.enabled:
        cur, reds, err, iters = solver(dict(fields), tol, max_iters)
        return SolveResult(fields=cur, reds=reds, err=err, iters=iters)
    # Instrumented plain path: same cached jitted solver as the disabled
    # path (identical dispatch cost). ``solve.dispatch`` ends once the
    # solve is enqueued; ``solve_until`` closes at the final-carry harvest,
    # which reads iters and err in one transfer. Cold calls — the ones
    # that paid trace+compile — are flagged via the jit cache size.
    size_fn = getattr(solver, "_cache_size", None)
    before = size_fn() if size_fn is not None else None
    with col.span("solve_until") as span:
        with col.span("solve.dispatch"):
            cur, reds, err, iters = solver(dict(fields), tol, max_iters)
        it, e = jax.device_get((iters, err))     # final-carry harvest
        it, e = int(it), float(e)
        span.set(kernel=_kernel_label(kernel), iters=it, err=e,
                 check_every=int(check_every),
                 cold=(size_fn() > before) if size_fn is not None else False)
    col.count("solve.steps", it)
    col.gauge("solve.iters", it)
    col.gauge("solve.err", e)
    return SolveResult(fields=cur, reds=reds, err=err, iters=iters)


# ---------------------------------------------------------------------------
# batch-axis solves: many independent samples through one device loop
# ---------------------------------------------------------------------------
#
# The serving scenario ("millions of users") is many SMALL independent
# solves — per-request scalars and initial conditions on a common grid —
# not one giant grid. A batched solver stacks them on a leading sample
# axis and advances the whole ensemble inside ONE jitted lax.while_loop:
# the per-sample step is the kernel's single-source jnp realization under
# jax.vmap (XLA fuses the batch axis like any other — on small grids the
# stacked step also uses the machine far better than B undersized
# launches), per-sample fused reductions come back as (B,) vectors, and a
# per-sample ACTIVE mask freezes finished samples — a converged, bad, or
# out-of-budget sample's buffers stop changing bitwise while stragglers
# continue — which is exactly the masking that lets a serving layer
# refill finished slots between chunks (continuous batching).
#
# Numerical health rides in the same loop: a `finite` reduction epilogue
# over the first output turns NaN/Inf into a per-sample indicator at
# check boundaries with zero extra HBM passes or host syncs; the loop
# retires poisoned samples (quarantine) instead of letting one diverging
# request wedge the batch (a NaN error would otherwise compare False
# against tol and masquerade as converged).


GUARD_NAME = "__finite"   # reserved reduction name for the health guard


@dataclasses.dataclass
class BatchCarry:
    """The device-resident state of a batched solve: every leaf carries a
    leading sample axis of extent B. Chunked drivers thread this through
    repeated jitted calls; all leaves are device values."""

    fields: dict[str, jax.Array]   # {name: (B, *grid)} double buffers
    reds: dict[str, jax.Array]     # {name: (B,)} last check's reductions
    err: jax.Array                 # (B,) f32 last error (±inf before first)
    steps: jax.Array               # (B,) i32 per-sample steps taken
    active: jax.Array              # (B,) bool — still iterating
    converged: jax.Array           # (B,) bool — crossed its own tol
    bad: jax.Array                 # (B,) bool — non-finite detected

    def tuple(self):
        return (self.fields, self.reds, self.err, self.steps, self.active,
                self.converged, self.bad)

    @classmethod
    def from_tuple(cls, t):
        return cls(*t)


@dataclasses.dataclass
class BatchedSolveResult:
    """Final state of :func:`solve_batch` (leading sample axis B).

    ``converged[b]`` — sample crossed its own tol; ``bad[b]`` — the
    finite guard tripped (NaN/Inf detected at a check boundary; the
    sample's buffers hold the detecting check's state and may contain
    non-finite values — consumers report the quarantine, not the
    payload); ``expired[b]`` — neither: the sample ran out of its step
    budget."""

    fields: dict[str, jax.Array]
    reds: dict[str, jax.Array]
    err: jax.Array
    iters: jax.Array
    converged: jax.Array
    bad: jax.Array

    @property
    def expired(self) -> jax.Array:
        return ~(self.converged | self.bad)

    def output(self, kernel) -> Any:
        tgts = {o: self.fields[t] for o, t in kernel.rotations.items()}
        if len(kernel.outputs) == 1:
            return tgts[kernel.outputs[0]]
        return tgts


def batchable_kernel(kernel):
    """The kernel variant a batched solve vmaps: the single-source update
    through the jnp (XLA-fused) realization, marching disabled (the
    sample axis is the parallel axis that feeds the machine; plane
    streaming inside a vmap adds nothing on bucket-sized grids). A
    pallas-backend kernel is re-bound to the jnp backend — same update
    fn, outputs, rotations, bcs and reductions, so results agree to
    reassociation (the paper's xPU single-source property is what makes
    this a one-liner)."""
    ps = kernel.ps
    if ps.backend == "jnp" and kernel.march_axis is None:
        return kernel
    from .parallel import StencilKernel

    ps2 = dataclasses.replace(ps, backend="jnp") if ps.backend != "jnp" \
        else ps
    return StencilKernel(ps2, kernel.fn, kernel.outputs, kernel.radius,
                         kernel.tile, kernel.vmem_budget, kernel.rotations,
                         kernel.bc, None, kernel.reductions)


def make_batched_solver(
    kernel,
    *,
    check_every: int = 1,
    error: str | Callable | None = None,
    until: str = "below",
    guard: bool = True,
):
    """Build the un-jitted batched driver
    ``solver(carry, scalars, tol, budget, max_steps) -> carry``.

    ``carry`` is a :class:`BatchCarry` tuple (see :meth:`BatchCarry.tuple`),
    ``scalars`` maps every scalar argument to a ``(B,)`` vector (each
    sample runs its own parameters), ``tol`` is a ``(B,)`` per-sample
    tolerance, ``budget`` a ``(B,)`` per-sample step cap (a deadline
    expressed in steps), and ``max_steps`` bounds this CALL — the loop
    exits when every sample is inactive or ``max_steps`` more steps have
    run, whichever first (chunked serving drivers pass their chunk size;
    :func:`solve_batch` passes the full budget).

    Semantics per check boundary (every ``check_every`` steps):

    * every ACTIVE sample advances; frozen samples are carried through
      ``jnp.where`` untouched (bitwise);
    * the per-sample fused error is compared against the sample's own
      tol (``until`` as in :func:`solve_until`);
    * with ``guard=True`` a ``finite`` reduction epilogue over the first
      output retires samples that went NaN/Inf (``bad``) the moment a
      check detects them, and a NaN error can never masquerade as
      convergence (the guard indicator is NaN-free by construction and
      takes precedence over the tol test);
    * a sample whose ``steps`` reached its budget goes inactive without
      ``converged`` or ``bad`` (the caller reads that as expiry).
    """
    if not kernel.reductions:
        raise ValueError(
            "batched solves need a kernel with fused reductions "
            "(declare reductions={'err': 'max_abs_diff(T2, T)'}-style on "
            "@parallel)"
        )
    err_fn = _resolve_error(kernel, error)   # against the DECLARED set
    kernel = batchable_kernel(kernel)
    rot = kernel.rotations
    if not rot or set(kernel.outputs) - set(rot):
        raise ValueError(
            "batched solves rotate double buffers between steps and need "
            "rotations covering every output (pass rotations={'T2': 'T'}-"
            "style mapping to @parallel)"
        )
    check_every = int(check_every)
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    if until not in ("below", "above"):
        raise ValueError(f"until must be 'below' or 'above', got {until!r}")
    plain = kernel.with_reductions(None)
    if guard:
        from ..ir import Reduction

        if GUARD_NAME in kernel.reductions:
            raise ValueError(f"reduction name {GUARD_NAME!r} is reserved "
                             "for the batched health guard")
        checked = kernel.with_reductions(
            dict(kernel.reductions,
                 **{GUARD_NAME: Reduction("finite", kernel.outputs[0])}))
    else:
        checked = kernel
    single = len(kernel.outputs) == 1
    red_names = tuple(kernel.reductions)

    def as_dict(res):
        return {kernel.outputs[0]: res} if single else dict(res)

    def rotate(cur, outs):
        cur = dict(cur)
        for o, tgt in rot.items():
            cur[o], cur[tgt] = cur[tgt], outs[o]
        return cur

    def sample_step(f, s):
        """One sample's check block: m-1 plain steps + 1 checked step."""
        cur = f
        for _ in range(check_every - 1):
            cur = rotate(cur, as_dict(plain(**cur, **s)))
        outs, reds = checked(**cur, **s)
        cur = rotate(cur, as_dict(outs))
        return cur, {n: jnp.asarray(v, jnp.float32)
                     for n, v in reds.items()}

    def solver(carry, scalars, tol, budget, max_steps):
        cur, reds, err, steps, active, converged, bad = carry
        tol = jnp.asarray(tol, jnp.float32)
        budget = jnp.asarray(budget, jnp.int32)
        max_steps = jnp.asarray(max_steps, jnp.int32)

        def cond(state):
            (_, _, _, _, active, _, _), t = state
            return jnp.any(active) & (t < max_steps)

        def body(state):
            (cur, reds, err, steps, active, converged, bad), t = state
            new_cur, new_reds = jax.vmap(sample_step)(cur, scalars)
            new_err = jnp.asarray(
                jax.vmap(lambda r: err_fn(
                    {n: r[n] for n in red_names}))(new_reds), jnp.float32)
            if guard:
                nonfin = (new_reds[GUARD_NAME] > 0) | ~jnp.isfinite(new_err)
            else:
                nonfin = ~jnp.isfinite(new_err)

            def freeze(new, old):
                keep = active.reshape(active.shape + (1,) * (new.ndim - 1))
                return jnp.where(keep, new, old)

            cur = {n: freeze(new_cur[n], cur[n]) for n in cur}
            reds = {n: jnp.where(active, new_reds[n], reds[n])
                    for n in red_names}
            err = jnp.where(active, new_err, err)
            steps = steps + jnp.where(active, check_every, 0)
            newly_bad = active & nonfin
            crossed = (err <= tol) if until == "below" else (err > tol)
            newly_conv = active & ~newly_bad & crossed
            bad = bad | newly_bad
            converged = converged | newly_conv
            active = active & ~newly_bad & ~newly_conv & (steps < budget)
            return ((cur, reds, err, steps, active, converged, bad),
                    t + check_every)

        state = ((cur, reds, err, steps, active, converged, bad),
                 jnp.int32(0))
        final, _ = jax.lax.while_loop(cond, body, state)
        return final

    return solver


# batched jitted solvers, memoized exactly like _SOLVER_CACHE (the key
# adds the batch extent + field shapes: the closure itself is shape-
# polymorphic, but one jit per (kernel, policy) signature suffices)
_BATCH_SOLVER_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def jitted_batched_solver(kernel, *, check_every=1, error=None,
                          until="below", guard=True):
    """The jitted driver for (kernel, policy), memoized on the kernel."""
    err_key = error if (error is None or isinstance(error, str)) \
        else id(error)
    key = (int(check_every), err_key, until, bool(guard))
    try:
        cache = _BATCH_SOLVER_CACHE.setdefault(kernel, {})
    except TypeError:
        cache = None
    if cache is not None and key in cache:
        return cache[key]
    solver = jax.jit(
        make_batched_solver(kernel, check_every=check_every, error=error,
                            until=until, guard=guard),
        static_argnums=())
    if cache is not None:
        cache[key] = solver
    return solver


def init_batch_carry(kernel, fields: Mapping[str, Any],
                     until: str = "below",
                     active: Any = None) -> BatchCarry:
    """A fresh :class:`BatchCarry` from stacked initial fields
    ``{name: (B, *grid)}`` (cast to the kernel's storage dtype).
    ``active`` preselects live samples (default: all)."""
    st = kernel.ps.dtype
    cur = {n: jnp.asarray(v, st) for n, v in fields.items()}
    b = next(iter(cur.values())).shape[0]
    for n, v in cur.items():
        if v.shape[0] != b:
            raise ValueError(
                f"field {n!r} has batch extent {v.shape[0]} != {b}; all "
                "stacked fields must share the leading sample axis")
    err0 = jnp.full((b,), jnp.inf if until == "below" else -jnp.inf,
                    jnp.float32)
    active = (jnp.ones((b,), bool) if active is None
              else jnp.asarray(active, bool))
    return BatchCarry(
        fields=cur,
        reds={n: jnp.zeros((b,), jnp.float32) for n in kernel.reductions},
        err=err0,
        steps=jnp.zeros((b,), jnp.int32),
        active=active,
        converged=jnp.zeros((b,), bool),
        bad=jnp.zeros((b,), bool),
    )


def solve_batch(
    kernel,
    fields: Mapping[str, Any],
    scalars: Mapping[str, Any] | None = None,
    *,
    tol: Any,
    max_iters: Any,
    check_every: int = 1,
    error: str | Callable | None = None,
    until: str = "below",
    guard: bool = True,
) -> BatchedSolveResult:
    """Solve B independent samples to their own convergence in ONE jitted
    device loop (see :func:`make_batched_solver` for the semantics).

    ``fields`` maps every field argument to a stacked ``(B, *grid)``
    array; ``scalars`` maps every scalar argument to a ``(B,)`` vector or
    a python number (broadcast to all samples). ``tol`` and ``max_iters``
    are likewise per-sample vectors or broadcast scalars. The loop runs
    until every sample converged, tripped the finite guard, or exhausted
    its own ``max_iters`` — finished samples freeze bitwise while
    stragglers continue."""
    carry = init_batch_carry(kernel, fields, until=until)
    b = carry.err.shape[0]
    scal = {n: jnp.broadcast_to(jnp.asarray(v), (b,))
            for n, v in (scalars or {}).items()}
    tolv = jnp.broadcast_to(jnp.asarray(tol, jnp.float32), (b,))
    budget = jnp.broadcast_to(jnp.asarray(max_iters, jnp.int32), (b,))
    solver = jitted_batched_solver(kernel, check_every=check_every,
                                   error=error, until=until, guard=guard)
    # cap = the largest per-sample budget, rounded up to a whole check
    cap = int(np.ceil(int(np.max(np.asarray(budget))) / check_every)
              ) * check_every
    final = solver(carry.tuple(), scal, tolv, budget, cap)
    out = BatchCarry.from_tuple(final)
    return BatchedSolveResult(fields=out.fields, reds=out.reds, err=out.err,
                              iters=out.steps, converged=out.converged,
                              bad=out.bad)
