"""True multi-process operation: launcher, rendezvous, gang supervisor.

Everything below runs the SAME ``elastic_solve_until`` that single-
process tests exercise — across genuinely separate OS processes joined
by ``jax.distributed``. Three layers:

  * :func:`initialize` — the per-process entry: resolves rank/world/
    coordinator from arguments or the ``REPRO_*`` environment, selects
    the gloo CPU collectives backend (CPU CI runs real cross-process
    collectives), and drives ``jax.distributed.initialize`` through a
    retrying, timeout-guarded rendezvous. A coordinator that is down, a
    joiner past the deadline, or a peer that died mid-init all surface
    as a pointed :class:`RendezvousError` within the configured budget —
    never an indefinite hang. Backoff between attempts goes through
    :func:`repro.distributed.fault.retry`;
    ``FaultPlan.kill_at_rendezvous`` injects mid-init death.

  * :class:`Supervisor` — the gang watcher: spawns one worker process
    per rank, namespaces their filesystem heartbeats by a per-attempt
    run id (and retires stale files from previous runs), and polls two
    liveness signals — exit codes and heartbeat staleness. One failed
    rank SIGTERMs then SIGKILLs the stragglers (peers wedge inside gloo
    collectives when a rank dies mid-step), re-plans the world to the
    largest checkpoint-compatible size, and relaunches; the workers'
    own checkpoint/resume logic carries the solve state across, so a
    SIGKILLed rank costs one restart and zero operator intervention.

  * the CLI — ``python -m repro.launch.multihost`` launches either the
    built-in demo solve (``--demo``, used by CI's multi-process smoke
    job) or an arbitrary per-rank command template::

        python -m repro.launch.multihost --world 4 --demo \
            --kill-rank 1 --kill-at 20      # supervised recovery demo
        python -m repro.launch.multihost --world 4 -- \
            python my_worker.py             # your own worker

Worker processes see ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` /
``REPRO_PROCESS_ID`` / ``REPRO_RUN_ID`` / ``REPRO_HEARTBEAT_DIR`` and
call :func:`initialize` with no arguments.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import subprocess
import sys
import time
from typing import Callable, Optional, Sequence

from ..distributed import fault

__all__ = [
    "RendezvousError", "DistContext", "Supervisor", "SuperviseOutcome",
    "initialize", "free_port", "default_coordinator",
    "kill_process", "heartbeat_ages", "check_one_process_per_chip",
    "ENV_COORDINATOR", "ENV_NUM_PROCESSES", "ENV_PROCESS_ID",
    "ENV_RUN_ID", "ENV_HEARTBEAT_DIR",
    "STALE_EXIT_CODE", "DEADLINE_EXIT_CODE",
]

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"
ENV_RUN_ID = "REPRO_RUN_ID"
ENV_HEARTBEAT_DIR = "REPRO_HEARTBEAT_DIR"

# supervisor-assigned exit reasons for ranks IT terminated (real worker
# exits keep their own codes; fault.KILL_EXIT_CODE marks planned kills)
STALE_EXIT_CODE = 114      # heartbeat went stale -> SIGKILLed as wedged
DEADLINE_EXIT_CODE = 115   # attempt exceeded its wall-clock deadline


class RendezvousError(RuntimeError):
    """``jax.distributed`` bring-up failed within the configured budget:
    coordinator unreachable, a joiner missed the deadline, or a peer
    died mid-init. Carries enough context to act on (who we dialed, as
    which rank, how long we tried)."""


def free_port() -> int:
    """An OS-assigned free TCP port (racy by nature — callers re-pick a
    fresh coordinator per attempt, so a rare collision costs one retry)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def default_coordinator() -> str:
    return f"127.0.0.1:{free_port()}"


def _chip_set(env: dict) -> Optional[frozenset]:
    """The TPU chips a child with this environment would open: None when
    it runs on another platform, every chip when it is not bound to its
    own (``TPU_VISIBLE_CHIPS``)."""
    plats = env.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        return None
    bound = env.get("TPU_VISIBLE_CHIPS", "")
    return frozenset(bound.split(",")) if bound else frozenset(["*"])


def check_one_process_per_chip(envs: Sequence[dict], what: str) -> None:
    """Fail fast where two of the child processes described by ``envs``
    would open the same TPU chip of this host. A chip belongs to one
    process: the second one to reach for it fails or hangs on the TPU
    runtime's lock. Chips are counted from the PCI bus, so the caller
    never initializes a JAX backend (which would itself hold them)."""
    sets = [s for s in map(_chip_set, envs) if s is not None]
    if len(sets) < 2:
        return
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id

    n_chips, _ = num_available_tpu_chips_and_device_id()
    if not n_chips:
        return
    for i, a in enumerate(sets):
        for b in sets[i + 1:]:
            if "*" in a or "*" in b or a & b:
                raise RuntimeError(
                    f"{what}: {len(sets)} processes would share this "
                    f"host's {n_chips} TPU chip(s), and a chip belongs to "
                    "one process — run them on the CPU (JAX_PLATFORMS=cpu "
                    "in their environment), give each its own chip "
                    "(TPU_VISIBLE_CHIPS), or drive the chips from one "
                    "process")


@dataclasses.dataclass(frozen=True)
class DistContext:
    """What :func:`initialize` hands the worker: its place in the gang
    plus the liveness plumbing the supervisor watches."""

    rank: int
    world: int
    coordinator: Optional[str]
    run_id: Optional[str]
    heartbeat_dir: Optional[str]

    def monitor(self, timeout_s: float = 30.0,
                straggler_factor: float = 1.5) -> Optional[fault.StepMonitor]:
        """A run-id-namespaced :class:`~repro.distributed.fault.
        StepMonitor` bumping this rank's heartbeat (None when the
        launcher gave no heartbeat dir)."""
        if not self.heartbeat_dir:
            return None
        return fault.StepMonitor(
            host_id=self.rank, heartbeat_dir=self.heartbeat_dir,
            straggler_factor=straggler_factor, timeout_s=timeout_s,
            run_id=self.run_id)


def _env_int(name: str) -> Optional[int]:
    val = os.environ.get(name)
    return int(val) if val not in (None, "") else None


def _await_coordinator(coordinator: str, deadline_s: float,
                       probe_s: float = 1.0) -> None:
    """Block until something is LISTENING at ``coordinator`` or raise
    :class:`ConnectionError` after ``deadline_s``.

    This probe runs before ``jax.distributed.initialize`` on
    non-coordinator ranks because XLA's distributed client does not
    surface connect failures as Python exceptions — its error-polling
    thread terminates the whole process with ``LOG(FATAL)`` on a
    RegisterTask deadline. Probing first keeps the coordinator-down
    failure mode catchable (and retryable) in-process."""
    host, _, port = coordinator.rpartition(":")
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            with socket.create_connection((host, int(port)), timeout=probe_s):
                return
        except OSError as e:
            if time.monotonic() >= deadline:
                raise ConnectionError(
                    f"nothing listening at coordinator {coordinator} "
                    f"within {deadline_s:.0f}s") from e
            time.sleep(min(probe_s, 0.2))


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None, *,
               timeout_s: float = 60.0,
               attempts: int = 2,
               backoff_s: float = 0.5,
               cpu_collectives: str = "gloo") -> DistContext:
    """Join the gang: ``jax.distributed.initialize`` with a bounded,
    retrying rendezvous. Arguments default from the ``REPRO_*``
    environment (set by :class:`Supervisor`); with no world configured
    this is a no-op returning a single-process context.

    Must run before any device-touching jax call — the CPU collectives
    backend can only be selected while the backend is uninitialized.
    Each attempt is bounded by ``timeout_s`` (jax's own
    ``initialization_timeout``); failures back off through
    :func:`fault.retry` and, once ``attempts`` are exhausted, raise a
    pointed :class:`RendezvousError` — never an indefinite hang."""
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR) or None
    num_processes = (num_processes if num_processes is not None
                     else _env_int(ENV_NUM_PROCESSES))
    process_id = (process_id if process_id is not None
                  else _env_int(ENV_PROCESS_ID))
    run_id = os.environ.get(ENV_RUN_ID) or None
    hb_dir = os.environ.get(ENV_HEARTBEAT_DIR) or None

    if coordinator is None and (num_processes is None or num_processes <= 1):
        _rank_telemetry(0)
        return DistContext(rank=0, world=1, coordinator=None,
                           run_id=run_id, heartbeat_dir=hb_dir)
    if coordinator is None or num_processes is None or process_id is None:
        raise RendezvousError(
            "incomplete rendezvous config: need coordinator, num_processes "
            f"and process_id (got {coordinator!r}, {num_processes!r}, "
            f"{process_id!r}) — set {ENV_COORDINATOR}/{ENV_NUM_PROCESSES}/"
            f"{ENV_PROCESS_ID} or pass them explicitly")

    import jax

    if cpu_collectives:
        jax.config.update("jax_cpu_collectives_implementation",
                          cpu_collectives)

    plan = fault.FaultPlan.active()
    state = {"attempt": 0}

    def attempt_once():
        state["attempt"] += 1
        if plan is not None:
            # plans arrive via this rank's own env, so no rank filter
            plan.on_rendezvous(state["attempt"])
        if process_id != 0:
            # rank 0 IS the coordinator; everyone else verifies it is up
            # before entering XLA (see _await_coordinator)
            _await_coordinator(coordinator, deadline_s=timeout_s)
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=max(int(timeout_s), 1))
        except Exception:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            raise

    try:
        fault.retry(attempt_once, attempts=max(int(attempts), 1),
                    backoff_s=backoff_s, max_backoff_s=10.0,
                    exceptions=(RuntimeError, OSError, ValueError,
                                ConnectionError))
    except Exception as e:
        raise RendezvousError(
            f"rank {process_id}/{num_processes} failed to rendezvous with "
            f"coordinator {coordinator} after {state['attempt']} attempt(s) "
            f"x {timeout_s:.0f}s: {type(e).__name__}: {e} — check that the "
            "coordinator process is up, the address is reachable, and all "
            f"{num_processes} processes launched within the timeout") from e

    _rank_telemetry(process_id)
    return DistContext(rank=process_id, world=num_processes,
                       coordinator=coordinator, run_id=run_id,
                       heartbeat_dir=hb_dir)


def _rank_telemetry(rank: int) -> None:
    """Split the env-enabled telemetry stream per rank (rank-stamped
    records into ``rank_<i>.jsonl`` — see ``telemetry.report --merge``)."""
    from .. import telemetry
    telemetry.configure_rank(rank)


# ---------------------------------------------------------------------------
# process plumbing shared by the gang supervisor and the serve worker pool
# ---------------------------------------------------------------------------
def kill_process(proc: subprocess.Popen, grace_s: float = 3.0) -> int:
    """SIGTERM, wait up to ``grace_s``, then SIGKILL. Returns the exit
    code (negative = died by signal)."""
    if proc.poll() is None:
        try:
            proc.terminate()
        except OSError:
            pass
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            try:
                proc.kill()
            except OSError:
                pass
            proc.wait()
    return proc.returncode


def heartbeat_ages(hb: fault.Heartbeat,
                   now: Optional[float] = None) -> dict[int, float]:
    """Seconds since each rank's last bump (ranks that never bumped are
    absent — cover them with an attempt deadline, not staleness)."""
    now = time.time() if now is None else now
    return {r: now - b["t"] for r, b in hb.read_all().items()}


@dataclasses.dataclass
class AttemptReport:
    attempt: int
    world: int
    run_id: str
    exit_codes: dict[int, int]
    reason: str
    duration_s: float


@dataclasses.dataclass
class SuperviseOutcome:
    restarts: int
    final_world: int
    exit_codes: list[int]          # per-attempt root-cause codes
    reports: list[AttemptReport]


class Supervisor:
    """Spawn-and-watch loop for one gang of worker processes.

    ``build_cmd(rank, world, attempt)`` returns the argv for one worker;
    ``rank_env(rank, world, attempt)`` optional per-rank env extras
    (fault-plan injection lives here). Each attempt gets a fresh
    coordinator port and a fresh run id (``<run_id>-a<attempt>``), so
    heartbeats from a dead attempt can never vouch for the new one;
    stale files are retired before spawning.

    Failure handling per attempt: the first nonzero exit code — or a
    heartbeat older than ``heartbeat_timeout_s`` (a wedged rank is
    SIGKILLed and charged :data:`STALE_EXIT_CODE`) — terminates the
    stragglers after ``grace_s`` and ends the attempt;
    ``attempt_deadline_s`` bounds everything else (rendezvous hangs,
    never-bumped ranks). :meth:`run` then re-plans the world via
    ``replan(world, rc)`` and relaunches, up to ``max_restarts``."""

    def __init__(self, build_cmd: Callable[[int, int, int], list[str]],
                 world: int, *,
                 heartbeat_dir: str,
                 run_id: Optional[str] = None,
                 heartbeat_timeout_s: float = 30.0,
                 grace_s: float = 3.0,
                 attempt_deadline_s: float = 600.0,
                 poll_s: float = 0.05,
                 env: Optional[dict] = None,
                 rank_env: Optional[Callable[[int, int, int], dict]] = None,
                 replan: Optional[Callable[[int, int], int]] = None,
                 max_restarts: int = 3,
                 verbose: bool = False):
        self.build_cmd = build_cmd
        self.world = int(world)
        self.heartbeat_dir = heartbeat_dir
        self.run_id = run_id or f"run{os.getpid()}"
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.grace_s = grace_s
        self.attempt_deadline_s = attempt_deadline_s
        self.poll_s = poll_s
        self.env = dict(env or {})
        self.rank_env = rank_env
        self.replan = replan
        self.max_restarts = max_restarts
        self.verbose = verbose
        self.reports: list[AttemptReport] = []
        os.makedirs(heartbeat_dir, exist_ok=True)

    def _say(self, msg: str) -> None:
        if self.verbose:
            print(f"[supervisor] {msg}", flush=True)

    def _base_env(self, attempt_run_id: str, coordinator: str,
                  world: int) -> dict:
        env = dict(os.environ)
        # each worker is exactly ONE process with its own local device;
        # an inherited fake-device flag would multiply the global mesh
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(flags)
        env.pop(fault.PLAN_ENV, None)   # plans are per-rank, via rank_env
        env[ENV_COORDINATOR] = coordinator
        env[ENV_NUM_PROCESSES] = str(world)
        env[ENV_RUN_ID] = attempt_run_id
        env[ENV_HEARTBEAT_DIR] = self.heartbeat_dir
        env.update(self.env)
        return env

    def run_attempt(self, attempt: int, world: int) -> int:
        """One gang launch to completion or first failure. Returns the
        attempt's root-cause exit code (0 = every rank exited 0)."""
        attempt_run_id = f"{self.run_id}-a{attempt}"
        fault.Heartbeat.retire_stale(self.heartbeat_dir)
        coordinator = default_coordinator()
        base = self._base_env(attempt_run_id, coordinator, world)
        envs = []
        for rank in range(world):
            env = dict(base)
            env[ENV_PROCESS_ID] = str(rank)
            if self.rank_env is not None:
                env.update(self.rank_env(rank, world, attempt) or {})
            envs.append(env)
        check_one_process_per_chip(envs, f"gang of {world} ranks")
        t0 = time.monotonic()
        procs: dict[int, subprocess.Popen] = {}
        logs = []
        try:
            for rank, env in enumerate(envs):
                log = open(os.path.join(
                    self.heartbeat_dir, f"{attempt_run_id}.rank{rank}.log"),
                    "wb")
                logs.append(log)
                procs[rank] = subprocess.Popen(
                    self.build_cmd(rank, world, attempt), env=env,
                    stdout=log, stderr=subprocess.STDOUT)
            self._say(f"attempt {attempt}: world={world} "
                      f"coordinator={coordinator} run_id={attempt_run_id}")
            rcs, reason = self._watch(procs, attempt_run_id, world)
        finally:
            for proc in procs.values():
                kill_process(proc, self.grace_s)
            for log in logs:
                log.close()
        root = self._root_cause(rcs)
        self.reports.append(AttemptReport(
            attempt=attempt, world=world, run_id=attempt_run_id,
            exit_codes=rcs, reason=reason,
            duration_s=time.monotonic() - t0))
        self._say(f"attempt {attempt}: rc={root} codes={rcs} ({reason})")
        return root

    def _watch(self, procs: dict[int, subprocess.Popen],
               attempt_run_id: str, world: int) -> tuple[dict[int, int], str]:
        hb = fault.Heartbeat(self.heartbeat_dir,
                             timeout_s=self.heartbeat_timeout_s,
                             run_id=attempt_run_id)
        deadline = time.monotonic() + self.attempt_deadline_s
        rcs: dict[int, int] = {}
        while True:
            for rank, proc in procs.items():
                if rank not in rcs and proc.poll() is not None:
                    rcs[rank] = proc.returncode
            live = [r for r in procs if r not in rcs]
            failed = sorted(r for r, c in rcs.items() if c != 0)
            if failed:
                reason = (f"rank(s) {failed} exited "
                          f"{[rcs[r] for r in failed]}; terminating "
                          f"{len(live)} straggler(s)")
                for rank in live:
                    rcs[rank] = kill_process(procs[rank], self.grace_s)
                return rcs, reason
            if not live:
                return rcs, "all ranks exited 0"
            ages = heartbeat_ages(hb)
            stale = sorted(r for r in live
                           if ages.get(r, 0.0) > self.heartbeat_timeout_s)
            if stale:
                reason = (f"rank(s) {stale} heartbeat stale "
                          f"(> {self.heartbeat_timeout_s:.0f}s) — SIGKILL")
                for rank in stale:
                    try:
                        procs[rank].kill()
                    except OSError:
                        pass
                    procs[rank].wait()
                    rcs[rank] = STALE_EXIT_CODE
                for rank in live:
                    if rank not in rcs:
                        rcs[rank] = kill_process(procs[rank], self.grace_s)
                return rcs, reason
            if time.monotonic() > deadline:
                reason = (f"attempt deadline {self.attempt_deadline_s:.0f}s "
                          "exceeded — terminating the gang")
                for rank in live:
                    kill_process(procs[rank], self.grace_s)
                    rcs[rank] = DEADLINE_EXIT_CODE
                return rcs, reason
            time.sleep(self.poll_s)

    @staticmethod
    def _root_cause(rcs: dict[int, int]) -> int:
        """The attempt's exit code: prefer a planned kill, then the first
        positive code (a real worker failure), then any nonzero
        (supervisor-terminated stragglers exit by signal = negative)."""
        codes = [rcs[r] for r in sorted(rcs)]
        if all(c == 0 for c in codes):
            return 0
        if fault.KILL_EXIT_CODE in codes:
            return fault.KILL_EXIT_CODE
        for c in codes:
            if c > 0:
                return c
        return next(c for c in codes if c != 0)

    def run(self) -> SuperviseOutcome:
        """The full supervised loop (delegates restart policy to
        :func:`repro.distributed.elastic.supervise`)."""
        from ..distributed import elastic

        restarts, final_world, codes = elastic.supervise(
            self.run_attempt, self.world,
            replan=self.replan, max_restarts=self.max_restarts)
        return SuperviseOutcome(restarts=restarts, final_world=final_world,
                                exit_codes=codes, reports=self.reports)


# ---------------------------------------------------------------------------
# built-in demo worker (CI smoke: real 4-process kill/replan/resume)
# ---------------------------------------------------------------------------
def _demo_worker() -> int:
    """One rank of the demo solve: rendezvous, then the same diffusion
    ``elastic_solve_until`` the single-process tests run — checkpointing
    globally so any later (smaller) world resumes it."""
    ctx = initialize(timeout_s=float(os.environ.get("REPRO_DEMO_RDV_S", 20)))

    import numpy as np

    from ..core import fd3d, init_parallel_stencil, iterate
    from ..distributed import elastic

    n = int(os.environ.get("REPRO_DEMO_N", 18))
    max_iters = int(os.environ.get("REPRO_DEMO_ITERS", 40))
    hb_timeout = float(os.environ.get("REPRO_DEMO_HB_S", 30))

    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions={"err": "max_abs_diff(T2, T)"})
    def kern(T2, T, dt):
        return {"T2": fd3d.inn(T) + dt * (fd3d.d2_xi(T) + fd3d.d2_yi(T)
                                          + fd3d.d2_zi(T))}

    factors = elastic.plan_factors(ctx.world, 1)
    elastic.validate_stencil_factors((n, n, n), factors, radius=1)
    rng = np.random.RandomState(0)
    T0 = np.asarray(rng.rand(n, n, n), np.float32)
    ck = iterate.Checkpointing(
        os.environ["REPRO_DEMO_CKPT"], save_every=1, blocking=True,
        monitor=ctx.monitor(timeout_s=hb_timeout))
    res = elastic.elastic_solve_until(
        kern, dict(T2=T0, T=T0), dict(dt=1e-3), factors=factors,
        tol=0.0, max_iters=max_iters, exchange=("T",), check_every=4,
        checkpoint=ck)
    if ctx.rank == 0 and os.environ.get("REPRO_DEMO_OUT"):
        np.save(os.environ["REPRO_DEMO_OUT"], np.asarray(res.fields["T"]))
    print(f"DONE rank={ctx.rank} world={ctx.world} iters={int(res.iters)} "
          f"resumed_from={res.resumed_from}", flush=True)
    return 0


def demo_supervisor(world: int, workdir: str, *,
                    n: int = 18, max_iters: int = 40,
                    kill_rank: Optional[int] = None,
                    kill_at: Optional[int] = None,
                    kill_at_rendezvous: Optional[int] = None,
                    heartbeat_timeout_s: float = 30.0,
                    attempt_deadline_s: float = 240.0,
                    rendezvous_timeout_s: float = 20.0,
                    max_restarts: int = 3,
                    run_id: Optional[str] = None,
                    verbose: bool = True) -> Supervisor:
    """The supervised demo gang (also the CI smoke harness): optionally
    SIGKILL-injects ``kill_rank`` at iteration ``kill_at`` (or on entry
    to rendezvous attempt ``kill_at_rendezvous``) on attempt 0 via
    ``REPRO_FAULT_PLAN``, and re-plans with
    :func:`~repro.distributed.elastic.plan_compatible` so the shrunken
    world still divides the grid."""
    from ..distributed import elastic

    shape = (n, n, n)
    world, _ = _compatible_or_raise(shape, world)

    def build_cmd(rank: int, w: int, attempt: int) -> list[str]:
        return [sys.executable, "-m", "repro.launch.multihost", "--worker"]

    def rank_env(rank: int, w: int, attempt: int) -> dict:
        env = {
            "REPRO_DEMO_N": str(n),
            "REPRO_DEMO_ITERS": str(max_iters),
            "REPRO_DEMO_HB_S": str(heartbeat_timeout_s),
            "REPRO_DEMO_RDV_S": str(rendezvous_timeout_s),
            "REPRO_DEMO_CKPT": os.path.join(workdir, "ckpt"),
            "REPRO_DEMO_OUT": os.path.join(workdir, "out.npy"),
        }
        if attempt == 0 and kill_rank == rank:
            if kill_at is not None:
                env[fault.PLAN_ENV] = fault.FaultPlan(
                    kill_at_step=kill_at).to_env()
            elif kill_at_rendezvous is not None:
                env[fault.PLAN_ENV] = fault.FaultPlan(
                    kill_at_rendezvous=kill_at_rendezvous).to_env()
        return env

    def replan(w: int, rc: int) -> int:
        return elastic.plan_compatible(shape, 1, max(w - 1, 1))[0]

    return Supervisor(
        build_cmd, world,
        heartbeat_dir=os.path.join(workdir, "hb"),
        run_id=run_id, heartbeat_timeout_s=heartbeat_timeout_s,
        attempt_deadline_s=attempt_deadline_s, rank_env=rank_env,
        replan=replan, max_restarts=max_restarts, verbose=verbose)


def _compatible_or_raise(shape: Sequence[int], world: int) -> tuple[int, tuple]:
    from ..distributed import elastic

    w, factors = elastic.plan_compatible(shape, 1, world)
    if w != world:
        raise ValueError(
            f"world {world} does not decompose grid {tuple(shape)} "
            f"(radius 1); largest compatible world is {w}")
    return w, factors


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.multihost",
        description="multi-process launcher/supervisor (see module doc)")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one demo worker rank (env-driven)")
    ap.add_argument("--demo", action="store_true",
                    help="run the supervised demo solve")
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--workdir", default="multihost_demo")
    ap.add_argument("--n", type=int, default=18)
    ap.add_argument("--max-iters", type=int, default=40)
    ap.add_argument("--kill-rank", type=int, default=None,
                    help="demo: SIGKILL this rank on attempt 0 ...")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="... at this iteration (exercises recovery)")
    ap.add_argument("--heartbeat-timeout", type=float, default=30.0)
    ap.add_argument("--deadline", type=float, default=240.0,
                    help="per-attempt wall-clock bound (s)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--run-id", default=None)
    ap.add_argument("cmd", nargs="*",
                    help="worker argv (after --) for non-demo gangs")
    args = ap.parse_args(argv)

    if args.worker:
        return _demo_worker()

    if args.demo:
        sup = demo_supervisor(
            args.world, args.workdir, n=args.n, max_iters=args.max_iters,
            kill_rank=args.kill_rank, kill_at=args.kill_at,
            heartbeat_timeout_s=args.heartbeat_timeout,
            attempt_deadline_s=args.deadline,
            max_restarts=args.max_restarts, run_id=args.run_id)
        out = sup.run()
        print(json.dumps({
            "restarts": out.restarts, "final_world": out.final_world,
            "exit_codes": out.exit_codes,
            "attempts": [dataclasses.asdict(r) for r in out.reports],
        }, indent=2))
        return 0

    if not args.cmd:
        ap.error("pass --demo, --worker, or a worker command after --")
    sup = Supervisor(
        lambda rank, world, attempt: list(args.cmd), args.world,
        heartbeat_dir=os.path.join(args.workdir, "hb"),
        run_id=args.run_id, heartbeat_timeout_s=args.heartbeat_timeout,
        attempt_deadline_s=args.deadline, max_restarts=args.max_restarts,
        verbose=True)
    out = sup.run()
    print(json.dumps({"restarts": out.restarts,
                      "final_world": out.final_world,
                      "exit_codes": out.exit_codes}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
