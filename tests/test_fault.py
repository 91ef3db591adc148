"""Fault tolerance: retry/backoff, deterministic fault injection,
durable checkpoints, kill/resume, and elastic restart on a reshaped mesh.

Comparison contract (same as test_reductions): a killed single-device
run resumed on the SAME machine replays the identical compiled program
from the checkpointed carry, so it is compared BITWISE against the
uninterrupted run. A resume on a *different* mesh re-decomposes the
global arrays and the rank-combined reductions reassociate — those
comparisons are allclose, never equality.

Process-death tests run real subprocesses: ``REPRO_FAULT_PLAN`` makes
an unmodified ``solve_until`` die via ``os._exit(113)`` at an exact
iteration count, the parent asserts the planned exit code, and a second
launch resumes from the atomic checkpoint.
"""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointError, CheckpointManager
from repro.core import fd3d, init_parallel_stencil, iterate
from repro.distributed import fault, overlap

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def run_proc(code: str, n_devices: int = 1, env_extra: dict | None = None,
             timeout: int = 560) -> subprocess.CompletedProcess:
    """Like conftest.run_subprocess but returns the CompletedProcess so
    kill-injection tests can assert a NONZERO planned exit code."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop(fault.PLAN_ENV, None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)


@pytest.fixture()
def active_plan(monkeypatch):
    """Install a FaultPlan as the process-wide active plan; restores the
    no-plan state afterwards."""
    def install(plan: fault.FaultPlan):
        monkeypatch.setenv(fault.PLAN_ENV, plan.to_env())
        fault.FaultPlan.reset_active()
        return fault.FaultPlan.active()
    yield install
    fault.FaultPlan.reset_active()


def diffusion_kernel():
    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions={"err": "max_abs_diff(T2, T)"})
    def kern(T2, T, dt):
        return {"T2": fd3d.inn(T) + dt * (fd3d.d2_xi(T) + fd3d.d2_yi(T)
                                          + fd3d.d2_zi(T))}

    return kern


def spike(n=16):
    return jnp.zeros((n, n, n), jnp.float32).at[n // 2, n // 2, n // 2].set(1.0)


# ---------------------------------------------------------------------------
# retry with backoff + jitter
# ---------------------------------------------------------------------------
def test_retry_backoff_schedule_and_jitter_bounds():
    waits: list[float] = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise OSError("transient")
        return "ok"

    out = fault.retry(flaky, attempts=4, backoff_s=0.1, max_backoff_s=0.3,
                      jitter=0.25, seed=7, sleep=waits.append)
    assert out == "ok" and calls["n"] == 4
    assert len(waits) == 3
    for i, w in enumerate(waits):
        nominal = min(0.1 * 2 ** i, 0.3)
        assert nominal * 0.75 <= w <= nominal * 1.25, (i, w, nominal)


def test_retry_jitter_deterministic_with_seed():
    def seq(seed):
        waits = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 4:
                raise OSError()
            return 1

        fault.retry(flaky, attempts=4, backoff_s=0.05, jitter=0.5,
                    seed=seed, sleep=waits.append)
        return waits

    assert seq(3) == seq(3)
    assert seq(3) != seq(4)


def test_retry_exhausts_and_propagates():
    waits = []
    with pytest.raises(OSError, match="persistent"):
        fault.retry(lambda: (_ for _ in ()).throw(OSError("persistent")),
                    attempts=3, backoff_s=0.01, sleep=waits.append)
    assert len(waits) == 2  # no sleep after the final attempt


def test_retry_does_not_catch_unlisted_exceptions():
    with pytest.raises(KeyError):
        fault.retry(lambda: {}["missing"], attempts=4, sleep=lambda s: None)


# ---------------------------------------------------------------------------
# FaultPlan parsing + hooks
# ---------------------------------------------------------------------------
def test_fault_plan_env_roundtrip():
    plan = fault.FaultPlan(kill_at_step=60, io_errors=2)
    again = fault.FaultPlan.from_env({fault.PLAN_ENV: plan.to_env()})
    assert again.kill_at_step == 60 and again.io_errors == 2
    assert fault.FaultPlan.from_env({}) is None


def test_fault_plan_rejects_bad_env():
    with pytest.raises(ValueError, match="unknown keys"):
        fault.FaultPlan.from_env({fault.PLAN_ENV: '{"kill_at": 3}'})
    with pytest.raises(ValueError, match="not valid JSON"):
        fault.FaultPlan.from_env({fault.PLAN_ENV: "{nope"})
    with pytest.raises(ValueError, match="JSON object"):
        fault.FaultPlan.from_env({fault.PLAN_ENV: "[1, 2]"})


def test_fault_plan_io_budget():
    plan = fault.FaultPlan(io_errors=2)
    with pytest.raises(fault.TransientIOError):
        plan.on_io("/a")
    with pytest.raises(fault.TransientIOError):
        plan.on_io("/b")
    plan.on_io("/c")  # budget spent: no raise


def test_fault_plan_on_step_respects_rank():
    plan = fault.FaultPlan(hang_at_step=5, hang_s=0.01, rank=1)
    t0 = time.perf_counter()
    plan.on_step(10, rank=0)           # not this plan's rank: no-op
    assert time.perf_counter() - t0 < 0.005
    plan.on_step(10, rank=1)           # hangs once
    assert plan.hang_at_step is None   # consumed


def test_kill_at_step_exits_with_planned_code():
    code = """
from repro.distributed import fault
plan = fault.FaultPlan(kill_at_step=3)
for step in range(10):
    plan.on_step(step)
print("UNREACHABLE")
"""
    p = run_proc(code)
    assert p.returncode == fault.KILL_EXIT_CODE, (p.stdout, p.stderr)
    assert "UNREACHABLE" not in p.stdout


# ---------------------------------------------------------------------------
# heartbeats, stragglers, monitored stepping
# ---------------------------------------------------------------------------
def test_heartbeat_dead_and_straggler_flagging(tmp_path):
    d = str(tmp_path)
    now = time.time()
    # ranks 0/3 healthy, rank 1 = straggler (slow EWMA), rank 2 = dead
    fault.Heartbeat(d, rank=0).bump(100, ewma_s=0.10)
    fault.Heartbeat(d, rank=3).bump(98, ewma_s=0.12)
    fault.Heartbeat(d, rank=1).bump(80, ewma_s=1.0)
    with open(os.path.join(d, "host_2.json"), "w") as f:
        json.dump({"step": 40, "t": now - 1000.0, "ewma_s": 0.1}, f)

    hb = fault.Heartbeat(d, rank=0, timeout_s=300.0)
    assert hb.dead_ranks(now=now) == [2]
    assert hb.dead_ranks(expected=[0, 1, 2, 3, 4], now=now) == [2, 4]

    mon = fault.StepMonitor(host_id=0, heartbeat_dir=d,
                            straggler_factor=1.5, timeout_s=300.0)
    health = mon.check_peers(now=now)
    assert health["dead"] == [2]
    assert health["stragglers"] == [1]


def test_heartbeat_ignores_torn_files(tmp_path):
    d = str(tmp_path)
    fault.Heartbeat(d, rank=0).bump(10)
    with open(os.path.join(d, "host_1.json"), "w") as f:
        f.write('{"step": 5, "t":')  # torn mid-write
    beats = fault.Heartbeat(d).read_all()
    assert list(beats) == [0]


def test_monitored_stepper_raises_rank_failure(tmp_path):
    d = str(tmp_path)
    with open(os.path.join(d, "host_7.json"), "w") as f:
        json.dump({"step": 1, "t": time.time() - 1000.0, "ewma_s": 0.1}, f)
    mon = fault.StepMonitor(host_id=0, heartbeat_dir=d, timeout_s=300.0)
    stepper = overlap.monitored(lambda x: x + 1, mon, check_peers_every=1)
    with pytest.raises(fault.RankFailure) as ei:
        stepper(jnp.float32(1.0))
    assert ei.value.dead == [7]
    # our own heartbeat was still bumped before the check
    assert 0 in fault.Heartbeat(d).read_all()


def test_supervise_replans_world_and_succeeds():
    seen = []

    def attempt(i, world):
        seen.append((i, world))
        return fault.KILL_EXIT_CODE if i < 2 else 0

    attempts, final_world, codes = fault_supervise(attempt, 4)
    assert attempts == 2 and final_world == 2
    assert codes == [fault.KILL_EXIT_CODE, fault.KILL_EXIT_CODE, 0]
    assert seen == [(0, 4), (1, 3), (2, 2)]


def fault_supervise(attempt, world):
    from repro.distributed import elastic
    return elastic.supervise(attempt, world)


def test_supervise_gives_up_after_max_restarts():
    from repro.distributed import elastic
    with pytest.raises(RuntimeError, match="gave up"):
        elastic.supervise(lambda i, w: 1, 4, max_restarts=2)


# ---------------------------------------------------------------------------
# checkpoint durability
# ---------------------------------------------------------------------------
def _tree(v=0.0, n=4):
    return {"fields": {"T": jnp.full((n, n), v, jnp.float32)},
            "err": jnp.float32(v)}


def test_checkpoint_atomic_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30, 40):
        mgr.save(s, _tree(float(s)))
    assert mgr.list_steps() == [30, 40]
    assert mgr.latest_step() == 40
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]
    tree, extra = mgr.restore(_tree())
    assert extra["step"] == 40
    assert float(tree["err"]) == 40.0


def test_keep_k_never_deletes_latest_pointed(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    for s in (10, 20, 30):
        mgr.save(s, _tree(float(s)))
    # crash-recovery state: a newer dir landed but the LATEST swap never
    # happened, so LATEST still names an old step
    with open(os.path.join(tmp_path, "LATEST"), "w") as f:
        f.write("step_%09d" % 10)
    mgr.keep = 1
    mgr._gc()
    # keep=1 would evict 10 and 20 — but LATEST names 10
    assert os.path.isdir(mgr.step_dir(10)), "LATEST-pointed step deleted"
    assert not os.path.isdir(mgr.step_dir(20))
    assert os.path.isdir(mgr.step_dir(30))
    # restore follows the pointer, not the newest dir
    _, extra = mgr.restore(_tree())
    assert extra["step"] == 10


def test_restore_explicit_step_and_shape_validation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(10, _tree(1.0))
    with pytest.raises(FileNotFoundError):
        mgr.restore(_tree(), step=99)
    with pytest.raises(CheckpointError, match="does not match restore"):
        mgr.restore({"fields": {"T": jnp.zeros((8, 8), jnp.float32)},
                     "err": jnp.float32(0)}, step=10)
    with pytest.raises(CheckpointError, match="absent from checkpoint"):
        mgr.restore({"fields": {"Q": jnp.zeros((4, 4), jnp.float32)},
                     "err": jnp.float32(0)}, step=10)


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(10, _tree(1.0))
    mgr.save(20, _tree(2.0))
    # tear the newest step's first tensor (short read on restore)
    d = mgr.step_dir(20)
    victim = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
    path = os.path.join(d, victim)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    # explicit step: the CheckpointError propagates
    with pytest.raises(CheckpointError):
        mgr.restore(_tree(), step=20)
    # implicit (LATEST): falls back to the previous intact step
    tree, extra = mgr.restore(_tree())
    assert extra["step"] == 10
    assert [s for s, _ in extra["skipped_corrupt"]] == [20]
    assert float(tree["err"]) == 1.0


def test_fault_plan_tears_scheduled_save(tmp_path, active_plan):
    active_plan(fault.FaultPlan(corrupt_checkpoint=2))
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(10, _tree(1.0))
    mgr.save(20, _tree(2.0))   # the torn one
    tree, extra = mgr.restore(_tree())
    assert extra["step"] == 10 and extra["skipped_corrupt"]


def test_transient_io_errors_absorbed_by_retry(tmp_path, active_plan):
    plan = active_plan(fault.FaultPlan(io_errors=3))
    mgr = CheckpointManager(str(tmp_path), keep=3, retry_backoff_s=0.001)
    mgr.save(10, _tree(5.0))   # write path retries through the budget
    assert plan.io_errors == 0
    tree, extra = mgr.restore(_tree())
    assert extra["step"] == 10 and float(tree["err"]) == 5.0


def test_async_save_failure_surfaces_on_wait(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), keep=3, retry_attempts=1)
    monkeypatch.setattr(mgr, "_write",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("disk")))
    mgr.save(10, _tree(), blocking=False)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        mgr.wait()


# ---------------------------------------------------------------------------
# checkpointed solve_until (single device): bitwise contract
# ---------------------------------------------------------------------------
def test_checkpointed_solve_bitwise_equals_plain(tmp_path):
    kern = diffusion_kernel()
    T0 = spike()
    fields = dict(T2=T0, T=T0)
    sc = dict(dt=1e-3)
    plain = iterate.solve_until(kern, fields, sc, tol=1e-6, max_iters=60,
                                check_every=5)
    ck = iterate.Checkpointing(str(tmp_path), save_every=2, blocking=True)
    chunked = iterate.solve_until(kern, fields, sc, tol=1e-6, max_iters=60,
                                  check_every=5, checkpoint=ck)
    assert int(chunked.iters) == int(plain.iters)
    assert float(chunked.err) == float(plain.err)
    for k in fields:
        np.testing.assert_array_equal(np.asarray(chunked.fields[k]),
                                      np.asarray(plain.fields[k]))
    assert chunked.saved_steps, "no checkpoints written"
    assert chunked.resumed_from is None


def test_resume_midway_bitwise_equals_uninterrupted(tmp_path):
    kern = diffusion_kernel()
    T0 = spike()
    fields, sc = dict(T2=T0, T=T0), dict(dt=1e-3)
    full = iterate.solve_until(kern, fields, sc, tol=0.0, max_iters=80,
                               check_every=4)
    ck = iterate.Checkpointing(str(tmp_path), save_every=5, blocking=True)
    part = iterate.solve_until(kern, fields, sc, tol=0.0, max_iters=40,
                               check_every=4, checkpoint=ck)
    assert int(part.iters) == 40
    resumed = iterate.solve_until(kern, fields, sc, tol=0.0, max_iters=80,
                                  check_every=4, checkpoint=ck)
    assert resumed.resumed_from == 40
    assert int(resumed.iters) == 80
    for k in fields:
        np.testing.assert_array_equal(np.asarray(resumed.fields[k]),
                                      np.asarray(full.fields[k]))


def test_solve_with_monitor_raises_on_dead_peer(tmp_path):
    hb_dir = str(tmp_path / "hb")
    os.makedirs(hb_dir)
    with open(os.path.join(hb_dir, "host_3.json"), "w") as f:
        json.dump({"step": 1, "t": time.time() - 1000.0, "ewma_s": 0.1}, f)
    mon = fault.StepMonitor(host_id=0, heartbeat_dir=hb_dir, timeout_s=300.0)
    ck = iterate.Checkpointing(str(tmp_path / "ck"), save_every=1,
                               blocking=True, monitor=mon)
    kern = diffusion_kernel()
    T0 = spike()
    with pytest.raises(fault.RankFailure) as ei:
        iterate.solve_until(kern, dict(T2=T0, T=T0), dict(dt=1e-3),
                            tol=0.0, max_iters=20, check_every=2,
                            checkpoint=ck)
    assert ei.value.dead == [3]


# ---------------------------------------------------------------------------
# process death + resume (real subprocesses, real os._exit)
# ---------------------------------------------------------------------------
_SOLVE_CHILD = r"""
import os, numpy as np, jax.numpy as jnp
from repro.core import fd3d, init_parallel_stencil, iterate

ps = init_parallel_stencil(backend="jnp", ndims=3)

@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def kern(T2, T, dt):
    return {"T2": fd3d.inn(T) + dt * (fd3d.d2_xi(T) + fd3d.d2_yi(T)
                                      + fd3d.d2_zi(T))}

n = 16
T0 = jnp.zeros((n, n, n), jnp.float32).at[n//2, n//2, n//2].set(1.0)
ck = iterate.Checkpointing(os.environ["CKPT_DIR"], save_every=2,
                           blocking=True)
res = iterate.solve_until(kern, dict(T2=T0, T=T0), dict(dt=1e-3),
                          tol=0.0, max_iters=60, check_every=5,
                          checkpoint=ck)
np.save(os.environ["OUT_NPY"], np.asarray(res.fields["T"]))
print("DONE", int(res.iters), res.resumed_from)
"""


def test_kill_at_step_then_resume_completes_bitwise(tmp_path):
    ck, out = str(tmp_path / "ck"), str(tmp_path / "out.npy")
    ref = str(tmp_path / "ref.npy")
    env = {"CKPT_DIR": ck, "OUT_NPY": out}

    # attempt 1: the plan kills the process at iteration 30 (a save
    # boundary) -- planned exit code, partial checkpoints on disk
    plan = fault.FaultPlan(kill_at_step=30)
    p = run_proc(_SOLVE_CHILD,
                 env_extra=dict(env, **{fault.PLAN_ENV: plan.to_env()}))
    assert p.returncode == fault.KILL_EXIT_CODE, (p.stdout, p.stderr)
    assert not os.path.exists(out)
    mgr = CheckpointManager(ck)
    assert mgr.latest_step() == 30

    # attempt 2 (no plan): resumes from step 30 and completes
    p = run_proc(_SOLVE_CHILD, env_extra=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "DONE 60 30" in p.stdout

    # reference: uninterrupted run in a fresh process
    p = run_proc(_SOLVE_CHILD,
                 env_extra={"CKPT_DIR": str(tmp_path / "ck_ref"),
                            "OUT_NPY": ref})
    assert p.returncode == 0, (p.stdout, p.stderr)
    np.testing.assert_array_equal(np.load(out), np.load(ref))


# ---------------------------------------------------------------------------
# elastic: kill on one mesh, resume on another (allclose contract)
# ---------------------------------------------------------------------------
_ELASTIC_CHILD = r"""
import os, numpy as np, jax, jax.numpy as jnp
from repro.core import fd3d, init_parallel_stencil, iterate
from repro.distributed import elastic

ps = init_parallel_stencil(backend="jnp", ndims=3)

@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def kern(T2, T, dt):
    return {"T2": fd3d.inn(T) + dt * (fd3d.d2_xi(T) + fd3d.d2_yi(T)
                                      + fd3d.d2_zi(T))}

n = 18  # interior 16: divides over 1, 2 and 4 ranks (radius 1)
rng = np.random.RandomState(0)
T0 = np.asarray(rng.rand(n, n, n), np.float32)
factors = (int(os.environ["FACTOR"]),)
ck = iterate.Checkpointing(os.environ["CKPT_DIR"], save_every=1,
                           blocking=True)
res = elastic.elastic_solve_until(
    kern, dict(T2=T0, T=T0), dict(dt=1e-3), factors=factors,
    tol=0.0, max_iters=40, exchange=("T",), check_every=4,
    checkpoint=ck)
np.save(os.environ["OUT_NPY"], np.asarray(res.fields["T"]))
print("DONE", int(res.iters), res.resumed_from)
"""


@pytest.mark.distributed
def test_elastic_kill_then_resume_on_shrunk_mesh(tmp_path):
    ck = str(tmp_path / "ck")
    out4, out_ref = str(tmp_path / "o4.npy"), str(tmp_path / "ref.npy")

    # 4-rank run dies at iteration 20 (after the save at 20)
    plan = fault.FaultPlan(kill_at_step=20)
    p = run_proc(_ELASTIC_CHILD, n_devices=4,
                 env_extra={"FACTOR": "4", "CKPT_DIR": ck, "OUT_NPY": out4,
                            fault.PLAN_ENV: plan.to_env()})
    assert p.returncode == fault.KILL_EXIT_CODE, (p.stdout, p.stderr)
    assert CheckpointManager(ck).latest_step() == 20

    # survivors: 2-rank mesh resumes the 4-rank checkpoint to completion
    p = run_proc(_ELASTIC_CHILD, n_devices=2,
                 env_extra={"FACTOR": "2", "CKPT_DIR": ck, "OUT_NPY": out4})
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "DONE 40 20" in p.stdout

    # reference: uninterrupted single-rank run; cross-mesh => allclose
    p = run_proc(_ELASTIC_CHILD, n_devices=1,
                 env_extra={"FACTOR": "1", "CKPT_DIR": str(tmp_path / "cr"),
                            "OUT_NPY": out_ref})
    assert p.returncode == 0, (p.stdout, p.stderr)
    np.testing.assert_allclose(np.load(out4), np.load(out_ref), atol=1e-5)


@pytest.mark.distributed
def test_elastic_resume_on_grown_mesh(tmp_path):
    ck = str(tmp_path / "ck")
    out, out_ref = str(tmp_path / "o.npy"), str(tmp_path / "ref.npy")

    # write a mid-run checkpoint on 2 ranks (capped run, no kill) ...
    code_half = _ELASTIC_CHILD.replace("max_iters=40", "max_iters=20")
    p = run_proc(code_half, n_devices=2,
                 env_extra={"FACTOR": "2", "CKPT_DIR": ck, "OUT_NPY": out})
    assert p.returncode == 0, (p.stdout, p.stderr)

    # ... scale UP: 4 ranks resume it to completion
    p = run_proc(_ELASTIC_CHILD, n_devices=4,
                 env_extra={"FACTOR": "4", "CKPT_DIR": ck, "OUT_NPY": out})
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "DONE 40 20" in p.stdout

    p = run_proc(_ELASTIC_CHILD, n_devices=1,
                 env_extra={"FACTOR": "1", "CKPT_DIR": str(tmp_path / "cr"),
                            "OUT_NPY": out_ref})
    assert p.returncode == 0, (p.stdout, p.stderr)
    np.testing.assert_allclose(np.load(out), np.load(out_ref), atol=1e-5)


# ---------------------------------------------------------------------------
# remesh planning
# ---------------------------------------------------------------------------
def test_plan_factors_shapes():
    from repro.distributed import elastic
    assert elastic.plan_factors(8, 1) == (8,)
    assert elastic.plan_factors(8, 2) == (4, 2)
    assert elastic.plan_factors(7, 2) == (7, 1)
    assert int(np.prod(elastic.plan_factors(12, 3))) == 12


def test_validate_stencil_factors_pointed_errors():
    from repro.distributed import elastic
    elastic.validate_stencil_factors((18, 18, 18), (4,), radius=1)
    with pytest.raises(ValueError, match="does not divide"):
        elastic.validate_stencil_factors((18, 18, 18), (5,), radius=1)
    with pytest.raises(ValueError, match="thinner than the ghost ring"):
        elastic.validate_stencil_factors((12, 12, 12), (8,), radius=2)


def test_decompose_gather_roundtrip(rng):
    from repro.distributed import elastic
    g = np.asarray(rng.rand(18, 10), np.float32)
    st = elastic.decompose_fields({"T": g}, (4,), radius=1)
    assert st["T"].shape[0] == 4
    back = elastic.gather_fields(st, (4,), radius=1)
    np.testing.assert_array_equal(back["T"], g)


# ---------------------------------------------------------------------------
# SIGKILL landing DURING an async CheckpointManager.save (kill_at_io):
# LATEST must stay on the previous good step, the torn in-flight step is
# skipped, and resume is bitwise (the PR-6 edge this pins down)
# ---------------------------------------------------------------------------
_ASYNC_SAVE_KILL_CHILD = r"""
import os, numpy as np, jax.numpy as jnp
from repro.core import fd3d, init_parallel_stencil, iterate

ps = init_parallel_stencil(backend="jnp", ndims=3)

@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def kern(T2, T, dt):
    return {"T2": fd3d.inn(T) + dt * (fd3d.d2_xi(T) + fd3d.d2_yi(T)
                                      + fd3d.d2_zi(T))}

n = 16
T0 = jnp.zeros((n, n, n), jnp.float32).at[n//2, n//2, n//2].set(1.0)
ck = iterate.Checkpointing(os.environ["CKPT_DIR"], save_every=2,
                           blocking=False)   # ASYNC writer thread
res = iterate.solve_until(kern, dict(T2=T0, T=T0), dict(dt=1e-3),
                          tol=0.0, max_iters=60, check_every=5,
                          checkpoint=ck)
np.save(os.environ["OUT_NPY"], np.asarray(res.fields["T"]))
print("DONE", int(res.iters), res.resumed_from)
"""


def _carry_like(n=16):
    z = np.zeros((n, n, n), np.float32)
    return {"fields": {"T": z, "T2": z},
            "reds": {"err": np.float32(0.0)}, "err": np.float32(0.0)}


def test_kill_during_async_save_leaves_latest_good_and_resumes_bitwise(
        tmp_path):
    ck, out = str(tmp_path / "ck"), str(tmp_path / "out.npy")
    ref = str(tmp_path / "ref.npy")
    env = {"CKPT_DIR": ck, "OUT_NPY": out}

    # each save guards 6 I/O ops (4 tensors + manifest + LATEST swap);
    # op 8 is the 2nd tensor write of the SECOND save -> the process
    # dies inside the async writer with step_20 still a .tmp dir
    plan = fault.FaultPlan(kill_at_io=8)
    p = run_proc(_ASYNC_SAVE_KILL_CHILD,
                 env_extra=dict(env, **{fault.PLAN_ENV: plan.to_env()}))
    assert p.returncode == fault.KILL_EXIT_CODE, (p.stdout, p.stderr)
    assert not os.path.exists(out)

    mgr = CheckpointManager(ck)
    assert mgr.latest_step() == 10          # LATEST: previous good step
    assert mgr.list_steps() == [10]         # torn step not listed
    assert os.path.isdir(mgr.step_dir(20) + ".tmp")  # the wreck

    # resume (no plan): picks up from 10 and completes
    p = run_proc(_ASYNC_SAVE_KILL_CHILD, env_extra=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "DONE 60 10" in p.stdout

    # uninterrupted reference in a fresh process: bitwise equal
    p = run_proc(_ASYNC_SAVE_KILL_CHILD,
                 env_extra={"CKPT_DIR": str(tmp_path / "ck_ref"),
                            "OUT_NPY": ref})
    assert p.returncode == 0, (p.stdout, p.stderr)
    np.testing.assert_array_equal(np.load(out), np.load(ref))


def test_torn_inflight_step_promoted_by_storage_is_skipped_corrupt(
        tmp_path):
    """The uglier crash window: the storage layer completed the rename
    and LATEST update but the tensor data never hit the platter (write
    reordering on power cut). restore(step=None) must walk past the torn
    step, record it in skipped_corrupt, and land on the previous good
    one; a checkpointed solve resumes from it bitwise."""
    ck, out = str(tmp_path / "ck"), str(tmp_path / "out.npy")
    ref = str(tmp_path / "ref.npy")
    env = {"CKPT_DIR": ck, "OUT_NPY": out}
    plan = fault.FaultPlan(kill_at_io=8)
    p = run_proc(_ASYNC_SAVE_KILL_CHILD,
                 env_extra=dict(env, **{fault.PLAN_ENV: plan.to_env()}))
    assert p.returncode == fault.KILL_EXIT_CODE, (p.stdout, p.stderr)

    # simulate the reordered-storage outcome: the torn dir appears
    # completed and LATEST names it
    mgr = CheckpointManager(ck)
    os.rename(mgr.step_dir(20) + ".tmp", mgr.step_dir(20))
    with open(os.path.join(ck, "LATEST"), "w") as f:
        f.write(os.path.basename(mgr.step_dir(20)))

    assert mgr.latest_step() == 20
    tree, extra = mgr.restore(_carry_like())
    assert extra["step"] == 10
    assert [s for s, _ in extra["skipped_corrupt"]] == [20]

    # the checkpointed solve takes the same fallback and stays bitwise
    p = run_proc(_ASYNC_SAVE_KILL_CHILD, env_extra=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "DONE 60 10" in p.stdout
    p = run_proc(_ASYNC_SAVE_KILL_CHILD,
                 env_extra={"CKPT_DIR": str(tmp_path / "ck_ref"),
                            "OUT_NPY": ref})
    assert p.returncode == 0, (p.stdout, p.stderr)
    np.testing.assert_array_equal(np.load(out), np.load(ref))


# ---------------- checkpoint ----------------
def test_checkpoint_atomicity_on_partial_write(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    tree = {"w": jnp.asarray(rng.randn(4, 4), jnp.float32)}
    mgr.save(1, tree)
    # simulate a crashed writer: leave a stale tmp dir + torn manifest
    os.makedirs(tmp_path / "step_000000002.tmp")
    with open(tmp_path / "step_000000002.tmp" / "manifest.json", "w") as f:
        f.write('{"truncat')
    restored, extra = mgr.restore({"w": jnp.zeros((4, 4))})
    assert extra["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        mgr.restore({"w": jnp.zeros((5,))})


def test_async_save_error_surfaces(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path))

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(np, "save", boom)
    with pytest.raises((RuntimeError, OSError)):
        mgr.save(1, {"w": jnp.zeros((2,))}, blocking=False)
        mgr.wait()


# ---------------- fault / stragglers ----------------
def test_straggler_and_dead_detection(tmp_path):
    mons = [fault.StepMonitor(host_id=i, heartbeat_dir=str(tmp_path),
                              straggler_factor=1.5, timeout_s=100)
            for i in range(4)]
    now = time.time()
    for i, m in enumerate(mons):
        for step in range(5):
            m.record(step, 1.0 if i != 2 else 3.0)  # host 2 is slow
    health = mons[0].check_peers()
    assert health["stragglers"] == [2]
    assert health["dead"] == []
    # host 3 goes silent
    data = json.load(open(tmp_path / "host_3.json"))
    data["t"] = now - 1000
    json.dump(data, open(tmp_path / "host_3.json", "w"))
    health = mons[0].check_peers()
    assert 3 in health["dead"]


def test_retry_recovers():
    calls = {"n": 0}
    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return 42
    assert fault.retry(flaky, attempts=5, backoff_s=0.0) == 42
