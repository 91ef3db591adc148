"""True multi-process operation: rendezvous bounds, run-id heartbeats,
the gang supervisor's kill/replan/resume loop, and the multi-process
serving pool.

Every ``@pytest.mark.multihost`` test here launches REAL OS processes
joined by ``jax.distributed`` over gloo CPU collectives — no simulated
devices on these paths. The acceptance scenario: a 4-process
``elastic_solve_until`` loses one rank to SIGKILL mid-solve; the
supervisor detects the exit, terminates the wedged stragglers, re-plans
the world to the largest grid-compatible size (4 -> 2, because 3 does
not divide the interior) and resumes from the last global checkpoint —
allclose to the uninterrupted 1-process reference.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.distributed import elastic, fault
from repro.launch import multihost

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(code: str, env_extra: dict | None = None,
              timeout: int = 180) -> subprocess.CompletedProcess:
    """One real single-device process (no fake-device XLA flags)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    env.pop(fault.PLAN_ENV, None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)


@pytest.mark.parametrize("envs,shared", [
    ([{}, {}], True),                                  # both default to TPU
    ([{"JAX_PLATFORMS": "tpu"}, {"TPU_VISIBLE_CHIPS": "0"}], True),
    ([{"TPU_VISIBLE_CHIPS": "0,1"}, {"TPU_VISIBLE_CHIPS": "1"}], True),
    ([{"TPU_VISIBLE_CHIPS": "0"}, {"TPU_VISIBLE_CHIPS": "1"}], False),
    ([{"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": "cpu"}], False),
    ([{}], False),
])
def test_one_process_per_chip(monkeypatch, envs, shared):
    """On a host with TPU chips, children that would open the same chip
    are refused before any is started; CPU children and children bound
    to disjoint chips pass."""
    from jax._src import hardware_utils
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (4, None))
    if shared:
        with pytest.raises(RuntimeError, match="a chip belongs to one"):
            multihost.check_one_process_per_chip(envs, "gang")
    else:
        multihost.check_one_process_per_chip(envs, "gang")
    # a host without chips never refuses
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (0, None))
    multihost.check_one_process_per_chip(envs, "gang")


# ---------------------------------------------------------------------------
# run-id heartbeat namespacing + stale retirement (satellite 1)
# ---------------------------------------------------------------------------
def test_heartbeat_run_id_namespacing(tmp_path):
    d = str(tmp_path)
    old = fault.Heartbeat(d, rank=0, run_id="dead-run")
    old.bump(99)
    new = fault.Heartbeat(d, rank=1, run_id="live-run")
    new.bump(5)
    # a fresh run NEVER reads the previous run's liveness
    assert list(new.read_all()) == [1]
    assert old.read_all()[0]["run_id"] == "dead-run"
    # legacy un-namespaced heartbeats are invisible to namespaced readers
    fault.Heartbeat(d, rank=2).bump(1)
    assert list(new.read_all()) == [1]


def test_heartbeat_retire_stale(tmp_path):
    d = str(tmp_path)
    fault.Heartbeat(d, rank=0, run_id="a").bump(1)
    fault.Heartbeat(d, rank=1, run_id="b").bump(1)
    fault.Heartbeat(d, rank=2).bump(1)          # legacy, no namespace
    retired = fault.Heartbeat.retire_stale(d, keep_run_id="b")
    assert retired == ["a.host_0.json", "host_2.json"]
    assert os.path.exists(os.path.join(d, "b.host_1.json"))
    # retire everything: a fresh launcher start
    assert fault.Heartbeat.retire_stale(d) == ["b.host_1.json"]
    assert fault.Heartbeat.retire_stale(d) == []


def test_dead_rank_detection_ignores_foreign_run(tmp_path):
    d = str(tmp_path)
    fault.Heartbeat(d, rank=0, run_id="old").bump(1)   # fresh file, old run
    hb = fault.Heartbeat(d, rank=1, run_id="new", timeout_s=10.0)
    hb.bump(1)
    # rank 0 of THIS run never beat: dead despite the old run's file
    assert hb.dead_ranks(expected=[0, 1]) == [0]


def test_monitor_run_id_passthrough(tmp_path):
    mon = fault.StepMonitor(host_id=3, heartbeat_dir=str(tmp_path),
                            run_id="r7", timeout_s=5.0)
    mon.record(1, 0.01)
    assert os.path.exists(os.path.join(str(tmp_path), "r7.host_3.json"))
    assert mon.check_peers()["dead"] == []


# ---------------------------------------------------------------------------
# rendezvous failure modes are BOUNDED (never hang)
# ---------------------------------------------------------------------------
_RDV_CHILD = r"""
import sys
from repro.launch import multihost
try:
    multihost.initialize(coordinator={coord!r}, num_processes=2,
                         process_id={rank}, timeout_s={timeout},
                         attempts={attempts})
except multihost.RendezvousError as e:
    print("RENDEZVOUS_ERROR:", e)
    sys.exit(7)
print("JOINED")
"""


@pytest.mark.multihost
def test_rendezvous_coordinator_down_is_pointed_not_a_hang():
    # nothing listens on this port: the non-coordinator rank must fail
    # with a pointed error within its bounded budget
    port = multihost.free_port()
    t0 = time.monotonic()
    p = run_child(_RDV_CHILD.format(coord=f"127.0.0.1:{port}", rank=1,
                                    timeout=5, attempts=2), timeout=120)
    took = time.monotonic() - t0
    assert p.returncode == 7, (p.stdout, p.stderr)
    assert "RENDEZVOUS_ERROR" in p.stdout
    assert "coordinator" in p.stdout and "127.0.0.1" in p.stdout
    assert took < 90, f"rendezvous failure took {took:.0f}s — not bounded"


@pytest.mark.multihost
def test_rendezvous_slow_joiner_is_time_bounded():
    # rank 0 brings up the coordinator and waits for a rank 1 that never
    # arrives. XLA's distributed client terminates the process with
    # LOG(FATAL) on the register deadline — no Python exception to
    # convert — so the contract here is a TIME-BOUNDED death that the
    # Supervisor turns into a replan/restart (see the mid-init test)
    t0 = time.monotonic()
    p = run_child(_RDV_CHILD.format(coord=multihost.default_coordinator(),
                                    rank=0, timeout=5, attempts=1),
                  timeout=120)
    took = time.monotonic() - t0
    assert p.returncode != 0
    assert "JOINED" not in p.stdout
    assert "DEADLINE_EXCEEDED" in p.stderr or "Deadline Exceeded" in p.stderr
    assert took < 90, f"slow-joiner wait took {took:.0f}s — not bounded"


def test_initialize_single_process_shortcut_and_config_errors():
    ctx = multihost.initialize()          # no world configured: a no-op
    assert (ctx.rank, ctx.world) == (0, 1)
    with pytest.raises(multihost.RendezvousError, match="incomplete"):
        multihost.initialize(coordinator="127.0.0.1:1", num_processes=4)


# ---------------------------------------------------------------------------
# THE acceptance scenario: 4 real processes, SIGKILL one, replan, resume
# ---------------------------------------------------------------------------
@pytest.mark.multihost
@pytest.mark.distributed
def test_four_process_kill_replan_resume_allclose(tmp_path):
    work = str(tmp_path / "gang")
    sup = multihost.demo_supervisor(
        4, work, kill_rank=1, kill_at=20, heartbeat_timeout_s=30.0,
        attempt_deadline_s=150.0, run_id="accept", verbose=False)
    out = sup.run()

    # one planned death, one restart, world re-planned 4 -> 2 (3 does
    # not divide the interior-16 grid)
    assert out.exit_codes[0] == fault.KILL_EXIT_CODE
    assert out.exit_codes[-1] == 0
    assert out.restarts == 1
    assert out.final_world == 2
    assert out.reports[0].exit_codes[1] == fault.KILL_EXIT_CODE
    assert "rank(s) [1] exited" in out.reports[0].reason

    # attempt 1 resumed from the last global checkpoint, not iteration 0
    log0 = os.path.join(work, "hb", "accept-a1.rank0.log")
    with open(log0) as f:
        tail = f.read()
    assert "resumed_from=20" in tail, tail

    # uninterrupted 1-process reference: allclose (cross-mesh contract)
    ref_work = str(tmp_path / "ref")
    ref = multihost.demo_supervisor(1, ref_work, run_id="ref",
                                    verbose=False).run()
    assert ref.exit_codes == [0]
    np.testing.assert_allclose(
        np.load(os.path.join(work, "out.npy")),
        np.load(os.path.join(ref_work, "out.npy")), atol=1e-5)


@pytest.mark.multihost
@pytest.mark.distributed
def test_mid_init_death_triggers_supervised_restart(tmp_path):
    # rank 1 dies ENTERING the rendezvous; rank 0's init times out; the
    # supervisor catches the planned exit, replans to 1 and completes —
    # all within the configured bounds
    work = str(tmp_path / "gang")
    sup = multihost.demo_supervisor(
        2, work, kill_rank=1, kill_at_rendezvous=1,
        rendezvous_timeout_s=10.0, attempt_deadline_s=120.0,
        run_id="midinit", verbose=False)
    t0 = time.monotonic()
    out = sup.run()
    took = time.monotonic() - t0
    assert out.exit_codes[0] == fault.KILL_EXIT_CODE
    assert out.exit_codes[-1] == 0
    assert out.final_world == 1
    assert took < 150, f"supervised restart took {took:.0f}s"
    assert os.path.exists(os.path.join(work, "out.npy"))


def test_supervisor_replan_respects_divisibility():
    # interior 16 (n=18, r=1): 4 -> 2, never 3
    assert elastic.plan_compatible((18, 18, 18), 1, 3) == (2, (2,))
    assert elastic.plan_compatible((18, 18, 18), 1, 4) == (4, (4,))
    with pytest.raises(ValueError, match="thinner than one ghost ring"):
        elastic.plan_compatible((3, 3, 3), 2, 4)
    with pytest.raises(ValueError, match="largest compatible world"):
        multihost.demo_supervisor(3, "/tmp/never-used")


# ---------------------------------------------------------------------------
# multi-process serving pool: worker death recovers claims, loses nothing
# ---------------------------------------------------------------------------
@pytest.mark.multihost
@pytest.mark.distributed
def test_process_pool_survives_worker_kills(tmp_path):
    from repro.core import iterate
    from repro.serve.pool import ProcessWorkerPool
    from repro.serve.procworker import demo_kernel

    n = 10
    rng = np.random.RandomState(3)
    inits = [np.asarray(rng.rand(n, n, n), np.float32) for _ in range(4)]

    # every first-generation worker dies after ONE served request; the
    # pool must recover the claims and respawn until all four resolve
    plan = fault.FaultPlan(kill_worker_after=1)
    pool = ProcessWorkerPool(
        str(tmp_path / "spool"), workers=2, heartbeat_timeout_s=60.0,
        max_worker_restarts=4, env={fault.PLAN_ENV: plan.to_env()})
    with pool:
        tickets = [pool.submit({"T2": a, "T": a}, {"dt": 1e-3},
                               tol=0.0, max_iters=8, check_every=4)
                   for a in inits]
        results = [t.result(timeout=150.0) for t in tickets]
    assert pool.restarts >= 1

    kern = demo_kernel()
    for a, (fields, meta) in zip(inits, results):
        ref = iterate.solve_until(kern, {"T2": a, "T": a}, {"dt": 1e-3},
                                  tol=0.0, max_iters=8, check_every=4)
        assert meta["iters"] == 8
        np.testing.assert_allclose(fields["T"], np.asarray(ref.fields["T"]),
                                   atol=1e-6)


@pytest.mark.multihost
@pytest.mark.distributed
def test_process_pool_recovers_wedged_worker_without_kill_loop(tmp_path):
    """The stale-heartbeat recovery path (not exit codes): a worker that
    WEDGES — alive but never bumping again — is SIGKILLed and its
    heartbeat file retired before the respawn. The replacement needs
    seconds of startup before its first bump; the dead incarnation's
    leftover file (still older than the timeout) must not condemn it,
    or the watcher kill-loops replacements until the restart budget is
    gone and the backlog is abandoned."""
    from repro.core import iterate
    from repro.serve.pool import ProcessWorkerPool
    from repro.serve.procworker import demo_kernel

    n = 10
    rng = np.random.RandomState(7)
    inits = [np.asarray(rng.rand(n, n, n), np.float32) for _ in range(3)]

    # the single first-generation worker serves ONE request, then wedges
    plan = fault.FaultPlan(wedge_worker_after=1)
    pool = ProcessWorkerPool(
        str(tmp_path / "spool"), workers=1, heartbeat_timeout_s=10.0,
        max_worker_restarts=2, env={fault.PLAN_ENV: plan.to_env()})
    with pool:
        tickets = [pool.submit({"T2": a, "T": a}, {"dt": 1e-3},
                               tol=0.0, max_iters=8, check_every=4)
                   for a in inits]
        results = [t.result(timeout=150.0) for t in tickets]
    assert pool.restarts >= 1
    assert not pool.failed, "replacement was kill-looped by the stale file"

    kern = demo_kernel()
    for a, (fields, meta) in zip(inits, results):
        ref = iterate.solve_until(kern, {"T2": a, "T": a}, {"dt": 1e-3},
                                  tol=0.0, max_iters=8, check_every=4)
        assert meta["iters"] == 8
        np.testing.assert_allclose(fields["T"], np.asarray(ref.fields["T"]),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# launcher CLI (the README runbook path)
# ---------------------------------------------------------------------------
@pytest.mark.multihost
@pytest.mark.distributed
def test_cli_demo_smoke(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = ""
    env["PYTHONPATH"] = SRC
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.multihost", "--demo",
         "--world", "2", "--workdir", str(tmp_path / "w"),
         "--max-iters", "8", "--deadline", "120"],
        capture_output=True, text=True, timeout=180, env=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    lines = p.stdout.splitlines()
    report = json.loads("\n".join(lines[lines.index("{"):]))
    assert report["restarts"] == 0 and report["final_world"] == 2
