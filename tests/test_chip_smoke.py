"""``chip_smoke.py`` off the chip: it refuses to report without a TPU,
and its phases run end to end at a tiny size in interpret mode
(``--rehearse``), which exits 3 and never prints the ``ok`` line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def run(args, cache, cwd=ROOT, n_devices=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}"
                        if n_devices else "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    return subprocess.run([sys.executable, os.path.basename(args[0])]
                          + args[1:], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def records(stdout):
    return [json.loads(line) for line in stdout.splitlines()
            if line.startswith("{")]


def test_refuses_without_a_tpu(tmp_path):
    p = run([SCRIPT], tmp_path)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert '"ok": true' not in p.stdout


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    p = run([SCRIPT], tmp_path, cwd=str(tmp_path))
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


@pytest.mark.parametrize("args,n_devices,phases", [
    (["--rehearse", "16"], None, ["a", "b", "c", "d"]),
    (["--chips", "4", "--rehearse", "8"], 4, ["4chips"]),
])
def test_rehearsal_runs_every_phase(tmp_path, args, n_devices, phases):
    p = run([SCRIPT] + args, tmp_path, n_devices=n_devices)
    assert p.returncode == 3, p.stderr[-2000:]
    recs = [r for r in records(p.stdout) if "phase" in r]
    assert [r["phase"] for r in recs] == phases
    assert all(r["ok"] and r["max_err"] <= r["bound"] for r in recs)
    assert '"ok": true, "device"' not in p.stdout
