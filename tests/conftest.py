import os
import sys

# Tests must see exactly ONE device. Multi-device tests spawn
# subprocesses with their own XLA_FLAGS (``run_subprocess`` below).
os.environ.setdefault("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"

# Make the example solvers importable as `examples.<name>` (they are
# library modules with a thin CLI; tests drive their step()/solve() APIs).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

SEED = 20260714


@pytest.fixture()
def rng():
    return np.random.RandomState(SEED)


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run a python snippet with N fake devices; returns stdout, asserts rc=0."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"subprocess failed:\n{p.stdout}\n{p.stderr}"
    return p.stdout
