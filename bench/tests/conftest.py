"""The benchmark's own tests run on the CPU, Pallas in interpret mode,
with four virtual devices for the four-chip cell.

    python -m pytest bench/tests

Besides ``BENCHMARK.json``'s cells they cover the four-chip cell
``fig1-512x4-weak``, built but not yet run on the chip: its entries, in
``data/fig1-512x4-weak.json``, are added to what the harness reads here.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

with open(os.path.join(os.path.dirname(__file__), "data",
                       "fig1-512x4-weak.json")) as f:
    UNPROVEN = json.load(f)
_read = harness.benchmark


def _with_unproven():
    bench = _read()
    if any(w["name"] == "fig1-512x4-weak" for w in bench["workloads"]):
        return bench
    for key in ("configs", "workloads", "per_layer"):
        bench[key] = bench[key] + UNPROVEN[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in UNPROVEN["reported_by"]:
            m["workloads"] = m["workloads"] + ["fig1-512x4-weak"]
    return bench


harness.benchmark = _with_unproven
