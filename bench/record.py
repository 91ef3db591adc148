#!/usr/bin/env python3
"""Record the small traces ``tests/test_spans.py`` reads, on one chip,
with the program's in-memory telemetry collector on: ten eager Fig. 1
``@parallel`` calls at 512^3 inside a ``bench.window`` annotation
(``fig1``), and one ``solve_until`` of the ``fig1-512-solve`` traffic
(``solve``), each as ``yardstick.trace.load`` keeps it, with the names of
the spans the program recorded. The benchmark's own runs never run this.

    python3 bench/record.py [--out tests/data/recorded_spans.json] \
        [--rehearse N]

``--rehearse N`` runs on the CPU at ``N`` points per axis (a check of the
script, not data for the tests).
"""
import argparse
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

LAUNCHES = 10


def device_stats(tdir):
    """Every statistic a device operation carries in the trace, with one
    value of each: where a name scope or kernel name could show."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    seen = {}
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/device:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                for k, v in e.stats:
                    seen.setdefault(f"{ln.name}/{k}", str(v)[:300])
    return seen


def record(harness, trace, col, workload, rehearse, seed=2 ** 31 + 11):
    """One cell's entry, warmed, then a short recorded window: ten eager
    steps, or one solve."""
    import jax

    spec = harness.find_cell(workload)
    cfg, traffic = spec["cfg"], spec["traffic"]
    if rehearse is not None:
        cfg = harness.shrink(cfg, rehearse)
    devs = harness.devices_for(1, allow_cpu=rehearse is not None)
    ref = harness.load_module("references", cfg["reference"])
    system = harness.load_module("systems", cfg["system"])
    ctx = harness.Context(cfg=cfg, traffic=traffic, seed=seed, ref=ref,
                          p=ref.params(cfg, seed), interpret=rehearse
                          is not None, devices=devs, abstract=False)
    entry = getattr(system, traffic["entry"])(ctx)
    if hasattr(entry, "advance"):
        def go(state):
            for _ in range(LAUNCHES):
                state = entry.advance(state)
            return state
    else:
        def go(state):
            fields, iters, _ = entry.solve(state)
            int(iters)
            return fields
    jax.block_until_ready(go(entry.state))          # compile and warm
    tdir = os.path.join(harness.OUT, "record", workload)
    shutil.rmtree(tdir, ignore_errors=True)
    try:
        with trace.recording(tdir):
            jax.block_until_ready(go(entry.state))
        data = trace.load(tdir)
        data["device_stats"] = device_stats(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    data["spans"] = sorted({r["name"] for r in col.records
                            if r.get("kind") == "span"})
    return data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(BENCH, "tests", "data",
                                                  "recorded_spans.json"))
    ap.add_argument("--rehearse", type=int)
    args = ap.parse_args(argv)

    from repro import telemetry

    col = telemetry.configure(None)
    import harness
    from yardstick import trace

    out = {name: record(harness, trace, col, cell, args.rehearse)
           for name, cell in (("fig1", "fig1-512-steps"),
                              ("solve", "fig1-512-solve"))}
    with open(args.out, "w") as f:
        json.dump(out, f, separators=(",", ":"))
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "spans": {k: v["spans"] for k, v in out.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
