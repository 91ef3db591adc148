#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and read the
program's own spans and compile counts beside the device trace
(``yardstick/spans.py``). The benchmark's own runs never run this.

    python3 bench/traced.py --workload <name> --seed <n> --seconds <s> \
        [--collector 0|1] [--rehearse N]

With ``--collector 1`` (the default) the program's in-memory telemetry
collector (``repro.telemetry.configure(None)``) is on from process start,
so its spans land in the trace and its compile counts cover set-up; with
``--collector 0`` the run is the benchmark's traced run, for the cost of
the collector. ``--rehearse N`` runs on the CPU at ``N`` points per chip
and axis. The last line of standard output is the traced run's result
with ``program`` added:

* ``mlups``: where the cell reports it, from the traced window's steps;
* ``host_dispatch_ms``: median ``stencil.dispatch`` span in the window;
* ``dispatch_idle_share``: percent of the window in which chip 0 is idle
  while the window's thread is inside ``stencil.dispatch``;
* ``setup_compile_s`` and ``setup_compiles``: compile seconds from
  process start to the end of set-up, in all and by function and phase;
  ``later_compiles`` the same after set-up (the window, the reference);
* ``idle_gaps``: chip 0's idle time labelled by program span;
* ``device_names``: for each program name (``parallel:<name>`` from a
  launch's ``kernel_metadata``, the ``halo.exchange`` and ``solve.check``
  scopes), how many device operations in the window carry it in their
  text or statistics, and one such operation.
"""
import time

T_WALL, T_START = time.time(), time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

# what the program names on the device: each Pallas launch's
# ``kernel_metadata={"parallel":"<@parallel name>"}`` (printed over three
# lines), and the two name scopes
LAUNCH = re.compile(r'kernel_metadata=\{\s*"parallel":"([^"]*)"')
SCOPES = ("halo.exchange", "solve.check")


def device_names(data):
    """Device operations in the window that carry each program name."""
    from yardstick import trace

    win = trace._window(data)
    found: dict = {}
    for pl in data["planes"]:
        if not trace.DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            for name, s, d, stats in ln["events"]:
                if win and not (win[0] <= s and s + d <= win[1]):
                    continue
                text = name + " " + " ".join(f"{k}={v}" for k, v in
                                             stats.items())
                keys = [k for k in SCOPES if k in text]
                keys += [f"parallel:{m}" for m in LAUNCH.findall(text)]
                for key in keys:
                        hit = found.setdefault(key, {"ops": 0})
                        hit["ops"] += 1
                        hit.setdefault("example", {
                            "plane": pl["name"], "line": ln["name"],
                            "name": name[:400] + (" ..." if len(name) > 400
                                                  else "") + name[-200:],
                            "stats": {k: str(v)[:300]
                                      for k, v in stats.items()}})
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--collector", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", type=int)
    args = ap.parse_args(argv)

    col = None
    if args.collector:
        from repro import telemetry

        col = telemetry.configure(None)

    import harness
    from repro.telemetry import report
    from yardstick import spans, trace

    kept = {}
    reduce = trace.reduce

    def reduce_and_keep(src, device_ids=None):
        kept["data"] = trace.load(src)
        return reduce(kept["data"], device_ids)

    trace.reduce = reduce_and_keep
    try:
        res = harness.run(args.workload, args.seed, args.seconds, True,
                          t_start=T_START, allow_cpu=args.rehearse is not None,
                          rehearse_n=args.rehearse)
    except harness.Refused as e:
        print(f"traced: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        trace.reduce = reduce
    data = kept["data"]
    records = col.records if col is not None else []
    spec = harness.find_cell(args.workload)
    cfg = spec["cfg"]
    if args.rehearse is not None:
        cfg = harness.shrink(cfg, args.rehearse)
    program = {}
    if any(m["name"] == "mlups" for m in spec["end_to_end"]):
        ring = harness.load_module("references", cfg["reference"]).RING
        program["mlups"] = harness.load_module("metrics", "mlups").read(
            types.SimpleNamespace(run=res["run"], cfg=cfg, ring=ring))
    until = T_WALL + res["run"]["setup_s"]
    names = {r["name"] for r in records if r.get("kind") == "span"}
    program.update(
        host_dispatch_ms=spans.dispatch_ms(data),
        dispatch_idle_share=spans.idle_share_under(data),
        setup_compile_s=spans.setup_compile_s(records, until),
        setup_compiles=report.compile_summary(records, until)[:12],
        later_compiles=report.compile_summary(
            [r for r in records if r.get("ts", 0.0) > until]),
        span_names=sorted(names),
        idle_gaps=(spans.program_gaps(data, names) or [])[:10],
        device_names=device_names(data))
    res["program"] = program
    res["collector"] = bool(args.collector)
    harness.report(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
