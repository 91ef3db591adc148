"""Generate the EXPERIMENTS.md roofline/dry-run tables from results/dryrun.

    PYTHONPATH=src python -m benchmarks.report > /tmp/tables.md

``--telemetry RUN.jsonl`` appends the per-phase timing and error-
trajectory tables of an instrumented run (a ``REPRO_TELEMETRY=`` JSONL
log) to the report — solve wall time split by span, the convergence
trajectory harvested at chunk boundaries, and compile time by function
and phase.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

HW = "TPU v5e: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link ICI"


def load(dirname="results/dryrun"):
    recs = {}
    for f in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        r = json.load(open(f))
        key = (r["arch"], r["shape"], r["mesh"])
        recs[key] = r
    return recs


def fmt_s(x):
    if x is None:
        return "-"
    if x >= 1:
        return f"{x:.2f}s"
    return f"{x*1e3:.2f}ms"


def dominant(r):
    ro = r["roofline"]
    terms = {"compute": ro["t_compute"],
             "memory": r.get("t_memory_analytic", ro["t_memory"]),
             "collective": ro["t_collective"]}
    dom = max(terms, key=terms.get)
    # roofline fraction: dominant ideal time / sum of all terms (serial
    # bound; overlap can push the achieved time toward the dominant term)
    tot = sum(terms.values())
    frac = terms[dom] / tot if tot else 0.0
    return dom, terms, frac


def roofline_table(recs, mesh="16x16"):
    lines = [
        f"| arch | shape | mode | t_compute | t_memory (A_eff) | t_collective | dominant | roofline frac | MODEL/HLO flops | fits HBM |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for (arch, shape, m), r in sorted(recs.items()):
        if m != mesh:
            continue
        if not r.get("runnable", True):
            lines.append(f"| {arch} | {shape} | - | - | - | - | skip | - | - | {r.get('skip_reason','')[:40]} |")
            continue
        if "error" in r:
            lines.append(f"| {arch} | {shape} | - | ERROR | | | | | | |")
            continue
        dom, terms, frac = dominant(r)
        mem = r.get("memory", {})
        temp = mem.get("temp_size_in_bytes", 0) / 2**30
        args = mem.get("argument_size_in_bytes", 0) / 2**30
        tot = temp + args
        # XLA-CPU promotes bf16 buffers to f32 (~2x inflation vs TPU-native
        # bf16); cells in the 16.5..33 G band fit on the real device.
        fits = ("yes" if tot <= 16.5 else
                f"yes† ({tot:.0f}G cpu-f32)" if tot <= 33.0 else
                f"NO ({tot:.0f}G)")
        ur = r["roofline"].get("useful_ratio", 0)
        lines.append(
            f"| {arch} | {shape} | {r['mode']} | {fmt_s(terms['compute'])} "
            f"| {fmt_s(terms['memory'])} | {fmt_s(terms['collective'])} "
            f"| {dom} | {frac:.2f} | {ur:.2f} | {fits} |")
    return "\n".join(lines)


def dryrun_table(recs):
    lines = [
        "| arch | shape | mesh | compile | per-dev HLO FLOPs | per-dev bytes (HLO walk) | collective wire bytes | collectives |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for (arch, shape, m), r in sorted(recs.items()):
        if not r.get("runnable", True):
            continue
        if "error" in r:
            lines.append(f"| {arch} | {shape} | {m} | ERROR | | | | |")
            continue
        ro = r["roofline"]
        cc = r["collectives"]["counts"]
        ccs = " ".join(f"{k.split('-')[-1][:6]}:{v}" for k, v in sorted(cc.items()))
        lines.append(
            f"| {arch} | {shape} | {m} | {r['compile_s']}s | {ro['flops']:.2e} "
            f"| {ro['hbm_bytes']:.2e} | {ro['wire_bytes']:.2e} | {ccs} |")
    return "\n".join(lines)


def telemetry_tables(log_path: str) -> str:
    """Per-phase timing + error-trajectory + compile tables rendered
    from an instrumented run's JSONL log (same aggregation as
    ``python -m repro.telemetry.report``, embedded in this report)."""
    from repro.telemetry import report as trep, schema as tschema

    records = tschema.load_records(log_path)
    parts = [f"<!-- telemetry: {len(records)} records from {log_path} -->"]
    for title, rows, cols in (
        ("Per-phase timing (telemetry spans)", trep.phase_summary(records),
         ["phase", "count", "total_s", "mean_s", "p50_s", "p90_s", "max_s"]),
        ("Error trajectory (chunk-boundary harvest)",
         trep.error_trajectory(records), ["iters", "err", "per_step_s"]),
        ("Compiles (jit.compiles / jit.compile_s)",
         trep.compile_summary(records),
         ["fn", "phase", "compiles", "seconds"]),
    ):
        t = trep.format_table(rows, cols, title)
        if t:
            parts.append("\n" + t)
    return "\n".join(parts)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--telemetry", metavar="RUN.jsonl", default=None,
                    help="append per-phase/error-trajectory tables from an "
                         "instrumented run's telemetry JSONL log")
    args = ap.parse_args(argv)
    recs = load()
    n_ok = sum(1 for r in recs.values() if r.get("runnable") and "error" not in r)
    n_skip = sum(1 for r in recs.values() if not r.get("runnable", True))
    n_err = sum(1 for r in recs.values() if "error" in r)
    print(f"<!-- {len(recs)} cells: {n_ok} compiled, {n_skip} spec-skips, {n_err} errors -->")
    print("\n## Single-pod (16x16 = 256 chips) roofline\n")
    print(roofline_table(recs, "16x16"))
    print("\n## Multi-pod (2x16x16 = 512 chips) dry-run\n")
    print(dryrun_table({k: v for k, v in recs.items() if k[2] == "2x16x16"}))
    if args.telemetry:
        print("\n" + telemetry_tables(args.telemetry))


if __name__ == "__main__":
    main()
