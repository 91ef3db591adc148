#!/usr/bin/env python3
"""Rehearse every cell without the chip.

    python3 bench/rehearse.py [--n 16] [--seconds 1] [--workload NAME ...]
                              [--no-compile]

1. Runs each cell end to end on the CPU at ``n`` interior points per chip
   and axis, Pallas in interpret mode, the four-chip cell on four virtual
   CPU devices, with ``--trace 0`` and ``--trace 1``: paths, control flow
   and the comparison with the reference. Rehearsal readings are never
   results: nothing here is a device metric.
2. Compiles each cell's timed call (its entry's ``advance`` or
   ``solve``, under ``jax.jit``) at its real size for a described TPU v5e
   (``v5e:2x2``), and the plain reference's chunk of steps, and prints
   each program's bytes per device and whether it holds a Pallas kernel.

Exits 0 when every step passed.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402


def rehearse_runs(names, n, seconds) -> bool:
    import harness

    ok = True
    for name in names:
        for trace in (False, True):
            res = harness.run(name, 2 ** 33 + 17, seconds, trace,
                              t_start=time.perf_counter(), allow_cpu=True,
                              rehearse_n=n)
            line = {"workload": name, "trace": int(trace), "n": n,
                    "correct": res["correct"],
                    "metrics": sorted(res["metrics"]),
                    "compared": res["compared"],
                    "compiles_in_window": res["run"]["compiles_in_window"]}
            print(json.dumps(line), flush=True)
            ok = ok and res["correct"] and not res["run"]["compiles_in_window"]
    return ok


def compile_for_v5e(names) -> bool:
    import jax
    from jax.experimental import topologies

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ok = True
    for name in names:
        spec = harness.find_cell(name)
        cfg, traffic = spec["cfg"], spec["traffic"]
        ref = harness.load_module("references", cfg["reference"])
        system = harness.load_module("systems", cfg["system"])
        devs = topo.devices[:spec["cell"]["chips"]]
        ctx = harness.Context(cfg=cfg, traffic=traffic, seed=0, ref=ref,
                              p=ref.params(cfg, 0), interpret=False,
                              devices=devs, abstract=True)
        entry = getattr(system, traffic["entry"])(ctx)
        call = getattr(entry, "advance", None) or entry.solve
        programs = [(traffic["entry"], jax.jit(call).lower(entry.state)),
                    ("reference", _reference_lowered(ctx, devs))]
        for label, lowered in programs:
            t0 = time.perf_counter()
            try:
                c = lowered.compile()
            except Exception as e:     # what the chip's compiler refuses
                ok = False
                print(json.dumps({"workload": name, "program": label,
                                  "error": str(e)[:2000]}), flush=True)
                continue
            m = c.memory_analysis()
            per_dev = (m.argument_size_in_bytes + m.output_size_in_bytes
                       + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(json.dumps({
                "workload": name, "program": label,
                "compile_s": time.perf_counter() - t0,
                "bytes_per_device": per_dev,
                "tpu_custom_call": "tpu_custom_call" in c.as_text()}),
                flush=True)
    return ok


def _reference_lowered(ctx, devs):
    """The reference's chunk of steps over the whole grid, sharded over
    the cell's chips as the run shards it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from yardstick import compare

    mesh_shape = tuple(ctx.cfg["mesh"])
    if len(devs) == 1:
        sh = SingleDeviceSharding(devs[0])
    else:
        axes = ("x", "y", "z")[:len(mesh_shape)]
        sh = NamedSharding(Mesh(np.array(devs).reshape(mesh_shape), axes),
                           P(*axes))
    state = {k: jax.ShapeDtypeStruct(tuple(ctx.cfg["grid"]), jnp.float32,
                                     sharding=sh) for k in ctx.ref.STATE}
    plain = compare.Reference(ctx.ref, ctx.cfg, ctx.p)
    return plain.program(compare.CHUNK).lower(state, ctx.p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--workload", action="append")
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args(argv)

    import harness

    names = args.workload or list(harness.cells())
    ok = rehearse_runs(names, args.n, args.seconds)
    if not args.no_compile:
        ok = compile_for_v5e(names) and ok
    print("rehearsal " + ("passed" if ok else "FAILED"), file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
