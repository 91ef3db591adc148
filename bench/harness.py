"""Run one benchmark cell once and print its result line.

Everything is found by name: the cell in ``BENCHMARK.json``; its
configuration in ``configs/<name>.json``, whose ``system`` names the
adapter to the program (``systems/<name>.py``) and whose ``reference``
names the plain reference (``references/<name>.py``); its traffic mix in
``traffic/<name>.json``, whose ``driver`` names the loop the window runs
and the comparison after it (``drivers/<name>.py``) and whose ``entry``
names the adapter's entry point; each metric's reader, end-to-end and
per-layer alike, in ``metrics/<name>.py``; the limits of the comparison
in ``limits/<cell>.json``.

A run: check the devices, build the state from the seed on the device,
warm every program the window runs (set-up ends here), run the window
(traced with ``--trace 1``), read the memory peak, free the program's
state, replay the reference and compare, print the compared numbers on
standard error and the result as the last line of standard output.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "_out")


class Refused(Exception):
    """The run cannot measure here (no chip, too few chips, unknown
    device): exit non-zero and print no result."""


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> dict:
    """Every cell of ``BENCHMARK.json`` by name."""
    return {w["name"]: w for w in benchmark()["workloads"]}


def find_cell(workload: str) -> dict:
    """The cell, its configuration and traffic, and the metrics it
    reports (``BENCHMARK.json``'s, where a metric lists the cell or lists
    no cells)."""
    bench = benchmark()
    cells_ = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells_:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells_)}")
    cell = cells_[workload]

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in names and reports(m)]
    return {"cell": cell, "cfg": load_json("configs", cell["config"]),
            "traffic": load_json("traffic", cell["traffic"]),
            "end_to_end": e2e, "per_layer": layer}


def shrink(cfg: dict, n: int) -> dict:
    """The configuration at ``n`` interior points per chip and axis, for
    rehearsals on the CPU; everything else as configured."""
    ring = load_module("references", cfg["reference"]).RING
    return dict(cfg, grid=[f * n + 2 * ring if f == 1 else f * n + 2
                           for f in cfg["mesh"]])


class Context(types.SimpleNamespace):
    """What an adapter's entry needs: configuration, traffic, the seed's
    parameters, the devices and the initial state built from them. With
    ``abstract`` set (a compile for described devices) the state is only
    shapes and shardings."""

    def initial(self):
        import jax

        def build():
            return self.ref.initial(self.cfg, self.p, tuple(self.cfg["grid"]))

        if not self.abstract:
            return build()
        one = jax.sharding.SingleDeviceSharding(self.devices[0])
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                for k, v in jax.eval_shape(build).items()}

    def initial_stacked(self, factors, sharding):
        """Per-rank state in the stacked ghost layout ``(*factors,
        *local)``, each rank's block computed on its own device."""
        import jax
        import jax.numpy as jnp

        g, nf = self.cfg["grid"], len(factors)
        inner = [(n - 2) // f for n, f in zip(g, factors)]
        shape = tuple(factors) + tuple(i + 2 for i in inner)

        def build():
            idx = [jax.lax.broadcasted_iota(jnp.int32, shape, a) * inner[a]
                   + jax.lax.broadcasted_iota(jnp.int32, shape, a + nf)
                   for a in range(nf)]
            return self.ref.fields_at(self.cfg, self.p, idx)

        if self.abstract:
            return {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=sharding)
                    for k, v in jax.eval_shape(build).items()}
        return jax.jit(build, out_shardings={
            k: sharding for k in self.ref.STATE})()


def devices_for(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r}); "
                      "this benchmark measures on the chip only")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX has "
                      f"{len(devs)}")
    if devs[0].platform == "tpu":
        from yardstick import peaks

        try:
            peaks.peak(devs[0].device_kind)
        except KeyError as e:
            raise Refused(str(e)) from None
    return devs[:chips]


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, allow_cpu: bool = False, rehearse_n: int | None = None,
        control: bool = False, fault: str | None = None) -> dict:
    """One run of one cell; returns the result dict (``compared`` last).

    ``allow_cpu`` and ``rehearse_n`` serve the CPU rehearsal and the tests
    only. ``control`` puts the lower-precision reference in the program's
    place (``yardstick/control.py``) and ``fault`` plants a named fault in
    the timed path (``yardstick/faults.py``); the benchmark's runs set
    neither."""
    import jax

    from yardstick import compare, compiles, trace as tr

    spec = find_cell(workload)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    if rehearse_n is not None:
        cfg = shrink(cfg, rehearse_n)
    devs = devices_for(cell["chips"], allow_cpu)
    on_tpu = devs[0].platform == "tpu"

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # cache every program, so that a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ref = load_module("references", cfg["reference"])
    system = load_module("systems", cfg["system"])
    driver = load_module("drivers", traffic["driver"])
    limits = load_json("limits", workload)["limits"]
    ctx = Context(cfg=cfg, traffic=traffic, seed=seed, ref=ref,
                  p=ref.params(cfg, seed), interpret=not on_tpu,
                  devices=devs, abstract=False)

    entry = getattr(system, traffic["entry"])(ctx)
    # the reference's layout: whole global fields, sharded over the mesh
    sharding = None
    if getattr(entry, "mesh", None) is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(entry.mesh, P(*entry.mesh.axis_names))
    if control:
        from yardstick import control as ctl

        ctl.install(entry, ctx, sharding)
    if fault is not None:
        from yardstick import faults

        faults.plant(fault, entry, ctx)
    warm = driver.warm(entry, traffic)
    setup_s = time.perf_counter() - t_start

    counter = compiles.Counter()
    tdir = os.path.join(OUT, "trace", workload)
    shutil.rmtree(tdir, ignore_errors=True)
    record = tr.recording(tdir) if trace else contextlib.nullcontext()
    with record, counter:
        res = driver.window(entry, traffic, seconds)
    mem = memory_peak(devs)
    outputs = entry.outputs(jax.block_until_ready(res.pop("state")))
    region = (slice(1, -1),) * 3 if getattr(entry, "interior_only",
                                            False) else None
    del entry

    t_check = time.perf_counter()
    plain = compare.Reference(ref, cfg, ctx.p, sharding=sharding)
    numbers = driver.check(plain, traffic, warm, res, outputs, region)
    check_s = time.perf_counter() - t_check
    del outputs
    ok, compared = compare.judge(numbers, limits)

    run_info = dict(res, **warm, seconds=seconds, seed=seed,
                    setup_s=setup_s, compiles_in_window=counter.count,
                    check_s=check_s)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": ok, "attempted": res["attempted"],
              "failed": 0 if ok else res["attempted"]}
    mctx = types.SimpleNamespace(trace=None, run=run_info, cfg=cfg,
                                 ring=ref.RING, traffic=traffic, cell=cell,
                                 kind=dev.device_kind)
    if trace:
        mctx.trace = tr.reduce(tdir, {d.id for d in devs})
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=mctx.trace.busy_s,
                      window_s=mctx.trace.window_s)
    result["metrics"] = read_metrics(
        spec["per_layer" if trace else "end_to_end"], mctx)
    if trace:
        result["breakdown"] = mctx.trace.breakdown
    result["device"] = device
    result["run"] = {k: v for k, v in run_info.items() if k != "iters"}
    result["compile_cache"] = cache_dir
    result["compared"] = compared
    return result


def read_metrics(metrics: list, mctx) -> dict:
    """Each metric from its reader, ``metrics/<name>.py``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = load_module("metrics", m["name"]).read(mctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def report(result: dict) -> None:
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
