"""Runtime telemetry: no-op-when-disabled, JSONL/Perfetto schema,
device-metric harvesting at existing sync points, spans on the
profiler's clock, compile counts.

The two contracts under test:
  * disabled mode is FREE — the global collector is the shared no-op
    singleton and the traced program of every instrumented entry point
    is byte-identical with telemetry on or off (zero-host-sync rule);
  * enabled mode writes schema-valid JSONL whose records carry the
    chunk spans / error trajectory / checkpoint latencies / compile
    counts, and its spans land on the host lines of a profiler trace.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import fd3d, init_parallel_stencil, iterate, teff
from repro.distributed import fault, halo
from repro.telemetry import export, report, schema

ERR = {"err": "max_abs_diff(T2, T)"}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with the env-default (disabled) state."""
    telemetry.reset()
    yield
    telemetry.reset()


def diffusion_kernel(reductions=ERR):
    ps = init_parallel_stencil(backend="jnp", ndims=3)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions=reductions)
    def kern(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}

    return kern


def setup3d(rng, shape=(12, 12, 12)):
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.asarray(rng.rand(*shape) + 0.5, jnp.float32)
    sc = dict(lam=1.0, dt=0.05, _dx=1.0, _dy=1.0, _dz=1.0)
    return T, Ci, sc


# ---------------------------------------------------------------- disabled
def test_disabled_is_shared_noop_singleton(monkeypatch):
    monkeypatch.delenv("REPRO_TELEMETRY", raising=False)
    telemetry.reset()
    col = telemetry.get()
    assert col is telemetry.NULL and not col.enabled
    assert telemetry.get() is col                 # cached, not re-resolved
    # span() hands back ONE shared reusable null context manager
    s1, s2 = col.span("a"), col.span("b", attr=1)
    assert s1 is s2
    with s1 as s:
        assert s is s1
    # every no-op path returns None and records nothing
    assert col.count("c") is None and col.gauge("g", 1.0) is None
    assert col.observe("h", 0.5) is None and col.event("e") is None
    col.span_end("x", 0.0, 1.0)
    col.flush(), col.close()
    # module-level conveniences route through the same singleton
    telemetry.count("c"), telemetry.gauge("g", 1), telemetry.event("e")
    assert not telemetry.enabled()


def test_env_enables_and_configure_overrides(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TELEMETRY", str(tmp_path / "run.jsonl"))
    telemetry.reset()
    col = telemetry.get()
    assert col.enabled and col.path == str(tmp_path / "run.jsonl")
    col2 = telemetry.configure(None)       # programmatic override
    assert telemetry.get() is col2 and col2.path is None
    telemetry.reset()
    monkeypatch.setenv("REPRO_TELEMETRY", "0")
    telemetry.reset()
    assert telemetry.get() is telemetry.NULL


def test_resolve_kwarg_contract():
    assert telemetry.resolve(False) is telemetry.NULL
    col = telemetry.Collector(None)
    assert telemetry.resolve(col) is col
    assert telemetry.resolve(None) is telemetry.get()
    forced = telemetry.resolve(True)
    assert forced.enabled


def test_traced_program_identical_on_off(rng):
    """Zero-host-sync rule, jaxpr-asserted: the solver's traced program
    does not change when a collector is active."""
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    kern = diffusion_kernel()
    args = (dict(T2=T, T=T, Ci=Ci), 1e-5, 50)
    off = str(jax.make_jaxpr(iterate.make_solver(kern, sc, check_every=2))(
        *args))
    telemetry.configure(None)              # enabled, in-memory
    on = str(jax.make_jaxpr(iterate.make_solver(kern, sc, check_every=2))(
        *args))
    assert on == off
    assert "callback" not in on and "outside_call" not in on


def test_disabled_solve_unperturbed(rng):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel()
    r0 = iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=2e-5,
                             max_iters=200, check_every=5, telemetry=False)
    col = telemetry.Collector(None)
    r1 = iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=2e-5,
                             max_iters=200, check_every=5, telemetry=col)
    # instrumented run: same math, bit-identical result
    assert int(r0.iters) == int(r1.iters)
    np.testing.assert_array_equal(np.asarray(r0.fields["T"]),
                                  np.asarray(r1.fields["T"]))
    assert any(r["kind"] == "span" and r["name"] == "solve_until"
               for r in col.records)


def test_solver_cache_reused_across_calls(rng):
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    kern = diffusion_kernel()
    s1 = iterate._jitted_solver(kern, sc, check_every=5, error=None,
                                until="below")
    s2 = iterate._jitted_solver(kern, sc, check_every=5, error=None,
                                until="below")
    assert s1 is s2
    s3 = iterate._jitted_solver(kern, sc, check_every=3, error=None,
                                until="below")
    assert s3 is not s1
    # unhashable scalars (mutable numpy buffer) opt out of the cache
    s4 = iterate._jitted_solver(kern, dict(sc, lam=np.array(1.0)),
                                check_every=5, error=None, until="below")
    assert s4 is not s1


# ----------------------------------------------------------------- enabled
def _run_checkpointed(rng, tmp_path, log="run.jsonl"):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel()
    path = str(tmp_path / log)
    # the GLOBAL collector, as REPRO_TELEMETRY= would install it: the
    # checkpoint/fault subsystems emit through the process singleton
    col = telemetry.configure(path)
    mon = fault.StepMonitor(host_id=0, heartbeat_dir=str(tmp_path / "hb"))
    ck = iterate.Checkpointing(str(tmp_path / "ck"), save_every=2,
                               blocking=False, monitor=mon)
    res = iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=2e-5,
                              max_iters=200, check_every=5, checkpoint=ck)
    telemetry.reset()                       # close + flush the log
    return res, col, path


def test_enabled_chunked_jsonl_schema_and_content(rng, tmp_path):
    res, col, path = _run_checkpointed(rng, tmp_path)
    counts = schema.validate_file(path)          # raises on any drift
    assert counts["meta"] == 1 and counts["span"] > 0
    records = schema.load_records(path)
    names = {(r["kind"], r.get("name")) for r in records}
    assert ("span", "solve.chunk") in names
    assert ("span", "checkpoint.snapshot") in names
    assert ("span", "checkpoint.write") in names
    assert ("event", "solve.trajectory") in names
    assert ("counter", "solve.steps") in names
    assert ("counter", "checkpoint.saves") in names
    assert ("gauge", "fault.ewma_step_s") in names
    # chunk spans carry the boundary harvest; steps sum to the iter count
    chunks = [r for r in records
              if r["kind"] == "span" and r["name"] == "solve.chunk"]
    assert sum(c["attrs"]["steps"] for c in chunks) == int(res.iters)
    assert chunks[0]["attrs"]["cold"] is True
    traj = [r for r in records
            if r["kind"] == "event" and r["name"] == "solve.trajectory"]
    errs = [t["attrs"]["err"] for t in traj]
    assert errs[-1] == pytest.approx(float(res.err))
    assert all(e >= errs[-1] for e in errs[:1])  # diffusion decays
    # StepMonitor surfaced on the result
    assert res.step_stats is not None and 0 in res.step_stats
    assert res.step_stats[0]["ewma_s"] > 0


def test_resume_event_and_restore_span(rng, tmp_path):
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel()
    ck = iterate.Checkpointing(str(tmp_path / "ck"), save_every=1,
                               blocking=True)
    iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=0.0,
                        max_iters=10, check_every=5, checkpoint=ck,
                        telemetry=False)
    col2 = telemetry.configure(None)        # restore emits via the global
    res = iterate.solve_until(kern, dict(T2=T, T=T, Ci=Ci), sc, tol=0.0,
                              max_iters=20, check_every=5, checkpoint=ck)
    assert res.resumed_from == 10
    ev = [r for r in col2.records
          if r["kind"] == "event" and r["name"] == "solve.resume"]
    assert ev and ev[0]["attrs"]["step"] == 10
    assert any(r["kind"] == "span" and r["name"] == "checkpoint.restore"
               for r in col2.records)
    assert any(r["kind"] == "counter" and r["name"] == "checkpoint.restores"
               for r in col2.records)


def test_chrome_trace_export(rng, tmp_path):
    _, _, path = _run_checkpointed(rng, tmp_path)
    records = schema.load_records(path)
    out = str(tmp_path / "trace.json")
    n = export.write_chrome_trace(records, out)
    trace = json.load(open(out))
    evs = trace["traceEvents"]
    assert n == len(evs) > 0
    phases = {e["ph"] for e in evs}
    assert "X" in phases and "C" in phases     # spans + counters
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)


def test_prometheus_export():
    col = telemetry.Collector(None)
    col.count("solve.steps", 30)
    col.count("solve.steps", 12)
    col.gauge("roofline.fraction", 0.83, kernel="kern")
    col.observe("chunk_s", 0.1)
    col.observe("chunk_s", 0.3)
    text = export.prometheus_text(col)
    assert "repro_solve_steps_total 42" in text
    assert 'repro_roofline_fraction{kernel="kern"} 0.83' in text
    assert 'quantile="0.5"' in text and "repro_chunk_s_count 2" in text


def test_schema_rejects_drift(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "meta", "ts": 0.0, "schema": 1,
                               "pid": 1}) + "\n" +
                   json.dumps({"kind": "span", "ts": 1.0, "name": "x",
                               "dur_s": -2.0}) + "\n")
    with pytest.raises(schema.SchemaError, match="dur_s"):
        schema.validate_file(str(bad))
    # CLI surface: exit 1 + INVALID verdict
    assert schema.main([str(bad)]) == 1


def test_report_cli(rng, tmp_path, capsys):
    _, _, path = _run_checkpointed(rng, tmp_path)
    trace = str(tmp_path / "trace.json")
    assert report.main([path, "--validate", "--trace", trace]) == 0
    out = capsys.readouterr().out
    assert "Per-phase spans" in out
    assert "solve.chunk" in out
    assert "Error trajectory" in out
    assert os.path.exists(trace)


# ------------------------------------------------------------------- peaks
def test_device_peaks_keyed_by_kind():
    from repro.core import teff
    # JAX reports a v5e chip as "TPU v5 lite"
    assert teff.device_peaks("TPU v5 lite") is teff.TPU_V5E
    assert teff.TPU_V5E.peak_bw == pytest.approx(819e9)
    # an unknown accelerator is an error, never a default peak
    with pytest.raises(ValueError, match="TPU v9"):
        teff.device_peaks("TPU v9")


# ------------------------------------------------------------- halo bytes
def test_exchange_byte_counts_hand_checked():
    shapes = {"A": (8, 6), "B": (8, 6)}
    isz = {"A": 4, "B": 4}
    isf = {"A": True, "B": True}
    # one mesh axis, radius 1, grouped: per side one message of
    # 2 fields * 1 plane * 6 elems = 12 f32 -> 48 B; two sides
    c = halo.exchange_byte_counts(shapes, isz, isf, n_axes=1)
    assert c == {"bytes_raw": 96, "bytes_wire": 96, "messages": 2}
    # bf16 wire: 2 B/elt
    c = halo.exchange_byte_counts(shapes, isz, isf, 1, compress="bf16")
    assert c["bytes_raw"] == 96 and c["bytes_wire"] == 48
    # int8 wire: BLOCK-padded q payload + one f32 scale per block,
    # and a second message (the scales) per slab
    from repro.distributed.compression import BLOCK
    c = halo.exchange_byte_counts(shapes, isz, isf, 1, compress="int8")
    assert c["bytes_wire"] == 2 * (BLOCK + 4)
    assert c["messages"] == 4
    # inactive axes ship nothing
    c = halo.exchange_byte_counts(shapes, isz, isf, 1, active=[False])
    assert c == {"bytes_raw": 0, "bytes_wire": 0, "messages": 0}
    # ungrouped: one message per field per side
    c = halo.exchange_byte_counts(shapes, isz, isf, 1, grouped=False)
    assert c["messages"] == 4 and c["bytes_raw"] == 96
    # int-typed fields never compress
    c = halo.exchange_byte_counts({"M": (8, 6)}, {"M": 1}, {"M": False}, 1,
                                  compress="bf16")
    assert c["bytes_wire"] == c["bytes_raw"] == 12


def test_exchange_telemetry_emission():
    """The instrumentation hook itself: analytic counts from static
    shapes, no device work (outside shard_map the axis probe fails ->
    every axis is assumed active)."""
    col = telemetry.Collector(None)
    A = jnp.ones((8, 6), jnp.float32)
    halo._emit_exchange_telemetry(col, dict(A=A), ("A",), ("x",),
                                  radius=1, depths=None, compress=None,
                                  grouped=True)
    ev = [r for r in col.records
          if r["kind"] == "event" and r["name"] == "halo.exchange_traced"]
    assert ev
    a = ev[-1]["attrs"]
    # one plane of 6 f32 per side, two sides: 48 raw bytes, 2 messages
    assert a["bytes_raw"] == a["bytes_wire"] == 48
    assert a["messages"] == 2 and a["fields"] == ["A"]
    assert any(r["kind"] == "counter" and r["name"] == "halo.traced_exchanges"
               for r in col.records)
    assert any(r["kind"] == "gauge"
               and r["name"] == "halo.bytes_wire_per_exchange"
               for r in col.records)


# ---------------------------------------------------------------- autotune
def test_autotune_decision_events():
    from repro.kernels import autotune

    telemetry.configure(None)
    col = telemetry.get()

    def make_step(tile, k):
        return lambda: jnp.zeros(())

    kw = dict(shape=(32, 32), dtype="float32", radius=1, n_fields=3,
              nsteps_candidates=(1,), tiles=[(32, 32), (8, 32)], iters=1,
              tag="telemetry-unit")
    autotune.autotune(make_step, **kw)
    autotune.autotune(make_step, **kw)
    evs = [r["attrs"]["cache"] for r in col.records
           if r["kind"] == "event" and r["name"] == "autotune.decision"]
    assert evs[0] == "miss" and "memory_hit" in evs[1:]
    miss = [r for r in col.records
            if r["kind"] == "event" and r["name"] == "autotune.decision"
            and r["attrs"]["cache"] == "miss"][0]
    assert miss["attrs"]["candidates_tried"] == 2


# -------------------------------------------------------------- percentiles
def test_measurement_percentiles():
    samples = [0.1, 0.2, 0.3, 0.4, 1.0]
    m = teff.Measurement(median_s=0.3, ci95_s=(0.1, 1.0), samples_s=samples)
    assert m.p50_s == pytest.approx(0.3)
    assert m.max_s == pytest.approx(1.0)
    assert m.mean_s == pytest.approx(0.4)
    assert m.p50_s <= m.p90_s <= m.max_s
    p = m.percentiles()
    assert set(p) == {"mean_s", "p50_s", "p90_s", "max_s"}


def test_measure_exposes_percentiles():
    m = teff.measure(lambda: jnp.zeros(8) + 1, iters=5, warmup=1)
    assert len(m.samples_s) == 5
    assert m.p50_s <= m.max_s
    assert m.percentiles()["max_s"] == max(m.samples_s)


# ----------------------------------------------------------------- overhead
def test_telemetry_overhead_under_2pct(rng):
    """Acceptance bound: <2% per-step overhead with telemetry on at 128^3
    on the jnp backend. The traced program is identical (asserted above),
    so the only added cost is a handful of host-side record appends per
    solve; min-over-samples comparison with retries keeps the check
    robust to shared-host noise."""
    shape = (128, 128, 128)
    T = jnp.asarray(rng.rand(*shape), jnp.float32)
    Ci = jnp.ones(shape, jnp.float32)
    sc = dict(lam=1.0, dt=1e-3, _dx=1.0, _dy=1.0, _dz=1.0)
    kern = diffusion_kernel()
    fields = dict(T2=T, T=T, Ci=Ci)
    col = telemetry.Collector(None)

    def run(sel):
        res = iterate.solve_until(kern, fields, sc, tol=0.0, max_iters=20,
                                  check_every=5, telemetry=sel)
        jax.block_until_ready(res.err)

    run(False), run(col)        # warm: compile once, AOT-compile once
    last = None
    for _ in range(3):          # retry against host noise
        off, on = [], []
        import time
        for _ in range(4):      # interleaved: both see the same drift
            t0 = time.perf_counter(); run(False)
            off.append(time.perf_counter() - t0)
            t0 = time.perf_counter(); run(col)
            on.append(time.perf_counter() - t0)
        last = min(on) / min(off) - 1.0
        if last < 0.02:
            break
    assert last < 0.02, f"telemetry overhead {last:.3%} >= 2%"


# ---------------------------------------------------------------------------
# hardened JSONL writer: transient I/O degrades to dropped-records-with-
# counter instead of killing the solve (serving-layer satellite)
# ---------------------------------------------------------------------------
class _FlakyIO:
    """Install/remove a FaultPlan object directly (bypassing the env) so
    the injection budget starts ticking exactly where the test says."""

    def __enter__(self):
        fault.FaultPlan.reset_active()
        return self

    def arm(self, **kw):
        plan = fault.FaultPlan(**kw)
        fault._active_plan, fault._active_loaded = plan, True
        return plan

    def __exit__(self, *exc):
        fault.FaultPlan.reset_active()
        return False


def test_writer_absorbs_transient_io_within_retry_budget(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv(fault.PLAN_ENV, fault.FaultPlan(io_errors=2).to_env())
    fault.FaultPlan.reset_active()
    path = str(tmp_path / "t.jsonl")
    col = telemetry.configure(path=path)
    for i in range(5):
        col.count("solve.steps", i)
    col.close()
    fault.FaultPlan.reset_active()
    lines = [json.loads(ln) for ln in open(path)]
    assert col.dropped_records == 0
    assert len(lines) == 6          # meta + 5 counters: nothing lost


def test_writer_drops_with_counter_when_retries_exhausted(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with _FlakyIO() as flaky:
        col = telemetry.configure(path=path)
        # budget > attempts: the next write fails all its retries
        flaky.arm(io_errors=col.IO_ATTEMPTS)
        col.count("lost_line", 1)       # dropped, MUST NOT raise
        col.count("landed_line", 1)     # budget spent: lands
        col.close()
    lines = [json.loads(ln) for ln in open(path)]
    mine = {"lost_line", "landed_line"}
    names = [ln.get("name") for ln in lines
             if ln["kind"] == "counter" and ln.get("name") in mine]
    assert col.dropped_records == 1
    assert names == ["landed_line"]
    # the in-memory view is complete regardless of sink health
    mem = [r.get("name") for r in col.records
           if r["kind"] == "counter" and r.get("name") in mine]
    assert mem == ["lost_line", "landed_line"]
    telemetry.reset()


def test_writer_degrades_to_memory_only_when_open_never_succeeds(
        tmp_path, monkeypatch):
    monkeypatch.setenv(fault.PLAN_ENV,
                       fault.FaultPlan(io_errors=50).to_env())
    fault.FaultPlan.reset_active()
    path = str(tmp_path / "never.jsonl")
    col = telemetry.configure(path=path)     # open exhausts retries
    col.count("a", 1)
    col.close()
    fault.FaultPlan.reset_active()
    assert not os.path.exists(path)
    assert col.records[0].get("sink_degraded") is True
    assert col.dropped_records == 2          # meta + counter
    assert [r["kind"] for r in col.records] == ["meta", "counter"]


def test_solve_survives_flaky_telemetry_sink(tmp_path):
    """The integration cut: a solve with telemetry on a flaky sink must
    complete normally — degraded observability, untouched results."""
    kern = diffusion_kernel()
    rng = np.random.RandomState(7)
    T, Ci, sc = setup3d(rng)
    clean = iterate.solve_until(kern, {"T": T, "T2": T, "Ci": Ci}, sc,
                                tol=1e-4, max_iters=200, check_every=4)
    path = str(tmp_path / "flaky.jsonl")
    with _FlakyIO() as flaky:
        col = telemetry.configure(path=path)
        flaky.arm(io_errors=3 * col.IO_ATTEMPTS)
        res = iterate.solve_until(kern, {"T": T, "T2": T, "Ci": Ci}, sc,
                                  tol=1e-4, max_iters=200, check_every=4,
                                  telemetry=col)
        col.close()
    np.testing.assert_array_equal(np.asarray(res.fields["T"]),
                                  np.asarray(clean.fields["T"]))
    assert col.dropped_records >= 1
    telemetry.reset()


# ------------------------------------------------------------- rank merge
def _write_rank_stream(path, spans, rank=None):
    """Hand-rolled per-rank JSONL with controlled timestamps."""
    with open(path, "w") as f:
        for ts, name, dur in spans:
            rec = {"kind": "span", "ts": ts, "name": name, "dur_s": dur}
            if rank is not None:
                rec["rank"] = rank
            f.write(json.dumps(rec) + "\n")


def test_merge_records_interleaves_by_timestamp(tmp_path):
    # rank 0's records are rank-stamped; rank 1's rely on the
    # rank_<i> filename fallback
    p0 = str(tmp_path / "rank_0.jsonl")
    p1 = str(tmp_path / "rank_1.jsonl")
    _write_rank_stream(p0, [(1.0, "solve.chunk", 0.5),
                            (3.0, "solve.chunk", 0.7)], rank=0)
    _write_rank_stream(p1, [(2.0, "solve.chunk", 0.6),
                            (4.0, "exchange", 0.1)])
    merged = report.merge_records([p0, p1])
    assert [r["ts"] for r in merged] == [1.0, 2.0, 3.0, 4.0]
    assert [r["rank"] for r in merged] == [0, 1, 0, 1]

    rows = report.per_rank_phase_summary(merged)
    assert {(r["phase"], r["rank"], r["count"]) for r in rows} == {
        ("solve.chunk", 0, 2), ("solve.chunk", 1, 1), ("exchange", 1, 1)}


def test_report_cli_merge_glob(tmp_path, capsys):
    p0 = str(tmp_path / "rank_0.jsonl")
    p1 = str(tmp_path / "rank_1.jsonl")
    _write_rank_stream(p0, [(1.0, "solve.chunk", 0.5)], rank=0)
    _write_rank_stream(p1, [(2.0, "solve.chunk", 0.9)], rank=1)
    assert report.main(["--merge", str(tmp_path / "rank_*.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "Per-rank phases" in out
    assert "ranks: [0, 1]" in out or "rank" in out
    # one row per (phase, rank): the straggling rank is visible as its
    # own 0.9 s row, not averaged into the other rank's 0.5 s
    assert "0.9" in out and "0.5" in out


def test_report_cli_merge_no_match_notice(tmp_path, capsys):
    lone = str(tmp_path / "run.jsonl")
    _write_rank_stream(lone, [(1.0, "solve.chunk", 0.5)])
    rc = report.main([lone, "--merge", str(tmp_path / "nope_*.jsonl")])
    assert rc == 0
    err = capsys.readouterr().err
    assert "no files match" in err


def test_collector_rank_stamp(tmp_path):
    path = str(tmp_path / "rank_3.jsonl")
    col = telemetry.configure_rank(3, path=path)
    col.count("steps", 2)
    with col.span("solve.chunk"):
        pass
    col.close()
    recs = schema.load_records(path)
    assert recs and all(r.get("rank") == 3 for r in recs)
    telemetry.reset()


# ------------------------------------------------ spans on the trace's clock
def test_null_span_needs_no_import():
    """Disabled, a span is the shared null span: nothing is imported for
    it (no jax, no profiler) and ``set`` is a no-op."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro import telemetry as t\n"
            "col = t.get()\n"
            "with col.span('stencil.dispatch') as s:\n"
            "    s.set(steps=1)\n"
            "assert col is t.NULL and s is col.span('x')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("REPRO_TELEMETRY", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _host_events(tdir):
    """(name, line) of every event on a host plane of the newest trace."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return [(e.name, ln.name) for pl in ProfileData.from_file(path).planes
            if pl.name.startswith("/host:") for ln in pl.lines
            for e in ln.events]


def test_enabled_span_lands_on_profiler_host_line(tmp_path):
    col = telemetry.configure(None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with col.span("probe.outer"):
            with col.span("probe.inner"):
                jax.block_until_ready(jnp.arange(8.0) * 2)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    lines = {name: line for name, line in events}
    assert "probe.outer" in lines and "probe.inner" in lines
    assert lines["probe.outer"] == lines["probe.inner"]   # one thread
    spans = [r for r in col.records if r["kind"] == "span"]
    assert [r["name"] for r in spans] == ["probe.inner", "probe.outer"]


def test_span_set_attrs_inside_block():
    col = telemetry.Collector(None)
    with col.span("solve.chunk", phase="warm") as span:
        span.set(steps=10)
    with pytest.raises(ValueError):
        with col.span("solve.chunk"):
            raise ValueError("boom")
    ok, failed = [r for r in col.records if r["kind"] == "span"]
    assert ok["attrs"] == {"phase": "warm", "steps": 10}
    assert failed["attrs"] == {"error": "ValueError"}


def pallas_kernel(reductions=None):
    ps = init_parallel_stencil(backend="pallas", ndims=3, interpret=True)

    @ps.parallel(outputs=("T2",), rotations={"T2": "T"},
                 reductions=reductions)
    def fig1(T2, T, Ci, lam, dt, _dx, _dy, _dz):
        return {"T2": fd3d.inn(T) + dt * (lam * fd3d.inn(Ci) * (
            fd3d.d2_xi(T) * _dx ** 2 + fd3d.d2_yi(T) * _dy ** 2 +
            fd3d.d2_zi(T) * _dz ** 2))}

    return fig1


def test_eager_call_dispatch_and_scalar_spans(rng):
    """An eager Pallas call is one ``stencil.dispatch`` span holding one
    ``stencil.scalars`` span; ``run_steps(k)`` is one dispatch too."""
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    kern = pallas_kernel()
    col = telemetry.configure(None)
    kern(T2=T, T=T, Ci=Ci, **sc)
    kern.run_steps(2, T2=T, T=T, Ci=Ci, **sc)
    spans = [r for r in col.records if r["kind"] == "span"]
    names = [r["name"] for r in spans]
    assert names == ["stencil.scalars", "stencil.dispatch",
                     "stencil.scalars", "stencil.dispatch"]
    inner, outer = spans[0], spans[1]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur_s"] <= outer["ts"] + outer["dur_s"] + 1e-6


@pytest.mark.parametrize("entry", ["eager_call", "run_steps", "solver"])
def test_instrumented_jaxpr_identical_on_off(rng, entry):
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    if entry == "solver":
        kern = pallas_kernel(ERR)
        fn = iterate._jitted_solver(kern, sc, check_every=2, error=None,
                                    until="below")
        args = (dict(T2=T, T=T, Ci=Ci), 1e-5, 10)
    else:
        kern = pallas_kernel()
        k = 1 if entry == "eager_call" else 2
        fn = lambda a, b, c: kern.run_steps(k, T2=a, T=b, Ci=c, **sc)  # noqa: E731
        args = (T, T, Ci)
    off = str(jax.make_jaxpr(fn)(*args))
    telemetry.configure(None)
    on = str(jax.make_jaxpr(fn)(*args))
    assert on == off
    assert "callback" not in on


def test_solve_until_harvests_in_one_transfer(rng, monkeypatch):
    """The instrumented solve reads iters and err in one device-to-host
    transfer; ``solve.dispatch`` ends inside ``solve_until``."""
    T, Ci, sc = setup3d(rng)
    kern = diffusion_kernel()
    fields = dict(T2=T, T=T, Ci=Ci)
    iterate.solve_until(kern, fields, sc, tol=2e-5, max_iters=50,
                        check_every=5, telemetry=False)   # compile
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(x) or real(x))
    monkeypatch.setattr(jax, "block_until_ready", lambda x: calls.append(
        "block") or x)
    col = telemetry.Collector(None)
    res = iterate.solve_until(kern, fields, sc, tol=2e-5, max_iters=50,
                              check_every=5, telemetry=col)
    assert len(calls) == 1 and len(calls[0]) == 2
    spans = {r["name"]: r for r in col.records if r["kind"] == "span"}
    solve, dispatch = spans["solve_until"], spans["solve.dispatch"]
    assert solve["ts"] <= dispatch["ts"]
    assert dispatch["dur_s"] <= solve["dur_s"]
    assert solve["attrs"]["iters"] == int(res.iters)
    assert solve["attrs"]["err"] == pytest.approx(float(res.err))
    assert solve["attrs"]["cold"] is False


def test_solve_check_named_scope_in_hlo(rng):
    """The solve's check (checked launch, reductions, error) carries the
    ``solve.check`` scope into the compiled program's metadata."""
    T, Ci, sc = setup3d(rng, shape=(8, 8, 8))
    solver = jax.jit(iterate.make_solver(diffusion_kernel(), sc,
                                         check_every=2))
    text = solver.lower(dict(T2=T, T=T, Ci=Ci), 1e-5, 10).compile().as_text()
    assert "/solve.check/" in text


# ----------------------------------------------------------- compile counts
_COMPILE_PROBE = """
import json
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro import telemetry
from repro.telemetry import report
col = telemetry.configure(None)

@jax.jit
def probe(x):
    return jnp.sin(x) * 2 + 1

probe(jnp.arange(16.0)).block_until_ready()
print(json.dumps({r["phase"]: r["compiles"]
                  for r in report.compile_summary(col.records)
                  if r["fn"] == "jit(probe)"}))
"""


def test_jit_compiles_counts_compile_then_cache_fetch(tmp_path):
    """Two processes sharing a persistent cache: the first compiles
    ``probe``, the second fetches it (and does not compile)."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
                   os.path.dirname(__file__), "..", "src"))
    env.pop("REPRO_TELEMETRY", None)
    seen = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _COMPILE_PROBE], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr
        seen.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert seen[0]["compile"] == 1 and "cache_fetch" not in seen[0]
    assert seen[1]["cache_fetch"] == 1 and "compile" not in seen[1]
    assert seen[0]["lower"] == seen[1]["lower"] == 1


def test_compile_summary_by_function_and_phase():
    col = telemetry.Collector(None)
    for fn, phase, s, ts in (("jit(a)", "compile", 2.0, 10.0),
                             ("jit(a)", "compile", 1.0, 20.0),
                             ("jit(b)", "cache_fetch", 0.5, 30.0)):
        col._emit({"kind": "counter", "ts": ts, "name": "jit.compiles",
                   "value": 1, "labels": {"fn": fn, "phase": phase}})
        col._emit({"kind": "counter", "ts": ts, "name": "jit.compile_s",
                   "value": s, "labels": {"fn": fn, "phase": phase}})
    rows = report.compile_summary(col.records)
    assert rows == [
        {"fn": "jit(a)", "phase": "compile", "compiles": 2, "seconds": 3.0},
        {"fn": "jit(b)", "phase": "cache_fetch", "compiles": 1,
         "seconds": 0.5}]
    assert report.compile_summary(col.records, until_ts=15.0) == [
        {"fn": "jit(a)", "phase": "compile", "compiles": 1, "seconds": 2.0}]


def test_elastic_chunk_span_scopes_and_jaxpr():
    """The elastic chunk driver on a 2x2x1 mesh of virtual devices: one
    ``elastic.chunk`` span per call, the same jaxpr with telemetry on and
    off, and the exchange and the check named in the compiled program."""
    from conftest import run_subprocess

    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import telemetry
from repro.core import init_parallel_stencil, fd3d as fd
from repro.distributed import elastic
from repro.launch.mesh import make_mesh

ps = init_parallel_stencil(backend="jnp", ndims=3)
@ps.parallel(outputs=("T2",), rotations={"T2": "T"},
             reductions={"err": "max_abs_diff(T2, T)"})
def fig1(T2, T, Ci, lam, dt, _dx, _dy, _dz):
    return {"T2": fd.inn(T) + dt*(lam*fd.inn(Ci)*(fd.d2_xi(T)*_dx**2
            + fd.d2_yi(T)*_dy**2 + fd.d2_zi(T)*_dz**2))}
sc = dict(lam=1.0, dt=1e-3, _dx=1.0, _dy=1.0, _dz=1.0)
factors, axes = (2, 2, 1), ("x", "y", "z")
mesh = make_mesh(factors, axes)
rng = np.random.RandomState(0)
g = {k: rng.rand(18, 18, 10).astype(np.float32) for k in ("T", "T2", "Ci")}
stacked = elastic.remesh(elastic.decompose_fields(g, factors, 1), mesh,
                         {k: P(*axes, None, None, None) for k in g})
chunk = elastic.make_elastic_solver(fig1, sc, mesh, factors, axes, ("T",),
                                    check_every=2)
args = (stacked, jnp.float32(0.0), jnp.int32(4))
off = str(jax.make_jaxpr(chunk)(*args))
col = telemetry.configure(None)
assert str(jax.make_jaxpr(chunk)(*args)) == off
text = jax.jit(chunk).lower(*args).compile().as_text()
assert "/halo.exchange/" in text and "/solve.check/" in text
def chunks():
    return [r for r in col.records
            if r["kind"] == "span" and r["name"] == "elastic.chunk"]
before = len(chunks())
for _ in range(2):
    jax.block_until_ready(chunk(*args))
assert len(chunks()) == before + 2, chunks()
print("ok")
""", n_devices=4)
    assert "ok" in out
