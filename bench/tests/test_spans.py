"""The reductions from the program's spans and compile counts
(``yardstick/spans.py``): on small synthetic traces, and on traces recorded
on a TPU v5e with the program's collector on (``data/recorded_spans.json``,
written by ``bench/record.py``: ten eager Fig. 1 calls at 512^3 inside a
``bench.window`` annotation, and one ``solve_until`` of 100 steps)."""
import json
import os

import pytest

from yardstick import spans, trace

with open(os.path.join(os.path.dirname(__file__), "data",
                       "recorded_spans.json")) as f:
    RECORDED = json.load(f)

K = 'custom_call_target="tpu_custom_call"'


def synthetic():
    """Window 0-100 ns on the main thread; eager calls at 10-20 and 60-80
    (scalars 12-15 inside the first); chip 0 busy 0-12, 25-60 and 75-90;
    an allocator span on another thread at 62-70 and a sync at 88-100."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                [trace.WINDOW, 0.0, 100.0, {}],
                ["stencil.dispatch", 10.0, 10.0, {}],
                ["stencil.scalars", 12.0, 3.0, {}],
                ["stencil.dispatch", 60.0, 20.0, {}]]},
            {"name": "pjrt", "events": [
                ["Allocate", 62.0, 8.0, {}], ["ReadSyncFlag", 88.0, 12.0,
                                              {}]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": trace.OPS_LINE,
                                             "events": [
            ["%step.1 = f32[2]{0} custom-call(), " + K, 0.0, 12.0, {}],
            ["%step.1 = f32[2]{0} custom-call(), " + K, 25.0, 35.0, {}],
            ["%step.1 = f32[2]{0} custom-call(), " + K, 75.0, 15.0, {}]]}]},
    ]}


def test_dispatch_ms_is_the_median_span_in_the_window():
    assert spans.dispatch_ms(synthetic()) == pytest.approx(15e-6)
    assert spans.dispatch_ms(synthetic(), "no.such.span") is None
    assert spans.dispatch_ms({"planes": []}) is None


def test_idle_share_under_dispatch():
    # idle 12-25 and 60-75 and 90-100; under dispatch 12-20 and 60-75
    assert spans.idle_share_under(synthetic()) == pytest.approx(23.0)
    assert spans.idle_share_under(synthetic(), "no.such.span") is None


def test_program_span_names_the_gap_it_covers():
    gaps = dict(spans.program_gaps(synthetic(), {"stencil.dispatch",
                                                 "stencil.scalars"}))
    # 12-25 (middle 18.5) and 60-75 (middle 67.5, inside the shorter
    # allocator span too) lie in eager calls; 90-100 in none
    assert gaps == {"stencil.dispatch": pytest.approx(28e-9),
                    "ReadSyncFlag": pytest.approx(10e-9)}
    # without program names, the reducer's own rule: the shortest span
    assert dict(spans.program_gaps(synthetic(), set())) == dict(
        trace.reduce(synthetic()).breakdown["idle_gaps"])


def test_setup_compile_s_counts_nested_compiles_once():
    def rec(ts, value, phase):
        return {"kind": "counter", "ts": ts, "name": "jit.compile_s",
                "value": value, "labels": {"fn": "f", "phase": phase}}
    records = [rec(10.0, 1.0, "trace"),      # inner trace 9-10
               rec(11.0, 3.0, "trace"),      # outer trace 8-11
               rec(12.0, 1.0, "lower"),      # 11-12
               rec(15.0, 2.0, "compile"),    # 13-15
               rec(30.0, 5.0, "compile"),    # after set-up
               {"kind": "counter", "ts": 14.0, "name": "jit.compiles",
                "value": 1}]
    assert spans.setup_compile_s(records, until_ts=20.0) == pytest.approx(6.0)
    assert spans.setup_compile_s([], until_ts=20.0) is None


def test_recorded_eager_calls_name_their_launch_and_idle():
    """On the chip: ten eager Fig. 1 calls, each a ``stencil.dispatch``
    span holding ``stencil.scalars``; each launch's device event carries
    the ``@parallel`` name; idle time under the calls is named by them."""
    data = RECORDED["fig1"]
    assert {"stencil.dispatch", "stencil.scalars"} <= set(data["spans"])
    red = trace.reduce(data)
    assert red.kernel_launches == 10 and red.launches_without_bytes == 0
    launches = [name for pl in data["planes"]
                if trace.DEVICE.match(pl["name"]) for ln in pl["lines"]
                if ln["name"] == trace.OPS_LINE for name, *_ in ln["events"]
                if K in name]
    assert len(launches) == 10
    assert all(trace.op_name(n) == "step" and '"parallel":"step"' in n
               for n in launches)
    # bytes and the kernel share read as before: metadata follows the
    # custom_call_target the reducer matches on
    assert red.kernel_bytes == 10 * (4 * 512 ** 3 * 4 + 5 * 4)
    ms = spans.dispatch_ms(data)
    assert 0 < ms < 5.0
    share = spans.idle_share_under(data)
    idle = 100 * (1 - red.busy_s / red.window_s)
    assert 0 < share <= idle + 1e-9
    gaps = dict(spans.program_gaps(data, data["spans"]))
    assert gaps.get("stencil.dispatch", 0) + gaps.get(
        "stencil.scalars", 0) > 0
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)


def test_recorded_solve_spans():
    data = RECORDED["solve"]
    assert {"solve_until", "solve.dispatch"} <= set(data["spans"])
    red = trace.reduce(data)
    assert red.kernel_launches == 100
    assert 0 < trace.hbm_share(red, "TPU v5 lite") < 100
