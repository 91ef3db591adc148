"""The trace reducer on traces recorded on a TPU v5e
(``data/recorded_trace.json``: ten eager Fig. 1 launches at 512^3 inside
a ``bench.window`` annotation, and one ``solve_until`` of 100 steps whose
launches sit inside a ``while``), and its interval arithmetic on small
synthetic cases."""
import json
import os

import pytest

from yardstick import peaks, trace

with open(os.path.join(os.path.dirname(__file__), "data",
                       "recorded_trace.json")) as f:
    RECORDED = json.load(f)

FIELD = 512 ** 3 * 4


def test_eager_launches_bytes_and_share():
    red = trace.reduce(RECORDED["fig1"])
    assert red.chips == 1 and red.kernel_launches == 10
    assert red.launches_without_bytes == 0
    # T2, T, Ci in and T2 out, plus five f32 scalars
    assert red.kernel_bytes == 10 * (4 * FIELD + 5 * 4)
    share = trace.hbm_share(red, "TPU v5 lite")
    assert 0 < share < 100
    assert share == pytest.approx(
        100 * red.kernel_bytes / (819e9 * red.kernel_s))
    assert 0 < red.busy_s <= red.window_s
    assert red.breakdown["device_ops"][0][0] == "tpu_custom_call"
    assert red.breakdown["idle_gaps"]
    idle = sum(s for _, s in red.breakdown["idle_gaps"])
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_launches_inside_a_while_count_once():
    red = trace.reduce(RECORDED["solve"])
    assert red.kernel_launches == 100
    ops = dict(red.breakdown["device_ops"])
    # the while's own time excludes the launches it holds
    assert ops["while"] < 0.01 * ops["body"]
    assert red.busy_s <= red.window_s * (1 + 1e-9)
    assert 0 < trace.hbm_share(red, "TPU v5 lite") < 100


def test_operation_kinds():
    k = 'custom_call_target="tpu_custom_call"'
    assert trace._op_kind(
        "%body.3 = f32[8]{0} custom-call(f32[8]{0} %a), " + k, {}) == "kernel"
    assert trace._op_kind(
        "%collective-permute-start.2 = (f32[1,514]{1,0}, f32[1,514]{1,0}) "
        "collective-permute-start(f32[1,514]{1,0} %s)", {}) == "collective"
    assert trace._op_kind(
        "%all-reduce.1 = f32[] all-reduce(f32[] %x), to_apply=%max",
        {}) == "collective"
    assert trace._op_kind("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p)",
                          {}) == "other"
    assert trace.op_name("%tpu_custom_call.12 = f32[1] x") == \
        "tpu_custom_call"


def test_launch_bytes_from_hlo_text():
    text = ('%k = (f32[4,4]{1,0}, bf16[2]{0}) custom-call(f32[1]{0} %s, '
            's32[] %i, f32[4,4]{1,0} %a), custom_call_target="tpu_custom_call"'
            ', operand_layout_constraints={f32[4,4]{1,0}}')
    assert trace.launch_bytes(text, {}) == 64 + 4 + 4 + 4 + 64
    assert trace.launch_bytes("%k = custom-call()", {}) is None


def test_interval_arithmetic():
    merged = trace._union([(5, 7), (0, 2), (1, 3)])
    assert merged == [[0, 3], [5, 7]]
    assert trace._length(merged) == 5
    assert trace._minus([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert trace._minus([[0, 4]], [[0, 4]]) == 0
    ops = [["w", 0.0, 10.0, {}], ["a", 1.0, 2.0, {}], ["b", 4.0, 3.0, {}]]
    assert trace._self_times(ops) == [5.0, 2.0, 3.0]


def test_exposed_collectives_and_idle_share():
    k = 'custom_call_target="tpu_custom_call"'
    data = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [trace.WINDOW, 0.0, 100.0, {}], ["PjitFunction(step)", 60.0, 30.0,
                                             {}]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": trace.OPS_LINE,
                                             "events": [
            ["%k = f32[2]{0} custom-call(f32[2]{0} %a), " + k, 0.0, 40.0, {}],
            ["%all-reduce = f32[] all-reduce(f32[] %x)", 40.0, 20.0, {}]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": trace.OPS_LINE,
                                             "events": [
            ["%k = f32[2]{0} custom-call(f32[2]{0} %a), " + k, 0.0, 50.0, {}],
            ["%all-reduce = f32[] all-reduce(f32[] %x)", 30.0, 30.0,
             {}]]}]}]}
    red = trace.reduce(data)
    assert red.chips == 2 and red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(60e-9)
    assert red.exposed_collective_s == pytest.approx(15e-9)
    assert trace.exposed_collective_share(red) == pytest.approx(15.0)
    assert red.breakdown["idle_gaps"] == [["PjitFunction(step)",
                                           pytest.approx(40e-9)]]
    assert trace.reduce(data, device_ids={1}).chips == 1


def test_no_launch_no_share_and_unknown_device():
    red = trace.reduce({"planes": []})
    assert trace.hbm_share(red, "TPU v5 lite") is None
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
