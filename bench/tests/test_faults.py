"""A run with its timed path broken underneath must come out not
``correct``; so must the control (the reference at bfloat16 in the
program's place). Small sizes on the CPU, Pallas in interpret mode, the
four-chip cell on four virtual devices, with the cells' own limits.

At 8 points per axis one step moves a field by far more than at 512^3,
so the answer one step late and the time levels swapped are also shown
caught on a Fig. 1 state that changes by less than 1e-4 a step, as a
512^3 one does: a hot spot four wide on the unit cube at 16 points per
axis, a warm chunk and one timed chunk of 10 steps each (far fewer than
the ~60 in which that grid settles)."""
import time

import jax.numpy as jnp
import pytest

import harness
from repro.core.parallel import StencilKernel
from repro.distributed import halo
from yardstick import faults

ALL = list(harness.cells())
SWAPPABLE = [c for c in ALL if harness.load_module(
    "references", harness.find_cell(c)["cfg"]["reference"]).INCREMENTS]
LAYOUT = [c for c in ALL if harness.find_cell(c)["cell"]["chips"] == 1]


def _run(cell, n=8, seconds=0.2, **kw):
    return harness.run(cell, 2 ** 36 + 11, seconds, False,
                       t_start=time.perf_counter(), allow_cpu=True,
                       rehearse_n=n, **kw)


def _patch_outputs(monkeypatch, change):
    """Wrap every kernel call so that each output goes through
    ``change(kernel, output name, value, call arguments)``."""
    orig = StencilKernel.__call__

    def call(self, **kw):
        res = orig(self, **kw)
        out, reds = res if self.reductions else (res, None)
        named = ({self.outputs[0]: out} if len(self.outputs) == 1
                 else dict(out))
        named = {o: change(self, o, v, kw) for o, v in named.items()}
        out = named[self.outputs[0]] if len(self.outputs) == 1 else named
        return (out, reds) if self.reductions else out

    monkeypatch.setattr(StencilKernel, "__call__", call)


@pytest.mark.parametrize("cell", ALL)
def test_sound_run_is_correct(cell):
    assert _run(cell)["correct"]


@pytest.mark.parametrize("cell", ALL)
def test_step_returning_its_state_unchanged_is_caught(cell, monkeypatch):
    _patch_outputs(monkeypatch,
                   lambda k, o, v, kw: kw[k.rotations.get(o, o)])
    res = _run(cell)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("cell", ALL)
def test_altered_answer_is_caught(cell, monkeypatch):
    # one interior cell of every output, off by a thousandth
    _patch_outputs(monkeypatch,
                   lambda k, o, v, kw: v.at[3, 3, 3].multiply(1.001))
    res = _run(cell)
    assert not res["correct"], res["compared"]


SLOW_N = 16


def _slow(monkeypatch):
    """Fig. 1 configurations with a hot spot wide enough that one step
    changes the field by less than 1e-4 of its largest value, and chunks
    short enough that the field is still far from settled."""
    shrink, find = harness.shrink, harness.find_cell

    def wide(cfg, n):
        out = shrink(cfg, n)
        if "hot_spot" in out:
            out["hot_spot"] = dict(out["hot_spot"], width=4.0)
        return out

    def short(workload):
        spec = find(workload)
        if "chunk_steps" in spec["traffic"]:
            spec["traffic"] = dict(spec["traffic"], chunk_steps=10)
        return spec

    monkeypatch.setattr(harness, "shrink", wide)
    monkeypatch.setattr(harness, "find_cell", short)


@pytest.mark.parametrize("cell", SWAPPABLE)
def test_swapped_time_levels_are_caught(cell, monkeypatch):
    _slow(monkeypatch)
    assert _run(cell, SLOW_N, 0.0)["correct"]
    res = _run(cell, SLOW_N, 0.0, fault="swapped")
    assert not res["correct"], res["compared"]
    assert res["compared"]["step_rel_err"]["value"] > 1.9


@pytest.mark.parametrize("cell", LAYOUT)
def test_answer_one_step_late_is_caught(cell, monkeypatch):
    _slow(monkeypatch)
    res = _run(cell, SLOW_N, 0.0, fault="extra_step")
    assert not res["correct"], res["compared"]
    gap = res["compared"]["fields_rel_err"]
    assert gap["value"] > gap["limit"]
    if cell in SWAPPABLE:
        assert gap["value"] < 1e-4          # one step's change, as at 512^3


def test_faults_refuse_what_they_cannot_plant():
    import types

    entry = types.SimpleNamespace(interior_only=True, outputs=dict)
    with pytest.raises(ValueError):
        faults.plant("extra_step", entry, None)


def test_exchange_left_out_is_caught(monkeypatch):
    monkeypatch.setattr(halo, "exchange_many",
                        lambda fields, *a, **k: dict(fields))
    res = _run("fig1-512x4-weak")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("cell", ALL)
def test_control_fails(cell):
    res = _run(cell, control=True)
    assert not res["correct"], res["compared"]
    assert res["compared"]["fields_rel_err"]["value"] > \
        res["compared"]["fields_rel_err"]["limit"]


def test_a_lower_precision_state_fails_the_judge():
    from yardstick import compare

    ok, out = compare.judge({"fields_rel_err": float("nan")},
                            {"fields_rel_err": 1.0})
    assert not ok and out["fields_rel_err"]["limit"] == 1.0
    a = jnp.linspace(1.0, 2.0, 64, dtype=jnp.float32).reshape(4, 4, 4)
    gap = compare.fields_rel_err({"T": a.astype(jnp.bfloat16)}, {"T": a})
    assert gap > 1e-4
