"""Every cell, configuration, traffic mix and metric in BENCHMARK.json is
found by name in files of its own, and the file keeps the contract's
character and size rules."""
import json
import os
import re

import pytest

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["bench"]


def test_names_units_and_metric_keys():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]] + CELLS
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", list(harness.cells()))
def test_cell_resolves_by_name(cell):
    spec = harness.find_cell(cell)
    cfg, traffic = spec["cfg"], spec["traffic"]
    assert cfg["name"] == spec["cell"]["config"]
    assert cfg["chips"] == spec["cell"]["chips"]
    system = harness.load_module("systems", cfg["system"])
    assert callable(getattr(system, traffic["entry"]))
    driver = harness.load_module("drivers", traffic["driver"])
    assert callable(driver.warm) and callable(driver.window)
    assert callable(driver.check)
    ref = harness.load_module("references", cfg["reference"])
    assert callable(ref.step) and callable(ref.params)
    limits = harness.load_json("limits", cell)["limits"]
    assert "fields_rel_err" in limits
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    path = os.path.join(harness.ROOT, cfg["file"])
    assert cfg["file"].startswith("bench/configs/")
    with open(path) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def test_every_file_name_is_made_of_name_characters():
    for dirpath, dirnames, files in os.walk(harness.BENCH):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "_out")]
        for f in files:
            if f.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), harness.ROOT)
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
