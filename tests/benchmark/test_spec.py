"""The harness is driven by data: every cell loads from files, and a cell,
a configuration, a mix and a per-layer metric added as NEW files and
entries are found without editing a file that exists."""

from __future__ import annotations

import json
import os

import pytest

import toy
from benchmark.harness import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_from_files(name):
    cell = spec.load_cell(name)
    assert cell.traffic["driver"] == "md"
    assert cell.config["family"] in ("mace", "tensornet")
    assert {m["name"] for m in cell.end_to_end} >= {
        "setup_s", "atom_steps_per_s_per_chip"}
    assert cell.per_layer and cell.limits
    spec.load_module(cell, "drivers", cell.traffic["driver"])
    spec.load_module(cell, "families", cell.config["family"])
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        assert callable(read) and params["reader"]
    # fixed capacities for this configuration: every seed, one executable
    assert cell.config_name in cell.traffic["caps"]


@pytest.mark.parametrize("name", CELLS)
def test_only_cells_across_chips_take_four(name):
    cell = spec.load_cell(name)
    assert cell.chips == (4 if name.endswith("-4c") else 1)


def test_additions_are_found_without_editing(tmp_path):
    root = toy.make_root(str(tmp_path))
    cell = spec.load_cell("toy-md", root)
    assert cell.config["model"] == toy.TOY_MODELS["mace"]
    assert cell.traffic["structure"]["reps"] == [3, 3, 3]
    added = [m for m in cell.per_layer if m["name"] == "toy.steps"]
    read, _ = spec.load_reader(cell, added[0])
    assert read({"steps": 5}, {}) == 5
    # and the committed cells still load from the same tree
    assert spec.load_cell(CELLS[0], root).name == CELLS[0]
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_and_units(metric):
    assert spec.NAME.match(metric["name"]), metric["name"]
    assert spec.UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads") for x in BENCH[key]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(spec.NAME.match(n) for n in names)
    for config in BENCH["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert config["file"].startswith(tuple(BENCH["paths"]))
        with open(os.path.join(spec.ROOT, config["file"])) as f:
            body = json.load(f)
        assert set(config["reduced"]) == set(body["reduced"])
        width = ("channels", "units", "radial_mlp", "num_rbf", "num_bessel")
        assert not set(body["reduced"]) & set(width)
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(layer) <= 200 and "\n" not in layer for layer in layers)
    assert any("mfu" in m["name"].split(".") for m in BENCH["per_layer"])
