"""``readers/program_phase.py`` on a hand-made run and phase log (answers
worked out by hand), and the ten metrics of PR 36 as the harness finds
them: files beside the others, entries at the end of ``BENCHMARK.json``."""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

from benchmark.harness import spec
from distmlip_tpu.telemetry import trace as program_trace

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SETUP_METRICS = {
    "entry.import_s.md": ("s", "Entry points"),
    "potential.graph_build_s.md": (
        "s", "Potentials (calculators/calculator.py)"),
    "model.trace_lower_s.md": ("s", "Models (models/*.py, whole step)"),
    "device.compile_or_load_s.md": ("s", "Device"),
    "device.setup_executables.md": ("count", "Device"),
    "potential.first_call_wait_s.md": (
        "s", "Potentials (calculators/calculator.py)"),
    "entry.setup_uncovered_share.md": ("%", "Entry points"),
}
SPLIT_METRICS = {
    "potential.prepare_ms_per_step.md": "distmlip/prepare",
    "potential.dispatch_ms_per_step.md": "distmlip/dispatch",
    "potential.results_to_host_ms_per_step.md": "distmlip/results_to_host",
}
# set-up from 100.0 (the log's earliest stamp) to 120.0 (the first timed
# step): 20 s. Thread ids are arbitrary.
LOG = [
    ("distmlip/import", 100.0, 101.0, 1),
    # the entry point's own jit (weights), before any potential exists
    ("jax/trace", 101.5, 102.5, 1),
    ("jax/lower", 102.5, 102.75, 1),
    ("jax/backend_compile", 102.75, 103.0, 1),
    ("distmlip/runtime_build", 103.0, 103.5, 1),
    ("distmlip/neighbor_build", 104.0, 105.0, 1),
    ("distmlip/partition", 105.0, 106.5, 1),
    ("distmlip/graph_upload", 106.5, 107.0, 1),
    # the step's trace, a nested jit's inside it, the lowering after it
    ("jax/trace", 107.0, 110.0, 1),
    ("jax/trace", 108.0, 109.0, 1),
    ("jax/lower", 110.0, 111.0, 1),
    ("jax/cache_retrieval", 111.2, 111.8, 1),
    ("jax/backend_compile", 111.0, 112.0, 1),
    # a small executable on another thread, overlapping the graph build
    ("jax/backend_compile", 104.5, 104.75, 2),
    ("distmlip/first_call.prepare", 104.0, 107.0, 1),
    ("distmlip/first_call.dispatch", 107.0, 112.0, 1),
    ("distmlip/first_call.wait", 112.0, 114.0, 1),
    ("distmlip/first_call.results_to_host", 114.0, 114.5, 1),
    # a rebuild that straddles the first timed step, one wholly after it
    ("distmlip/device_rebuild", 119.0, 121.0, 1),
    ("jax/backend_compile", 125.0, 130.0, 1),
]


class Planes:
    """All the reader asks of a trace: whether a device is in it."""

    def __init__(self, *names):
        self.names = list(names)

    def device_planes(self):
        return self.names


RUN = {"trace": Planes("/device:TPU:0"),
       "spans": [("bench/calculate", 118.0, 118.5),
                 ("bench/md_step", 120.0, 122.0),
                 ("bench/md_step", 122.0, 124.0)]}
EXPECTED = {
    "entry.import_s.md": 1.0,
    # 0.5 + 1.0 + 1.5 + 0.5, and the second of the rebuild before 120.0
    "potential.graph_build_s.md": 4.5,
    # inside the first call's dispatch alone, the union 107-111: the nested
    # trace's second counts once (sum: 5.0), the weights' jit not at all
    "model.trace_lower_s.md": 4.0,
    "device.compile_or_load_s.md": 1.5,
    "device.setup_executables.md": 3,
    "potential.first_call_wait_s.md": 2.0,
    # covered: 100-101, 101.5-103.5, 104-114.5, 119-120 = 14.5 of 20 s
    "entry.setup_uncovered_share.md": 27.5,
}


def entry(name: str) -> dict:
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


def read(name: str, run: dict):
    cell = spec.load_cell(CELLS[0])
    reader, params = spec.load_reader(cell, entry(name))
    return reader(run, params)


@pytest.fixture
def log(monkeypatch):
    monkeypatch.setattr(program_trace, "phases", lambda: list(LOG))
    # pytest's own __main__ has no T_START: the log's earliest stamp
    assert not isinstance(getattr(sys.modules["__main__"], "T_START", None),
                          float)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_log(name, log):
    assert read(name, RUN) == pytest.approx(EXPECTED[name])


def test_union_is_not_the_sum_and_the_parts_make_the_interval(log, capsys):
    nested = [b - a for n, a, b, _ in LOG
              if n in ("jax/trace", "jax/lower") and a >= 107.0]
    assert sum(nested) == 5.0 and read("model.trace_lower_s.md", RUN) == 4.0
    share = read("entry.setup_uncovered_share.md", RUN)
    line = next(l for l in capsys.readouterr().err.splitlines()
                if l.startswith("[bench] setup_phases "))
    split = json.loads(line[len("[bench] setup_phases "):])
    assert split["interval_s"] == 20.0 and split["covered_s"] == 14.5
    assert split["covered_s"] + share / 100.0 * split["interval_s"] == \
        pytest.approx(split["interval_s"])
    # 100-101 | 0.5 | 101.5-103.5 | 0.5 | 104-114.5 | 4.5 | 119-120
    assert split["longest_uncovered"] == [
        [4.5, "distmlip/first_call.results_to_host",
         "distmlip/device_rebuild"],
        [0.5, "distmlip/runtime_build", "distmlip/neighbor_build"],
        [0.5, "distmlip/import", "jax/trace"]]
    assert sum(g[0] for g in split["longest_uncovered"]) == \
        split["interval_s"] - split["covered_s"]
    assert split["s_and_count_by_phase"]["jax/trace"] == [4.0, 3]
    assert split["s_and_count_by_phase"]["distmlip/device_rebuild"] == [1.0, 1]
    assert "jax/cache_retrieval" in split["s_and_count_by_phase"]


def test_the_process_start_is_mains_t_start_where_it_is_a_float(
        log, monkeypatch):
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 95.0,
                        raising=False)
    assert read("entry.import_s.md", RUN) == 1.0
    # covered 14.5 of the 25 s from 95.0
    assert read("entry.setup_uncovered_share.md", RUN) == pytest.approx(42.0)
    # a start inside a phase clips it
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.25)
    assert read("entry.import_s.md", RUN) == 0.75
    monkeypatch.setattr(sys.modules["__main__"], "T_START", "soon")
    assert read("entry.import_s.md", RUN) == 1.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_to_read_is_none(name, monkeypatch):
    # an empty log
    monkeypatch.setattr(program_trace, "phases", lambda: [])
    assert read(name, RUN) is None
    # a run without a timed step
    monkeypatch.setattr(program_trace, "phases", lambda: list(LOG))
    assert read(name, {**RUN, "spans": [("bench/calculate", 1., 2.)]}) is None
    assert read(name, {**RUN, "spans": []}) is None
    # the CPU of the tests: no device plane, or no trace at all
    assert read(name, {**RUN, "trace": Planes()}) is None
    assert read(name, {**RUN, "trace": None}) is None
    # a program from before the log (the parent of PR 36)
    monkeypatch.delattr(program_trace, "phases")
    assert read(name, RUN) is None


def test_a_phase_the_set_up_never_ran_reads_zero_not_none(monkeypatch):
    monkeypatch.setattr(program_trace, "phases", lambda: [LOG[0]])
    assert read("device.compile_or_load_s.md", RUN) == 0.0
    assert read("device.setup_executables.md", RUN) == 0
    assert read("entry.setup_uncovered_share.md", RUN) == pytest.approx(95.0)


@pytest.mark.parametrize("name", sorted(SETUP_METRICS) + sorted(SPLIT_METRICS))
def test_new_metric_is_an_added_file_and_a_last_entry(name):
    metric = entry(name)
    assert "workloads" not in metric          # every cell has a set-up
    assert metric["better"] == "lower"
    if name in SETUP_METRICS:
        unit, layer = SETUP_METRICS[name]
        assert (metric["unit"], metric["layer"], metric["moves"]) == (
            unit, layer, "setup_s")
        assert metric["source"] == ("program_counter" if unit == "count"
                                    else "program_span")
    else:
        assert (metric["unit"], metric["moves"], metric["source"]) == (
            "ms", "atom_steps_per_s_per_chip", "program_span")
    with open(os.path.join(spec.ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        params = json.load(f)
    assert params["reader"] == ("program_phase" if name in SETUP_METRICS
                                else "host_span_ms_per_step")
    assert os.path.exists(os.path.join(
        spec.ROOT, "benchmark", "readers", params["reader"] + ".py"))
    # added at the end: the accepted entries keep their places
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) >= len(names) - 10


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_reports_the_ten(cell_name):
    cell = spec.load_cell(cell_name)
    reported = {m["name"] for m in cell.per_layer}
    assert reported >= set(SETUP_METRICS) | set(SPLIT_METRICS)
    for metric in cell.per_layer:
        reader, params = spec.load_reader(cell, metric)
        assert callable(reader) and params["reader"]
    # the sum the ledger has stays, beside its three parts
    assert "potential.host_ms_per_step.md" in reported


def test_phase_and_span_names_are_the_programs():
    """Every name a metric file picks is a string the program records."""
    source = ""
    for path in ("distmlip_tpu/calculators/calculator.py",
                 "distmlip_tpu/calculators/batched.py",
                 "distmlip_tpu/telemetry/trace.py",
                 "distmlip_tpu/__init__.py"):
        with open(os.path.join(spec.ROOT, path)) as f:
            source += f.read()
    recorded = set(re.findall(r'"((?:distmlip|jax)/[\w./]+)"', source))
    for name in list(SETUP_METRICS) + list(SPLIT_METRICS):
        with open(os.path.join(spec.ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            params = json.load(f)
        picked = (params.get("phases", []) + params.get("spans", [])
                  + params.get("within", []))
        assert set(picked) <= recorded, (name, set(picked) - recorded)
        if name in SPLIT_METRICS:
            assert picked == [SPLIT_METRICS[name]]
    with open(os.path.join(spec.ROOT, "benchmark", "metrics",
                           "potential.host_ms_per_step.md.json")) as f:
        assert sorted(json.load(f)["spans"]) == sorted(SPLIT_METRICS.values())
    calculator = open(os.path.join(
        spec.ROOT, "distmlip_tpu/calculators/calculator.py")).read()
    for span in SPLIT_METRICS.values():
        assert f'annotate("{span}")' in calculator
