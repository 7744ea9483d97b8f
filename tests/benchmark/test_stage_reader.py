"""The readers of the per-stage metrics, on a small recorded trace with the
stage tables a tracing session would have left
(``data/small_stage_trace.json``; answers worked out by hand below), and the
whole toy run: on the CPU the program builds its tables, and the metrics
that need a device plane stay out of the result line."""

from __future__ import annotations

import json
import os

import pytest

from benchmark.harness import spec
from benchmark.harness.trace import Trace
from distmlip_tpu.telemetry import trace as session
from test_run_cli import last_line, on_cpu  # noqa: F401 - fixtures

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "small_stage_trace.json")
STAGE_METRICS = {
    # TPU:0, self times: while.1 1500 (4000 less the 1000 and 1500 inside
    # it), fusion.7 1000, closed_call.3 1500, fusion.9 2000, fusion.12
    # 1000, copy.4 500, collective-permute-start.2 500, fusion.30 1000,
    # fusion.31 1000: 10000 ns of operations over 2 traced steps
    "model.edge_prep_ms_per_step.md": (1500 + 2000) / 2 / 1e6,
    "model.edge_message_ms_per_step.md": 1000 / 2 / 1e6,
    "model.edge_aggregate_ms_per_step.md": 1500 / 2 / 1e6,
    "model.node_update_ms_per_step.md": 1000 / 2 / 1e6,
    # recompute 1500 + backward 2000 + fusion.12, whose two tables
    # disagree on the stage and agree on the pass: 1000
    "model.backward_share.md": 45.0,
    # fusion.12 (two tables, two stages), copy.4 (no declared scope),
    # fusion.30 (in no table)
    "model.unattributed_share.md": 25.0,
}
HALO_NS = 500


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return Trace.from_json(FIXTURE), json.load(f)["tables"]


@pytest.fixture
def run(recorded, monkeypatch):
    trace, tables = recorded
    monkeypatch.setattr(session, "stage_tables", lambda: tables)
    cell = spec.load_cell(spec.load_benchmark()["workloads"][0]["name"])
    return {"trace": trace, "traced_steps": 2, "steps": 2, "cell": cell}


def read(run, name):
    metric = next(m for m in spec.load_benchmark()["per_layer"]
                  if m["name"] == name)
    reader, params = spec.load_reader(run["cell"], metric)
    return reader(run, params)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_stage_metric_on_the_recorded_trace(run, name):
    assert read(run, name) == pytest.approx(STAGE_METRICS[name])


def test_stages_halo_and_unattributed_add_up_to_the_op_time(run, capsys):
    """Self times: the while counts once. The four times, the halo's and
    the unattributed add up to all the busiest device's operations did;
    the backward share is another cut of the same time."""
    own = run["trace"].self_times("/device:TPU:0")
    total = sum(own.values())
    assert total == 10000 and own["while.1_s32[]"] == 1500
    ms = sum(read(run, n) for n in STAGE_METRICS
             if n.endswith("_ms_per_step.md"))
    unattributed = read(run, "model.unattributed_share.md") / 100 * total
    assert ms * 1e6 * 2 + HALO_NS + unattributed == pytest.approx(total)
    # the metric that logs prints the whole cut once, the halo with it
    logged = json.loads(capsys.readouterr().err.split("stage_time ", 1)[1])
    assert logged["ms_per_step_by_stage"]["halo"] == HALO_NS / 2 / 1e6
    assert logged["op_self_ms_per_step"] == total / 2 / 1e6
    assert logged["longest_unattributed_ms_per_step"][0][0].startswith(
        ("fusion.12", "fusion.30"))


def test_host_spans_are_summed_over_every_host_thread(run):
    # prepare 600 + 300, dispatch 250, results_to_host 550 + 300; the wait
    # and the spans nested in them are not the potential's own host time
    assert read(run, "potential.host_ms_per_step.md") == pytest.approx(
        2000 / 2 / 1e6)


@pytest.mark.parametrize("name", [*sorted(STAGE_METRICS),
                                  "potential.host_ms_per_step.md"])
def test_nothing_to_read_without_a_device_plane(run, name):
    """A CPU's trace has host planes and no device plane: every reader
    leaves its metric out, as it does for a run that was not traced."""
    host_only = Trace([e for e in run["trace"].events
                       if not e[0].startswith("/device:")])
    assert read({**run, "trace": host_only}, name) is None
    assert read({**run, "trace": None}, name) is None


@pytest.mark.parametrize("tables", [[], None], ids=["no-session", "parent"])
def test_nothing_to_read_without_a_table(run, monkeypatch, tables):
    """No session was closed, or the program is from before the tables
    (the parent commit under this benchmark: no such function)."""
    if tables is None:
        monkeypatch.delattr(session, "stage_tables")
    else:
        monkeypatch.setattr(session, "stage_tables", lambda: tables)
    assert all(read(run, name) is None for name in STAGE_METRICS)
    # a program without the spans: nothing for the host reader either
    bare = Trace([e for e in run["trace"].events
                  if not e[2].startswith("distmlip/")])
    assert read({**run, "trace": bare},
                "potential.host_ms_per_step.md") is None


def test_traced_toy_run_builds_tables_and_keeps_the_line_whole(
        on_cpu, capsys, monkeypatch):  # noqa: F811
    monkeypatch.setattr(session, "_stage_tables", [])
    assert on_cpu(trace=1, seed=11) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is True and "breakdown" not in result
    assert not set(result["metrics"]) & (
        set(STAGE_METRICS) | {"potential.host_ms_per_step.md"})
    # the program did its part: the step's table is there, stages named,
    # after the potential was closed and jax's caches cleared
    (table,) = session.stage_tables()
    assert table["executable"] == "potential" and "error" not in table
    stages = {row["stage"] for row in table["instructions"]}
    assert {"edge_message", "edge_aggregate", "node_tensor"} <= stages
