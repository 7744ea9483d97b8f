"""Compiler/device observability plane: compile telemetry, roofline
accounting, perf-regression baseline gate.

The contracts under test:

- every compile point feeds ONE event stream (``obs/profiling.py``) with
  the fresh-vs-AOT split: a BatchedPotential bucket compile records
  ``fresh``; a replica restarted onto a warm AOT cache records ``aot``
  rehydrates and keeps ``compile_count == 0`` (the restart gate);
- ``jaxpr_flop_estimate`` is dot_general-exact; roofline rows derive
  intensity/achieved/MFU without a chip, and record-derived rows
  tolerate mixed rounds where only some records carry FLOP estimates;
- ``tools/perf_gate.py`` classifies identity rounds ok (exit 0),
  synthetic regressions as regressions (exit 3), respects the
  allow-list, rejects malformed baselines (exit 2), and the
  ``--check-schema`` self-test catches a comparator that stops doing
  any of that.
"""

import json
import os
import sys

import numpy as np
import pytest

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, BatchedPotential
from distmlip_tpu.models import PairConfig, PairPotential
from distmlip_tpu.obs import Observability, profiling, uninstall
from distmlip_tpu.obs.roofline import (RooflineRow, bytes_touched,
                                       format_roofline_table,
                                       jaxpr_flop_estimate,
                                       rows_from_records)
from distmlip_tpu.telemetry import StepRecord

pytestmark = [pytest.mark.profiling, pytest.mark.tier1]

REPO = os.path.join(os.path.dirname(__file__), "..")


def make_atoms(n=16, seed=0, a=3.6):
    rng = np.random.default_rng(seed)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    reps = (2, 2, 1) if n >= 16 else (1, 1, 1)
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.02, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart,
                 cell=lattice)


@pytest.fixture(scope="module")
def pair():
    model = PairPotential(PairConfig(cutoff=4.0))
    return model, model.init()


@pytest.fixture(autouse=True)
def _fresh_compile_log():
    profiling.reset_compile_log()
    yield
    profiling.reset_compile_log()
    uninstall()


# ---------------------------------------------------------------------------
# compile telemetry: the event log + metrics registry
# ---------------------------------------------------------------------------


def test_compile_log_records_and_resets():
    profiling.record_compile(site="test", kind=profiling.KIND_FRESH,
                             wall_s=0.25, bucket_key="n=64/e=256/B=1")
    profiling.record_compile(site="test", kind=profiling.KIND_AOT,
                             wall_s=0.01, executable_bytes=1234)
    evs = profiling.compile_events()
    assert [e.kind for e in evs] == ["fresh", "aot"]
    assert evs[0].bucket_key == "n=64/e=256/B=1"
    assert evs[1].executable_bytes == 1234
    assert profiling.compile_counts() == {"fresh": 1, "aot": 1}
    d = evs[0].as_dict()
    assert d["site"] == "test" and d["wall_s"] == 0.25
    profiling.reset_compile_log()
    assert profiling.compile_counts() == {}


def test_compile_events_feed_metrics_registry():
    hub = Observability.enable()
    profiling.record_compile(site="batched_bucket",
                             kind=profiling.KIND_FRESH, wall_s=0.5)
    profiling.record_compile(site="aot_dispatch",
                             kind=profiling.KIND_AOT, wall_s=0.002)
    text = hub.metrics.render()
    assert ('distmlip_compiles_total{site="batched_bucket",kind="fresh"} 1'
            in text)
    assert ('distmlip_compiles_total{site="aot_dispatch",kind="aot"} 1'
            in text)
    assert "distmlip_compile_seconds_bucket" in text


def test_record_compile_survives_broken_registry(monkeypatch):
    """A broken metrics backend must not fail a compile that succeeded."""

    class Boom:
        def histogram(self, *a, **k):
            raise RuntimeError("metrics backend down")

        def counter(self, *a, **k):
            raise RuntimeError("metrics backend down")

    from distmlip_tpu.obs import runtime as obsrt

    monkeypatch.setattr(obsrt, "metrics", lambda: Boom())
    ev = profiling.record_compile(site="x", kind="fresh", wall_s=0.1)
    assert ev.wall_s == 0.1
    assert profiling.compile_counts() == {"fresh": 1}


def test_batched_bucket_compile_records_fresh(pair):
    model, params = pair
    pot = BatchedPotential(model, params)
    pot.calculate([make_atoms(seed=1)])
    counts = profiling.compile_counts()
    assert counts.get("fresh", 0) >= 1
    assert not counts.get("aot", 0)
    # warm repeat (same bucket): no new events
    n0 = len(profiling.compile_events())
    pot.calculate([make_atoms(seed=2)])
    assert len(profiling.compile_events()) == n0


def test_aot_restart_gate_splits_fresh_vs_aot(pair, tmp_path):
    """First potential compiles FRESH and exports; a 'restarted' second
    potential on the same cache dir REHYDRATES: aot events, and the
    restart gate's compile_count == 0 still holds."""
    from distmlip_tpu.fleet import install_aot_cache

    model, params = pair
    cache_dir = str(tmp_path / "aot")
    pot1 = BatchedPotential(model, params)
    install_aot_cache(pot1, cache_dir)
    pot1.calculate([make_atoms(seed=3)])
    counts = profiling.compile_counts()
    assert counts.get("fresh", 0) >= 1
    assert pot1.aot_cache.stats()["saved"] >= 1

    pot2 = BatchedPotential(model, params)
    install_aot_cache(pot2, cache_dir)
    pot2.calculate([make_atoms(seed=4)])  # same shape bucket
    counts = profiling.compile_counts()
    assert counts.get("aot", 0) >= 1, counts
    assert pot2.compile_count == 0        # the restart gate
    assert pot2.aot_cache.stats()["rehydrated"] >= 1
    aot_evs = [e for e in profiling.compile_events() if e.kind == "aot"]
    assert aot_evs[0].executable_bytes > 0


def test_metrics_label_cardinality_cap_overflows_to_other():
    from distmlip_tpu.obs import MetricsRegistry, parse_exposition

    reg = MetricsRegistry(max_label_children=4)
    fam = reg.counter("x_total", "cardinality probe", labels=("k",))
    for i in range(10):
        fam.labels(k=f"v{i}").inc()
    vals = parse_exposition(reg.render())
    assert vals.get('x_total{k="_other"}', 0) == 6.0
    assert vals.get('distmlip_metrics_label_overflow_total'
                    '{metric="x_total"}', 0) == 6.0
    # capped children keep their own identity
    assert vals.get('x_total{k="v0"}') == 1.0


# ---------------------------------------------------------------------------
# roofline accounting
# ---------------------------------------------------------------------------


def test_jaxpr_flop_estimate_dot_general_exact():
    import jax
    import jax.numpy as jnp

    jaxpr = jax.make_jaxpr(lambda a, b: a @ b)(
        jnp.ones((4, 8)), jnp.ones((8, 3)))
    # 2*M*N*K = 2*4*3*8
    assert jaxpr_flop_estimate(jaxpr) == pytest.approx(192.0)
    # elementwise arithmetic: ~1 FLOP/element; data movement: 0
    jaxpr2 = jax.make_jaxpr(lambda x: (x + x).reshape(2, 8))(jnp.ones(16))
    assert jaxpr_flop_estimate(jaxpr2) == pytest.approx(16.0)


def test_bytes_touched_and_roofline_row():
    class Plan:
        arg_bytes = 1000
        const_bytes = 200
        out_bytes = 300

    assert bytes_touched(Plan()) == 1500
    r = RooflineRow(program="p", flops=3.0e9, bytes=1.5e7, time_s=0.01,
                    peak_flops=1.0e12, peak_bytes_per_s=1.0e10,
                    n_devices=2, source="measured")
    assert r.intensity == pytest.approx(200.0)
    assert r.achieved_flops == pytest.approx(3.0e11)
    assert r.mfu == pytest.approx(0.15)
    assert r.ridge_bound == "compute"
    low = RooflineRow(program="q", flops=1.0e6, bytes=1.0e6,
                      peak_flops=1.0e12, peak_bytes_per_s=1.0e10)
    assert low.ridge_bound == "memory" and low.mfu == 0.0
    # v5e's ridge is 197e12 / 819e9 ~ 240 FLOP/byte, not a constant 100
    v5e = RooflineRow(program="v", flops=2.0e9, bytes=1.0e7,
                      peak_flops=197e12, peak_bytes_per_s=819e9)
    assert v5e.intensity == pytest.approx(200.0)
    assert v5e.ridge_bound == "memory"
    unknown = RooflineRow(program="u", flops=1.0, bytes=1.0)
    assert unknown.ridge_bound == ""
    table = format_roofline_table([r, low, unknown])
    assert "p" in table and "n/a" in table
    assert r.as_dict()["mfu"] == pytest.approx(0.15)


def test_rows_from_records_mixed_round_no_keyerror():
    recs = [
        # a bench-stamped record: FLOPs + measured device time
        StepRecord(kind="batched_calculate", bucket_key="n=64/e=256/B=1",
                   timings={"device_s": 0.01}, est_peak_bytes=10**6,
                   num_partitions=2,
                   extra={"flops_per_step": 2.0e9}),
        # warm sibling without the extra — must not erase the group's flops
        StepRecord(kind="batched_calculate", bucket_key="n=64/e=256/B=1",
                   timings={"device_s": 0.02}),
        # compile step: excluded from the warm-step median
        StepRecord(kind="batched_calculate", bucket_key="n=64/e=256/B=1",
                   timings={"device_s": 9.0}, compiled=True),
        # plain serving record with no FLOP estimate: yields no row
        StepRecord(kind="serve_batch", timings={"device_s": 0.005}),
        # old-writer record parsed from JSONL (no compile fields at all)
        StepRecord.from_dict({"kind": "calculate", "step": 1}),
    ]
    rows = rows_from_records(recs)
    assert len(rows) == 1
    row = rows[0]
    assert row.program == "batched_calculate[n=64/e=256/B=1]"
    assert row.flops == pytest.approx(2.0e9)
    assert row.time_s == pytest.approx(0.02)  # median of the warm steps
    assert row.n_devices == 2 and row.source == "measured"
    assert rows_from_records([]) == []


def test_roofline_cli_time_lookup_is_longest_substring():
    import tools.roofline as rl

    times = {"train_step": 1.0, "train_step[tensornet][2x1]": 2.0}
    assert rl._lookup_time("train_step[tensornet][2x1]", times) == 2.0
    assert rl._lookup_time("train_step[tensornet][1x1]", times) == 1.0
    assert rl._lookup_time("potential[mace][1x1]", times) == 0.0


def test_roofline_cli_jsonl_times(tmp_path):
    path = tmp_path / "run.jsonl"
    recs = [
        StepRecord(kind="batched_calculate", bucket_key="b1",
                   timings={"device_s": 0.02}),
        StepRecord(kind="batched_calculate", bucket_key="b1",
                   timings={"device_s": 0.04}),
        StepRecord(kind="batched_calculate", bucket_key="b1",
                   timings={"device_s": 9.0}, compiled=True),
    ]
    path.write_text("".join(r.to_json() + "\n" for r in recs))
    import tools.roofline as rl

    times = rl._times_from_jsonl(str(path))
    assert times["b1"] == pytest.approx(0.04)  # warm median, compile skipped


# ---------------------------------------------------------------------------
# perf-regression baseline gate
# ---------------------------------------------------------------------------


@pytest.fixture()
def pg():
    import tools.perf_gate as pg

    return pg


def test_validate_baseline_schema(pg):
    good = {"schema": 1, "metrics": {
        "v": {"value": 1.0, "tolerance_frac": 0.5,
              "direction": "higher_is_better"}},
        "allow_regressions": []}
    assert pg.validate_baseline(good) == []
    assert pg.validate_baseline([]) != []
    assert pg.validate_baseline({"schema": 99, "metrics": {}}) != []
    bad_dir = {"schema": 1, "metrics": {
        "v": {"value": 1.0, "tolerance_frac": 0.5, "direction": "up"}}}
    assert any("direction" in e for e in pg.validate_baseline(bad_dir))
    bad_allow = {"schema": 1, "metrics": {
        "v": {"value": 1.0, "tolerance_frac": 0.5,
              "direction": "higher_is_better"}},
        "allow_regressions": ["ghost"]}
    assert any("ghost" in e for e in pg.validate_baseline(bad_allow))


def test_compare_statuses_and_allow_list(pg):
    base = {"schema": 1, "allow_regressions": ["lat"], "metrics": {
        "thr": {"value": 100.0, "tolerance_frac": 0.1,
                "direction": "higher_is_better"},
        "lat": {"value": 1.0, "tolerance_frac": 0.1,
                "direction": "lower_is_better"},
        "cnt": {"value": 3.0, "tolerance_frac": 0.0,
                "direction": "lower_is_better"}}}
    by = {n: s for n, s, _ in pg.compare(
        base, {"thr": 50.0, "lat": 2.0, "cnt": 3.0})}
    assert by == {"thr": "regression", "lat": "allowed_regression",
                  "cnt": "ok"}
    by = {n: s for n, s, _ in pg.compare(base, {"thr": 200.0, "cnt": 2.0})}
    assert by["thr"] == "improved" and by["cnt"] == "improved"
    assert by["lat"] == "missing"
    # within-band noise is ok in both directions
    by = {n: s for n, s, _ in pg.compare(
        base, {"thr": 95.0, "lat": 1.05, "cnt": 3.0})}
    assert set(by.values()) == {"ok"}


def test_hbm_drift_watch_runs_whenever_measured(pg):
    assert pg.hbm_drift_findings({}) == []
    flagged = pg.hbm_drift_findings({"hbm_est_over_measured": 5.0})
    assert flagged and flagged[0][1] == "regression"
    ok = pg.hbm_drift_findings({"hbm_estimator_ratio": 1.2})
    assert ok and ok[0][1] == "ok"


def test_perf_gate_cli_exit_codes(pg, tmp_path):
    result = tmp_path / "round.json"
    result.write_text("# noise line\n" + json.dumps(
        {"value": 100.0, "batched_compiles": 2, "note": "str ignored",
         "flag": True}) + "\n")
    baseline = tmp_path / "BASELINE.json"
    assert pg.main(["--input", str(result),
                    "--write-baseline", str(baseline)]) == 0
    doc = json.loads(baseline.read_text())
    assert doc["metrics"]["value"]["direction"] == "higher_is_better"
    assert doc["metrics"]["batched_compiles"]["tolerance_frac"] == 0.0
    assert "flag" not in doc["metrics"] and "note" not in doc["metrics"]

    # identity: exit 0
    assert pg.main(["--input", str(result),
                    "--baseline", str(baseline)]) == 0
    # seeded synthetic regression: exit 3
    reg = tmp_path / "regressed.json"
    reg.write_text(json.dumps({"value": 10.0, "batched_compiles": 5}))
    assert pg.main(["--input", str(reg),
                    "--baseline", str(baseline)]) == 3
    # allow-listed: back to exit 0
    doc["allow_regressions"] = ["value", "batched_compiles"]
    baseline.write_text(json.dumps(doc))
    assert pg.main(["--input", str(reg),
                    "--baseline", str(baseline)]) == 0
    # malformed baseline: exit 2
    baseline.write_text("{\"schema\": 1}")
    assert pg.main(["--input", str(result),
                    "--baseline", str(baseline)]) == 2
    # usage error: both/neither input
    assert pg.main(["--baseline", str(baseline)]) == 2


def test_perf_gate_check_schema_self_test(pg, tmp_path):
    good = tmp_path / "B.json"
    good.write_text(json.dumps({
        "schema": 1, "allow_regressions": [], "metrics": {
            "v": {"value": 1.0, "tolerance_frac": 0.5,
                  "direction": "higher_is_better"}}}))
    assert pg.main(["--check-schema", "--baseline", str(good)]) == 0
    assert pg.main(["--check-schema",
                    "--baseline", str(tmp_path / "missing.json")]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    assert pg.main(["--check-schema", "--baseline", str(bad)]) == 2


def test_no_baseline_is_committed(pg, tmp_path):
    """PR 21 deleted PERF_BASELINE.json (CPU figures under device metric
    names). Until a chip run writes the next one, --check-schema tests the
    comparator alone (the form contract_check --lint chains), and a gate
    run without --baseline has nothing to read: exit 2, not a silent
    pass."""
    assert not os.path.exists(pg.DEFAULT_BASELINE)
    assert pg.main(["--check-schema"]) == 0
    result = tmp_path / "r.json"
    result.write_text(json.dumps({"value": 1.0}))
    assert pg.main(["--input", str(result)]) == 2


def test_metrics_from_jsonl_compile_split(pg, tmp_path):
    path = tmp_path / "run.jsonl"
    recs = [
        StepRecord(kind="batched_calculate", compiled=True,
                   compile_s=0.5, compile_kind="fresh",
                   timings={"device_s": 0.6}),
        StepRecord(kind="batched_calculate", compile_s=0.01,
                   compile_kind="aot", timings={"device_s": 0.02}),
        StepRecord(kind="batched_calculate", timings={"device_s": 0.01}),
    ]
    path.write_text("".join(r.to_json() + "\n" for r in recs))
    m = pg.metrics_from_jsonl(str(path))
    assert m["compiles_fresh"] == 1.0
    assert m["compiles_aot"] == 1.0
    assert m["compile_time_s"] == pytest.approx(0.51)
    assert m["n_records"] == 3.0


def test_contract_check_lint_chains_perf_gate():
    import subprocess

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "contract_check.py"),
         "--only-lint", "--json"],
        capture_output=True, text=True, timeout=300)
    rep = json.loads(out.stdout)
    gate = rep["lint"].get("perf_gate")
    assert gate is not None and gate["returncode"] == 0, gate
