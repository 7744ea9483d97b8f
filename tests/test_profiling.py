"""Compiler/device observability plane: compile telemetry, roofline
accounting.

The contracts under test:

- every compile point feeds ONE event stream (``obs/profiling.py``) with
  the fresh-vs-AOT split: a BatchedPotential bucket compile records
  ``fresh``; a replica restarted onto a warm AOT cache records ``aot``
  rehydrates and keeps ``compile_count == 0`` (the restart gate);
- ``jaxpr_flop_estimate`` is dot_general-exact; roofline rows derive
  intensity/achieved/MFU without a chip, and record-derived rows
  tolerate mixed rounds where only some records carry FLOP estimates.
"""

import os

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
    # "cache": the same build served by jax's persistent compile cache
    assert counts.get("fresh", 0) + counts.get("cache", 0) >= 1
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
