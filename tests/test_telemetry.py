"""Telemetry subsystem: StepRecord round-trips, sinks receiving records from
a real DistPotential step (CPU), report aggregation over a synthetic run,
and the zero-overhead disabled path."""

import json

import numpy as np
import pytest

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.calculators.device_md import DeviceMD
from distmlip_tpu.models import PairConfig, PairPotential
from distmlip_tpu.telemetry import (AggregatingSink, JsonlSink, StepRecord,
                                    StderrSummarySink, Telemetry, annotate,
                                    set_tracing, tracing_enabled)
from distmlip_tpu.telemetry.report import aggregate, main as report_main, \
    read_jsonl
from distmlip_tpu.telemetry.trace import _NullContext


def make_atoms(rng, reps=(3, 3, 3), a=3.8, noise=0.03):
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


def _pot(**kw):
    model = PairPotential(PairConfig(cutoff=3.5, kind="lj"))
    params = model.init()
    params = {"eps": params["eps"] * 0.1, "sigma": params["sigma"]}
    return DistPotential(model, params, compute_stress=True, **kw)


# ---------------------------------------------------------------------------
# StepRecord schema
# ---------------------------------------------------------------------------


def test_step_record_roundtrip():
    rec = StepRecord(
        step=7, kind="md_chunk",
        timings={"neighbor_s": 0.01, "partition_s": 0.002, "device_s": 0.1,
                 "total_s": 0.115},
        n_atoms=108, num_partitions=2, n_cap=128, e_cap=2048,
        n_nodes_per_part=[64, 60], n_edges_per_part=[1500, 1400],
        node_occupancy=0.5, edge_occupancy=0.73,
        halo_send_per_part=[12, 10], halo_recv_per_part=[10, 12],
        graph_reused=True, compiled=True, compile_cache_size=3,
        device_memory={"dev0_bytes_in_use": 1 << 20},
        extra={"steps_done": 40},
    )
    back = StepRecord.from_json(rec.to_json())
    assert back == rec
    # JSONL line is a flat JSON object
    d = json.loads(rec.to_json())
    assert d["kind"] == "md_chunk" and d["extra"]["steps_done"] == 40


def test_step_record_forward_compat():
    """Unknown keys from a newer writer land in extra, not lost/crashing."""
    d = StepRecord(step=1).to_dict()
    d["future_field"] = 42
    back = StepRecord.from_dict(d)
    assert back.step == 1 and back.extra["future_field"] == 42


def test_step_record_total_and_imbalance():
    r = StepRecord(timings={"neighbor_s": 0.2, "device_s": 0.3})
    assert r.total_s == pytest.approx(0.5)
    r2 = StepRecord(halo_send_per_part=[30, 10])
    assert r2.halo_imbalance() == pytest.approx(1.5)
    assert StepRecord().halo_imbalance() == 1.0


# ---------------------------------------------------------------------------
# sinks receiving records from a real CPU DistPotential step
# ---------------------------------------------------------------------------


def test_distpotential_emits_records(rng, tmp_path):
    path = str(tmp_path / "run.jsonl")
    agg = AggregatingSink()
    tel = Telemetry([agg, JsonlSink(path)])
    pot = _pot(num_partitions=2, telemetry=tel)
    atoms = make_atoms(rng)
    for _ in range(3):
        atoms.positions += rng.normal(0, 0.01, atoms.positions.shape)
        pot.calculate(atoms)
    tel.close()

    assert agg.n_records == 3
    assert agg.totals["device_s"] > 0
    recs = read_jsonl(path)
    assert len(recs) == 3
    for r in recs:
        assert r.kind == "calculate"
        assert r.num_partitions == 2 and r.n_atoms == len(atoms)
        # per-phase timings present
        for k in ("neighbor_s", "partition_s", "device_s", "total_s"):
            assert k in r.timings
        # graph shape + padding occupancy
        assert r.n_cap > 0 and 0 < r.node_occupancy <= 1.0
        assert r.e_cap > 0 and 0 < r.edge_occupancy <= 1.0
        assert len(r.n_nodes_per_part) == 2
        # halo volumes per partition (P=2 slabs exchange both directions)
        assert len(r.halo_send_per_part) == 2
        assert all(v > 0 for v in r.halo_send_per_part)
        # every sent row is received somewhere
        assert sum(r.halo_recv_per_part) == sum(r.halo_send_per_part)
        # skin=0: every step rebuilds
        assert r.rebuild and not r.graph_reused
    # first step compiled the potential, later steps hit the executable cache
    assert recs[0].compiled
    assert recs[0].compile_cache_size >= 1
    assert not recs[-1].compiled
    # summary renders the phase table
    s = agg.summary()
    assert "device_s" in s and "records=3" in s


def test_skin_cache_hits_recorded(rng):
    agg = AggregatingSink()
    pot = _pot(num_partitions=1, skin=1.0, async_rebuild=False,
               telemetry=Telemetry([agg]))
    atoms = make_atoms(rng)
    pot.calculate(atoms)
    atoms.positions += 1e-4  # far inside the Verlet budget
    pot.calculate(atoms)
    assert agg.rebuilds == 1
    assert agg.n_records == 2


def test_device_md_chunk_records(rng):
    agg = AggregatingSink()
    pot = _pot(num_partitions=1, skin=1.0, async_rebuild=False)
    atoms = make_atoms(rng)
    atoms.set_maxwell_boltzmann_velocities(50.0, rng=rng)
    md = DeviceMD(pot, atoms, timestep=0.5, telemetry=Telemetry([agg]))
    md.run(10)
    assert agg.n_records >= 1
    assert agg.totals["device_s"] > 0
    assert agg.samples["total_s"]  # chunk wall time recorded


def test_aggregating_sink_bounded_memory():
    """Sample buffers decimate past max_samples; totals/means stay exact."""
    agg = AggregatingSink(max_samples=64)
    n = 1000
    for i in range(n):
        agg.emit(StepRecord(timings={"device_s": float(i)}))
    assert len(agg.samples["device_s"]) < 64
    s = agg.phase_stats("device_s")
    assert s["count"] == n
    assert s["total_s"] == pytest.approx(sum(range(n)))
    assert s["mean_s"] == pytest.approx(sum(range(n)) / n)
    # decimated percentiles still track the distribution
    assert abs(s["p50_s"] - n / 2) < n * 0.05
    # no halo data -> no imbalance stat claimed (matches report.py)
    assert agg.max_halo_imbalance == 0.0
    assert "max_halo_imbalance" not in agg.summary()


def test_emit_after_close_is_noop(tmp_path):
    path = str(tmp_path / "x.jsonl")
    tel = Telemetry([JsonlSink(path), AggregatingSink()])
    tel.emit(StepRecord(step=0, timings={"total_s": 0.1}))
    tel.close()
    tel.emit(StepRecord(step=1, timings={"total_s": 0.1}))  # must not raise
    assert len(read_jsonl(path)) == 1


def test_stderr_summary_sink(capsys):
    sink = StderrSummarySink(every=2)
    tel = Telemetry([sink])
    for i in range(3):
        tel.emit(StepRecord(step=i, timings={"device_s": 0.01},
                            node_occupancy=0.8, rebuild=(i == 0)))
    tel.close()
    err = capsys.readouterr().err
    # one periodic line (step 1) + one close line (step 2)
    assert err.count("# telemetry") == 2
    assert "node_occ=0.80" in err


# ---------------------------------------------------------------------------
# report aggregation
# ---------------------------------------------------------------------------


def _synthetic_run(path, n=20):
    with open(path, "w") as f:
        for i in range(n):
            rec = StepRecord(
                step=i, timings={"neighbor_s": 0.01, "device_s": 0.10,
                                 "total_s": 0.11},
                n_atoms=256, num_partitions=4, n_cap=128, e_cap=1024,
                node_occupancy=0.8, edge_occupancy=0.75,
                halo_send_per_part=[10, 11, 10, 9],
                rebuild=(i % 5 == 0), graph_reused=(i % 5 != 0))
            f.write(rec.to_json() + "\n")
        # wedge-style stall
        f.write(StepRecord(step=n, timings={"device_s": 5.0, "total_s": 5.0},
                           node_occupancy=0.8, edge_occupancy=0.7,
                           ).to_json() + "\n")
        # occupancy collapse + halo imbalance
        f.write(StepRecord(step=n + 1,
                           timings={"device_s": 0.1, "total_s": 0.11},
                           node_occupancy=0.1, edge_occupancy=0.08,
                           halo_send_per_part=[100, 5, 5, 5],
                           ).to_json() + "\n")


def test_report_aggregates_and_flags(tmp_path):
    path = str(tmp_path / "synthetic.jsonl")
    _synthetic_run(path)
    recs = read_jsonl(path)
    rep = aggregate(recs)
    assert rep.n_records == 22
    assert rep.phases["device_s"]["count"] == 22
    assert rep.phases["device_s"]["max_s"] == pytest.approx(5.0)
    assert rep.phases["neighbor_s"]["p50_s"] == pytest.approx(0.01)
    kinds = {a.kind for a in rep.anomalies}
    assert kinds == {"stall", "occupancy_collapse", "halo_imbalance"}
    txt = rep.render()
    assert "ANOMALIES" in txt and "device_s" in txt
    # per-phase table has the percentile columns
    assert "p99_ms" in rep.table()


def test_stall_detection_is_per_kind():
    """A DeviceMD chunk legitimately spans many calculate-steps of wall
    time; it must not be flagged as a stall against the calculate median."""
    recs = [StepRecord(step=i, kind="calculate",
                       timings={"total_s": 0.1}) for i in range(10)]
    recs += [StepRecord(step=10 + i, kind="md_chunk",
                        timings={"total_s": 5.0}) for i in range(4)]
    rep = aggregate(recs)
    assert not [a for a in rep.anomalies if a.kind == "stall"]
    # a genuine stall WITHIN a kind still flags
    recs.append(StepRecord(step=99, kind="md_chunk",
                           timings={"total_s": 100.0}))
    rep = aggregate(recs)
    stalls = [a for a in rep.anomalies if a.kind == "stall"]
    assert len(stalls) == 1 and stalls[0].step == 99


def test_report_cli(tmp_path, capsys):
    path = str(tmp_path / "synthetic.jsonl")
    _synthetic_run(path)
    out_json = str(tmp_path / "report.json")
    rc = report_main([path, "--json", out_json])
    assert rc == 4  # anomalies flagged
    out = capsys.readouterr().out
    assert "phase" in out and "ANOMALIES" in out
    rep = json.loads(open(out_json).read())
    assert rep["n_records"] == 22 and rep["anomalies"]
    # clean run exits 0
    clean = str(tmp_path / "clean.jsonl")
    with open(clean, "w") as f:
        for i in range(5):
            f.write(StepRecord(step=i, timings={"device_s": 0.1,
                                                "total_s": 0.1},
                               node_occupancy=0.9,
                               edge_occupancy=0.9).to_json() + "\n")
    assert report_main([clean]) == 0
    assert report_main([]) == 2  # usage


def test_report_skips_corrupt_lines(tmp_path):
    path = str(tmp_path / "trunc.jsonl")
    with open(path, "w") as f:
        f.write(StepRecord(step=0, timings={"total_s": 0.1}).to_json() + "\n")
        f.write('{"step": 1, "timings"')  # killed mid-write
    assert len(read_jsonl(path)) == 1


def test_report_mixed_compile_telemetry_records():
    """A round mixing writers — some records carry the PR-16 compile
    fields, some are old-writer JSONL without them — must aggregate and
    render without KeyErrors, with the compile split counting only the
    records that have it and percentiles unskewed by the absent fields."""
    recs = [
        StepRecord(step=0, compiled=True, compile_s=0.8,
                   compile_kind="fresh",
                   timings={"device_s": 0.9, "total_s": 0.95}),
        StepRecord(step=1, compile_s=0.01, compile_kind="aot",
                   timings={"device_s": 0.02, "total_s": 0.03}),
    ]
    # old-writer records: parsed from dicts WITHOUT the compile fields
    recs += [StepRecord.from_dict(
        {"step": 2 + i, "timings": {"device_s": 0.1, "total_s": 0.11}})
        for i in range(8)]
    rep = aggregate(recs)
    assert rep.counters["compiles_fresh"] == 1
    assert rep.counters["compiles_aot"] == 1
    assert rep.counters["compile_time_s"] == pytest.approx(0.81)
    # the old-writer majority keeps the warm percentile honest
    assert rep.phases["device_s"]["p50_s"] == pytest.approx(0.1)
    txt = rep.render()
    assert "compile: fresh=1 aot_rehydrate=1" in txt


def test_report_no_compile_fields_at_all():
    """Pure old-writer rounds carry NO compile keys — the report omits
    the section instead of inventing zeros."""
    recs = [StepRecord.from_dict(
        {"step": i, "timings": {"total_s": 0.1}}) for i in range(5)]
    rep = aggregate(recs)
    assert "compiles_fresh" not in rep.counters
    assert "compile:" not in rep.render()


def test_report_roofline_section_from_records():
    """Records carrying FLOP estimates surface a roofline table in the
    report; mixed groups without estimates degrade to fewer rows."""
    recs = [
        StepRecord(step=0, kind="batched_calculate", bucket_key="b1",
                   timings={"device_s": 0.01, "total_s": 0.02},
                   est_peak_bytes=10**6,
                   extra={"flops_per_step": 1.0e9}),
        StepRecord(step=1, kind="serve_batch",
                   timings={"device_s": 0.005, "total_s": 0.01}),
    ]
    rep = aggregate(recs)
    rows = rep.counters.get("roofline", [])
    assert len(rows) == 1
    assert rows[0]["program"] == "batched_calculate[b1]"
    assert "roofline" in rep.render()


# ---------------------------------------------------------------------------
# disabled path: zero overhead
# ---------------------------------------------------------------------------


def test_annotate_noop_when_disabled():
    assert not tracing_enabled()
    cm = annotate("distmlip/neighbor_build")
    assert isinstance(cm, _NullContext)
    # the SAME shared object every call — no per-call allocation
    assert annotate("other") is cm
    with cm:
        pass
    set_tracing(True)
    try:
        assert not isinstance(annotate("x"), _NullContext)
    finally:
        set_tracing(False)


def test_no_records_without_telemetry(rng, monkeypatch):
    """With telemetry unset, calculate() never constructs a StepRecord."""
    import distmlip_tpu.calculators.calculator as calc_mod

    def boom(*a, **kw):
        raise AssertionError("StepRecord constructed on the disabled path")

    monkeypatch.setattr(calc_mod, "StepRecord", boom)
    pot = _pot(num_partitions=1)
    res = pot.calculate(make_atoms(rng))
    assert np.isfinite(res["energy"])
    # last_timings backward-compat surface still populated
    assert pot.last_timings["device_s"] > 0


def test_disabled_hub_not_invoked(rng):
    class Exploding(AggregatingSink):
        def emit(self, record):
            raise AssertionError("sink invoked while disabled")

    tel = Telemetry([Exploding()], enabled=False)
    pot = _pot(num_partitions=1, telemetry=tel)
    res = pot.calculate(make_atoms(rng))
    assert np.isfinite(res["energy"])


# ---------------------------------------------------------------------------
# stage scopes, stage tables, host spans (PR 26)
# ---------------------------------------------------------------------------

import jax  # noqa: E402

from distmlip_tpu.analysis.ir import iter_sites  # noqa: E402
from distmlip_tpu.calculators import MolecularDynamics  # noqa: E402
from distmlip_tpu.calculators.batched import BatchedPotential  # noqa: E402
from distmlip_tpu.models import (MACE, MACEConfig, TensorNet,  # noqa: E402
                                 TensorNetConfig)
from distmlip_tpu.parallel.audit import (count_collectives,  # noqa: E402
                                         ppermutes_by_scope)
from distmlip_tpu.telemetry import STAGES, stage_tables  # noqa: E402
from distmlip_tpu.telemetry import trace as trace_mod  # noqa: E402
from distmlip_tpu.telemetry.stages import (pass_of, stage_of,  # noqa: E402
                                           stage_table)

TOY = {
    "mace": lambda: MACE(MACEConfig(
        num_species=95, channels=8, l_max=2, a_lmax=2, hidden_lmax=1,
        correlation=2, num_interactions=2, num_bessel=4, radial_mlp=8,
        radial_layers=2, cutoff=3.0, avg_num_neighbors=12.0, edge_chunk=512,
        zbl=True, remat=True)),
    "tensornet": lambda: TensorNet(TensorNetConfig(
        units=8, num_rbf=4, num_layers=2, cutoff=3.0)),
}
# what a family has no code for (the table of telemetry/stages.py)
# edge_rotation and expert_mix are eSCN-MD's (tests/test_escn_md_stages.py)
# the bond graph's four are CHGNet's (tests/test_chgnet_stages.py)
# node_gate is NequIP's (tests/test_nequip_stages.py)
ESCN_ONLY = {"edge_rotation", "expert_mix"}
BOND_GRAPH = {"line_geometry", "line_message", "angle_update", "bond_map"}
NEQUIP_ONLY = {"node_gate"}
# DimeNet++'s three (tests/test_dimenet_stages.py)
DIMENET_ONLY = {"triplet_basis", "triplet_message", "edge_update"}
NOT_IN = {"mace": ESCN_ONLY | BOND_GRAPH | NEQUIP_ONLY | DIMENET_ONLY,
          "tensornet": ESCN_ONLY | BOND_GRAPH | NEQUIP_ONLY | DIMENET_ONLY | {
              "edge_gather", "pair_repulsion"}}


def toy_potential(family, rng, nparts=1, **kw):
    model = TOY[family]()
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.3, **kw)
    atoms = make_atoms(rng, reps=(3 * nparts, 2, 2), a=3.9)
    return pot, atoms


def step_jaxpr(pot, atoms):
    graph, _, positions = pot._prepare(atoms)
    return jax.make_jaxpr(pot._potential)(pot.params, graph, positions)


@pytest.mark.parametrize("kernels", [None, "interpret"])
@pytest.mark.parametrize("family", ["mace", "tensornet"])
def test_every_stage_is_scoped_where_the_work_happens(rng, family, kernels):
    """Each declared stage is on the scope stack of some equation of the
    energy-and-forces program (two partitions, so the halo has work), and
    no contraction, scatter-add or kernel call is outside every stage."""
    pot, atoms = toy_potential(family, rng, nparts=2, kernels=kernels)
    sites = list(iter_sites(step_jaxpr(pot, atoms)))
    seen = {stage_of(site.stack) for site in sites}
    assert set(STAGES) - NOT_IN[family] <= seen
    heavy = [s for s in sites if s.primitive in (
        "dot_general", "scatter-add", "scatter_add", "pallas_call")]
    assert {s.primitive for s in heavy} >= {"dot_general"}
    if kernels == "interpret":
        assert any(s.primitive == "pallas_call" for s in heavy)
    outside = [(s.primitive, s.stack) for s in heavy
               if stage_of(s.stack) is None]
    assert not outside


def test_stage_and_pass_of_an_op_name():
    fwd = "jit(potential)/energy_and_grad/jvp(model_energy/interaction0/radial_mlp)/dot_general"
    bwd = ("jit(potential)/energy_and_grad/transpose(jvp(model_energy))/"
           "interaction0/checkpoint/edge_gather/while/body/closed_call/"
           "checkpoint/edge_aggregate/scatter-add")
    again = bwd.replace("checkpoint/edge_aggregate",
                        "checkpoint/rematted_computation/edge_message")
    assert (stage_of(fwd), pass_of(fwd)) == ("radial_mlp", "forward")
    # the innermost declared name wins
    assert (stage_of(bwd), pass_of(bwd)) == ("edge_aggregate", "backward")
    assert (stage_of(again), pass_of(again)) == ("edge_message", "recompute")
    # parallel/halo.py's own scopes are the halo stage
    assert stage_of("jit(f)/halo_exchange/halo/shift1/ppermute") == "halo"
    # a jitted function's name is no scope; an undeclared scope is no stage
    assert stage_of("jit(readout)/stress/mul") is None
    assert stage_of("") is None and pass_of("") == "forward"


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/jvp(edge_message)/mul"}
  ROOT %a = f32[8]{0} add(%m, %p), metadata={op_name="jit(f)/jvp(edge_aggregate)/add"}
}

%fused_computation.1 (q: f32[8]) -> (f32[8], f32[8]) {
  %q = f32[8]{0} parameter(0)
  %n = f32[8]{0} negate(%q), metadata={op_name="jit(f)/transpose(jvp(readout))/neg"}
  ROOT %t = (f32[8]{0}, f32[8]{0}) tuple(%n, %q)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jvp(edge_aggregate)/add"}
  %fusion.1 = (f32[8]{0}, f32[8]{0}) fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  %gte = f32[8]{0} get-tuple-element(%fusion.1), index=0
  %cc = f32[8]{0} custom-call(%gte), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(edge_aggregate))/pallas_call"}
  %copy.3 = f32[8]{0} copy(%cc)
  %copy.4 = f32[8]{0} copy(%x)
  ROOT %add.9 = f32[8]{0} add(%copy.3, %copy.4), metadata={op_name="jit(f)/add"}
}
"""


def test_stage_table_reads_fusions_by_their_root():
    rows = {r["head"]: r for r in stage_table(HLO)}
    assert set(rows) == {
        "%fusion = f32[8]{0} fusion",
        "%fusion.1 = (f32[8]{0}, f32[8]{0}) fusion",
        '%cc = f32[8]{0} custom-call custom_call_target="tpu_custom_call"',
        "%copy.3 = f32[8]{0} copy", "%copy.4 = f32[8]{0} copy",
        "%add.9 = f32[8]{0} add"}
    mixed = rows["%fusion = f32[8]{0} fusion"]
    assert mixed["stage"] == "edge_aggregate"      # the root's
    assert mixed["stages"] == ["edge_aggregate", "edge_message"]
    # no metadata of its own: the one stage its fused instructions agree on
    alone = rows["%fusion.1 = (f32[8]{0}, f32[8]{0}) fusion"]
    assert alone["stage"] == "readout" and alone["stages"] == ["readout"]
    kernel = rows['%cc = f32[8]{0} custom-call '
                  'custom_call_target="tpu_custom_call"']
    assert (kernel["stage"], kernel["pass"]) == ("edge_aggregate", "backward")
    # the compiler's own copy, without metadata: what it copies decides
    copied = rows["%copy.3 = f32[8]{0} copy"]
    assert (copied["stage"], copied["pass"], copied["inherited"]) == (
        "edge_aggregate", "backward", True)
    # a copy of a parameter has nothing to inherit
    assert rows["%copy.4 = f32[8]{0} copy"]["stage"] is None


REMAT_LOOP_HLO = """HloModule jit_f

%fused_piece (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/jvp(model/edge_message)/mul"}
}

%fused_back (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%p), metadata={op_name="jit(f)/transpose(jvp(model))/edge_message/neg"}
}

%inner_body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %v = f32[8]{0} get-tuple-element(%s), index=1
  %deep = f32[8]{0} add(%v, %v), metadata={op_name="jit(f)/jvp(model/node_tensor)/add"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %deep)
}

%inner_cond (s: (s32[], f32[8])) -> pred[] {
  %s = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  ROOT %lt = pred[] compare(%i, %i), direction=LT
}

%body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %v = f32[8]{0} get-tuple-element(%s), index=1
  %piece = f32[8]{0} fusion(%v), kind=kLoop, calls=%fused_piece, metadata={op_name="jit(f)/jvp(model/edge_message)/mul"}
  %again = f32[8]{0} multiply(%piece, %v), metadata={op_name="jit(f)/transpose(jvp(model))/checkpoint/rematted_computation/edge_message/mul"}
  %back = f32[8]{0} fusion(%again), kind=kLoop, calls=%fused_back, metadata={op_name="jit(f)/transpose(jvp(model))/edge_message/neg"}
  %st = (s32[], f32[8]{0}) tuple(%i, %back)
  %nested = (s32[], f32[8]{0}) while(%st), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(f)/jvp(model/node_tensor)/while"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %back)
}

%cond (s: (s32[], f32[8])) -> pred[] {
  %s = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  ROOT %lt = pred[] compare(%i, %i), direction=LT
}

%fwd_body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %v = f32[8]{0} get-tuple-element(%s), index=1
  %first = f32[8]{0} fusion(%v), kind=kLoop, calls=%fused_piece, metadata={op_name="jit(f)/jvp(model/edge_message)/mul"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %first)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %st = (s32[], f32[8]{0}) tuple(%z, %x)
  %scan = (s32[], f32[8]{0}) while(%st), condition=%cond, body=%fwd_body, metadata={op_name="jit(f)/jvp(model/edge_gather)/while"}
  %loop = (s32[], f32[8]{0}) while(%scan), condition=%cond, body=%body, metadata={op_name="jit(f)/transpose(jvp(model))/edge_gather/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%loop), index=1
}
"""


def test_an_instruction_inherits_the_pass_of_the_loop_it_runs_in():
    """A checkpointed scan body's pieces keep the forward's ``op_name`` (no
    ``rematted_computation``) in the backward scan's ``while``: they run
    when that loop runs, so their pass is ``recompute``. ``mace-md-1c``
    read 116.5 ms a step of them as ``forward`` (PERF.md section 5, PR 35)."""
    rows = {r["head"].split(" = ")[0]: r for r in stage_table(REMAT_LOOP_HLO)}
    piece = rows["%piece"]
    assert (piece["stage"], piece["pass"], piece["pass_inherited"]) == (
        "edge_message", "recompute", True)
    # what says its pass itself keeps it, unmarked
    assert rows["%again"]["pass"] == "recompute"
    assert rows["%back"]["pass"] == "backward"
    assert "pass_inherited" not in rows["%again"]
    assert "pass_inherited" not in rows["%back"]
    # a loop within the loop, and its body, run there too
    assert (rows["%nested"]["pass"], rows["%deep"]["pass"],
            rows["%deep"]["pass_inherited"]) == ("recompute", "recompute",
                                                 True)
    # the same fusion in the forward scan stays forward, as do the loops
    assert rows["%first"]["pass"] == "forward"
    assert "pass_inherited" not in rows["%first"]
    assert rows["%scan"]["pass"] == "forward"
    assert rows["%loop"]["pass"] == "backward"


@pytest.fixture
def fresh_session(monkeypatch):
    """The module's session state, emptied for one test."""
    monkeypatch.setattr(trace_mod, "_stage_tables", [])
    monkeypatch.setattr(trace_mod, "_noted", {})
    yield
    set_tracing(False)


def test_stage_table_of_a_compiled_step_outlives_the_potential(
        rng, fresh_session):
    pot, atoms = toy_potential("mace", rng)
    md = MolecularDynamics(atoms, pot, ensemble="nve", timestep=0.05)
    set_tracing(True)
    md.step()
    md.step()
    assert stage_tables() == []          # built when the session closes
    set_tracing(False)
    pot.close()
    del pot, md
    jax.clear_caches()
    (table,) = stage_tables()
    assert table["executable"] == "potential" and "error" not in table
    rows = table["instructions"]
    by_stage = {s: [r for r in rows if r["stage"] == s] for s in STAGES}
    # one partition: the halo has no work
    assert all(by_stage[s] for s in STAGES
               if s != "halo"
               and s not in ESCN_ONLY | BOND_GRAPH | NEQUIP_ONLY
               | DIMENET_ONLY)
    passes = {r["pass"] for r in rows}
    assert passes == {"forward", "backward", "recompute"}
    assert any(r["pass"] != "forward" for r in by_stage["edge_aggregate"])
    # plain data: no executable, no buffer, no text of the module
    text = json.dumps(stage_tables())
    assert json.loads(text) == stage_tables() and "HloModule" not in text
    assert all(set(r) <= {"head", "stage", "pass", "stages", "inherited"}
               for r in rows)


class Counting:
    """A jitted callable that counts its calls and its lowerings."""

    def __init__(self, fn):
        self.fn, self.calls, self.lowerings = fn, 0, 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)

    def lower(self, *args):
        self.lowerings += 1
        return self.fn.lower(*args)


def test_nothing_is_noted_or_lowered_while_tracing_is_off(
        rng, fresh_session):
    pot, atoms = toy_potential("tensornet", rng)
    pot._potential = counted = Counting(pot._potential)
    pot.calculate(atoms)
    pot.calculate(atoms)
    assert (counted.calls, counted.lowerings) == (2, 0)
    assert trace_mod._noted == {} and stage_tables() == []
    # one session: the step is noted at each dispatch, lowered once at
    # the close and not while the session is open
    set_tracing(True)
    pot.calculate(atoms)
    pot.calculate(atoms)
    assert counted.lowerings == 0 and len(trace_mod._noted) == 1
    set_tracing(False)
    assert (counted.calls, counted.lowerings) == (4, 1)
    assert trace_mod._noted == {} and len(stage_tables()) == 1


@pytest.fixture
def spans(monkeypatch):
    """Every TraceAnnotation as (name, names of the spans open around
    it)."""
    seen, open_ = [], []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append((self.name, tuple(open_)))
            open_.append(self.name)

        def __exit__(self, *exc):
            open_.pop()
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    return seen


PARTS = ["distmlip/dispatch", "distmlip/wait", "distmlip/results_to_host"]


def test_host_spans_reach_the_last_copy(rng, spans, fresh_session):
    pot, atoms = toy_potential("tensornet", rng)
    md = MolecularDynamics(atoms, pot, ensemble="nve", timestep=0.05)
    md.step()
    assert spans == []                   # only while tracing is on
    set_tracing(True)
    md.step()
    set_tracing(False)
    md.step()
    call = ("distmlip/integrate", "distmlip/calculate")
    assert spans == [
        ("distmlip/integrate", ()),
        ("distmlip/calculate", call[:1]),
        ("distmlip/prepare", call),
        ("distmlip/positions_upload", call + ("distmlip/prepare",)),
        ("distmlip/potential", call),
        *[(name, call + ("distmlip/potential",)) for name in PARTS]]


def test_batched_potential_has_the_same_spans(rng, spans, fresh_session):
    model = TOY["tensornet"]()
    pot = BatchedPotential(model, model.init(jax.random.PRNGKey(0)))
    batch = [make_atoms(rng, reps=(2, 2, 2), a=3.9),
             make_atoms(rng, reps=(2, 2, 1), a=3.9)]
    pot.calculate(batch)
    assert spans == []
    set_tracing(True)
    pot.calculate(batch)
    set_tracing(False)
    names = [name for name, _ in spans]
    assert names[:2] == ["distmlip/calculate", "distmlip/prepare"]
    assert names[-4:] == ["distmlip/batched_potential", *PARTS]
    assert all(around == ("distmlip/calculate", "distmlip/batched_potential")
               for _, around in spans[-3:])
    assert [t["executable"] for t in stage_tables()] == ["potential"]


@pytest.mark.parametrize("family, before", [("mace", 7), ("tensornet", 8)])
def test_ppermutes_by_scope_are_unchanged(rng, family, before):
    """The new scopes sit around parallel/halo.py's, not in their place:
    every ppermute is still counted under ``halo_exchange``, as many as
    on the commit before the stages (counted there with this very
    program)."""
    pot, atoms = toy_potential(family, rng, nparts=2)
    jaxpr = step_jaxpr(pot, atoms)
    scopes = ppermutes_by_scope(jaxpr)
    total = count_collectives(jaxpr).get("ppermute", 0)
    assert sum(scopes.values()) == total
    assert all("halo_exchange" in s and stage_of(s) == "halo"
               for s in scopes)
    assert total == before
