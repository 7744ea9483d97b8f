"""Satellites of the overlap-aware halo pipeline PR: FLOP/MFU cost model,
HBM-aware prefetch guard, block-plan marker guard, latency-hiding flag
helper, telemetry field plumbing, and the halo_audit CLI."""

import json
import os

import jax
import numpy as np
import pytest

from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig
from distmlip_tpu.models.pair import PairConfig, PairPotential
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.partition import build_plan
from distmlip_tpu.telemetry import StepRecord
from distmlip_tpu.utils.flops import (mfu, model_flop_estimate,
                                      peak_flops_per_device)
from tests.utils import make_crystal

CFG = CHGNetConfig(num_species=4, units=16, num_rbf=6, num_blocks=3,
                   cutoff=3.2, bond_cutoff=2.6)


# ---------------------------------------------------------------------------
# FLOP estimate + mfu
# ---------------------------------------------------------------------------


def test_flop_estimate_scales_with_graph():
    model = CHGNet(CFG)
    f1 = model_flop_estimate(model, 100, 2000, 5000)
    f2 = model_flop_estimate(model, 200, 4000, 10000)
    assert f1 > 0
    assert 1.8 < f2 / f1 < 2.2  # edge/line-dominated: ~linear in graph size

    pair = PairPotential(PairConfig())
    assert 0 < model_flop_estimate(pair, 100, 2000) < f1

    class Unknown:
        cfg = None

    assert model_flop_estimate(Unknown(), 100, 2000) == 0.0


def test_flop_estimate_mace_tensornet():
    from distmlip_tpu.models.mace import MACE, MACEConfig
    from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig

    mace = MACE(MACEConfig(num_species=4, channels=16, l_max=2, a_lmax=2,
                           hidden_lmax=1, correlation=2, num_interactions=2,
                           num_bessel=6, radial_mlp=16, cutoff=3.0))
    tn = TensorNet(TensorNetConfig(num_species=4, units=16, num_rbf=8,
                                   cutoff=3.0))
    assert model_flop_estimate(mace, 100, 2000) > 0
    assert model_flop_estimate(tn, 100, 2000) > 0


def test_mfu_accounting():
    assert mfu(1e11, 0.5, 2, peak=1e12) == pytest.approx(0.1)
    # nothing measured -> not computed
    assert mfu(0.0, 0.5, 2, peak=1e12) is None
    assert mfu(1e11, 0.0, 2, peak=1e12) is None
    # CPU: no published peak -> not computed, never 0.0 and never fabricated
    assert peak_flops_per_device() is None
    assert mfu(1e11, 0.5, 2) is None


def test_steprecord_new_fields_roundtrip():
    rec = StepRecord(step=3, collective_count=11,
                     frontier_edge_frac=0.25, flops_per_step=1.5e9,
                     mfu=0.31, prefetch_skipped_hbm=True)
    # a file written before PR 30 carries the exchange's name: kept, in extra
    old = json.dumps({**json.loads(rec.to_json()), "halo_mode": "coalesced"})
    back = StepRecord.from_json(old)
    assert back.extra["halo_mode"] == "coalesced"
    assert back.collective_count == 11
    assert back.frontier_edge_frac == pytest.approx(0.25)
    assert back.mfu == pytest.approx(0.31)
    assert back.prefetch_skipped_hbm is True


def test_report_surfaces_pipeline_counters(tmp_path):
    from distmlip_tpu.telemetry.report import aggregate, read_jsonl

    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for i in range(4):
            f.write(StepRecord(
                step=i, timings={"total_s": 0.1, "device_s": 0.08},
                collective_count=11, mfu=0.2,
                frontier_edge_frac=0.3,
                prefetch_skipped_hbm=(i == 2)).to_json() + "\n")
    rep = aggregate(read_jsonl(str(path)))
    c = rep.counters
    assert c["collective_count"] == 11
    assert c["mean_mfu"] == pytest.approx(0.2)
    assert c["prefetch_skipped_hbm"] == 1
    text = rep.render()
    assert "halo pipeline: collectives/step=11" in text and "mfu" in text


# ---------------------------------------------------------------------------
# telemetry through DistPotential (collective_count, frontier frac, flops)
# ---------------------------------------------------------------------------


def test_calculate_emits_pipeline_telemetry(rng):
    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.telemetry import Telemetry, TelemetrySink

    class Capture(TelemetrySink):
        def __init__(self):
            self.records = []

        def emit(self, rec):
            self.records.append(rec)

    cart, lattice, species = make_crystal(rng, reps=(4, 2, 2), a=3.5)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.concatenate([[0], np.arange(0, 8)]).astype(np.int32)
    sink = Capture()
    pot = DistPotential(CHGNet(CFG), CHGNet(CFG).init(jax.random.PRNGKey(0)),
                        num_partitions=2, species_map=smap, skin=0.4,
                        telemetry=Telemetry([sink]))
    pot.calculate(atoms)
    pot.calculate(atoms)  # warm path: cached graph -> collective count known
    rec = sink.records[-1]
    assert rec.frontier_edge_frac > 0.0
    assert rec.flops_per_step > 0.0
    assert rec.collective_count > 0
    assert rec.mfu is None  # CPU: no published peak


# ---------------------------------------------------------------------------
# HBM-aware prefetch guard
# ---------------------------------------------------------------------------


def test_prefetch_skipped_when_hbm_tight(rng, monkeypatch):
    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.calculators import calculator as calc_mod

    cart, lattice, species = make_crystal(rng, reps=(4, 2, 2), a=3.5)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.concatenate([[0], np.arange(0, 8)]).astype(np.int32)
    pot = DistPotential(CHGNet(CFG), CHGNet(CFG).init(jax.random.PRNGKey(0)),
                        num_partitions=1, species_map=smap, skin=0.5,
                        prefetch_frac=0.0)
    pot.calculate(atoms)

    # pretend the live graph holds 60% of HBM -> speculation must be vetoed
    monkeypatch.setattr(calc_mod, "_hbm_usage_frac", lambda stats=None: 0.6)
    atoms.positions = atoms.positions + 0.05
    pot.calculate(atoms)
    assert pot.prefetch_skipped_hbm >= 1
    assert pot._prefetch is None

    # with headroom the speculative build launches again
    monkeypatch.setattr(calc_mod, "_hbm_usage_frac", lambda stats=None: 0.1)
    atoms.positions = atoms.positions + 0.05
    pot.calculate(atoms)
    assert pot._prefetch is not None
    pot.close()


def test_hbm_usage_frac_parsing():
    from distmlip_tpu.calculators.calculator import _hbm_usage_frac

    stats = {"dev0_bytes_in_use": 30, "dev0_bytes_limit": 100,
             "dev1_bytes_in_use": 80, "dev1_bytes_limit": 100,
             "dev0_peak_bytes_in_use": 95}
    assert _hbm_usage_frac(stats) == pytest.approx(0.8)
    assert _hbm_usage_frac({}) is None
    assert _hbm_usage_frac({"dev0_bytes_in_use": 10}) is None


# ---------------------------------------------------------------------------
# block-plan marker guard (plan.kind)
# ---------------------------------------------------------------------------


def test_block_plan_section_guard(rng):
    cart, lattice, species = make_crystal(rng, reps=(4, 4, 4), a=3.6)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 3.2, bond_r=2.7)
    slab = build_plan(nl, lattice, [1, 1, 1], 2, 3.2, 2.7, True)
    block = build_plan(nl, lattice, [1, 1, 1], 4, 3.2, 2.7, True,
                       grid=(2, 2, 1))
    assert slab.kind == "slab"
    assert block.kind == "block"
    assert build_plan(nl, lattice, [1, 1, 1], 1, 3.2).kind == "single"

    # slab sections still work; block sections raise loudly
    s, e = slab.section(0, "to", 1)
    assert e >= s
    with pytest.raises(ValueError, match="block plans"):
        block.section(0, "to", 1)
    with pytest.raises(ValueError, match="block plans"):
        block.bond_section(0, "from", 1)
    # owned_counts stays valid for every kind
    assert block.owned_counts.sum() == len(cart)


def test_edge_is_frontier_matches_layout(rng):
    cart, lattice, species = make_crystal(rng, reps=(6, 2, 2), a=3.5)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 3.2)
    plan = build_plan(nl, lattice, [1, 1, 1], 2, 3.2)
    for p in range(2):
        fr = plan.edge_is_frontier(p)
        oc = plan.owned_counts[p]
        np.testing.assert_array_equal(fr, plan.src_local[p] >= oc)
        assert 0 < fr.sum() < len(fr)  # both segments non-empty


# ---------------------------------------------------------------------------
# boundary-aligned chunk layout (chunked-model fast path under the split)
# ---------------------------------------------------------------------------


def test_chunk_layout_never_straddles_boundary():
    from distmlip_tpu.ops.chunk import chunk_layout

    # split layout: dst sorted within [0, 300) and [300, 500)
    dst = np.concatenate([np.sort(np.random.default_rng(0).integers(
        0, 50, 300)), np.sort(np.random.default_rng(1).integers(0, 50, 200))])
    row_idx, row_valid, K, chunk = chunk_layout(500, 128, 300)
    assert len(row_idx) == K * chunk
    gathered = dst[row_idx].reshape(K, chunk)
    for k in range(K):
        assert np.all(np.diff(gathered[k]) >= 0), f"chunk {k} unsorted"
    # every real row appears exactly once
    assert np.array_equal(np.sort(row_idx[row_valid]), np.arange(500))
    # unsplit degenerates to the plain layout
    ri, rv, K2, c2 = chunk_layout(500, 128, None)
    assert np.array_equal(ri[rv], np.arange(500))
    gathered = dst[ri].reshape(K2, c2)  # plain chunks may straddle; no claim
    # edgeless graph
    ri, rv, K3, c3 = chunk_layout(0, 128, None)
    assert K3 == 1 and c3 == 0 and len(ri) == 0


# ---------------------------------------------------------------------------
# XLA flags: the package sets none
# ---------------------------------------------------------------------------


def test_import_sets_no_xla_flags():
    """Importing the package and building a mesh leaves XLA_FLAGS and
    LIBTPU_INIT_ARGS as the caller set them: async collective-permute and
    the latency-hiding scheduler are the TPU compiler's defaults (PR 21
    read them off the optimised HLO on four v5e chips), so nothing is
    appended at import time any more."""
    import subprocess
    import sys

    code = (
        "import os\n"
        "before = (os.environ.get('XLA_FLAGS'),"
        " os.environ.get('LIBTPU_INIT_ARGS'))\n"
        "import distmlip_tpu\n"
        "from distmlip_tpu.parallel import graph_mesh\n"
        "graph_mesh(1)\n"
        "after = (os.environ.get('XLA_FLAGS'),"
        " os.environ.get('LIBTPU_INIT_ARGS'))\n"
        "assert before == after, (before, after)\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# halo_audit CLI
# ---------------------------------------------------------------------------


def test_halo_audit_cli(capsys):
    import tools.halo_audit as audit_cli

    rc = audit_cli.main(["--model", "pair", "--nparts", "2", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    progs = report["programs"]
    assert list(progs) == ["potential"]
    assert progs["potential"]["total"] > 0
