"""NequIP's energy-and-forces step at jaxpr level, the twin of
``tests/test_chgnet_stages.py``: every equation of the model carries a
stage (what the benchmark's ``model.unattributed_share.md`` reads on the
chip); the gate reads under its own stage ``node_gate``, ``radial_mlp``
holds the MLP alone, the partitions exchange the flat rows four times, and
no other model's step knows the new stage. Since PR 35 a scan's chunk body
is the one thing NequIP and MACE checkpoint: no ``remat2`` lies inside
another, and forces agree with the same model at ``remat=False``.
"""

import dataclasses
import re
from collections import Counter

import jax
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.geometry import frac_to_cart, make_supercell
from distmlip_tpu.models import (CHGNet, CHGNetConfig, ESCN, ESCNConfig,
                                 ESCNMD, ESCNMDConfig, MACE, MACEConfig,
                                 NequIP, NequIPConfig, TensorNet,
                                 TensorNetConfig)
from distmlip_tpu.telemetry import STAGES
from distmlip_tpu.telemetry.stages import stage_of

# what NequIP has code for; the rest are other families'
NEQUIP = {"edge_geometry", "edge_gather", "radial_mlp", "edge_message",
          "edge_aggregate", "node_linear", "node_gate", "readout", "halo"}
LOOPS = ("scan", "while")


def config(**kw):
    return NequIPConfig(**{**dict(
        num_species=20, irreps=((8, 4, 2),) * 4 + ((8,),), num_bessel=6,
        radial_hidden=(8, 8), cutoff=3.5, cutoff_on=3.0,
        avg_num_neighbors=12.0, edge_chunk=256), **kw})


def atoms_of(nparts=1):
    rng = np.random.default_rng(7)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = make_supercell(unit, np.eye(3) * 3.9, (3 * nparts, 2, 2))
    cart = frac_to_cart(frac, lattice) + rng.normal(0, 0.03, (len(frac), 3))
    numbers = np.where(np.arange(len(cart)) % 3 == 0, 8, 14)
    return Atoms(numbers=numbers, positions=cart, cell=lattice)


def step_sites(model, nparts=1, **kw):
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.3, **kw)
    graph, _, positions = pot._prepare(atoms_of(nparts))
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    return [s for s in iter_sites(jaxpr) if "model_energy" in s.stack]


def test_the_gate_is_a_declared_stage():
    assert "node_gate" in STAGES and NEQUIP <= set(STAGES)
    base = "jit(potential)/energy_and_grad/jvp(model_energy)/convolution2/"
    assert stage_of(base + "node_gate/logistic") == "node_gate"
    assert stage_of(base + "transpose(jvp(node_gate))/mul") == "node_gate"
    assert stage_of(base + "checkpoint/node_linear/dot_general") == \
        "node_linear"
    assert stage_of(base + "halo/halo_exchange/ppermute") == "halo"


@pytest.mark.parametrize("nparts, kernels, dtype", [
    (1, None, "bfloat16"), (4, None, "bfloat16"), (1, "interpret", "float32"),
    (4, "interpret", "bfloat16")])
def test_every_equation_of_the_model_carries_a_stage(nparts, kernels, dtype):
    cfg = config(dtype=dtype)
    model = step_sites(NequIP(cfg), nparts, kernels=kernels)
    assert len(model) > 200
    bare = sorted({(s.primitive, s.stack) for s in model
                   if stage_of(s.stack) is None})
    # ``convolution{t}`` is no stage, as MACE's ``interaction{t}``, and no
    # equation sits directly under it: the convolution is not checkpointed
    # (PR 35), so no checkpoint call of the backward is left there
    assert bare == []
    assert not [s.stack for s in model if s.primitive == "remat2"
                and re.search(r"/convolution\d\)*$", s.stack)]
    seen = {stage_of(s.stack) for s in model} - {None}
    assert seen == NEQUIP - ({"halo"} if nparts == 1 else set())
    if kernels == "interpret":
        calls = [s for s in model if s.primitive == "pallas_call"]
        assert calls and {stage_of(s.stack) for s in calls} == {
            "edge_aggregate"}
    # the gate: activations and products, no contraction
    gate = {s.primitive for s in model if stage_of(s.stack) == "node_gate"}
    assert {"logistic", "mul", "add"} <= gate and "dot_general" not in gate
    # radial_mlp is the MLP alone: inside the scans it contracts over the
    # Bessel rows or a hidden layer, never over harmonics or channels
    dots = [s for s in model if s.primitive == "dot_general"
            and any(p in LOOPS for p in s.path) and "transpose" not in s.stack
            and "pallas_call" not in s.path]
    inner = lambda s: s.eqn.invars[1].aval.shape[0]
    radial = [s for s in dots if stage_of(s.stack) == "radial_mlp"]
    assert radial and {inner(s) for s in radial} == {cfg.num_bessel, 8}
    coupling = [s for s in dots if stage_of(s.stack) == "edge_message"]
    # the harmonics against the coupling table, channels against the tile
    assert {inner(s) for s in coupling} == {9, 8, 4, 2}
    assert {stage_of(s.stack) for s in dots} == {"radial_mlp", "edge_message"}
    # four exchanges of the whole flat row (8 + 3 x 4 + 5 x 2 numbers), one
    # coalesced buffer to each neighbour: none after the embedding, none
    # after the last convolution
    sends = [s for s in model if s.primitive == "ppermute"
             and "transpose" not in s.stack]
    if nparts > 1:
        assert {stage_of(s.stack) for s in sends} == {"halo"}
        assert len(sends) == 4 * 2
        assert all(s.eqn.invars[0].aval.shape[-1] % 30 == 0 for s in sends)
        assert not [s for s in sends if "convolution" in s.stack]
    else:
        assert not sends


OTHERS = {
    "mace": lambda: MACE(MACEConfig(
        num_species=20, channels=8, l_max=2, a_lmax=2, hidden_lmax=1,
        correlation=2, num_interactions=2, num_bessel=4, radial_mlp=8,
        radial_layers=2, cutoff=3.5, avg_num_neighbors=12.0, edge_chunk=256)),
    "tensornet": lambda: TensorNet(TensorNetConfig(
        units=8, num_rbf=4, num_layers=2, cutoff=3.5)),
    "chgnet": lambda: CHGNet(CHGNetConfig(
        num_species=20, units=8, num_rbf=5, num_angle=2, num_blocks=2,
        cutoff=3.5, bond_cutoff=3.0)),
    "escn": lambda: ESCN(ESCNConfig(
        num_species=20, channels=8, l_max=2, num_layers=2, cutoff=3.5,
        edge_chunk=256)),
    "escn_md": lambda: ESCNMD(ESCNMDConfig(
        max_num_elements=20, sphere_channels=8, lmax=2, mmax=2, num_layers=2,
        hidden_channels=8, edge_channels=8, num_distance_basis=8, cutoff=3.5,
        avg_degree=12.0, edge_chunk=256, num_experts=2)),
}


def scanning(family, **kw):
    """A toy model whose layers scan chunks, its layer scope, its layers and
    the stages of the scans in each (MACE: the edge scan and, with
    ``node_chunk`` below the node count, the node scan)."""
    if family == "mace":
        cfg = dataclasses.replace(OTHERS["mace"]().cfg, node_chunk=16, **kw)
        return MACE(cfg), "interaction", 2, ["edge_gather", "node_tensor"]
    return NequIP(config(**kw)), "convolution", 5, ["edge_gather"]


@pytest.mark.parametrize("nparts", [1, 4])
@pytest.mark.parametrize("family", ["mace", "nequip"])
def test_a_chunk_body_is_the_only_checkpoint(family, nparts):
    """One checkpoint level (PR 35): under ``remat=True`` no ``remat2``
    equation of the step lies inside another's body, and each edge scan and
    MACE's node scan has exactly one, the chunk body in its backward scan;
    so a chunk's forward runs twice a step, not three times."""
    model, layer, n_layers, scan_stages = scanning(family, remat=True)
    sites = step_sites(model, nparts)
    scans = [s for s in sites if s.primitive == "scan"]
    remats = [s for s in sites if s.primitive == "remat2"]
    assert not [s.stack for s in remats if "remat2" in s.path]
    # forward and backward of every scan; the checkpoint calls are the
    # backward scans' bodies, one each
    assert len(scans) == 2 * n_layers * len(scan_stages)
    body = lambda jaxpr: id(getattr(jaxpr, "jaxpr", jaxpr))
    backward = [body(s.eqn.params["jaxpr"]) for s in scans
                if "transpose(" in s.stack]
    assert sorted(body(s.jaxpr) for s in remats) == sorted(backward)
    where = Counter((re.search(layer + r"\d", s.stack).group(),
                     stage_of(s.stack)) for s in remats)
    assert where == {(f"{layer}{t}", stage): 1
                     for t in range(n_layers) for stage in scan_stages}


@pytest.mark.parametrize("nparts", [1, 4])
def test_forces_agree_with_the_model_that_keeps_everything(nparts):
    """The single checkpoint level still differentiates through the scans
    and the halo exchange: chunked (K > 1) float32 NequIP at ``remat=True``
    against the same model at ``remat=False``, to round-off."""
    atoms, out = atoms_of(nparts), {}
    for remat in (True, False):
        model = NequIP(config(remat=remat))
        pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                            num_partitions=nparts, skin=0.3)
        out[remat] = pot.calculate(atoms)
        assert min(pot.last_stats["n_edges_per_part"]) > 2 * 256
    scale = np.abs(out[False]["forces"]).max()
    assert scale > 1e-3
    assert abs(out[True]["energy"] - out[False]["energy"]) < \
        1e-6 * abs(out[False]["energy"])
    np.testing.assert_allclose(out[True]["forces"], out[False]["forces"],
                               atol=2e-6 * scale)


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_no_other_model_knows_the_new_stage(name):
    """Declaring ``node_gate`` moved nothing in the five older models: no
    equation of their steps resolves to it or sits under a scope of
    NequIP's, on one partition or on two."""
    for nparts in (1, 2):
        sites = step_sites(OTHERS[name](), nparts)
        assert len(sites) > 100
        assert "node_gate" not in {stage_of(s.stack) for s in sites}
        assert not [s.stack for s in sites if "node_gate" in s.stack
                    or "convolution" in s.stack]
