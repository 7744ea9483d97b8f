"""NequIP's energy-and-forces step at jaxpr level, the twin of
``tests/test_chgnet_stages.py``: every equation of the model carries a
stage (what the benchmark's ``model.unattributed_share.md`` reads on the
chip) but each convolution's checkpoint's own, as in MACE; the gate reads
under its own stage ``node_gate``, ``radial_mlp`` holds
the MLP alone, the partitions exchange the flat rows four times, and no other
model's step knows the new stage.
"""

import re

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
    # ``convolution{t}`` is no stage, as MACE's ``interaction{t}``: the
    # backward's checkpoint call and the sums of the chunk rows' cotangents
    # (harmonics, Bessel rows) over the five convolutions sit directly
    # under it, and nothing else does
    assert {p for p, _ in bare} == {"remat2", "add_any"}, bare[:10]
    assert all(re.search(r"/convolution\d\)*$", stack)
               for _, stack in bare), bare[:10]
    assert len([s for s in model if s.primitive == "remat2"
                and stage_of(s.stack) is None]) == 5
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
