"""CHGNet's energy-and-forces step at jaxpr level, the twin of
``tests/test_escn_md_stages.py``: every equation of the model carries a
stage (what the benchmark's ``model.unattributed_share.md`` reads on the
chip), the bond graph's work reads under its own four stages and not as
atom-graph work, and the two index remaps resolve to ``bond_map``.
"""

import jax
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.geometry import frac_to_cart, make_supercell
from distmlip_tpu.models import CHGNet, CHGNetConfig
from distmlip_tpu.telemetry import STAGES
from distmlip_tpu.telemetry.stages import stage_of

BOND_GRAPH = ("line_geometry", "line_message", "angle_update", "bond_map")
# what CHGNet has no code for: a chunked scan, Wigner blocks, experts,
# rank-2 node products, an equivariant gate, ZBL
NOT_CHGNET = {"edge_gather", "edge_rotation", "expert_mix", "node_tensor",
              "node_gate", "pair_repulsion"}


def config(**kw):
    return CHGNetConfig(**{**dict(
        num_species=20, units=8, num_rbf=5, num_angle=2, num_blocks=3,
        cutoff=3.5, bond_cutoff=3.0), **kw})


def atoms_of(nparts=1):
    rng = np.random.default_rng(7)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = make_supercell(unit, np.eye(3) * 3.9, (3 * nparts, 2, 2))
    cart = frac_to_cart(frac, lattice) + rng.normal(0, 0.03, (len(frac), 3))
    numbers = np.where(np.arange(len(cart)) % 3 == 0, 8, 14)
    return Atoms(numbers=numbers, positions=cart, cell=lattice)


def step_sites(cfg, nparts=1, **kw):
    model = CHGNet(cfg)
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.3, **kw)
    graph, _, positions = pot._prepare(atoms_of(nparts))
    assert graph.has_bond_graph
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    return list(iter_sites(jaxpr))


def test_the_four_bond_graph_stages_are_declared():
    assert set(BOND_GRAPH) <= set(STAGES)
    base = "jit(potential)/energy_and_grad/jvp(model_energy)/"
    assert stage_of(base + "edge_to_bond/scatter") == "bond_map"
    assert stage_of(base + "transpose(jvp(bond_to_edge))/gather") == "bond_map"
    # innermost wins: the remap inside the geometry block is the remap
    assert stage_of(base + "line_geometry/edge_to_bond/mul") == "bond_map"
    assert stage_of(base + "line_geometry/halo_exchange_all/ppermute") == "halo"
    assert stage_of(base + "line_message/line_message/dot_general") == \
        "line_message"


@pytest.mark.parametrize("nparts, kernels, dtype", [
    (1, None, "bfloat16"), (4, None, "bfloat16"), (1, "interpret", "float32"),
    (4, "interpret", "bfloat16")])
def test_every_equation_of_the_model_carries_a_stage(nparts, kernels, dtype):
    sites = step_sites(config(dtype=dtype), nparts, kernels=kernels)
    model = [s for s in sites if "model_energy" in s.stack]
    assert len(model) > 200
    bare = sorted({(s.primitive, s.stack) for s in model
                   if stage_of(s.stack) is None})
    assert not bare, bare[:10]
    seen = {stage_of(s.stack) for s in model}
    expected = set(STAGES) - NOT_CHGNET - (
        {"halo"} if nparts == 1 else set())
    # the fused kernel is one operation: no separate message stage
    if kernels == "interpret":
        expected -= {"edge_message"}
    assert expected <= seen, expected - seen
    if kernels == "interpret":
        calls = [s for s in model if s.primitive == "pallas_call"]
        assert {stage_of(s.stack) for s in calls} == {"edge_aggregate",
                                                      "line_message"}


@pytest.mark.parametrize("nparts", [1, 4])
def test_three_body_work_does_not_read_as_atom_graph_work(nparts):
    """The dispatcher opens its own two scopes, innermost: the call over
    the line list names them ``line_message``, so no contraction over
    line rows (4 x units wide in) sits under an atom-graph stage, and the
    atom conv's (3 x units wide in) stays where TensorNet's is."""
    cfg = config(dtype="bfloat16")
    sites = step_sites(cfg, nparts)
    dots = [s for s in sites if s.primitive == "dot_general"
            and "model_energy" in s.stack and "transpose" not in s.stack]
    wide = lambda s: s.eqn.invars[0].aval.shape[-1]
    line = [s for s in dots if wide(s) == 4 * cfg.units]
    atom = [s for s in dots if wide(s) == 3 * cfg.units]
    assert line and atom
    assert {stage_of(s.stack) for s in line} == {"line_message",
                                                 "angle_update"}
    assert {stage_of(s.stack) for s in atom} == {"edge_message"}
    # two bond blocks: node phase twice, angle phase once (the last block's
    # would feed nothing), core and gate each
    per = lambda st: sum(stage_of(s.stack) == st for s in line)
    assert per("line_message") == 2 * per("angle_update") > 0
    # sums onto atoms are scatter-adds, aggregate work; the sum onto bonds is
    # a sum over the slabs of the in-line table: line work, and no scatter
    # (the remaps' own scatters are bond_map's, the exchange's halo's)
    forward = [s for s in sites if "transpose" not in s.stack
               and "model_energy" in s.stack]
    adds = {stage_of(s.stack) for s in forward
            if s.primitive in ("scatter-add", "scatter_add")}
    assert "edge_aggregate" in adds
    assert adds <= {"edge_aggregate", "bond_map", "halo"}
    rows = lambda v: v.aval.shape[0]
    table = [s for s in forward if s.primitive == "custom_vjp_call"]
    slab_sums = [s for s in table
                 if rows(s.eqn.outvars[0]) < rows(s.eqn.invars[0])]
    assert len(slab_sums) == 2          # one a bond conv
    assert {stage_of(s.stack) for s in slab_sums} == {"line_message"}
    # of the lines' three addresses only the source is an index: one gather
    # a reader of ``b[line_src]`` (the 4-wide geometry rows, the rows in two
    # bond convs and one angle update), none by destination or centre:
    # those are repeats of the bond rows over the table's slabs
    n_lines = rows(slab_sums[0].eqn.invars[0])
    by_line = [s for s in forward if s.primitive == "gather"
               and rows(s.eqn.outvars[0]) == n_lines]
    assert len(by_line) == 1 + 2 + 1, [s.stack for s in by_line]
    repeats = [s for s in table if rows(s.eqn.outvars[0]) == n_lines]
    assert len(repeats) == 1 + 2 * 2 + 2
    assert {stage_of(s.stack) for s in repeats} == {
        "line_geometry", "line_message", "angle_update"}


def test_last_stats_carry_real_bonds_and_lines_per_partition():
    model = CHGNet(config())
    atoms = atoms_of(4)
    for nparts in (1, 4):
        pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                            num_partitions=nparts, skin=0.3)
        pot.calculate(atoms)
        stats = pot.last_stats
        assert len(stats["n_bonds_per_part"]) == nparts
        assert len(stats["n_lines_per_part"]) == nparts
        assert sum(stats["n_lines_per_part"]) == stats["n_lines"]
        # fcc inside 3.3 A: 12 bonds an atom, 12 x 11 lines; every bond is
        # computed by exactly one partition
        assert sum(stats["n_bonds_per_part"]) == 12 * len(atoms)
        assert stats["n_lines"] == 132 * len(atoms)
        pot.close()
