"""EquiformerV2's energy-and-forces step at jaxpr level, the twin of
``tests/test_dimenet_stages.py``: every equation of the model carries a
stage (what the benchmark's ``model.unattributed_share.md`` reads on the
chip) and the attention and the grids read under their two new stages; and
the model on its own: the published parameter count on both sides,
invariance under rotation, translation and permutation, forces as the
slope of the energy, the grids' round trip and the smooth leaky ReLU.

Toy sizes keep lmax 4, mmax 2 and the 18 x 18 grid, with narrow channels
and two heads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.geometry import frac_to_cart, make_supercell
from distmlip_tpu.models import EquiformerV2, EquiformerV2Config
from distmlip_tpu.models.equiformer_v2 import force_head_size, slrelu
from distmlip_tpu.ops.s2_grid import grid_tables
from distmlip_tpu.telemetry import STAGES
from distmlip_tpu.telemetry.stages import stage_of

NEW = {"edge_attention", "s2_activation"}
# what EquiformerV2 has code for; the rest are other families'
EQUIFORMER = NEW | {"edge_geometry", "edge_gather", "radial_mlp",
                    "edge_message", "edge_rotation", "edge_aggregate",
                    "node_linear", "node_tensor", "readout", "halo"}


def config(**kw):
    return EquiformerV2Config(**{**dict(
        num_species=20, sphere_channels=8, attn_hidden_channels=8,
        num_heads=2, attn_alpha_channels=4, attn_value_channels=4,
        ffn_hidden_channels=8, edge_channels=8, num_distance_basis=8,
        num_layers=2, cutoff=3.5, edge_chunk=128), **kw})


def atoms_of(nparts=1, seed=7):
    rng = np.random.default_rng(seed)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = make_supercell(unit, np.eye(3) * 3.9, (3 * nparts, 2, 2))
    cart = frac_to_cart(frac, lattice) + rng.normal(0, 0.05, (len(frac), 3))
    numbers = np.where(np.arange(len(cart)) % 3 == 0, 8, 14)
    return Atoms(numbers=numbers, positions=cart, cell=lattice)


def potential(model=None, nparts=1, **kw):
    model = model or EquiformerV2(config())
    return DistPotential(model, model.init(jax.random.PRNGKey(0)),
                         num_partitions=nparts, skin=0.3, **kw)


def step_sites(model, nparts=1, **kw):
    pot = potential(model, nparts, **kw)
    graph, _, positions = pot._prepare(atoms_of(nparts))
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    return [s for s in iter_sites(jaxpr) if "model_energy" in s.stack]


def test_the_new_stages_are_declared():
    assert NEW <= set(STAGES) and EQUIFORMER <= set(STAGES)
    base = "jit(potential)/energy_and_grad/jvp(model_energy)/layer0/"
    assert stage_of(base + "edge_gather/edge_attention/exp") == \
        "edge_attention"
    assert stage_of(base + "transpose(jvp(s2_activation))/dot_general") == \
        "s2_activation"


@pytest.mark.parametrize("nparts, dtype, kernels", [
    (1, "float32", False), (1, "bfloat16", "interpret"),
    (4, "bfloat16", False), (4, "bfloat16", "interpret")])
def test_every_equation_of_the_model_carries_a_stage(nparts, dtype, kernels):
    model = step_sites(EquiformerV2(config(dtype=dtype)), nparts,
                       kernels=kernels)
    assert len(model) > 500
    bare = sorted({(s.primitive, s.stack) for s in model
                   if stage_of(s.stack) is None})
    assert bare == []
    seen = {stage_of(s.stack) for s in model}
    assert seen == EQUIFORMER - ({"halo"} if nparts == 1 else set())
    # the softmax's max and sum, the grid products, SiLU on the grid
    attention = {s.primitive for s in model
                 if stage_of(s.stack) == "edge_attention"}
    assert {"scatter-max", "exp", "dot_general"} <= attention
    grid = {s.primitive for s in model
            if stage_of(s.stack) == "s2_activation"}
    assert {"dot_general", "logistic"} <= grid
    # per block the logits' map and the messages' sum: two scans over the
    # chunks, besides the edge-degree embedding's
    scans = [s for s in model if s.primitive == "scan"
             and "transpose" not in s.stack and "scan" not in s.path
             and "pallas_call" not in s.path]
    assert len(scans) == 1 + 2 * config().num_layers
    if kernels:
        # the in-place sum of each scan (the rotation kernel takes 128
        # lanes) and the edge grid's kernel a block: the forward, its
        # recompute in the checkpointed backward and the backward, with
        # its products and SiLU inside it
        calls = [s for s in model if s.primitive == "pallas_call"]
        assert {stage_of(s.stack) for s in calls} == {
            "edge_aggregate", "s2_activation"}
        assert sum(stage_of(s.stack) == "s2_activation" for s in calls) == (
            3 * config().num_layers)
        inside = {s.primitive for s in model if "pallas_call" in s.path
                  and stage_of(s.stack) == "s2_activation"}
        assert {"dot_general", "tanh"} <= inside
        # the grid itself never leaves the kernel: no value outside it
        # holds the 324 (padded: 336) points of a column
        outside = [v.aval.shape for s in model if "pallas_call" not in s.path
                   and stage_of(s.stack) == "s2_activation"
                   for v in s.eqn.outvars if hasattr(v.aval, "shape")]
        assert outside and not any({324, 336} & set(shape)
                                   for shape in outside)


# ---- the model on its own --------------------------------------------------

def test_published_size():
    """The 31M model: 27,827,969 parameters built, 3,118,081 in fairchem's
    direct-force head, which is counted and not run: 30,946,050, as the
    equations give them; the reference's tree holds the same."""
    from benchmark.families import equiformer as family

    model = EquiformerV2(EquiformerV2Config())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(shapes) == 27_827_969
    assert force_head_size(model.cfg) == 3_118_081
    assert count(shapes) + force_head_size(model.cfg) == 30_946_050
    assert abs(30_946_050 / 31e6 - 1) < 0.01
    cfg = {k: getattr(model.cfg, k) for k in model.cfg.__dataclass_fields__}
    tables = family.reference.Tables(cfg)
    assert family.reference.parameter_count(cfg, tables) == {
        "built": 27_827_969, "force_head": 3_118_081}
    theirs = jax.eval_shape(
        lambda k: family.reference.init_params(cfg, tables, k),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(theirs) == jax.tree.structure(shapes)
    assert ([x.shape for x in jax.tree.leaves(theirs)]
            == [x.shape for x in jax.tree.leaves(shapes)])


@pytest.fixture(scope="module")
def base():
    pot = potential()
    atoms = atoms_of(1)
    return pot, atoms, pot.calculate(atoms)


@pytest.mark.parametrize("move", ["rotation", "translation", "permutation"])
def test_invariance(base, move):
    """The energy is unchanged and the forces move with the atoms. The grids
    are not rotation invariant (SiLU on 324 points aliases), so a rotation
    moves the energy by about 1e-5 of itself and the forces by a few 1e-3
    of their largest; translation and permutation move nothing but
    rounding."""
    pot, atoms, out = base
    rng = np.random.default_rng(3)
    pos, cell, numbers = atoms.positions, np.asarray(atoms.cell), atoms.numbers
    forces = out["forces"]
    if move == "rotation":
        q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
        q = q * np.sign(np.linalg.det(q))
        pos, cell, forces = pos @ q.T, cell @ q.T, forces @ q.T
    elif move == "translation":
        pos = pos + np.array([0.37, -1.1, 2.3])
    else:
        order = rng.permutation(len(pos))
        pos, numbers, forces = pos[order], numbers[order], forces[order]
    moved = pot.calculate(Atoms(numbers=numbers, positions=pos, cell=cell))
    aliasing = move == "rotation"
    assert abs(moved["energy"] - out["energy"]) < (
        1e-4 if aliasing else 2e-6) * abs(out["energy"])
    scale = np.abs(out["forces"]).max()
    assert scale > 1e-5
    np.testing.assert_allclose(moved["forces"], forces,
                               atol=(2e-2 if aliasing else 2e-4) * scale)


def test_forces_are_the_slope_of_the_energy(base):
    pot, atoms, out = base
    h = 2e-3
    for atom, axis in ((0, 0), (5, 1), (17, 2)):
        energy = []
        for sign in (1, -1):
            pos = atoms.positions.copy()
            pos[atom, axis] += sign * h
            energy.append(pot.calculate(Atoms(
                numbers=atoms.numbers, positions=pos,
                cell=atoms.cell))["energy"])
        numeric = -(energy[0] - energy[1]) / (2 * h)
        scale = np.abs(out["forces"]).max()
        assert numeric == pytest.approx(out["forces"][atom, axis],
                                        rel=2e-2, abs=2e-2 * scale)


def test_grids_round_trip():
    """From-grid after to-grid is the identity on degree <= 4 on the node
    grid, and fairchem's scale squared on the edge grid's degrees 3 and 4;
    the columns are the harmonics at the points (l = 1: sqrt(3 / 4 pi) times
    the point)."""
    to_grid, from_grid = (np.asarray(t, np.float64)
                          for t in grid_tables(4, 18))
    np.testing.assert_allclose(from_grid @ to_grid, np.eye(25), atol=2e-6)
    to_grid, from_grid = (np.asarray(t, np.float64)
                          for t in grid_tables(4, 18, 2))
    scale = np.repeat([1, 1, 1, 7 / 5, 9 / 5], 2 * np.arange(5) + 1)
    np.testing.assert_allclose(from_grid @ to_grid, np.diag(scale),
                               atol=4e-6)
    from distmlip_tpu.ops.s2_grid import grid_points
    np.testing.assert_allclose(
        to_grid[:, 1:4], np.sqrt(3 / (4 * np.pi)) * grid_points(18),
        atol=1e-6)
    # the edge model keeps |m| <= 2: 19 columns
    assert EquiformerV2(config()).edge_grid[0].shape == (324, 19)


def test_smooth_leaky_relu():
    """0.6 t + 0.4 t (2 sigmoid(t) - 1): 0 at 0, slope 0.2 far left, 1 far
    right."""
    got = np.asarray(slrelu(jnp.asarray([0.0, 1.0, -1.0, -50.0])))
    s = 1 / (1 + np.exp(-1.0))
    np.testing.assert_allclose(
        got, [0.0, 0.6 + 0.4 * (2 * s - 1), -0.6 + 0.4 * (2 * s - 1), -10.0],
        rtol=1e-6)
