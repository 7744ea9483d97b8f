"""DimeNet++'s energy-and-forces step at jaxpr level, the twin of
``tests/test_nequip_stages.py``: every equation of the model carries a
stage (what the benchmark's ``model.unattributed_share.md`` reads on the
chip) and the triplet work reads under its three new stages; and the
model on its own: the published parameter count, invariance under rotation,
translation and permutation, forces as the slope of the energy, the sign of
cos theta on a hand-built three-atom line, and the bases (the roots and
normalisers of j_l, j_l in float32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.geometry import frac_to_cart, make_supercell
from distmlip_tpu.models import (CHGNet, CHGNetConfig, DimeNetPP,
                                 DimeNetPPConfig, NequIP, NequIPConfig,
                                 TensorNet, TensorNetConfig)
from distmlip_tpu.models.dimenet import triplet_cos
from distmlip_tpu.ops import radial
from distmlip_tpu.telemetry import STAGES
from distmlip_tpu.telemetry.stages import stage_of

NEW = {"triplet_basis", "triplet_message", "edge_update"}
# what DimeNet++ has code for; the rest are other families'
DIMENET = NEW | {"edge_geometry", "bond_map", "edge_aggregate", "readout",
                 "halo"}


def config(**kw):
    return DimeNetPPConfig(**{**dict(
        num_species=20, hidden_channels=16, out_emb_channels=32,
        int_emb_size=8, basis_emb_size=4, num_blocks=2, cutoff=3.5), **kw})


def atoms_of(nparts=1, seed=7):
    rng = np.random.default_rng(seed)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = make_supercell(unit, np.eye(3) * 3.9, (3 * nparts, 2, 2))
    cart = frac_to_cart(frac, lattice) + rng.normal(0, 0.05, (len(frac), 3))
    numbers = np.where(np.arange(len(cart)) % 3 == 0, 8, 14)
    return Atoms(numbers=numbers, positions=cart, cell=lattice)


def potential(model=None, nparts=1, **kw):
    model = model or DimeNetPP(config())
    return DistPotential(model, model.init(jax.random.PRNGKey(0)),
                         num_partitions=nparts, skin=0.3, **kw)


def step_sites(model, nparts=1, **kw):
    pot = potential(model, nparts, **kw)
    graph, _, positions = pot._prepare(atoms_of(nparts))
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    return [s for s in iter_sites(jaxpr) if "model_energy" in s.stack]


def test_the_new_stages_are_declared():
    assert NEW <= set(STAGES) and DIMENET <= set(STAGES)
    base = "jit(potential)/energy_and_grad/jvp(model_energy)/"
    assert stage_of(base + "triplet_message/triplet_basis/mul") == \
        "triplet_basis"
    assert stage_of(base + "transpose(jvp(triplet_message))/scatter-add") \
        == "triplet_message"
    assert stage_of(base + "edge_update/dot_general") == "edge_update"


@pytest.mark.parametrize("nparts, dtype", [
    (1, "float32"), (1, "bfloat16"), (4, "bfloat16")])
def test_every_equation_of_the_model_carries_a_stage(nparts, dtype):
    model = step_sites(DimeNetPP(config(dtype=dtype)), nparts)
    assert len(model) > 200
    bare = sorted({(s.primitive, s.stack) for s in model
                   if stage_of(s.stack) is None})
    assert bare == []
    seen = {stage_of(s.stack) for s in model}
    assert seen == DIMENET - ({"halo"} if nparts == 1 else set())
    # the gather of the source rows is the triplet message's, the Legendre
    # rows the basis's; no contraction in the basis
    message = {s.primitive for s in model
               if stage_of(s.stack) == "triplet_message"}
    assert {"gather", "scan", "dot_general"} <= message
    basis = {s.primitive for s in model
             if stage_of(s.stack) == "triplet_basis"}
    assert {"sin", "cos", "mul"} <= basis and "dot_general" not in basis
    # per block one exchange of s, and one of the bond geometry a step
    sends = [s for s in model if s.primitive == "ppermute"
             and "transpose" not in s.stack]
    if nparts > 1:
        assert {stage_of(s.stack) for s in sends} == {"halo"}
        assert len(sends) == (1 + config().num_blocks) * 2
    else:
        assert not sends


@pytest.mark.parametrize("kernels", [False, "interpret"])
def test_the_reads_by_centre_keep_the_triplet_stage(kernels):
    """The scan reads a slab's source rows by centre atom: the repeat over
    each centre's bond rows, its transposed sum (the Pallas kernel, or
    XLA's scatter-add) and the permutations into and out of centre order
    all read ``triplet_message`` (its basis ``triplet_basis``), none
    ``edge_aggregate``, whose scope the kernel dispatcher's segment sums
    open."""
    model = step_sites(DimeNetPP(config()), 1, kernels=kernels)
    in_scan = {stage_of(s.stack) for s in model if "scan" in s.path}
    assert in_scan == {"triplet_message", "triplet_basis"}
    sums = [s for s in model if "scan" in s.path and s.primitive == (
        "pallas_call" if kernels else "scatter-add")]
    assert sums and {stage_of(s.stack) for s in sums} == {"triplet_message"}
    # the permutations and their transposes: gathers, outside the scan
    perms = [s for s in model if s.primitive == "gather"
             and "custom_vjp_call" in s.path and "scan" not in s.path]
    assert len(perms) >= 4
    assert {stage_of(s.stack) for s in perms} == {"triplet_message"}
    # outside the scan the triplet message scatters once a block: every
    # slab's rows by centre, gathered before the scan, onto the bond rows
    onto_bonds = [s for s in model if s.primitive == "scatter-add"
                  and "scan" not in s.path
                  and stage_of(s.stack) == "triplet_message"]
    assert len(onto_bonds) == config().num_blocks


OTHERS = {
    "tensornet": lambda: TensorNet(TensorNetConfig(
        units=8, num_rbf=4, num_layers=2, cutoff=3.5)),
    "chgnet": lambda: CHGNet(CHGNetConfig(
        num_species=20, units=8, num_rbf=5, num_angle=2, num_blocks=2,
        cutoff=3.5, bond_cutoff=3.0)),
    "nequip": lambda: NequIP(NequIPConfig(
        num_species=20, irreps=((8, 4, 2),) * 2 + ((8,),), num_bessel=6,
        radial_hidden=(8, 8), cutoff=3.5, cutoff_on=3.0,
        avg_num_neighbors=12.0, edge_chunk=256)),
}


@pytest.mark.parametrize("name", sorted(OTHERS))
def test_no_other_model_knows_the_new_stages(name):
    """Declaring the three stages moved nothing in the other models: no
    equation of their steps resolves to one of them (CHGNet shares the
    in-line table, NequIP and TensorNet the edge code)."""
    sites = step_sites(OTHERS[name]())
    assert len(sites) > 100
    assert not NEW & {stage_of(s.stack) for s in sites}


# ---- the model on its own --------------------------------------------------

def test_published_size():
    """PyG's ``DimeNetPlusPlus`` at ``from_qm9_pretrained``'s sizes and 95
    species rows: embedding block 62,336, four interaction blocks of
    166,912, five output blocks of 231,168 and 6 frequencies."""
    model = DimeNetPP(DimeNetPPConfig())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(shapes) == 1_885_830
    assert count(shapes["embedding"]) == 62_336
    assert [count(b) for b in shapes["interactions"]] == [166_912] * 4
    assert [count(b) for b in shapes["outputs"]] == [231_168] * 5


@pytest.fixture(scope="module")
def base():
    pot = potential()
    atoms = atoms_of(1)
    return pot, atoms, pot.calculate(atoms)


@pytest.mark.parametrize("move", ["rotation", "translation", "permutation"])
def test_invariance(base, move):
    """The energy is unchanged and the forces move with the atoms."""
    _, atoms, out = base
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
    moved = potential().calculate(
        Atoms(numbers=numbers, positions=pos, cell=cell))
    assert abs(moved["energy"] - out["energy"]) < 2e-6 * abs(out["energy"])
    scale = np.abs(out["forces"]).max()
    assert scale > 1e-2
    np.testing.assert_allclose(moved["forces"], forces, atol=2e-5 * scale)


def test_forces_are_the_slope_of_the_energy(base):
    _, atoms, out = base
    h = 2e-3
    for atom, axis in ((0, 0), (5, 1), (17, 2)):
        energy = []
        for sign in (1, -1):
            pos = atoms.positions.copy()
            pos[atom, axis] += sign * h
            energy.append(potential().calculate(Atoms(
                numbers=atoms.numbers, positions=pos,
                cell=atoms.cell))["energy"])
        numeric = -(energy[0] - energy[1]) / (2 * h)
        assert numeric == pytest.approx(out["forces"][atom, axis],
                                        rel=2e-2, abs=2e-3)


def test_cos_theta_on_a_three_atom_line():
    """k = (0, 0, 0), j = (1.5, 0, 0), i = (3, 0, 0): the chain k -> j -> i
    runs straight on, theta_kji = 0 and cos +1 (DimeNet's convention, the
    angle between the bonds' directions); bending i to (1.5, 1.5, 0) makes
    it a right angle, and folding it back onto k gives -1. CHGNet's angle
    at the centre reads -1 on the straight chain."""
    k, j = np.array([0.0, 0, 0]), np.array([1.5, 0, 0])

    def cos_of(i):
        kj, ji = j - k, i - j
        row = lambda v: jnp.asarray(np.r_[v, np.linalg.norm(v)][None],
                                    jnp.float32)
        return float(triplet_cos(row(kj), row(ji))[0])

    assert cos_of(np.array([3.0, 0, 0])) == pytest.approx(1.0, abs=1e-6)
    assert cos_of(np.array([1.5, 1.5, 0])) == pytest.approx(0.0, abs=1e-6)
    assert cos_of(np.array([0.5, 0, 0])) == pytest.approx(-1.0, abs=1e-6)
    # the program's Y_l0 rows at theta = 0: sqrt((2l+1)/4pi) P_l(1)
    Y = radial.legendre_rows(jnp.ones((1,)), 7)[0]
    np.testing.assert_allclose(Y, np.sqrt((2 * np.arange(7) + 1)
                                          / (4 * np.pi)), rtol=1e-6)


def test_roots_and_normalisers_against_float64():
    special = pytest.importorskip("scipy.special")
    z, norms = radial.spherical_bessel_table(7, 6)
    for l in range(7):
        assert np.abs(special.spherical_jn(l, z[l])).max() < 1e-12
        np.testing.assert_allclose(
            norms[l], np.sqrt(2.0) / np.abs(special.spherical_jn(l + 1,
                                                                 z[l])),
            rtol=1e-12)
    # each row is the first six roots: increasing, the first after l pi/2
    assert np.all(np.diff(z, axis=1) > 2.5) and np.all(z[:, 0] > 3.0)
    np.testing.assert_allclose(z[0], np.pi * np.arange(1, 7), rtol=1e-14)


def test_float32_jl_over_the_bonds_of_a_solid():
    """Within 1e-5 of each (l, n) row's largest value, x in [0.5, 1]."""
    special = pytest.importorskip("scipy.special")
    z, _ = radial.spherical_bessel_table(7, 6)
    x = np.linspace(0.5, 1.0, 4001)
    arg = z[None] * x[:, None, None]
    got = np.asarray(radial.spherical_bessel_jl(
        7, jnp.asarray(arg, jnp.float32)), np.float64)
    exact = np.stack([special.spherical_jn(l, arg[:, l]) for l in range(7)],
                     axis=1)
    err = np.abs(got - exact).max(axis=0) / np.abs(exact).max(axis=0)
    assert err.max() < 1e-5, err.max()
