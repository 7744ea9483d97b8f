"""The program (``CHGNet`` through ``DistPotential`` and
``MolecularDynamics``) against the plain reference of ``chgnet`` at toy
width on the CPU: float32 on one and on four virtual devices (the bond halo
exchange), bfloat16 against the reference's own bfloat16 rounding and the
float8 control, and two faults in the bond graph that have to read outside
the tolerance.

The toy structure has two species; the driver's atom bucket is set to 1 (no
ghost atoms), so energies compare too: an atom without edges has the energy
of its embedding through the readout, not zero.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

import toy
from benchmark.drivers import md
from benchmark.harness import compare, spec
from test_chgnet_cell import two_species  # registers toy.TOY_MODELS["chgnet"]

# float32 program against the float32 reference: the sound program reads
# 1e-6 to 4e-6 on both numbers (one and four partitions, XLA and the
# interpreted kernel); the smaller of the two faults below, an angle update
# skipped in a toy of three blocks (one update, which reaches the energy
# through one bond conv and the last atom conv), reads 1.3e-4, bond_to_edge
# left out 8.9e-3. 2e-5 lies five times from either side. ``toy.TIGHT``'s
# 1e-3 would pass the first fault.
FLOAT32_TOLERANCE = 2e-5
LIMITS = {"force_rel_err": FLOAT32_TOLERANCE,
          "kick_rel_err": FLOAT32_TOLERANCE}


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("tables"))


def toy_run(monkeypatch, tmp_path, tables_dir, chips=1, reps=(3, 3, 3),
            kernels=None, limits=LIMITS, **kw):
    root = toy.make_root(str(tmp_path), "chgnet", reps=reps, chips=chips,
                         limits=limits, **kw)
    cell = spec.load_cell("toy-md", root)
    real = md.build_atoms

    def build_atoms(traffic, seed):
        atoms = real(traffic, seed)
        atoms.numbers = two_species(atoms.numbers)
        return atoms

    monkeypatch.setattr(md, "build_atoms", build_atoms)
    monkeypatch.setattr(md, "ATOM_BUCKET", 1)
    state = md.set_up(cell, 11, jax.devices()[:chips], tables_dir=tables_dir,
                      kernels=kernels)
    stats = dict(state.pot.last_stats)
    window = md.run_window(state, 1e-6)  # one whole step
    md.release_program(state)
    return state, window, stats


@pytest.mark.parametrize("chips, reps, kernels", [
    (1, (3, 3, 3), None), (1, (3, 3, 3), "interpret"),
    (4, (12, 3, 3), None)])
def test_program_agrees_with_reference(monkeypatch, tmp_path, tables_dir,
                                       chips, reps, kernels):
    """Neighbour graph with its bond radius, slab partition, the bond and
    line arrays, both index remaps, the coalesced atom + bond halo, the
    dispatcher over a sorted line list (XLA and the interpreted kernel) and
    the backward that gives forces, against the reference's own cell list,
    per-atom bond tables and plain forward: float32 on both sides."""
    state, window, stats = toy_run(monkeypatch, tmp_path, tables_dir, chips,
                                   reps, kernels)
    assert len(set(state.atoms.numbers.tolist())) == 2
    assert window.steps == 1 and window.rebuilds == 0
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    assert [c["limit"] for c in verdict["compared"]] == [FLOAT32_TOLERANCE] * 2
    assert verdict["numbers"]["energy_err_per_atom"] < 1e-6
    # both sides found the same bond graph, each by its own route
    jax.effects_barrier()
    found = state.tables.found
    assert len(stats["n_bonds_per_part"]) == chips
    assert sum(stats["n_bonds_per_part"]) == found["n_bonds"]
    assert sum(stats["n_lines_per_part"]) == found["n_lines"]
    assert found["n_lines"] == 132 * len(state.atoms)
    if chips > 1:
        assert sum(stats["bond_halo_send_per_part"]) > 0
    assert stats["kernel_ops"]["edge_aggregate"] == (
        [0, 5] if kernels is None and chips == 1 else
        [5, 0] if kernels == "interpret" else [0, 8])


def skipped_angle_update(monkeypatch):
    """The angle features stay what the embedding made them."""
    from distmlip_tpu.models import CHGNet

    monkeypatch.setattr(CHGNet, "_angle_conv",
                        lambda self, blk, lg, v, b, a, line_ok: a)


def bond_to_edge_left_out(monkeypatch):
    """What the bond convolution made of a bond never reaches its edge."""
    from distmlip_tpu.parallel.halo import LocalGraph

    monkeypatch.setattr(LocalGraph, "bond_to_edge",
                        lambda self, bond_feats, edge_feats: edge_feats)


@pytest.mark.parametrize("fault", [skipped_angle_update,
                                   bond_to_edge_left_out])
def test_a_fault_in_the_bond_graph_reads_outside_the_tolerance(
        monkeypatch, tmp_path, tables_dir, fault):
    fault(monkeypatch)
    state, window, _ = toy_run(monkeypatch, tmp_path, tables_dir)
    verdict = md.check(state, window)
    print(fault.__name__, verdict["compared"])
    assert not verdict["correct"]
    forces = {c["name"]: c["value"] for c in verdict["compared"]}[
        "force_rel_err"]
    assert forces > 5 * FLOAT32_TOLERANCE


def test_control_fails_where_the_program_passes(monkeypatch, tmp_path,
                                                tables_dir):
    """bfloat16 program against the float32 reference in units of the
    reference's own bfloat16 rounding, and the control (the reference in
    float8 in the program's place) against the same. The program keeps
    its features and their sums in bfloat16 between contractions, the
    rounded reference rounds the operands of contractions alone: 2 to 4 on
    this number is the program as stated, 8 (``toy.SERVED``, the limits'
    shape of the committed cells) is not."""
    state, window, _ = toy_run(monkeypatch, tmp_path, tables_dir,
                               compute_dtype="bfloat16", limits=toy.SERVED)
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    program = verdict["numbers"]
    forces = md.reference_forces(
        state, window.positions, ("float8_e4m3fn",))["float8_e4m3fn"][1]
    reference = verdict["reference"]
    control = (compare.relative(forces, reference["forces"])
               / compare.relative(reference["rounding_forces"],
                                  reference["forces"]))
    limit = toy.SERVED["force_err_vs_rounding"]
    print(program, control)
    assert 0.3 < program["force_err_vs_rounding"] < limit < control
    assert control > 3 * program["force_err_vs_rounding"]


def test_magnetic_moments_ride_the_same_forward(monkeypatch, tmp_path,
                                                tables_dir):
    """``compute_magmom=True``: the sitewise readout before the last atom
    conv, against the reference's."""
    from distmlip_tpu.calculators import DistPotential

    from benchmark.families import chgnet as family
    from benchmark.reference import chgnet as ref
    from benchmark.reference.common import neighbour_pairs

    cfg = toy.TOY_MODELS["chgnet"]
    root = toy.make_root(str(tmp_path), "chgnet")
    atoms = md.build_atoms(spec.load_cell("toy-md", root).traffic, 3)
    atoms.numbers = two_species(atoms.numbers)
    tables = ref.Tables(cfg)
    params = ref.init_params(cfg, tables, jax.random.PRNGKey(3))
    model = family.build_model(cfg)
    out = DistPotential(model, family.program_params(params, tables, model),
                        num_partitions=1, compute_magmom=True).calculate(atoms)
    src, dst, shift = neighbour_pairs(atoms.positions, atoms.cell,
                                      cfg["cutoff"])
    with jax.default_matmul_precision("highest"):
        _, sites = ref.site_energies(
            params, cfg, tables, np.asarray(atoms.numbers, np.int32),
            np.asarray(atoms.positions, np.float32),
            (src, dst, np.asarray(shift, np.float32)), with_sites=True)
    assert float(np.abs(sites).max()) > 1e-2
    np.testing.assert_allclose(out["magmoms"], sites, atol=2e-5)
