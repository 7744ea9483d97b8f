"""The program (``ESCNMD`` through ``DistPotential`` and
``MolecularDynamics``) against the plain reference of ``escn`` at toy width
on the CPU: float32 on one and on four virtual devices, then bfloat16
against the float8 control.

The toy structure has two species and no ghost atoms. The reference's gate
reads the mean species embedding over every atom it is given; the md driver
pads the reference's atoms with edge-less ghosts of ``species[0]``, which
leaves that mean alone only in a one-species structure, as the cell's is.
So these runs set the driver's atom bucket to 1: no ghosts.
"""

from __future__ import annotations

import jax
import pytest

import toy
from benchmark.drivers import md
from benchmark.harness import compare, spec
from test_uma_cell import two_species  # registers toy.TOY_MODELS["escn"]


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("tables"))


def toy_run(monkeypatch, tmp_path, tables_dir, chips=1, reps=(3, 3, 3), **kw):
    root = toy.make_root(str(tmp_path), "escn", reps=reps, chips=chips, **kw)
    cell = spec.load_cell("toy-md", root)
    real = md.build_atoms

    def build_atoms(traffic, seed):
        atoms = real(traffic, seed)
        atoms.numbers = two_species(atoms.numbers)
        return atoms

    monkeypatch.setattr(md, "build_atoms", build_atoms)
    monkeypatch.setattr(md, "ATOM_BUCKET", 1)
    state = md.set_up(cell, 11, jax.devices()[:chips], tables_dir=tables_dir,
                      kernels="interpret")
    window = md.run_window(state, 1e-6)  # one whole step
    md.release_program(state)
    return state, window


@pytest.mark.parametrize("chips, reps", [(1, (3, 3, 3)), (4, (12, 3, 3))])
def test_program_agrees_with_reference(monkeypatch, tmp_path, tables_dir,
                                       chips, reps):
    """Neighbour graph, partition and halo, the expert merge, the Jd-table
    Wigner blocks, the chunked scan with the interpreted kernel and the
    backward that gives forces, against the reference's own cell list,
    frames and plain forward: float32 on both sides."""
    state, window = toy_run(monkeypatch, tmp_path, tables_dir, chips, reps)
    assert len(set(state.atoms.numbers.tolist())) == 2
    assert window.steps == 1 and window.rebuilds == 0
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    # TIGHT's limits are 1e-3; the forces here are small (random weights:
    # 1e-2 eV/A), so float32 rounding is a larger share than in MACE's test
    for number in verdict["compared"]:
        assert number["value"] < 3e-4, number
    assert verdict["numbers"]["energy_err_per_atom"] < 1e-6


def test_control_fails_where_the_program_passes(monkeypatch, tmp_path,
                                                tables_dir):
    """bfloat16 program against the float32 reference, and the control
    (the reference in float8 in the program's place) against the same."""
    state, window = toy_run(monkeypatch, tmp_path, tables_dir,
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
