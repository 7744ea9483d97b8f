"""Static calculation + MD with a distributed MACE potential.

Mirrors the reference's example notebooks (examples/*.ipynb): build a
perturbed supercell, enable distributed evaluation over all devices, run a
static calc, then a short NVT trajectory.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds. For the 8-virtual-device CPU mesh:
#   JAX_PLATFORMS=cpu python examples/01_static_and_md.py
jax.config.update("jax_num_cpu_devices", 8)  # read by the CPU backend only

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import (Atoms, DistPotential, MolecularDynamics,
                                      TrajectoryObserver)
from distmlip_tpu.models import MACE, MACEConfig

# ~4k-atom perturbed Si supercell
rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
frac, lattice = geometry.make_supercell(unit, np.eye(3) * 5.43, (10, 10, 10))
cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, 0.05, (len(frac), 3))
atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)

model = MACE(MACEConfig(cutoff=5.0))
params = model.init(jax.random.PRNGKey(0))  # or utils.load_params("mace.npz")

# default AUTO partitioning: all devices, clamped by the slab rule
pot = DistPotential(model, params, skin=0.5)
res = pot.calculate(atoms)
print(f"E = {res['energy']:.4f} eV   |F|max = {np.abs(res['forces']).max():.4f} eV/A")
print(pot.partition_report(atoms))

atoms.set_maxwell_boltzmann_velocities(600.0, rng=rng)
obs = TrajectoryObserver(atoms)
md = MolecularDynamics(atoms, pot, ensemble="nvt_bussi", timestep=2.0,
                       temperature=600.0, trajectory=obs, loginterval=10)
md.run(100)
obs.save("si_md.npz")
print(f"final T = {atoms.temperature():.0f} K, rebuilds = {pot.rebuild_count}")
