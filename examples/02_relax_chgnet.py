"""Structure relaxation (positions + cell) with distributed CHGNet."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds. For the 8-virtual-device CPU mesh:
#   JAX_PLATFORMS=cpu python examples/02_relax_chgnet.py
jax.config.update("jax_num_cpu_devices", 8)  # read by the CPU backend only

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, DistPotential, Relaxer
from distmlip_tpu.models import CHGNet, CHGNetConfig

rng = np.random.default_rng(1)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
frac, lattice = geometry.make_supercell(unit, np.eye(3) * 3.6, (6, 6, 6))
cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, 0.08, (len(frac), 3))
atoms = Atoms(numbers=np.full(len(cart), 3), positions=cart, cell=lattice * 1.02)

model = CHGNet(CHGNetConfig(cutoff=5.0, bond_cutoff=3.0))
params = model.init(jax.random.PRNGKey(0))
# default AUTO partitioning: all devices, clamped by the slab rule
pot = DistPotential(model, params, skin=0.4)

out = Relaxer(pot, optimizer="fire", relax_cell=True).relax(atoms, steps=300)
print(f"converged={out.converged} steps={out.nsteps} E={out.energy:.4f} eV "
      f"|F|max={np.abs(out.forces).max():.4f}")
