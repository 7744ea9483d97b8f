"""Batched screening: relax a pool of candidate structures in one program.

The serving/screening workload the batched engine targets: many SMALL
structures, evaluated as one block-diagonally packed super-graph per step
(see README "Batched inference"). A stream of varied candidate sizes hits
a small fixed set of compiled executables thanks to the geometric
BucketPolicy ladder — watch `compile_count` stay flat while sizes vary.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import (Atoms, BatchedMD, BatchedPotential,
                                      BatchedRelaxer)
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.telemetry import AggregatingSink, Telemetry

rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])


def candidate(reps, a, noise):
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


# a candidate pool with mixed sizes and lattice constants
pool = [candidate((2, 1, 1), 5.3, 0.15), candidate((2, 2, 1), 5.5, 0.2),
        candidate((1, 1, 1), 5.2, 0.1), candidate((2, 2, 2), 5.4, 0.12)]

model = TensorNet(TensorNetConfig(num_species=95, cutoff=4.5))
params = model.init(jax.random.PRNGKey(0))

telemetry = Telemetry([AggregatingSink()])
pot = BatchedPotential(model, params, skin=0.5, telemetry=telemetry)

# one device program evaluates the whole pool
results = pot.calculate(pool)
for i, r in enumerate(results):
    print(f"candidate {i}: E = {r['energy']:.4f} eV, "
          f"fmax = {np.abs(r['forces']).max():.3f} eV/A")
print(f"bucket = {pot.last_bucket_key}, compiles = {pot.compile_count}")

# batched FIRE: converged candidates freeze in place, the batch exits
# when all are done
relaxed = BatchedRelaxer(pot, fmax=0.05).relax(pool, steps=200)
for i, res in enumerate(relaxed):
    print(f"candidate {i}: converged={res.converged} in {res.nsteps} steps, "
          f"E = {res.energy:.4f} eV")

# short fixed-cell MD on the relaxed pool, one temperature per candidate
for a in (r.atoms for r in relaxed):
    a.set_maxwell_boltzmann_velocities(300.0, rng=rng)
md = BatchedMD([r.atoms for r in relaxed], pot, ensemble="nvt_berendsen",
               temperature=[200.0, 300.0, 400.0, 500.0], timestep=1.0,
               seed=0)
md.run(20)
print("per-candidate temperatures after 20 fs:",
      np.round(md.temperatures(), 1))
print(f"total compiles across calculate/relax/MD: {pot.compile_count}")
