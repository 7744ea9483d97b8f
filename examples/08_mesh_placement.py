"""2-D mesh placements: batch-parallel x graph-parallel on one named mesh.

One ``Mesh(("batch", "spatial"))`` serves every placement of a packed
batch (see README "2-D mesh"): B structures x 1 slab (pure batch-parallel,
zero collectives), 1 structure x S slabs (the spatial halo ring), and
B x S where each packed structure is itself spatially partitioned. The
communication contract — the batch axis NEVER carries a collective, the
spatial axis pays exactly the 1-D ring's ppermutes — is auditable at the
jaxpr level, shown below.

Run: JAX_PLATFORMS=cpu python examples/08_mesh_placement.py
(8 virtual CPU devices; without the variable it runs on the devices jax
finds and needs eight of them)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# 8 virtual CPU devices so every placement of a 2-D mesh runs for real;
# decided before the backend initializes, read by the CPU backend only
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, BatchedPotential, DistPotential
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.parallel import (BATCH_AXIS, SPATIAL_AXIS, device_mesh,
                                   make_batched_potential_fn)
from distmlip_tpu.parallel.audit import collectives_by_axis
from distmlip_tpu.partition import pack_structures

rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])


def structure(reps, a=3.5, noise=0.05):
    """A perturbed fcc supercell, wide along x so it slabs into S=2 parts
    (slab rule: extent / S > 2x cutoff)."""
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


# a small screening pool: sizes and cells differ, every structure wide
# enough to spatially partition
pool = [structure((4, 1, 1)), structure((4, 2, 1), a=3.7),
        structure((5, 1, 1), a=3.4), structure((4, 1, 1), a=3.6)]

model = TensorNet(TensorNetConfig(num_species=95, cutoff=3.2))
params = model.init(jax.random.PRNGKey(0))

# single-device reference for the parity compare
ref_pot = DistPotential(model, params, num_partitions=1)
refs = [ref_pot.calculate(a) for a in pool]

# the same pool across three placements at equal or growing chip count:
#   (4, 1) — pure batch-parallel: one structure per batch shard, no halo
#   (1, 2) — the spatial ring: every structure split into 2 slabs
#   (4, 2) — mixed: 4 batch shards x 2 slabs each = all 8 devices
for B, S in [(4, 1), (1, 2), (4, 2)]:
    pot = BatchedPotential(model, params, mesh=device_mesh(B, S))
    results = pot.calculate(pool)
    d_e = max(abs(r["energy"] - ref["energy"])
              for r, ref in zip(results, refs))
    d_f = max(np.abs(r["forces"] - ref["forces"]).max()
              for r, ref in zip(results, refs))
    print(f"placement {B}x{S} (batch x spatial): "
          f"dE_max={d_e:.2e} eV  dF_max={d_f:.2e} eV/A  "
          f"bucket={pot.last_bucket_key}")

# the communication contract, read off the jaxpr: collectives attributed
# per mesh axis — the batch axis is silent at EVERY placement, and the
# spatial ppermute count at (4, 2) matches the 1-D ring at S=2 (packing
# adds structures, not communication)
print("\ncollectives per mesh axis:")
for B, S in [(4, 1), (1, 2), (4, 2)]:
    graph, _host = pack_structures(pool, cutoff=3.2,
                                   batch_parts=B, spatial_parts=S)
    fn = make_batched_potential_fn(model.energy_fn, mesh=device_mesh(B, S))
    by_axis = collectives_by_axis(
        jax.make_jaxpr(fn)(params, graph, graph.positions))
    batch_n = sum(by_axis.get(BATCH_AXIS, {}).values())
    spatial = dict(by_axis.get(SPATIAL_AXIS, {}))
    print(f"  {B}x{S}: batch axis = {batch_n}, spatial axis = {spatial}")
assert batch_n == 0, "the batch axis must never carry a collective"

# oversized-structure routing: a ServeEngine over a mesh-placed
# BatchedPotential routes small requests to the batch axis and anything
# past max_batch_atoms to a DistPotential on the SPATIAL sub-axis of the
# same mesh — one mesh, two routes, uniform telemetry
from distmlip_tpu.serve import ServeEngine

big = structure((6, 2, 2))
engine = ServeEngine(BatchedPotential(model, params, mesh=device_mesh(4, 2)),
                     max_batch=4, max_wait_s=0.005,
                     max_batch_atoms=len(big) - 1)
futures = [engine.submit(a) for a in pool + [big]]
engine.drain(timeout=300)
for i, f in enumerate(futures):
    route = "spatial lane" if i == len(pool) else "batch axis"
    print(f"request {i} ({route}): E = {f.result()['energy']:.4f} eV")
print(f"oversized requests routed to the spatial axis: "
      f"{engine.stats.fallback_requests} "
      f"(lane partitions: {engine._spatial_lane.num_partitions})")
engine.close()
