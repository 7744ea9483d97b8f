"""Shared helpers for model tests: build systems, run potentials.

``run_potential`` memoizes the jitted potential per (model, nparts,
compute_stress) and shares one sticky CapacityPolicy across calls, so
repeated evaluations of the same system (finite-difference loops,
cutoff-smoothness scans, rotated copies) hit XLA's jit cache instead of
recompiling — this is what keeps the suite wall time bounded.
"""

import weakref

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.models import PairConfig, PairPotential
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.parallel import graph_mesh, make_potential_fn
from distmlip_tpu.partition import CapacityPolicy, build_plan, build_partitioned_graph


def make_crystal(rng, reps=(4, 4, 4), a=4.0, noise=0.05, n_species=2):
    """Perturbed fcc-ish supercell with random species."""
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, noise, (len(frac), 3))
    species = rng.integers(0, n_species, len(frac)).astype(np.int32)
    return cart, lattice, species


def make_atoms(rng, reps=(3, 3, 3), a=3.8, noise=0.03):
    """Perturbed fcc Si supercell as Atoms (the calculators' tests)."""
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


def lj_potential():
    """Two-partition Lennard-Jones DistPotential, eps scaled to 0.1 (the
    MD and relaxer tests' module-scoped ``potential`` fixtures)."""
    model = PairPotential(PairConfig(cutoff=3.5, kind="lj"))
    params = model.init()
    params = {"eps": params["eps"] * 0.1, "sigma": params["sigma"]}
    return DistPotential(model, params, num_partitions=2, compute_stress=True)


_SHARED_CAPS = CapacityPolicy()
# model -> {(nparts, compute_stress): jitted potential}; weak keys so
# function-scoped models don't pin memory or alias recycled ids
_POT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _potential_for(energy_fn, nparts, compute_stress, grid=None):
    owner = getattr(energy_fn, "__self__", None)
    if owner is None:
        mesh = graph_mesh(nparts) if nparts > 1 else None
        return make_potential_fn(energy_fn, mesh, compute_stress=compute_stress)
    per_owner = _POT_CACHE.setdefault(owner, {})
    key = (nparts, bool(compute_stress), grid)
    if key not in per_owner:
        mesh = graph_mesh(nparts) if nparts > 1 else None
        per_owner[key] = make_potential_fn(
            energy_fn, mesh, compute_stress=compute_stress
        )
    return per_owner[key]


def run_potential(
    energy_fn, params, cart, lattice, species, r, nparts,
    bond_r=0.0, use_bond_graph=False, caps=None, compute_stress=True,
    dtype=np.float32, grid=None,
):
    """Full pipeline: neighbors -> partition -> graph -> potential."""
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], r, bond_r=bond_r)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, r, bond_r,
                      use_bond_graph, grid=grid)
    graph, host = build_partitioned_graph(
        plan, nl, species, lattice, caps=caps or _SHARED_CAPS, dtype=dtype
    )
    pot = _potential_for(energy_fn, nparts, compute_stress, grid)
    out = pot(params, graph, graph.positions)
    forces = host.gather_owned(np.asarray(out["forces"]), len(cart))
    return float(out["energy"]), forces, np.asarray(out["stress"])
