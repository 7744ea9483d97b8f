"""The operation counts behind ``model.mfu.md`` against a count of the
contractions in the jaxpr of the plain, un-rematerialised, unpadded
energy-and-forces program at a small size; ``segment_sum``'s bytes against
its shapes."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.families import mace, tensornet
from benchmark.harness import structures
from benchmark.reference import common

FAMILIES = {"mace": mace, "tensornet": tensornet}


def contraction_flops(jaxpr) -> float:
    """2 * batch * M * N * K over every ``dot_general`` that contracts
    something, through every nested jaxpr."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if lc:
                batch = np.prod([lhs[i] for i in lb])
                k = np.prod([lhs[i] for i in lc])
                m = np.prod(lhs) / batch / k
                n = np.prod(rhs) / batch / k
                total += 2.0 * batch * m * n * k
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += contraction_flops(sub)
    return total


@pytest.mark.parametrize("name", ["mace", "tensornet"])
def test_step_flops_against_the_jaxpr(name, tmp_path):
    family, cfg = FAMILIES[name], toy.TOY_MODELS[name]
    tables = family.reference.Tables(cfg, str(tmp_path))
    params = family.reference.init_params(cfg, tables, jax.random.PRNGKey(0))
    numbers, positions, cell = structures.perturbed_fcc(
        (3, 3, 3), 3.9, 0.04, 14, seed=0)
    src, dst, shift = common.neighbour_pairs(positions, cell, cfg["cutoff"])
    edges = (jnp.asarray(src), jnp.asarray(dst),
             jnp.asarray(shift, jnp.float32))

    def energy(pos):
        return family.reference.site_energies(
            params, cfg, tables, jnp.asarray(numbers), pos, edges,
            edge_block=None, node_block=None).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(energy))(
        jnp.asarray(positions, jnp.float32))
    counted = contraction_flops(jaxpr.jaxpr)
    ours = family.step_flops(cfg, tables, len(numbers), len(src))
    # within 3 %: the count takes Y_0 (a constant) as moving with the atoms
    assert ours == pytest.approx(counted, rel=0.03), (ours, counted)


def test_step_flops_scale_with_atoms_and_edges(tmp_path):
    cfg = toy.TOY_MODELS["mace"]
    tables = mace.reference.Tables(cfg, str(tmp_path))
    one = mace.step_flops(cfg, tables, 100, 4000)
    assert mace.step_flops(cfg, tables, 200, 8000) == pytest.approx(2 * one)
    assert mace.step_flops(cfg, tables, 100, 8000) > one


def test_segment_sum_bytes_follow_its_shapes(tmp_path):
    cfg = toy.TOY_MODELS["mace"]
    tables = mace.reference.Tables(cfg, str(tmp_path))
    work = mace.kernel_work(cfg, tables, n_atoms=100, n_edges_built=5000)
    c = cfg["channels"]
    rows = [sum(2 * lo + 1 for _, _, lo in tables.paths[t])
            for t in range(cfg["num_interactions"])]
    assert rows == [9, 21]   # l_max = a_lmax = 2, hidden_lmax = 1
    expected = sum(2 * q * c * (5000 + 100) + 4 * 5000 for q in rows)
    assert work["segment_sum"]["bytes"] == expected
    assert work["segment_sum"]["flops"] == sum(5000 * q * c for q in rows)
    assert tensornet.kernel_work({}, None, 1, 1) == {}
