"""Halo exchange on a real multi-device CPU mesh: correctness + gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.parallel import GRAPH_AXIS, graph_in_specs, graph_mesh
from distmlip_tpu.parallel.halo import local_graph_from_stacked
from distmlip_tpu.partition import build_plan, build_partitioned_graph
from tests.conftest import random_cell


R = 3.0


def setup(rng, nparts, bond=False):
    box = max(16.0, nparts * 8.0)
    cart, lattice, species, pbc = random_cell(rng, n_atoms=int(0.02 * box**3), box=box)
    nl = neighbor_list_numpy(cart, lattice, pbc, R, bond_r=2.0)
    plan = build_plan(nl, lattice, pbc, nparts, R, 2.0, use_bond_graph=bond)
    graph, host = build_partitioned_graph(plan, nl, species, lattice)
    return nl, plan, graph, host


@pytest.mark.parametrize("nparts", [2, 4, 8])
def test_halo_exchange_delivers_owner_rows(rng, nparts):
    nl, plan, graph, host = setup(rng, nparts)
    mesh = graph_mesh(nparts)
    n = nl.wrapped_cart.shape[0]
    # unique global feature per atom
    feats_global = np.arange(n, dtype=np.float32)[:, None] * 10.0 + np.arange(
        4, dtype=np.float32
    )
    local = host.scatter_global(feats_global, graph.n_cap)
    # zero the halo rows: the exchange must repopulate them
    for p in range(nparts):
        oc = host.owned_counts[p]
        local[p, oc:] = 0.0

    def f(graph_l, feats):
        lg, _ = local_graph_from_stacked(graph_l, GRAPH_AXIS)
        return lg.halo_exchange(feats[0])[None]

    out = jax.shard_map(
        f, mesh=mesh, in_specs=(graph_in_specs(graph), P(GRAPH_AXIS)),
        out_specs=P(GRAPH_AXIS), check_vma=False,
    )(graph, jnp.asarray(local))
    out = np.asarray(out)
    for p in range(nparts):
        g = plan.global_ids[p]
        np.testing.assert_allclose(out[p, : len(g)], feats_global[g], atol=0)


@pytest.mark.parametrize("nparts", [2, 4])
def test_halo_exchange_gradients_flow_to_owner(rng, nparts):
    """d(sum of halo rows)/d(owned rows) must be 1 at the owner slots."""
    nl, plan, graph, host = setup(rng, nparts)
    mesh = graph_mesh(nparts)
    n = nl.wrapped_cart.shape[0]

    def loss(graph_l, feats):
        lg, _ = local_graph_from_stacked(graph_l, GRAPH_AXIS)
        full = lg.halo_exchange(feats[0])
        halo_mask = lg.node_mask & ~lg.owned_mask
        return jax.lax.psum(jnp.sum(full * halo_mask[:, None]), GRAPH_AXIS)

    def total(feats):
        return jax.shard_map(
            loss, mesh=mesh, in_specs=(graph_in_specs(graph), P(GRAPH_AXIS)),
            out_specs=P(), check_vma=False,
        )(graph, feats)

    local = jnp.asarray(host.scatter_global(np.zeros((n, 2), np.float32), graph.n_cap))
    g = np.asarray(jax.grad(total)(local))
    # each border (to-section) row contributes once; pure rows not at all
    for p in range(nparts):
        m = plan.node_markers[p]
        P_ = plan.num_partitions
        np.testing.assert_allclose(g[p, : m[1]], 0.0)  # pure
        np.testing.assert_allclose(g[p, m[1] : m[1 + P_]], 1.0)  # to-sections
        np.testing.assert_allclose(g[p, m[1 + P_] :], 0.0)  # halo+pad


@pytest.mark.parametrize("nparts", [2, 4])
def test_bond_halo_exchange(rng, nparts):
    nl, plan, graph, host = setup(rng, nparts, bond=True)
    mesh = graph_mesh(nparts)
    # global bond feature = f(global edge id)
    def seed(p):
        arr = np.zeros((graph.b_cap, 3), np.float32)
        b_edge = plan.bond_global_edge[p]
        owned_b = plan.bond_markers[p][1 + nparts]
        arr[:owned_b] = b_edge[:owned_b, None].astype(np.float32) + np.arange(3)
        return arr

    local = jnp.asarray(np.stack([seed(p) for p in range(nparts)]))

    def f(graph_l, feats):
        lg, _ = local_graph_from_stacked(graph_l, GRAPH_AXIS)
        return lg.bond_halo_exchange(feats[0])[None]

    out = np.asarray(
        jax.shard_map(
            f, mesh=mesh, in_specs=(graph_in_specs(graph), P(GRAPH_AXIS)),
            out_specs=P(GRAPH_AXIS), check_vma=False,
        )(graph, local)
    )
    for p in range(nparts):
        b_edge = plan.bond_global_edge[p]
        nb = len(b_edge)
        want = b_edge[:, None].astype(np.float32) + np.arange(3)
        np.testing.assert_allclose(out[p, :nb], want, atol=0)
