"""Partitioner invariants: disjoint cover, edge conservation, halo alignment,
line-graph equivalence vs a brute-force global line graph."""

import numpy as np
import pytest

from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.partition import PartitionError, build_plan
from tests.conftest import random_cell

R = 3.0
BOND_R = 2.0


def make_plan(rng, P, n_atoms=None, box=None, bond=False):
    # slab width must exceed 2*R for the one-destination halo invariant
    box = box or max(16.0, P * 8.0)
    n_atoms = n_atoms or int(0.02 * box**3)
    cart, lattice, species, pbc = random_cell(rng, n_atoms=n_atoms, box=box)
    nl = neighbor_list_numpy(cart, lattice, pbc, R, bond_r=BOND_R)
    plan = build_plan(nl, lattice, pbc, P, R, BOND_R, use_bond_graph=bond)
    return plan, nl, lattice


@pytest.mark.parametrize("P", [1, 2, 4])
def test_owned_disjoint_cover(rng, P):
    plan, nl, _ = make_plan(rng, P)
    n = nl.wrapped_cart.shape[0]
    seen = np.zeros(n, dtype=int)
    for p in range(P):
        owned = plan.global_ids[p][: plan.owned_counts[p]]
        seen[owned] += 1
    np.testing.assert_array_equal(seen, np.ones(n, dtype=int))


@pytest.mark.parametrize("P", [1, 2, 4])
def test_edge_conservation(rng, P):
    plan, nl, _ = make_plan(rng, P)
    all_ids = np.concatenate([plan.edge_ids[p] for p in range(P)])
    assert len(all_ids) == nl.num_edges
    np.testing.assert_array_equal(np.sort(all_ids), np.arange(nl.num_edges))


@pytest.mark.parametrize("P", [2, 4])
def test_edge_localization(rng, P):
    """Local endpoints must map back to the correct global endpoints."""
    plan, nl, _ = make_plan(rng, P)
    for p in range(P):
        g = plan.global_ids[p]
        np.testing.assert_array_equal(g[plan.src_local[p]], nl.src[plan.edge_ids[p]])
        np.testing.assert_array_equal(g[plan.dst_local[p]], nl.dst[plan.edge_ids[p]])


@pytest.mark.parametrize("P", [2, 4])
def test_halo_alignment(rng, P):
    """to_q section of p and from_p section of q hold the same global ids in
    the same order — the exchange is then a pure slot copy."""
    plan, _, _ = make_plan(rng, P)
    for p in range(P):
        for q in range(P):
            if p == q:
                continue
            ts, te = plan.section(p, "to", q)
            fs, fe = plan.section(q, "from", p)
            np.testing.assert_array_equal(
                plan.global_ids[p][ts:te], plan.global_ids[q][fs:fe]
            )


@pytest.mark.parametrize("P", [2, 4])
def test_border_reach(rng, P):
    """Every cross-partition edge's src is present in the dst's partition."""
    plan, nl, _ = make_plan(rng, P)
    for p in range(P):
        assert np.all(plan.g2l[p][nl.src[plan.edge_ids[p]]] >= 0)


def test_too_many_partitions_raises(rng):
    cart, lattice, _, pbc = random_cell(rng, n_atoms=60, box=10.0)
    nl = neighbor_list_numpy(cart, lattice, pbc, R)
    with pytest.raises(PartitionError):
        build_plan(nl, lattice, pbc, 8, R)


def _global_line_graph(nl):
    """Brute-force directed line graph over within-bond edges.

    (e1=(s->d), e2=(d->k)) with k != s; returns the set of global edge-id
    pairs plus the center atom d.
    """
    W = np.nonzero(nl.bond_mask)[0]
    pairs = set()
    by_src = {}
    for e in W:
        by_src.setdefault(int(nl.src[e]), []).append(e)
    for e1 in W:
        d = int(nl.dst[e1])
        for e2 in by_src.get(d, []):
            if int(nl.dst[e2]) == int(nl.src[e1]):
                continue
            pairs.add((int(e1), int(e2), d))
    return pairs


@pytest.mark.parametrize("P", [1, 2, 4])
def test_line_graph_equivalence(rng, P):
    plan, nl, _ = make_plan(rng, P, bond=True)
    got = set()
    for p in range(P):
        b_edge = plan.bond_global_edge[p]
        g = plan.global_ids[p]
        for ls, ld, c in zip(plan.line_src[p], plan.line_dst[p], plan.line_center_local[p]):
            got.add((int(b_edge[ls]), int(b_edge[ld]), int(g[c])))
    want = _global_line_graph(nl)
    assert got == want


@pytest.mark.parametrize("P", [2, 4])
def test_line_graph_no_duplicates(rng, P):
    plan, _, _ = make_plan(rng, P, bond=True)
    total, uniq = 0, set()
    for p in range(P):
        b_edge = plan.bond_global_edge[p]
        for ls, ld in zip(plan.line_src[p], plan.line_dst[p]):
            uniq.add((int(b_edge[ls]), int(b_edge[ld])))
            total += 1
    assert total == len(uniq)


@pytest.mark.parametrize("P", [2, 4])
def test_bond_halo_alignment(rng, P):
    plan, _, _ = make_plan(rng, P, bond=True)
    for p in range(P):
        for q in range(P):
            if p == q:
                continue
            ts, te = plan.bond_section(p, "to", q)
            fs, fe = plan.bond_section(q, "from", p)
            np.testing.assert_array_equal(
                plan.bond_global_edge[p][ts:te], plan.bond_global_edge[q][fs:fe]
            )


@pytest.mark.parametrize("P", [1, 2, 4])
def test_bond_mapping(rng, P):
    """Owned bond nodes map to local edges carrying the same global edge."""
    plan, nl, _ = make_plan(rng, P, bond=True)
    for p in range(P):
        local_edge_global = plan.edge_ids[p][plan.bond_mapping_edge[p]]
        bond_global = plan.bond_global_edge[p][plan.bond_mapping_bond[p]]
        np.testing.assert_array_equal(local_edge_global, bond_global)


@pytest.mark.parametrize("P", [2, 4])
@pytest.mark.parametrize("bond", [False, True])
def test_native_matches_numpy_oracle(rng, P, bond):
    """The C++ partitioner must reproduce the numpy plan EXACTLY."""
    from distmlip_tpu.neighbors import neighbor_list_numpy

    box = max(16.0, P * 8.0)
    cart, lattice, _, pbc = random_cell(rng, n_atoms=int(0.02 * box**3), box=box)
    nl = neighbor_list_numpy(cart, lattice, pbc, R, bond_r=BOND_R)
    p_np = build_plan(nl, lattice, pbc, P, R, BOND_R, bond, impl="numpy")
    p_nat = build_plan(nl, lattice, pbc, P, R, BOND_R, bond, impl="native")
    for p in range(P):
        np.testing.assert_array_equal(p_np.global_ids[p], p_nat.global_ids[p])
        np.testing.assert_array_equal(p_np.node_markers[p], p_nat.node_markers[p])
        np.testing.assert_array_equal(p_np.edge_ids[p], p_nat.edge_ids[p])
        np.testing.assert_array_equal(p_np.src_local[p], p_nat.src_local[p])
        np.testing.assert_array_equal(p_np.dst_local[p], p_nat.dst_local[p])
        if bond:
            np.testing.assert_array_equal(p_np.bond_markers[p], p_nat.bond_markers[p])
            np.testing.assert_array_equal(
                p_np.bond_global_edge[p], p_nat.bond_global_edge[p])
            np.testing.assert_array_equal(p_np.line_src[p], p_nat.line_src[p])
            np.testing.assert_array_equal(p_np.line_dst[p], p_nat.line_dst[p])
            np.testing.assert_array_equal(
                p_np.line_center_local[p], p_nat.line_center_local[p])
            np.testing.assert_array_equal(
                p_np.bond_mapping_edge[p], p_nat.bond_mapping_edge[p])
            np.testing.assert_array_equal(
                p_np.bond_mapping_bond[p], p_nat.bond_mapping_bond[p])
    np.testing.assert_array_equal(p_np.nodes_to_partition, p_nat.nodes_to_partition)


def test_native_partitioner_rejects_multidest(rng):
    from distmlip_tpu.neighbors import neighbor_list_numpy

    cart, lattice, _, pbc = random_cell(rng, n_atoms=200, box=16.0)
    nl = neighbor_list_numpy(cart, lattice, pbc, R)
    # P=4 on a 16 A box: slab 4 A > R so check_partition_size passes, but
    # nodes reach both sides (width < 2R) -> both impls must raise
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(PartitionError):
            build_plan(nl, lattice, pbc, 4, R, impl="native")
        with pytest.raises(PartitionError):
            build_plan(nl, lattice, pbc, 4, R, impl="numpy")


def test_make_walls_atoms_on_planes():
    """Perfect supercells put whole atom planes exactly at k/P: walls must
    nudge off them in either direction, stay strictly increasing, and stay
    inside (0, 1)."""
    from distmlip_tpu.partition.partitioner import EPSILON, make_walls

    P = 4
    frac = np.repeat(np.arange(P) / P, 16)          # planes at 0, .25, .5, .75
    walls = make_walls(frac, P)
    assert np.all(np.diff(walls) > 0)
    assert walls[0] > 0.0 and walls[-1] < 1.0
    assert np.abs(frac[:, None] - walls[None, :]).min() >= EPSILON
    # planes crowding a wall from above force a DOWNWARD nudge
    dense_above = np.concatenate(
        [frac, 0.25 + np.arange(1, 30) * 10 * EPSILON]
    )
    walls2 = make_walls(dense_above, P)
    assert walls2[0] < 0.25
    assert np.abs(dense_above[:, None] - walls2[None, :]).min() >= EPSILON
    assert np.all(np.diff(walls2) > 0)


def test_perfect_crystal_partition_end_to_end(rng):
    """A perfect (unperturbed) supercell — atoms exactly on wall planes —
    must partition with all invariants intact."""
    from distmlip_tpu import geometry

    unit = np.array([[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.0, (8, 2, 2))
    cart = geometry.frac_to_cart(frac, lattice)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], R, bond_r=0.0)
    plan = build_plan(nl, lattice, [1, 1, 1], 4, R)
    n = len(cart)
    seen = np.zeros(n, dtype=int)
    for p in range(4):
        mk = plan.node_markers[p]
        owned = plan.global_ids[p][: mk[1 + 4]]
        seen[owned] += 1
    assert np.all(seen == 1)
    assert sum(len(e) for e in plan.edge_ids) == nl.num_edges
