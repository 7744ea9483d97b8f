"""Overlap-aware halo pipeline (ISSUE 2): coalesced ppermute payloads,
interior/frontier edge split, and the fused site readout.

Three certification surfaces:
- jaxpr-level collective counts — the exchange emits exactly ONE ppermute
  per round, and the fused magmom readout adds no forward pass;
- numerical equivalence — the multi-partition program agrees with the
  single-partition one (no exchange at all) on energy/forces/stress,
  gradients still flow to the owning partition, and the interior/frontier
  reorder is an exact permutation of the unsplit edge list;
- fused readout parity — energy_and_aux_fn magmoms of the two-partition
  program match magmom_fn on the single-partition graph.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig
from distmlip_tpu.models.pair import PairConfig, PairPotential
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.parallel import (GRAPH_AXIS, graph_in_specs, graph_mesh,
                                   make_potential_fn)
from distmlip_tpu.parallel.audit import (count_collectives,
                                         ppermutes_by_scope)
from distmlip_tpu.parallel.halo import local_graph_from_stacked
from distmlip_tpu.partition import (CapacityPolicy, build_partitioned_graph,
                                    build_plan)
from tests.utils import make_crystal

CFG = CHGNetConfig(
    num_species=4, units=16, num_rbf=6, num_angle=4, num_blocks=3,
    cutoff=3.2, bond_cutoff=2.6,
)
A_LAT = 3.5
MODEL = CHGNet(CFG)
PAIR = PairPotential(PairConfig(cutoff=3.0))


@pytest.fixture(scope="module")
def params():
    return MODEL.init(jax.random.PRNGKey(0))


def _system(rng, reps=(6, 3, 3)):
    return make_crystal(rng, reps=reps, a=A_LAT)


def _graph(system, nparts, bond=True, frontier_split=True, caps=None):
    cart, lattice, species = system
    bond_r = CFG.bond_cutoff if bond else 0.0
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], CFG.cutoff,
                             bond_r=bond_r)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, CFG.cutoff, bond_r,
                      use_bond_graph=bond)
    graph, host = build_partitioned_graph(
        plan, nl, species, lattice, caps=caps or CapacityPolicy(),
        frontier_split=frontier_split)
    return cart, nl, plan, graph, host


def _ppermute_count(fn, *args):
    return count_collectives(jax.make_jaxpr(fn)(*args)).get("ppermute", 0)


# ---------------------------------------------------------------------------
# jaxpr-level collective counts
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_coalesced_one_ppermute_per_exchange_round(rng, params):
    """Each CHGNet sync point (atom+bond refresh together) emits exactly
    ONE ppermute on a 2-partition graph (single ring shift): the forward
    trunk's count equals its number of exchange rounds."""
    cart, nl, plan, graph, host = _graph(_system(rng), 2)
    mesh = graph_mesh(2)

    def forward(params, graph, positions):
        def local(g, pos):
            lg, _ = local_graph_from_stacked(g, GRAPH_AXIS)
            return MODEL.energy_fn(params, lg, pos[0])[None]

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(graph_in_specs(graph), P(GRAPH_AXIS)),
            out_specs=P(GRAPH_AXIS), check_vma=False,
        )(graph, positions)

    n = _ppermute_count(forward, params, graph, graph.positions)
    # exchange rounds for num_blocks=3 with bond graph: 1 fused init
    # (v + bond geometry) + per inner block (2 of them): 1 fused (v + b),
    # plus 1 bond-only refresh feeding the SECOND block's angle conv — the
    # last block's refresh/angle update feeds nothing and is skipped (dead
    # communication, flagged by the dead_compute pass); the final atom conv
    # re-uses the last exchange
    assert n == 4, f"expected 4 coalesced exchange rounds, traced {n}"

    # every ppermute sits under a halo scope (no stray collectives)
    scopes = ppermutes_by_scope(jax.make_jaxpr(forward)(
        params, graph, graph.positions))
    assert sum(scopes.values()) == n


@pytest.mark.tier1
def test_fused_readout_adds_no_forward(rng, params):
    """The aux (magmom) output rides the energy program: identical
    collective and GEMM counts to the energy-only potential — i.e. no
    second forward pass (compile-level certification)."""
    cart, nl, plan, graph, host = _graph(_system(rng), 2)
    mesh = graph_mesh(2)
    args = (params, graph, graph.positions)

    pot = make_potential_fn(MODEL.energy_fn, mesh)
    pot_aux = make_potential_fn(MODEL.energy_and_aux_fn, mesh, aux=True)
    assert _ppermute_count(pot_aux, *args) == _ppermute_count(pot, *args)

    def dots(fn):
        c = count_collectives(jax.make_jaxpr(fn)(*args))
        jaxpr = jax.make_jaxpr(fn)(*args)
        from distmlip_tpu.parallel.audit import _iter_eqns

        return sum(1 for e in _iter_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "dot_general"), c
    n_dots, _ = dots(pot)
    n_dots_aux, _ = dots(pot_aux)
    # the sitewise linear adds exactly one extra (tiny) GEMM, nothing else
    assert n_dots_aux - n_dots <= 1


# ---------------------------------------------------------------------------
# numerical equivalence
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_halo_modes_match_single_partition_chgnet(rng, params):
    """energy/forces/stress of the two-partition program agree <= 1e-5
    (fp32) with the single-partition one, which exchanges nothing, on a
    bond-graph CHGNet system (acceptance criterion)."""
    caps = CapacityPolicy()
    system = _system(rng)
    outs = {}
    for nparts in (1, 2):
        cart, nl, plan, graph, host = _graph(system, nparts, caps=caps)
        mesh = graph_mesh(nparts) if nparts > 1 else None
        pot = make_potential_fn(MODEL.energy_fn, mesh)
        out = pot(params, graph, graph.positions)
        outs[nparts] = (
            float(out["energy"]),
            host.gather_owned(np.asarray(out["forces"]), len(cart)),
            np.asarray(out["stress"]),
        )
    e0, f0, s0 = outs[1]
    assert np.abs(f0).max() > 1e-4  # non-degeneracy guard
    e, f, s = outs[2]
    assert abs(e - e0) <= 1e-5 * max(1.0, abs(e0))
    np.testing.assert_allclose(f, f0, atol=1e-5)
    np.testing.assert_allclose(s, s0, atol=1e-5)


@pytest.mark.tier1
def test_halo_modes_match_pair(rng):
    p = PAIR.init()
    caps = CapacityPolicy()
    cart, lattice, species = make_crystal(rng, reps=(8, 3, 3), a=A_LAT)
    outs = {}
    for nparts in (1, 4):
        nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], PAIR.cfg.cutoff)
        plan = build_plan(nl, lattice, [1, 1, 1], nparts, PAIR.cfg.cutoff)
        graph, host = build_partitioned_graph(plan, nl, species, lattice,
                                              caps=caps)
        mesh = graph_mesh(nparts) if nparts > 1 else None
        pot = make_potential_fn(PAIR.energy_fn, mesh)
        out = pot(p, graph, graph.positions)
        outs[nparts] = (float(out["energy"]),
                        host.gather_owned(np.asarray(out["forces"]),
                                          len(cart)))
    e0, f0 = outs[1]
    assert np.abs(f0).max() > 1e-4
    e, f = outs[4]
    assert abs(e - e0) <= 1e-5 * max(1.0, abs(e0))
    np.testing.assert_allclose(f, f0, atol=1e-5)


@pytest.mark.parametrize("tables", ["node", "bond"])
def test_gradients_flow_to_owner_both_modes(rng, tables):
    """d(sum of received rows)/d(local rows) counts, at every local row,
    the partitions it is sent to, and is 0 elsewhere — through
    ``halo_exchange`` (node tables) and ``bond_halo_exchange`` (bond
    tables): the transposed-ppermute force flow."""
    nparts = 2 if tables == "bond" else 4
    cart, nl, plan, graph, host = _graph(
        make_crystal(rng, reps=(8, 2, 2), a=A_LAT), nparts,
        bond=tables == "bond")
    mesh = graph_mesh(nparts)
    if tables == "bond":
        exchange = lambda lg, x: lg.bond_halo_exchange(x)
        cap = graph.b_cap
        send, send_mask, recv = (np.asarray(graph.bond_halo_send_idx),
                                 np.asarray(graph.bond_halo_send_mask),
                                 np.asarray(graph.bond_halo_recv_idx))
    else:
        exchange = lambda lg, x: lg.halo_exchange(x)
        cap = graph.n_cap
        send, send_mask, recv = (np.asarray(graph.halo_send_idx),
                                 np.asarray(graph.halo_send_mask),
                                 np.asarray(graph.halo_recv_idx))

    def loss(graph_l, recv_l, feats):
        lg, _ = local_graph_from_stacked(graph_l, GRAPH_AXIS)
        full = exchange(lg, feats[0])
        received = jnp.zeros(cap, bool).at[recv_l[:, 0].reshape(-1)].set(
            True, mode="drop")
        return jax.lax.psum(jnp.sum(full * received[:, None]), GRAPH_AXIS)

    def total(feats):
        return jax.shard_map(
            loss, mesh=mesh,
            in_specs=(graph_in_specs(graph), P(None, GRAPH_AXIS),
                      P(GRAPH_AXIS)),
            out_specs=P(), check_vma=False,
        )(graph, jnp.asarray(recv), feats)

    g = np.asarray(jax.grad(total)(jnp.zeros((nparts, cap, 2), jnp.float32)))
    want = np.zeros((nparts, cap))
    for si in range(send.shape[0]):
        for p in range(nparts):
            np.add.at(want[p], send[si, p][send_mask[si, p]], 1.0)
    assert want.sum() > 0 and want.sum() == (recv < cap).sum()
    np.testing.assert_array_equal(g[..., 0], want)
    np.testing.assert_array_equal(g[..., 1], want)
    if tables == "node":
        for p in range(nparts):
            m = plan.node_markers[p]
            P_ = plan.num_partitions
            np.testing.assert_allclose(g[p, : m[1]], 0.0)          # pure
            np.testing.assert_allclose(g[p, m[1]: m[1 + P_]], 1.0)  # to-sections
            np.testing.assert_allclose(g[p, m[1 + P_]:], 0.0)      # halo+pad


def test_exchange_all_matches_sequential(rng):
    """Coalescing N arrays into one ppermute delivers exactly what N
    separate ``halo_exchange`` calls deliver — mixed widths and dtypes
    included — in one collective instead of N."""
    nparts = 2
    cart, nl, plan, graph, host = _graph(_system(rng), nparts)
    mesh = graph_mesh(nparts)
    n = len(cart)
    fa = rng.standard_normal((n, 5)).astype(np.float32)
    fb = rng.standard_normal((n, 3)).astype(np.float32)
    la = host.scatter_global(fa, graph.n_cap)
    lb = host.scatter_global(fb, graph.n_cap)
    for p in range(nparts):
        oc = host.owned_counts[p]
        la[p, oc:] = 0.0
        lb[p, oc:] = 0.0

    def run(together):
        def f(g, xa, xb):
            lg, _ = local_graph_from_stacked(g, GRAPH_AXIS)
            xb = xb[0].astype(jnp.bfloat16)
            if together:
                (a, b), _ = lg.exchange_all((xa[0], xb), ())
            else:
                a, b = lg.halo_exchange(xa[0]), lg.halo_exchange(xb)
            return a[None], b.astype(jnp.float32)[None]

        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(graph_in_specs(graph), P(GRAPH_AXIS), P(GRAPH_AXIS)),
            out_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS)), check_vma=False)

    args = (graph, jnp.asarray(la), jnp.asarray(lb))
    a_c, b_c = run(True)(*args)
    a_l, b_l = run(False)(*args)
    np.testing.assert_array_equal(np.asarray(a_c), np.asarray(a_l))
    np.testing.assert_array_equal(np.asarray(b_c), np.asarray(b_l))
    assert (2 * _ppermute_count(run(True), *args)
            == _ppermute_count(run(False), *args) > 0)
    # and the refreshed rows carry the owner's values
    for p in range(nparts):
        g_ids = plan.global_ids[p]
        np.testing.assert_allclose(np.asarray(a_c)[p, : len(g_ids)],
                                   fa[g_ids], atol=0)


# ---------------------------------------------------------------------------
# interior/frontier reorder
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_frontier_reorder_is_exact_permutation(rng, params):
    """The split layout holds the SAME edge set as the unsplit one, each
    segment is dst-sorted, interior edges read no halo rows — and model
    results agree with the unsplit layout."""
    caps_a, caps_b = CapacityPolicy(), CapacityPolicy()
    system = _system(rng)
    cart, nl, plan, g_split, host = _graph(system, 2, caps=caps_a)
    _, _, _, g_flat, host_flat = _graph(system, 2, frontier_split=False,
                                        caps=caps_b)
    assert g_split.has_bond_graph
    assert 0 < g_split.e_split < g_split.e_cap
    assert g_flat.e_split == g_flat.e_cap  # unsplit sentinel

    for p in range(2):
        oc = host.owned_counts[p]
        mask = np.asarray(g_split.edge_mask[p])
        src = np.asarray(g_split.edge_src[p])
        dst = np.asarray(g_split.edge_dst[p])
        s = g_split.e_split
        # per-segment sorted (incl. padding contract)
        assert np.all(np.diff(dst[:s]) >= 0)
        assert np.all(np.diff(dst[s:]) >= 0)
        # interior reads owned rows only; frontier src are halo rows
        assert np.all(src[:s][mask[:s]] < oc)
        assert np.all(src[s:][mask[s:]] >= oc)
        # same (src, dst, offset) multiset as the unsplit layout
        off = np.asarray(g_split.edge_offset[p])
        flat_mask = np.asarray(g_flat.edge_mask[p])
        flat_rows = np.stack(
            [np.asarray(g_flat.edge_src[p])[flat_mask],
             np.asarray(g_flat.edge_dst[p])[flat_mask]], axis=1)
        split_rows = np.stack([src[mask], dst[mask]], axis=1)
        assert flat_rows.shape == split_rows.shape
        key = lambda rows: rows[np.lexsort(rows.T)]
        np.testing.assert_array_equal(key(flat_rows), key(split_rows))
        assert mask.sum() == flat_mask.sum()
        assert np.all(np.abs(off[~mask]) == 0)

    mesh = graph_mesh(2)
    pot = make_potential_fn(MODEL.energy_fn, mesh)
    out_s = pot(params, g_split, g_split.positions)
    out_f = pot(params, g_flat, g_flat.positions)
    f_s = host.gather_owned(np.asarray(out_s["forces"]), len(cart))
    f_f = host_flat.gather_owned(np.asarray(out_f["forces"]), len(cart))
    assert abs(float(out_s["energy"]) - float(out_f["energy"])) <= 1e-5
    np.testing.assert_allclose(f_s, f_f, atol=1e-5)


def test_aggregate_edges_matches_unsorted_reference(rng):
    """LocalGraph.aggregate_edges == a plain unsorted segment_sum over the
    same (data, dst, mask) — the per-segment sorted fast path changes
    nothing."""
    cart, nl, plan, graph, host = _graph(_system(rng), 2)
    lg, _ = local_graph_from_stacked(
        jax.tree.map(lambda x: jnp.asarray(x)
                     if hasattr(x, "dtype") else x, graph), None)
    data = jnp.asarray(
        rng.standard_normal((graph.e_cap, 4)).astype(np.float32))
    mask = lg.edge_mask
    got = np.asarray(lg.aggregate_edges(data, mask))
    want = np.asarray(jax.ops.segment_sum(
        jnp.where(mask[:, None], data, 0.0), lg.edge_dst,
        num_segments=lg.n_cap))
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# fused site readout
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_fused_magmom_parity_vs_site_fn(rng, params):
    """DistPotential's fused aux magmoms on two partitions == the model's
    own ``magmom_fn`` on the single-partition graph."""
    from distmlip_tpu.calculators import Atoms, DistPotential

    system = make_crystal(rng, reps=(4, 2, 2), a=A_LAT)
    cart, lattice, species = system
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.concatenate([[0], np.arange(0, 8)]).astype(np.int32)
    pot = DistPotential(MODEL, params, num_partitions=2,
                        species_map=smap, compute_magmom=True)
    fused = pot.calculate(atoms)["magmoms"]
    pot.close()
    _, _, _, graph, host = _graph(system, 1)
    lg, pos = local_graph_from_stacked(graph, None)
    want = host.gather_owned(
        np.asarray(MODEL.magmom_fn(params, lg, pos))[None], len(cart))
    assert fused.shape == want.shape == (len(cart),)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(fused, want, atol=1e-5)
