"""CHGNet's lines as a slot-major in-line table (``partition/graph.line_table``).

The host helper on a hand-built ragged list and on real plans (one and four
partitions, both packers of ``partition/batch.py``); the same lines by
centre atom (``partition/graph.center_table``, what DimeNet++'s scan reads)
on the same plans, a box narrower than twice the cutoff and a cut of
``dimenet-pp-md-1c``'s mix; ``LocalGraph``'s two
methods that know the order against ``x[line_dst]`` and ``masked_segment_sum``
of the dst-sorted list the table replaced, values and gradients, float32 and
bfloat16; and CHGNet's energy and forces on a two-species toy with vacancies
(ragged in-degrees) against the same model over plain ``x[idx]`` gathers and
``masked_segment_sum`` on that sorted list, a reference this file keeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.calculators import Atoms
from distmlip_tpu.kernels.dispatch import Gather, fused_edge_aggregate
from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.ops.nn import gated_mlp, gather_rows, mlp
from distmlip_tpu.ops import radial
from distmlip_tpu.ops.segment import masked_segment_sum
from distmlip_tpu.parallel import graph_mesh, make_potential_fn
from distmlip_tpu.parallel.halo import local_graph_from_stacked
from distmlip_tpu.partition import (BucketPolicy, CapacityPolicy, FixedCaps,
                                    build_partitioned_graph, build_plan,
                                    pack_structures)
from distmlip_tpu.partition.capacity import line_table_cap
from distmlip_tpu.partition import bucket_key, fixed_caps_for_batches
from distmlip_tpu.partition.graph import (center_table, line_slots_needed,
                                          line_table, line_table_stats,
                                          live_mask, redirects_per_row)
from distmlip_tpu.train.data import structure_needs
from distmlip_tpu.telemetry import scope
from tests.utils import make_crystal

CFG = CHGNetConfig(num_species=4, units=16, num_rbf=6, num_angle=4,
                   num_blocks=3, cutoff=3.2, bond_cutoff=2.6)
A_LAT = 3.5  # fcc nn distance a / sqrt(2) = 2.47 A < bond_cutoff


def ragged_crystal(rng, reps):
    """Two species, one atom in eight taken out: bonds with 6 to 11
    in-lines."""
    cart, lattice, species = make_crystal(rng, reps=reps, a=A_LAT)
    keep = rng.random(len(cart)) > 0.125
    return cart[keep], lattice, species[keep]


def build(cart, lattice, species, nparts, caps=None):
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], CFG.cutoff,
                             bond_r=CFG.bond_cutoff)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, CFG.cutoff,
                      CFG.bond_cutoff, True)
    graph, host = build_partitioned_graph(plan, nl, species, lattice,
                                          caps=caps or CapacityPolicy())
    return plan, graph, host


def live(count, slabs):
    """``(slabs, b_cap)``: slot ``k`` of row ``b`` is live, ``k < n_b``."""
    return np.arange(slabs)[:, None] < np.asarray(count)[None, :]


def table_lines(graph, p):
    """The live lines of partition ``p`` read back from its table, as
    ``(src, dst, centre)`` rows in dst-sorted, slot-ascending order (the
    list the table replaced) with each row's table entry."""
    b_cap = graph.b_cap
    slabs = graph.line_src.shape[-1] // b_cap
    mask = live(graph.line_count[p], slabs)
    k, dst = np.nonzero(mask.T)[::-1]      # dst-major, slot ascending
    entry = k * b_cap + dst
    src = np.asarray(graph.line_src[p])[entry]
    return src, dst, np.asarray(graph.bond_center[p])[dst], entry


def check_table(graph, p):
    """What every table holds: entries in bounds, a bond's live slots a
    prefix of its slots below its line count, pad slots masked."""
    b_cap = graph.b_cap
    slabs = graph.line_src.shape[-1] // b_cap
    src = np.asarray(graph.line_src[p])
    assert src.shape == (slabs * b_cap,) and src.min() >= 0
    assert src.max() < b_cap
    center = np.asarray(graph.bond_center[p])
    assert center.shape == (b_cap,) and 0 <= center.min()
    assert center.max() < graph.n_cap
    count = np.asarray(graph.line_count[p])
    assert count.shape == (b_cap,) and 0 <= count.min()
    assert count.max() <= slabs
    mask = np.asarray(graph.line_mask[p]).reshape(slabs, b_cap)
    assert np.all(mask[1:] <= mask[:-1])
    np.testing.assert_array_equal(mask, live(count, slabs))


# ---- the helper ------------------------------------------------------------

def test_hand_built_ragged_table():
    """Eight bond rows, three slabs: rows 2 and 6 with three in-lines (K),
    row 5 with one, the others with none; the list in no order."""
    line_src = np.array([7, 1, 0, 3, 4, 5, 1])
    line_dst = np.array([6, 2, 2, 5, 6, 6, 2])
    center = np.array([9, 4, 4, 8, 9, 9, 4])
    assert line_slots_needed([line_dst]) == 3
    src, count, bond_center = line_table(line_src, line_dst, center, 8, 3)
    assert count.tolist() == [0, 0, 3, 0, 0, 1, 3, 0]
    np.testing.assert_array_equal(live_mask(count, 3),
                                  live(count, 3).reshape(-1))
    assert live_mask(np.zeros((2, 0), int), 0).shape == (2, 0)
    # a bond's lines keep their order in the list, slot by slot
    assert src.reshape(3, 8)[:, 2].tolist() == [1, 0, 1]
    assert src.reshape(3, 8)[:, 6].tolist() == [7, 4, 5]
    assert src.reshape(3, 8)[:, 5].tolist() == [3, 5, 5]   # pads: own row
    # pad slots are not live and point at their own (valid) row
    pads = ~live(count, 3).reshape(-1)
    assert np.array_equal(src[pads], np.tile(np.arange(8), 3)[pads])
    assert bond_center.tolist() == [0, 0, 4, 0, 0, 8, 9, 0]
    # more slabs than needed: whole pad slabs
    src4, count4, _ = line_table(line_src, line_dst, center, 8, 4)
    assert not live(count4, 4)[3:].any() and np.array_equal(src4[:24], src)
    # an empty list, and no slab at all
    src0, count0, c0 = line_table(np.zeros(0, int), np.zeros(0, int),
                                  np.zeros(0, int), 8, 2)
    assert not count0.any() and src0.tolist() == list(range(8)) * 2
    assert line_table(np.zeros(0, int), np.zeros(0, int), np.zeros(0, int),
                      8, 0)[0].shape == (0,)
    assert line_slots_needed([np.zeros(0, int)]) == 0
    with pytest.raises(ValueError, match="cannot hold 3 lines"):
        line_table(line_src, line_dst, center, 8, 2)
    with pytest.raises(AssertionError, match="share their centre"):
        line_table(line_src, line_dst, np.arange(7), 8, 3)


def test_lines_capacity_is_whole_slabs():
    """``lines`` is asked for as in-degree x bond rows computed; a policy
    whose answer divides to fewer slabs of ``b_cap`` is asked again, and a
    fixed mix too small fails as any capacity does."""
    fixed = FixedCaps({"lines": 11 * 1024})
    assert line_table_cap(fixed, 11, 1000, 1024) == 11 * 1024
    with pytest.raises(ValueError, match="cannot hold"):
        line_table_cap(FixedCaps({"lines": 11 * 1024 - 1}), 11, 1000, 1024)
    for policy in (CapacityPolicy(), BucketPolicy()):
        b_cap = policy.get("bonds", 1000)
        assert b_cap % 128 == 0
        cap = line_table_cap(policy, 11, 1000, b_cap)
        assert cap % b_cap == 0 and cap // b_cap >= 11


@pytest.mark.parametrize("nparts", [1, 4])
def test_table_holds_the_plan_lines(rng, nparts):
    cart, lattice, species = ragged_crystal(rng, (8, 3, 3))
    plan, graph, host = build(cart, lattice, species, nparts)
    slabs = graph.line_src.shape[-1] // graph.b_cap
    assert graph.b_cap % 128 == 0
    assert slabs >= line_slots_needed(plan.line_dst)
    assert not hasattr(graph, "line_dst") and not hasattr(graph, "line_center")
    live = 0
    for p in range(nparts):
        check_table(graph, p)
        src, dst, center, _ = table_lines(graph, p)
        order = np.argsort(plan.line_dst[p], kind="stable")
        np.testing.assert_array_equal(src, plan.line_src[p][order])
        np.testing.assert_array_equal(dst, plan.line_dst[p][order])
        np.testing.assert_array_equal(center,
                                      plan.line_center_local[p][order])
        # ragged: some bond a partition computes has fewer in-lines than
        # the largest in-degree, and halo / padded rows have none
        counts = np.bincount(dst, minlength=graph.b_cap)
        computed = np.asarray(graph.bond_map_bond[p])[
            np.asarray(graph.bond_map_mask[p])]
        assert counts[computed].min() < counts.max()
        assert counts.sum() == counts[computed].sum()
        live += len(dst)
    stats = host.stats
    assert stats["n_lines"] == live == sum(len(x) for x in plan.line_src)
    assert stats["line_slots"] == slabs
    rows = sum(stats["n_bonds_per_part"])
    assert stats["line_table_fill"] == pytest.approx(live / (slabs * rows))
    assert 0.5 < stats["line_table_fill"] < 1.0
    assert line_table_stats(graph) == {
        "line_slots": slabs, "line_table_fill": stats["line_table_fill"],
        "line_redirects": 1}
    assert stats["line_redirects"] == 1


def _atoms(cart, lattice, species):
    return Atoms(numbers=np.asarray(species) + 1, positions=cart, cell=lattice)


@pytest.mark.parametrize("spatial, batch", [(1, 1), (2, 2)])
def test_packers_build_one_table_over_the_batch(rng, spatial, batch):
    """``pack_structures`` (one shard) and ``pack_structures_mesh`` (two
    batch shards of two slabs): K is the largest in-degree in the batch and
    every structure's lines are in its partition's table."""
    structs = [ragged_crystal(rng, (8, 2, 2)), ragged_crystal(rng, (8, 3, 2)),
               make_crystal(rng, reps=(8, 2, 2), a=A_LAT)]
    graph, host = pack_structures(
        [_atoms(*s) for s in structs], CFG.cutoff,
        bond_cutoff=CFG.bond_cutoff, use_bond_graph=True,
        spatial_parts=spatial, batch_parts=batch)
    slabs = graph.line_src.shape[-1] // graph.b_cap
    want_lines, want_slots = 0, 0
    for cart, lattice, _ in structs:
        nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], CFG.cutoff,
                                 bond_r=CFG.bond_cutoff)
        plan = build_plan(nl, lattice, [1, 1, 1], spatial, CFG.cutoff,
                          CFG.bond_cutoff, True)
        want_lines += sum(len(x) for x in plan.line_src)
        want_slots = max(want_slots, line_slots_needed(plan.line_dst))
    assert want_slots == 11 <= slabs          # the whole crystal's 11
    live = 0
    for p in range(graph.num_partitions):
        check_table(graph, p)
        src, dst, center, _ = table_lines(graph, p)
        live += len(dst)
        # a line's bonds meet at its centre: dst leaves it, src enters it
        # (read from the packed bond map and edge arrays)
        bm = np.asarray(graph.bond_map_mask[p])
        edge_of = np.full(graph.b_cap, -1)
        edge_of[np.asarray(graph.bond_map_bond[p])[bm]] = \
            np.asarray(graph.bond_map_edge[p])[bm]
        assert np.all(edge_of[dst] >= 0)       # only computed bonds
        np.testing.assert_array_equal(
            np.asarray(graph.edge_src[p])[edge_of[dst]], center)
    assert live == want_lines == host.stats["n_lines"]
    assert host.stats["line_slots"] == slabs
    assert 0.4 < host.stats["line_table_fill"] < 1.0
    # the centre tables hold the lines of the slot-major table, as
    # build_partitioned_graph's do
    assert host.stats["line_redirects"] == 1
    for p in range(graph.num_partitions):
        src, dst, _, _ = table_lines(graph, p)
        same_lines(center_lines(graph, p), (src, dst))


# ---- the same lines by centre atom (partition/graph.center_table) ----------

def center_lines(graph, p):
    """Every live slot ``k < n_b`` the centre tables describe, as ``(src,
    dst)`` rows: the ``k``-th in-bond of the row's centre, or, where the
    slot is redirected, the row the centre's redirected bonds read at
    ``k``. Checks what every such table holds on the way."""
    b_cap, n_cap = graph.b_cap, graph.n_cap
    center = np.asarray(graph.bond_center[p])
    center_in = np.asarray(graph.center_in[p])
    count = np.asarray(graph.line_count[p])
    bits = np.asarray(graph.redirect_bits[p]).view(np.uint32)
    K = graph.line_src.shape[-1] // b_cap
    assert center_in.shape == (K, n_cap, 2) and count.max() <= K
    assert 0 <= center_in.min() and center_in.max() < b_cap
    assert bits.shape == (-(-K // 32), b_cap)
    order = np.asarray(graph.bond_order[p])
    rank = np.asarray(graph.bond_rank[p])
    assert np.all(np.diff(center[order]) >= 0)
    np.testing.assert_array_equal(rank[order], np.arange(b_cap))
    # slot k of a row is redirected where bit k % 32 of word k // 32 is
    # set; a redirected slot is a live slot
    k = np.arange(K)
    redirected = (bits[k // 32] >> (k % 32)[:, None].astype(np.uint32)) & 1
    assert not (redirected.astype(bool) & ~live(count, K)).any()
    dst = np.repeat(np.arange(b_cap), count)
    k = np.arange(len(dst)) - np.repeat(np.cumsum(count) - count, count)
    return center_in[k, center[dst], redirected[k, dst]], dst


def same_lines(got, want):
    key = lambda src, dst: np.sort(np.asarray(src, np.int64) * (1 << 32)
                                   + np.asarray(dst, np.int64))
    a, b = key(*got), key(*want)
    assert len(np.unique(b)) == len(b)      # the list holds no line twice
    np.testing.assert_array_equal(a, b)


def tiny_bond_graph():
    """One fcc cell 3.5 A wide with every edge under 3.2 A a bond (as
    DimeNet++ builds it): a centre's neighbour is there in several images,
    and a bond skips every in-bond from its destination atom."""
    rng = np.random.default_rng(5)
    cart, lattice, species = make_crystal(rng, reps=(1, 1, 1), a=3.5,
                                          noise=0.08)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 3.2, bond_r=3.2)
    plan = build_plan(nl, lattice, [1, 1, 1], 1, 3.2, 3.2, True)
    graph, host = build_partitioned_graph(plan, nl, species, lattice,
                                          caps=CapacityPolicy())
    return plan, graph, host


@pytest.mark.parametrize("nparts", [1, 4, "tiny"])
def test_center_tables_hold_the_plan_lines(rng, nparts):
    """One slot per line of the list, no line twice, every one below its
    row's count; the same K as the slot-major table; ``m`` 1 where the box
    is wide and more in a box narrower than twice the cutoff."""
    if nparts == "tiny":
        plan, graph, host = tiny_bond_graph()
    else:
        plan, graph, host = build(*ragged_crystal(rng, (8, 3, 3)), nparts)
    slabs = graph.line_src.shape[-1] // graph.b_cap
    assert graph.center_in.shape[1] == slabs
    m = host.stats["line_redirects"]
    assert m >= 2 if nparts == "tiny" else m == 1
    for p in range(graph.num_partitions):
        same_lines(center_lines(graph, p),
                   (plan.line_src[p], plan.line_dst[p]))
        np.testing.assert_array_equal(
            graph.line_count[p],
            np.bincount(plan.line_dst[p], minlength=graph.b_cap))


def test_center_table_keeps_k_and_wants_one_redirect_at_the_cells_mix():
    """``dimenet-pp-md-1c``'s mix (perturbed fcc, a = 3.9 A, sigma 0.04 A,
    bonds = edges under 5.0 + 0.5 A) on a 3 x 3 x 3 cut, 11.7 A wide:
    K is the list's largest in-degree, as the slot-major table's, and one
    redirected slot a row is room enough."""
    rng = np.random.default_rng(0)
    cart, lattice, species = make_crystal(rng, reps=(3, 3, 3), a=3.9,
                                          noise=0.04, n_species=1)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 5.5, bond_r=5.5)
    plan = build_plan(nl, lattice, [1, 1, 1], 1, 5.5, 5.5, True)
    b_cap, n_cap = 128 * (-(-len(nl.src) // 128)), 128
    need = line_slots_needed(plan.line_dst)
    center_in, bits = center_table(
        plan.line_src[0], plan.line_dst[0], plan.line_center_local[0],
        b_cap, n_cap, need)
    assert center_in.shape == (need, n_cap, 2)
    assert np.bincount(plan.line_dst[0]).max() == need
    assert bits.shape == (-(-need // 32), b_cap)
    # nearly every bond skips one in-bond of its centre below its count
    per_row = redirects_per_row(bits)
    assert per_row.max() == 1
    assert 0.9 < per_row.sum() / len(nl.src) < 1.0
    with pytest.raises(ValueError, match="cannot hold"):
        center_table(plan.line_src[0], plan.line_dst[0],
                     plan.line_center_local[0], b_cap, n_cap, need - 1)


def test_frozen_caps_give_a_tiny_and_a_wide_cell_one_shape(rng):
    """A cell narrower than twice the bond cutoff redirects several slots
    of a bond row, a wide one a slot at most: under one set of frozen
    capacities (``fixed_caps_for_batches`` over both) their packs have the
    same shapes and bucket, so a training run over both compiles one
    step."""
    tiny = make_crystal(np.random.default_rng(5), reps=(1, 1, 1), a=A_LAT,
                        noise=0.08)
    atoms = [_atoms(*s) for s in (tiny, ragged_crystal(rng, (4, 3, 3)))]
    caps = fixed_caps_for_batches(structure_needs(
        atoms, CFG.cutoff, bond_cutoff=CFG.bond_cutoff, use_bond_graph=True),
        1)
    packs = [pack_structures([a], CFG.cutoff, bond_cutoff=CFG.bond_cutoff,
                             use_bond_graph=True, caps=caps) for a in atoms]
    tiny_m, wide_m = (h.stats["line_redirects"] for _, h in packs)
    assert tiny_m >= 2 and wide_m == 1
    shapes = [[np.shape(x) for x in jax.tree.leaves(g)] for g, _ in packs]
    assert shapes[0] == shapes[1]
    assert bucket_key(packs[0][0]) == bucket_key(packs[1][0])


# ---- the two methods that know the order -----------------------------------

@pytest.fixture(scope="module")
def ragged_lg():
    rng = np.random.default_rng(7)
    _, graph, _ = build(*ragged_crystal(rng, (4, 3, 3)), 1)
    lg, _ = local_graph_from_stacked(graph, None)
    src, dst, center, entry = table_lines(graph, 0)
    return lg, {"src": src, "dst": dst, "center": center, "entry": entry}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_methods_against_the_sorted_list(ragged_lg, dtype):
    lg, old = ragged_lg
    rng = np.random.default_rng(3)
    n_old, slots = len(old["dst"]), lg.line_slots * lg.b_cap
    x = jnp.asarray(rng.normal(size=(lg.b_cap, 8)), dtype)
    # rows at every line's destination: x[line_dst] of the old list
    rows = lg.at_line_dst(x)
    assert rows.shape == (slots, 8) and rows.dtype == dtype
    np.testing.assert_array_equal(rows[old["entry"]], x[old["dst"]])
    flags = jnp.asarray(rng.random(lg.b_cap) > 0.5)
    np.testing.assert_array_equal(lg.at_line_dst(flags)[old["entry"]],
                                  flags[old["dst"]])
    # its cotangent: the old gather's scatter-add, accumulated in float32
    # and rounded once
    w_old = jnp.asarray(rng.normal(size=(n_old, 8)), dtype)
    w = jnp.zeros((slots, 8), dtype).at[old["entry"]].set(w_old)
    got = jax.grad(lambda t: (lg.at_line_dst(t).astype(jnp.float32)
                              * w.astype(jnp.float32)).sum())(x)
    want = jax.ops.segment_sum(w_old.astype(jnp.float32), old["dst"],
                               num_segments=lg.b_cap).astype(dtype)
    assert got.dtype == dtype
    np.testing.assert_array_equal(got, want)
    # the sum onto bonds: masked_segment_sum of the old sorted list (pad
    # slots hold garbage that the mask removes)
    y = jnp.asarray(rng.normal(size=(slots, 8)), dtype)
    keep_old = rng.random(n_old) > 0.2
    mask = jnp.zeros(slots, bool).at[old["entry"]].set(keep_old)
    summed = lg.sum_to_line_dst(y, mask)
    want = masked_segment_sum(y[old["entry"]], old["dst"], lg.b_cap,
                              jnp.asarray(keep_old), indices_are_sorted=True)
    assert summed.shape == (lg.b_cap, 8) and summed.dtype == dtype
    np.testing.assert_array_equal(summed, want)
    # and its cotangent: the rows of g at the lines' destination, masked
    g = jnp.asarray(rng.normal(size=(lg.b_cap, 8)), dtype)
    got = jax.grad(lambda t: (lg.sum_to_line_dst(t, mask).astype(jnp.float32)
                              * g.astype(jnp.float32)).sum())(y)
    np.testing.assert_array_equal(
        got[old["entry"]],
        (g[old["dst"]] * jnp.asarray(keep_old)[:, None].astype(dtype)))
    assert not np.asarray(got)[~np.asarray(mask)].any()
    # each is the other's transpose
    _, vjp = jax.vjp(lg.at_line_dst, x)
    np.testing.assert_array_equal(vjp(y)[0], lg.sum_to_line_dst(y))


def test_a_derivative_of_any_order_keeps_the_two_forms(ragged_lg):
    """The repeat and the slab sum are each other's ``custom_vjp``: first
    and second derivatives hold concatenates of the rows and adds of slab
    slices only, no ``pad`` (a slice's own transpose), no scatter and no
    gather."""
    from distmlip_tpu.analysis.ir import iter_sites

    lg, _ = ragged_lg
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(lg.b_cap, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(lg.line_slots * lg.b_cap, 4)),
                    jnp.float32)

    def energy(x):
        return (lg.sum_to_line_dst(jnp.tanh(lg.at_line_dst(x) * w)) ** 2).sum()

    force_loss = lambda x: (jax.grad(energy)(x) ** 2).sum()
    for fn in (jax.grad(energy), jax.grad(force_loss)):
        prims = {s.primitive for s in iter_sites(jax.make_jaxpr(fn)(x))}
        assert {"concatenate", "slice"} <= prims
        assert not prims & {"pad", "gather", "scatter-add", "scatter_add",
                            "dynamic_update_slice"}, prims
    # and the second derivative is right: against the reshape form
    plain = lambda x: (jnp.tanh(jnp.tile(x, (lg.line_slots, 1)) * w).reshape(
        lg.line_slots, lg.b_cap, 4).sum(0) ** 2).sum()
    np.testing.assert_allclose(
        jax.grad(force_loss)(x),
        jax.grad(lambda x: (jax.grad(plain)(x) ** 2).sum())(x),
        rtol=2e-4, atol=1e-5)


# ---- the model against the sorted list it replaced --------------------------

class ListCHGNet(CHGNet):
    """CHGNet's three-body work as it stood on the dst-sorted line list:
    plain ``x[idx]`` gathers by ``line_src``, ``line_dst`` and
    ``line_center`` and ``masked_segment_sum`` onto ``line_dst``. The list
    is the single-partition plan's, kept here as constants."""

    def __init__(self, config, plan, b_cap):
        super().__init__(config)
        order = np.argsort(plan.line_dst[0], kind="stable")
        self.line_src = jnp.asarray(plan.line_src[0][order], jnp.int32)
        self.line_dst = jnp.asarray(plan.line_dst[0][order], jnp.int32)
        self.line_center = jnp.asarray(plan.line_center_local[0][order],
                                       jnp.int32)
        self.b_cap = b_cap

    def _line_features(self, params, fp, lg, bgeo, dtype):
        b_vec, b_d = bgeo[:, :3], bgeo[:, 3]
        b_real = self._is_bond(b_d)
        line_ok = b_real[self.line_src] & b_real[self.line_dst]
        v1, v2 = b_vec[self.line_src], b_vec[self.line_dst]
        d1 = jnp.maximum(b_d[self.line_src], 1e-6)
        d2 = jnp.maximum(b_d[self.line_dst], 1e-6)
        cos_t = jnp.clip(-jnp.sum(v1 * v2, axis=-1) / (d1 * d2),
                         -1.0 + 1e-6, 1.0 - 1e-6)
        return mlp(params["angle_emb"], radial.matgl_fourier_expansion(
            jnp.arccos(cos_t), fp["freq_angle"]).astype(dtype)), line_ok

    def _bond_node_conv(self, blk, lg, v, b, a, tbw, line_ok):
        def line_msg(b_src, b_dst, a_row, v_ctr):
            return gated_mlp(blk["node_update"], jnp.concatenate(
                [b_src, b_dst, a_row, v_ctr], axis=-1))

        with scope("line_message"):
            agg = fused_edge_aggregate(
                line_msg,
                [Gather(b, self.line_src), Gather(b, self.line_dst), a,
                 Gather(v, self.line_center)],
                self.line_dst, self.b_cap, line_ok, indices_are_sorted=True,
                kernels=False)
            upd = agg @ blk["node_out"]["w"]
            return b + (upd * tbw if tbw is not None else upd)

    def _angle_conv(self, blk, lg, v, b, a, line_ok):
        feats = jnp.concatenate(
            [gather_rows(b, self.line_src), gather_rows(b, self.line_dst), a,
             gather_rows(v, self.line_center)], axis=-1)
        m = gated_mlp(blk["angle_update"], feats)
        return a + m * line_ok[:, None].astype(m.dtype)


@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(11)
    cart, lattice, species = ragged_crystal(rng, (8, 3, 3))
    assert len(set(species.tolist())) == 2
    params = CHGNet(CFG).init(jax.random.PRNGKey(0))
    plan, graph, host = build(cart, lattice, species, 1)
    reference = ListCHGNet(CFG, plan, graph.b_cap)
    out = make_potential_fn(reference.energy_fn, None, kernels=False)(
        params, graph, graph.positions)
    forces = host.gather_owned(np.asarray(out["forces"]), len(cart))
    assert np.abs(forces).max() > 1e-5
    return {"system": (cart, lattice, species), "params": params,
            "energy": float(out["energy"]), "forces": forces,
            "stress": np.asarray(out["stress"])}


@pytest.mark.parametrize("nparts, kernels", [
    (1, False), (1, "interpret"), (4, False), (4, "interpret")])
def test_chgnet_on_the_table_is_chgnet_on_the_list(toy, nparts, kernels):
    cart, lattice, species = toy["system"]
    _, graph, host = build(cart, lattice, species, nparts)
    assert host.stats["line_table_fill"] < 0.9        # ragged
    mesh = graph_mesh(nparts) if nparts > 1 else None
    out = make_potential_fn(CHGNet(CFG).energy_fn, mesh, kernels=kernels)(
        toy["params"], graph, graph.positions)
    forces = host.gather_owned(np.asarray(out["forces"]), len(cart))
    assert abs(float(out["energy"]) - toy["energy"]) < 2e-5 * max(
        1.0, abs(toy["energy"]))
    scale = np.abs(toy["forces"]).max()
    np.testing.assert_allclose(forces, toy["forces"], atol=2e-5 * scale + 2e-6)
    np.testing.assert_allclose(np.asarray(out["stress"]), toy["stress"],
                               atol=1e-6)
