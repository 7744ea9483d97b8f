"""``LocalGraph.in_line_sum`` against a plain loop over the line list.

The scan reads a slab's source rows by centre atom, two rows an atom
(``center_in``: the slot's in-bond and the row the centre's redirected bonds
read), and repeats them over each centre's bond rows
(``kernels/dispatch.fused_segment_repeat``); here its sum
and first derivatives (in the source rows and in the destination rows) are
held to ``segment_sum`` of ``line_fn`` over the partitioner's line list, on
ragged graphs at one and two partitions (source rows that are halo rows),
and on a box so small that a centre has several in-bonds from one atom
(``m >= 2`` redirected slots in a row), on the XLA path and the interpreted
kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.kernels.dispatch import counting, repeat_edge_block
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.parallel.halo import LocalGraph
from distmlip_tpu.partition import (CapacityPolicy, build_partitioned_graph,
                                    build_plan)
from distmlip_tpu.partition.graph import line_table_stats
from tests.utils import make_crystal

# the bond graph is every edge inside the cutoff, as in DimeNet++
CUTOFF = 3.0
F_SRC, F_DST, WIDTH = 12, 3, 5


def bond_graph(cart, lattice, nparts):
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], CUTOFF, bond_r=CUTOFF)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, CUTOFF, CUTOFF, True)
    graph, _ = build_partitioned_graph(
        plan, nl, np.zeros(len(cart), np.int32), lattice,
        caps=CapacityPolicy())
    return plan, graph


def ragged(nparts, seed):
    """fcc at a = 3.5 A (12 in-bonds an atom, 11 lines into a bond) with
    one atom in eight taken out."""
    rng = np.random.default_rng(seed)
    cart, lattice, _ = make_crystal(rng, reps=(4 * nparts, 3, 3), a=3.5)
    keep = rng.random(len(cart)) > 0.125
    return bond_graph(cart[keep], lattice, nparts)


def tiny():
    """One fcc cell 3.5 A wide under a 3.0 A cutoff: each neighbour is
    there in up to four images, so a bond skips up to four in-bonds."""
    rng = np.random.default_rng(5)
    cart, lattice, _ = make_crystal(rng, reps=(1, 1, 1), a=3.5, noise=0.08)
    return bond_graph(cart, lattice, 1)


def local(graph, p, kernels):
    names = ("line_src", "line_count", "bond_center", "center_in",
             "bond_order", "bond_rank", "redirect_bits")
    return LocalGraph(
        axis_name=None, shifts=(), n_cap=graph.n_cap, e_cap=graph.e_cap,
        b_cap=graph.b_cap, species=None, node_mask=None, owned_mask=None,
        edge_src=None, edge_dst=None, edge_offset=None, edge_mask=None,
        halo_send_idx=None, halo_send_mask=None, halo_recv_idx=None,
        lattice=None, has_bond_graph=True, kernels=kernels,
        **{n: jnp.asarray(np.asarray(getattr(graph, n))[p]) for n in names})


def line_fn(src, dst):
    """Every column of both rows reaches the result, nonlinearly."""
    a = jnp.tanh(src[:, :WIDTH] + src[:, WIDTH:2 * WIDTH] * dst[:, :1])
    return a * (1.0 + dst[:, 1:2] * src[:, -2:-1]) + jnp.sin(dst[:, 2:3])


def rows(rng, graph):
    return (jnp.asarray(rng.normal(size=(graph.b_cap, F_SRC)), jnp.float32),
            jnp.asarray(rng.normal(size=(graph.b_cap, F_DST)), jnp.float32))


def case(plan, graph, p, kernels):
    """(loss of the scan, loss of the list, weights) for partition p."""
    lg = local(graph, p, kernels)
    src_l = jnp.asarray(plan.line_src[p], jnp.int32)
    dst_l = jnp.asarray(plan.line_dst[p], jnp.int32)
    w = jnp.asarray(np.random.default_rng(p).normal(
        size=(graph.b_cap, WIDTH)), jnp.float32)

    def scan(s, d):
        out = lg.in_line_sum(line_fn, s, (d,), WIDTH)
        return jnp.sum(out * w), out

    def plain(s, d):
        out = jax.ops.segment_sum(line_fn(s[src_l], d[dst_l]), dst_l,
                                  num_segments=graph.b_cap)
        return jnp.sum(out * w), out

    return scan, plain


GRAPHS = {"ragged-1": lambda: ragged(1, 3), "ragged-2": lambda: ragged(2, 4),
          "tiny": tiny}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph_case(request):
    return request.param, GRAPHS[request.param]()


@pytest.mark.parametrize("kernels", [False, "interpret"])
def test_values_and_slopes_against_the_line_list(graph_case, kernels):
    name, (plan, graph) = graph_case
    m = line_table_stats(graph)["line_redirects"]
    assert m >= 2 if name == "tiny" else m == 1
    rng = np.random.default_rng(11)
    for p in range(graph.num_partitions):
        assert len(plan.line_src[p]) > 0
        s, d = rows(rng, graph)
        scan, plain = case(plan, graph, p, kernels)
        with counting() as kc:
            (_, got), grads = jax.value_and_grad(
                scan, argnums=(0, 1), has_aux=True)(s, d)
        assert kc.ops["segment_repeat"] == (
            [0, 1] if kernels is False else [1, 0])
        (_, want), want_grads = jax.value_and_grad(
            plain, argnums=(0, 1), has_aux=True)(s, d)
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, atol=2e-5 * scale)
        for g, h in zip(grads, want_grads):
            np.testing.assert_allclose(g, h, atol=2e-5 * float(
                jnp.abs(h).max()))
        if graph.num_partitions > 1:
            # some lines read a halo bond row, which has no line of its own
            halo = np.asarray(graph.line_count[p]) == 0
            assert halo[np.asarray(plan.line_src[p])].any()


def test_second_derivative_through_the_kernel():
    """A force loss differentiates through the backward: the kernel's sum
    and the permutations' gathers have transposes of their own."""
    plan, graph = ragged(1, 3)
    s, d = rows(np.random.default_rng(2), graph)
    scan_k, plain = case(plan, graph, 0, "interpret")
    scan_x, _ = case(plan, graph, 0, False)

    def second(loss):
        return jax.grad(lambda s_: jnp.sum(jax.grad(
            lambda d_: loss(s_, d_)[0])(d) ** 2))(s)

    want = second(plain)
    for scan in (scan_k, scan_x):
        np.testing.assert_allclose(second(scan), want, atol=1e-4 * float(
            jnp.abs(want).max()))


def test_the_kernel_takes_whole_edge_blocks():
    """The repeat's sum streams the bond rows in the largest blocks of 128
    rows that divide them, up to 2048: no padded copy of the rows; where
    those would be short, blocks of 1024 and a padded copy."""
    assert repeat_edge_block(394368) == 1664   # dimenet-pp-md-1c's bonds
    assert repeat_edge_block(4096) == 2048 and repeat_edge_block(640) == 640
    assert repeat_edge_block(128 * 3) == 384
    assert repeat_edge_block(128 * 17) == 1024      # 17 blocks of 128 only
    assert repeat_edge_block(128 * 3083) == 1024    # 3083 is a prime
    assert repeat_edge_block(256) == 256 and repeat_edge_block(100) == 128
