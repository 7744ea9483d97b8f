"""The chunked edge sum's in-place accumulate: ``pallas_segment_sum_into``
(interpret mode) against ``acc + masked_segment_sum``, its dispatcher, and
gradients through ``LocalGraph.scan_edges`` on both paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.kernels import (counting, fused_segment_sum_into,
                                  pallas_segment_sum_into)
from distmlip_tpu.kernels.dispatch import (TPU_DEFAULT_MODE,
                                           segment_sum_carry,
                                           segment_sum_result)
from distmlip_tpu.ops.segment import masked_segment_sum
from distmlip_tpu.parallel import GRAPH_AXIS, graph_mesh
from distmlip_tpu.parallel.halo import LocalGraph

pytestmark = pytest.mark.pallas

TILE, BLK, WIDTH = 8, 16, 128
N_SEG = 64          # eight tiles of eight rows


def _ids(rng, kind):
    """(ids, mask) of one dst-sorted chunk, by where its rows land."""
    def draw(lo, hi, e=100):
        return np.sort(rng.integers(lo, hi, e)).astype(np.int32)

    if kind == "inside_one_tile":
        ids = draw(17, 23)
    elif kind == "across_a_tile_edge":
        ids = draw(20, 27)
    elif kind == "every_tile":        # one edge a node: the span is all
        ids = np.arange(N_SEG, dtype=np.int32)
    elif kind == "wholly_masked":
        ids = draw(30, 45)
        return ids, np.zeros(ids.shape, bool)
    elif kind == "pad_rows_repeat_last_id":
        ids = draw(9, 38, 70)
        ids = np.concatenate([ids, np.full(30, ids[-1], np.int32)])
        return ids, np.arange(100) < 70
    elif kind == "first_and_last_tile_only":
        ids = np.concatenate([draw(0, 3, 50), draw(61, 64, 50)])
    else:
        raise KeyError(kind)
    return ids, rng.random(ids.shape) < 0.8


KINDS = ["inside_one_tile", "across_a_tile_edge", "every_tile",
         "wholly_masked", "pad_rows_repeat_last_id",
         "first_and_last_tile_only"]


@pytest.mark.tier1
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", KINDS)
def test_kernel_adds_in_place_over_the_touched_tiles(rng, kind, dtype):
    ids, mask = _ids(rng, kind)
    acc = jnp.asarray(rng.normal(size=(N_SEG, WIDTH)), dtype)
    data = jnp.asarray(rng.normal(size=(len(ids), WIDTH)), dtype)
    out = pallas_segment_sum_into(acc, data, jnp.asarray(ids),
                                  jnp.asarray(mask), tile_n=TILE,
                                  edge_blk=BLK, interpret=True)
    assert out.shape == acc.shape and out.dtype == acc.dtype
    want = acc.astype(jnp.float32) + masked_segment_sum(
        data.astype(jnp.float32), jnp.asarray(ids), N_SEG,
        jnp.asarray(mask), indices_are_sorted=True)
    # one rounding of the float32 sum to the carry's dtype
    tol = 2e-6 if dtype == "float32" else 2.0 ** -8
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=tol,
        atol=tol * float(np.abs(np.asarray(want)).max()))
    touched = np.zeros(N_SEG, bool)
    touched[ids[0] // TILE * TILE:(ids[-1] // TILE + 1) * TILE] = True
    assert touched.all() == (kind == "every_tile"
                             or kind == "first_and_last_tile_only")
    assert np.array_equal(np.asarray(out)[~touched],
                          np.asarray(acc)[~touched])
    if kind == "wholly_masked":
        assert np.array_equal(np.asarray(out), np.asarray(acc))


@pytest.mark.tier1
@pytest.mark.parametrize("n_seg", [61, 200])
def test_kernel_on_a_ragged_last_tile_and_default_tiles(rng, n_seg):
    """A capacity that is no multiple of the tile (toy graphs), trailing
    axes kept, tile sizes left to ``_pick_tiles``."""
    ids = np.sort(rng.integers(n_seg - 30, n_seg, 90)).astype(np.int32)
    mask = rng.random(90) < 0.8
    acc = jnp.asarray(rng.normal(size=(n_seg, 2, 4)), jnp.float32)
    data = jnp.asarray(rng.normal(size=(90, 2, 4)), jnp.float32)
    want = acc + masked_segment_sum(data, jnp.asarray(ids), n_seg,
                                    jnp.asarray(mask),
                                    indices_are_sorted=True)
    for carry in (acc, acc.reshape(n_seg, 8)):
        out = pallas_segment_sum_into(carry, data, jnp.asarray(ids),
                                      jnp.asarray(mask), interpret=True)
        assert out.shape == carry.shape
        np.testing.assert_allclose(np.asarray(out).reshape(want.shape),
                                   np.asarray(want), atol=2e-6)


def _chunks(rng, shape=(2, 4)):
    """Three dst-sorted chunks of 100 rows with their masks."""
    kinds = ("across_a_tile_edge", "pad_rows_repeat_last_id", "every_tile")
    out = []
    for kind in kinds:
        ids, mask = _ids(rng, kind)
        ids, mask = np.resize(ids, 100), np.resize(mask, 100)
        ids.sort()
        out.append((jnp.asarray(rng.normal(size=(100,) + shape),
                                jnp.float32),
                    jnp.asarray(ids), jnp.asarray(mask)))
    return out


def _sum_chunks(chunks, kernels, shape=(2, 4)):
    carry = segment_sum_carry(N_SEG, shape, jnp.float32, kernels)
    for data, ids, mask in chunks:
        carry = fused_segment_sum_into(carry, data, ids, mask,
                                       kernels=kernels)
    return segment_sum_result(carry)


@pytest.mark.tier1
def test_dispatch_counts_its_own_op_and_xla_path_is_the_plain_sum(rng):
    chunks = _chunks(rng)
    assert TPU_DEFAULT_MODE["segment_sum_into"] == "pallas"

    def plain(*rows):
        acc = jnp.zeros((N_SEG, 2, 4), jnp.float32)
        for d, (_, ids, mask) in zip(rows, chunks):
            acc = acc + masked_segment_sum(d, ids, N_SEG, mask,
                                           indices_are_sorted=True)
        return acc

    def through(kernels):
        return lambda *rows: _sum_chunks(
            [(d, ids, mask) for d, (_, ids, mask) in zip(rows, chunks)],
            kernels)

    rows = [c[0] for c in chunks]
    with counting() as kc:
        xla = jax.make_jaxpr(through(False))(*rows)
    assert kc.ops == {"segment_sum_into": [0, 3]}
    # kernels off: the carry is the array and the program is
    # acc + masked_segment_sum, equation for equation
    assert str(xla) == str(jax.make_jaxpr(plain)(*rows))

    # the kernel path: flat rows for the kernel, a shadow for the backward
    acc0, shadow0 = segment_sum_carry(N_SEG, (2, 4), jnp.float32,
                                      "interpret")
    assert acc0.shape == (N_SEG, 8) and shadow0.shape == (N_SEG, 2, 4)
    assert not np.asarray(acc0).any() and not np.asarray(shadow0).any()
    with counting() as kc:
        got = through("interpret")(*rows)
    assert kc.ops == {"segment_sum_into": [3, 0]}
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(*rows)),
                               atol=2e-6)


@pytest.mark.tier1
def test_dispatch_grads_match_the_xla_path(rng):
    chunks = _chunks(rng)
    r = jnp.asarray(rng.normal(size=(N_SEG, 2, 4)), jnp.float32)

    def loss(kernels):
        return lambda *rows: jnp.sum(r * jnp.tanh(_sum_chunks(
            [(d, ids, mask) for d, (_, ids, mask) in zip(rows, chunks)],
            kernels)))

    rows = [c[0] for c in chunks]
    got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(*rows)
    want = jax.grad(loss(False), argnums=(0, 1, 2))(*rows)
    for g, w, (_, _, mask) in zip(got, want, chunks):
        assert g.shape == w.shape and np.abs(np.asarray(w)).max() > 1e-2
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-6)
        # masked and pad rows get no cotangent
        assert not np.asarray(g)[~np.asarray(mask)].any()


# ---------------------------------------------------------------------------
# through LocalGraph.scan_edges
# ---------------------------------------------------------------------------

N_CAP, Q, C = 300, 2, 4       # three tiles of 128 rows, the last ragged
E_CAP, E_SPLIT = 720, 288
# chunk -> K: one chunk a segment (K == 1 unsplit), or several
CHUNKS = {"K1": 0, "Kmany": 100}


def scan_arrays(rng, split, shards=None):
    lead = () if shards is None else (shards,)
    bounds = [0, E_SPLIT, E_CAP] if split else [0, E_CAP]
    dst = np.zeros(lead + (E_CAP,), np.int32)
    for a, b in zip(bounds, bounds[1:]):
        dst[..., a:b] = np.sort(rng.integers(0, N_CAP, lead + (b - a,)))
    mask = rng.random(lead + (E_CAP,)) < 0.8
    return dict(
        src=rng.integers(0, N_CAP, lead + (E_CAP,)).astype(np.int32),
        dst=dst, mask=mask,
        w=rng.normal(size=lead + (E_CAP, Q, C)).astype(np.float32),
        h=rng.normal(size=lead + (N_CAP, C)).astype(np.float32),
        r=rng.normal(size=lead + (N_CAP, Q, C)).astype(np.float32))


def scan_graph(a, split, kernels, axis_name=None):
    return LocalGraph(
        axis_name=axis_name, shifts=(), n_cap=N_CAP, e_cap=E_CAP, b_cap=0,
        species=None, node_mask=None, owned_mask=None, edge_src=a["src"],
        edge_dst=a["dst"], edge_offset=None, edge_mask=a["mask"],
        halo_send_idx=None, halo_send_mask=None, halo_recv_idx=None,
        lattice=None, e_split=E_SPLIT if split else -1, kernels=kernels)


def scan_loss(a, split, chunk, remat, kernels, axis_name=None):
    lg = scan_graph(a, split, kernels, axis_name)

    def loss(h, w):
        def per_chunk(srcc, dstc, maskc, wc):
            return (jnp.tanh(h[srcc]) * (1.0 + h[dstc]))[:, None, :] * wc

        out = lg.scan_edges(per_chunk, lg.edge_chunks(chunk, w), (Q, C),
                            h.dtype, remat=remat)
        return jnp.sum(jnp.tanh(out) * a["r"]), out

    return loss


def value_and_grads(loss, a):
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(a["h"], a["w"])
    return (out, *grads)


def assert_same(got, want):
    for name, g, w in zip(("sum", "d/d node rows", "d/d edge rows"),
                          got, want):
        assert g.shape == w.shape, name
        assert np.abs(np.asarray(w)).max() > 1e-2, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=2e-5, err_msg=name)


@pytest.mark.tier1
@pytest.mark.parametrize("remat", [True, False], ids=["remat", "saved"])
@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("split", [False, True], ids=["unsplit", "split"])
def test_scan_edges_grads_equal_between_paths(rng, split, chunk, remat):
    a = jax.tree.map(jnp.asarray, scan_arrays(rng, split))
    K = scan_graph(a, split, False).edge_chunks(CHUNKS[chunk])[0].shape[0]
    assert (K == 1) == (chunk == "K1" and not split)
    with counting() as kc:
        got = value_and_grads(
            scan_loss(a, split, CHUNKS[chunk], remat, "interpret"), a)
    assert kc.ops["segment_sum_into"][1] == 0
    assert kc.ops["segment_sum_into"][0] >= 1
    want = value_and_grads(
        scan_loss(a, split, CHUNKS[chunk], remat, False), a)
    assert_same(got, want)


@pytest.mark.tier1
@pytest.mark.parametrize("split", [False, True], ids=["unsplit", "split"])
def test_scan_edges_grads_equal_between_paths_on_four_devices(rng, split):
    from jax.sharding import PartitionSpec as P

    a = scan_arrays(rng, split, shards=4)

    def local(kernels):
        def fn(a):
            a = jax.tree.map(lambda x: x[0], a)
            loss = scan_loss(a, split, CHUNKS["Kmany"], True, kernels,
                             GRAPH_AXIS)
            return jax.tree.map(lambda x: x[None], value_and_grads(loss, a))

        return jax.jit(jax.shard_map(
            fn, mesh=graph_mesh(4), in_specs=P(GRAPH_AXIS),
            out_specs=P(GRAPH_AXIS), check_vma=False))

    got, want = local("interpret")(a), local(False)(a)
    assert got[0].shape == (4, N_CAP, Q, C)
    assert_same(got, want)
    assert np.abs(np.asarray(got[0][0] - got[0][1])).max() > 0.1


@pytest.mark.tier1
@pytest.mark.parametrize("remat", [True, False], ids=["remat", "saved"])
def test_the_carry_is_no_residual_of_the_scan(rng, remat):
    """The backward needs ids and mask only: no scan of the gradient
    program stacks the ``(n_cap, W)`` accumulator per chunk (71 x 303 MB
    at MACE's size), and the forward scans carry it flat."""
    a = jax.tree.map(jnp.asarray, scan_arrays(rng, False))
    loss = scan_loss(a, False, CHUNKS["Kmany"], remat, "interpret")
    jaxpr = jax.make_jaxpr(jax.grad(lambda h, w: loss(h, w)[0],
                                    argnums=(0, 1)))(a["h"], a["w"])
    scans = [s.eqn for s in iter_sites(jaxpr)
             if s.eqn.primitive.name == "scan"]
    assert scans
    carried = 0
    for eqn in scans:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = v.aval.shape
            assert shape[1:] not in ((N_CAP, Q * C), (N_CAP, Q, C)), shape
            carried += shape == (N_CAP, Q * C)
    assert carried >= 2      # in and out of the forward scan at least
    # and inside a scan nothing but the kernel produces the accumulator:
    # no whole-array add or select per chunk
    for eqn in scans:
        for inner in eqn.params["jaxpr"].jaxpr.eqns:
            if inner.primitive.name in ("add", "add_any", "select_n"):
                assert all(v.aval.shape not in ((N_CAP, Q * C), (N_CAP, Q, C))
                           for v in inner.outvars), inner
