"""The chunk-ordered per-edge rows of the edge scans (ops/chunk.take_rows):
the values of the gather through ``row_index`` that it replaced, a
transpose without a scatter-add, and models whose energy-and-forces
programs no longer scatter-add over the edge list; then the seam the
models reach them through (``LocalGraph.edge_chunks`` / ``scan_edges``)
against ``LocalGraph.aggregate_edges`` of the unchunked messages."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.models import ESCN, ESCNConfig, MACE, MACEConfig
from distmlip_tpu.neighbors import neighbor_list_numpy
from distmlip_tpu.ops.chunk import chunk_layout, take_rows
from distmlip_tpu.parallel import GRAPH_AXIS, graph_mesh, make_potential_fn
from distmlip_tpu.parallel.halo import LocalGraph
from distmlip_tpu.partition import (CapacityPolicy, build_partitioned_graph,
                                    build_plan)
from distmlip_tpu.telemetry import set_tracing, stage_tables
from distmlip_tpu.telemetry import trace as trace_mod
from tests.utils import make_crystal

# (e_cap, chunk, e_split)
LAYOUTS = {
    "unsplit_remainder": (500, 128, None),
    "unsplit_exact": (512, 128, None),
    "split_both_remainders": (500, 128, 300),
    "split_on_chunk_boundary": (512, 128, 256),
    "split_one_exact": (500, 128, 384),
    "below_one_chunk": (50, 128, None),
    "split_below_one_chunk": (100, 128, 60),
    "unchunked_split": (100, 0, 60),
    "empty_interior": (500, 128, 0),
    "split_at_cap": (500, 128, 500),
    "no_edges": (0, 128, None),
}
INPUTS = {
    "int32": lambda rng, e: rng.integers(0, 1000, e).astype(np.int32),
    "bool": lambda rng, e: rng.random(e) < 0.5,
    "bfloat16": lambda rng, e: jnp.asarray(
        rng.normal(size=(e, 10)), jnp.bfloat16),
    "float32": lambda rng, e: rng.normal(size=(e, 3)).astype(np.float32),
}


def scatter_adds(closed_jaxpr):
    return [s.eqn for s in iter_sites(closed_jaxpr)
            if s.eqn.primitive.name in ("scatter-add", "scatter_add")]


@pytest.mark.parametrize("dtype", list(INPUTS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_take_rows_equals_the_gather(rng, layout, dtype):
    e_cap, chunk, e_split = LAYOUTS[layout]
    row_index, row_valid, K, c = chunk_layout(e_cap, chunk, e_split)
    x = jnp.asarray(INPUTS[dtype](rng, e_cap))
    got = take_rows(x, chunk, e_split)
    want = x[row_index]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.shape[0] == K * c == len(row_valid)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # the clamped chunk size chunk_layout returns lays out the same rows
    assert np.array_equal(np.asarray(take_rows(x, c, e_split)),
                          np.asarray(want))
    if not (~row_valid).any() and e_cap:
        assert got is x          # nothing padded: no copy at all


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_take_rows_transposes_without_a_scatter_add(rng, layout, dtype):
    e_cap, chunk, e_split = LAYOUTS[layout]
    row_index, row_valid, _, _ = chunk_layout(e_cap, chunk, e_split)
    x = jnp.asarray(INPUTS[dtype](rng, e_cap))
    w = jnp.asarray(rng.normal(size=(len(row_index),) + x.shape[1:]),
                    jnp.float32)

    def through(rows):
        return lambda x: jnp.sum(w * rows(x).astype(jnp.float32))

    sliced = jax.grad(through(lambda x: take_rows(x, chunk, e_split)))
    gathered = jax.grad(through(lambda x: x[row_index]))
    got, ref = np.asarray(sliced(x), np.float64), np.asarray(gathered(x),
                                                             np.float64)
    # a row that no pad row repeats receives its one cotangent untouched
    repeated = np.unique(row_index[~row_valid])
    plain = np.setdiff1d(np.arange(e_cap), repeated)
    assert np.array_equal(got[plain], ref[plain])
    # a segment's last row: its own cotangent plus up to chunk - 1 pad
    # rows', summed in another order (in bfloat16 the cotangents are
    # rounded before they are summed, on either path)
    exact = np.zeros((e_cap,) + x.shape[1:])
    np.add.at(exact, row_index, np.asarray(w, np.float64))
    scale = np.zeros_like(exact)
    np.add.at(scale, row_index, np.abs(np.asarray(w, np.float64)))
    eps = 2.0 ** -6 if dtype == "bfloat16" else 2.0 ** -22
    assert np.all(np.abs(got - exact)[repeated] <= eps * scale[repeated])
    if dtype == "float32":
        np.testing.assert_allclose(got, ref, rtol=0, atol=5e-6)
    assert not scatter_adds(jax.make_jaxpr(sliced)(x))
    if e_cap:
        assert scatter_adds(jax.make_jaxpr(gathered)(x))


# ---------------------------------------------------------------------------
# the models: no scatter-add over edge rows in energy and forces
# ---------------------------------------------------------------------------

MACE_TOY = MACE(MACEConfig(
    num_species=95, channels=8, l_max=2, a_lmax=2, hidden_lmax=1,
    correlation=2, num_interactions=2, num_bessel=4, radial_mlp=8,
    radial_layers=2, cutoff=3.0, avg_num_neighbors=12.0, edge_chunk=160,
    zbl=True, remat=True))
ESCN_TOY = ESCN(ESCNConfig(
    num_species=95, channels=8, l_max=2, num_layers=1, num_bessel=4,
    edge_channels=4, cutoff=3.0, edge_chunk=160, remat=True))


def toy_atoms(rng, nparts=1):
    cart, lattice, _ = make_crystal(rng, reps=(3 * nparts, 2, 2), a=3.9,
                                    noise=0.03)
    return Atoms(numbers=np.full(len(cart), 14), positions=cart,
                 cell=lattice)


def toy_step(model, rng, nparts):
    """(jaxpr of the energy-and-forces step, per-partition graph)."""
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.3)
    graph, _, positions = pot._prepare(toy_atoms(rng, nparts))
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    pot.close()
    return jaxpr, graph


@pytest.mark.parametrize("nparts", [1, 2], ids=["unsplit", "split"])
@pytest.mark.parametrize("model", [MACE_TOY, ESCN_TOY],
                         ids=["mace", "escn"])
def test_no_scatter_add_over_the_edge_list(rng, model, nparts):
    jaxpr, graph = toy_step(model, rng, nparts)
    e_cap, n_cap = graph.edge_src.shape[-1], graph.species.shape[-1]
    split = (int(graph.e_split) if nparts > 1 else None)
    row_index, _, K, chunk = chunk_layout(
        e_cap, model.cfg.edge_chunk,
        split if split is not None and 0 <= split < e_cap else None)
    # the case means something: several chunks, pad rows, and with two
    # partitions an active interior/frontier split
    assert K > 2 and K * chunk > e_cap
    if nparts > 1:
        assert 0 < split < e_cap
    edge_rows = {e_cap, K * chunk}
    assert n_cap not in edge_rows and chunk not in edge_rows
    onto = [eqn.invars[0].aval.shape for eqn in scatter_adds(jaxpr)]
    over_edges = [s for s in onto if s and s[0] in edge_rows]
    assert not over_edges
    # the sums onto nodes (segment sums, the transposes of the src-row
    # gathers) are scatter-adds still
    assert any(s and s[0] == n_cap for s in onto)


def test_compiled_edge_gather_stage_holds_no_scatter(rng, monkeypatch):
    """The stage table of the compiled MACE step: ``edge_gather`` has
    instructions in every pass, none of them a scatter."""
    monkeypatch.setattr(trace_mod, "_stage_tables", [])
    monkeypatch.setattr(trace_mod, "_noted", {})
    pot = DistPotential(MACE_TOY, MACE_TOY.init(jax.random.PRNGKey(0)),
                        num_partitions=1, skin=0.3)
    set_tracing(True)
    try:
        pot.calculate(toy_atoms(rng))
    finally:
        set_tracing(False)
    pot.close()
    (table,) = stage_tables()
    assert "error" not in table
    gather = [r for r in table["instructions"]
              if "edge_gather" in (r["stage"], *r.get("stages", ()))]
    assert {r["pass"] for r in gather} >= {"forward", "backward"}
    # XLA:CPU wraps a scatter into a fusion it names for it
    assert not [r["head"] for r in gather if "scatter" in r["head"]]
    # the table does show scatters where the program has them
    assert any("scatter" in r["head"] and r["stage"] == "edge_aggregate"
               for r in table["instructions"])


# ---------------------------------------------------------------------------
# forces through the chunk-ordered rows
# ---------------------------------------------------------------------------

CFG = MACEConfig(
    num_species=4, channels=16, l_max=2, a_lmax=2, hidden_lmax=1,
    correlation=3, num_interactions=2, num_bessel=6, radial_mlp=16,
    cutoff=3.2, avg_num_neighbors=12.0)


def test_chunked_forces_match_unchunked_under_a_frontier_split():
    """Several chunks with pad rows in both segments of an active
    interior/frontier split against one chunk per segment: energy and
    forces to float32 rounding (tests/test_mace.py holds the unsplit
    case)."""
    cart, lattice, species = make_crystal(np.random.default_rng(7),
                                          reps=(8, 3, 3))
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], CFG.cutoff)
    plan = build_plan(nl, lattice, [1, 1, 1], 2, CFG.cutoff, 0.0, False)
    graph, host = build_partitioned_graph(plan, nl, species, lattice,
                                          caps=CapacityPolicy())
    e_cap, e_split = graph.edge_src.shape[-1], int(graph.e_split)
    K = chunk_layout(e_cap, 100, e_split)[2]
    assert 0 < e_split < e_cap and e_split % 100 and (e_cap - e_split) % 100
    assert K > 3
    params = MACE(CFG).init(jax.random.PRNGKey(0))
    out = {}
    for chunk in (0, 100):
        model = MACE(dataclasses.replace(CFG, edge_chunk=chunk))
        pot = make_potential_fn(model.energy_fn, graph_mesh(2))
        res = pot(params, graph, graph.positions)
        out[chunk] = (float(res["energy"]),
                      host.gather_owned(np.asarray(res["forces"]), len(cart)),
                      np.asarray(res["stress"]))
    (e0, f0, s0), (e1, f1, s1) = out[0], out[100]
    assert np.abs(f0).max() > 1e-3
    assert abs(e0 - e1) < 1e-5 * max(1.0, abs(e0))
    np.testing.assert_allclose(f0, f1, atol=1e-5)
    np.testing.assert_allclose(s0, s1, atol=1e-7)


# ---------------------------------------------------------------------------
# the seam: LocalGraph.edge_chunks / scan_edges
# ---------------------------------------------------------------------------

N_CAP, FEAT = 20, 3
# layout -> (e_cap, e_split): segments of 36 and 60 rows, or one of 96
SEAM_LAYOUTS = {"unsplit": (96, 96), "split": (96, 36), "edgeless": (0, 0)}
# 12 divides 36, 60 and 96; 25 divides none of them
SEAM_CHUNKS = {"unchunked": 0, "divides_segments": 12, "remainders": 25,
               "above_e_cap": 1000}
SEAM_CASES = [(layout, chunk) for layout in ("unsplit", "split")
              for chunk in SEAM_CHUNKS] + [("edgeless", "remainders")]


def seam_arrays(rng, e_cap, e_split, shards=None):
    """Toy per-edge and per-node arrays (a leading axis of ``shards``
    when given): dst nondecreasing within each segment and restarting at
    the split, a few edges masked out, the LAST row of each segment a
    real edge (so a pad row that kept its mask would count twice)."""
    lead = () if shards is None else (shards,)
    bounds = [b for b in (0, e_split, e_cap) if b <= e_cap]
    dst = np.zeros(lead + (e_cap,), np.int32)
    for a, b in zip(bounds, bounds[1:]):
        dst[..., a:b] = np.sort(rng.integers(0, N_CAP, lead + (b - a,)))
    mask = rng.random(lead + (e_cap,)) < 0.8
    for b in bounds[1:]:
        mask[..., b - 1:b] = True
    return dict(
        src=rng.integers(0, N_CAP, lead + (e_cap,)).astype(np.int32),
        dst=dst, mask=mask,
        w=rng.normal(size=lead + (e_cap, FEAT)).astype(np.float32),
        h=rng.normal(size=lead + (N_CAP, FEAT)).astype(np.float32),
        r=rng.normal(size=lead + (N_CAP, FEAT)).astype(np.float32))


def seam_graph(a, e_split, axis_name=None):
    return LocalGraph(
        axis_name=axis_name, shifts=(), n_cap=N_CAP,
        e_cap=a["src"].shape[0], b_cap=0, species=None, node_mask=None,
        owned_mask=None, edge_src=a["src"], edge_dst=a["dst"],
        edge_offset=None, edge_mask=a["mask"], halo_send_idx=None,
        halo_send_mask=None, halo_recv_idx=None, lattice=None,
        e_split=e_split, kernels=False)


def seam_and_reference(a, e_split, chunk, axis_name=None):
    """Two functions of (node rows, per-edge rows), each returning (a
    scalar for gradients, the sum onto nodes): the sum through the seam,
    and aggregate_edges over the unchunked messages."""
    lg = seam_graph(a, e_split, axis_name)

    def through_seam(h, w):
        def per_chunk(srcc, dstc, maskc, wc):
            return jnp.tanh(h[srcc]) * wc * (1.0 + h[dstc])

        out = lg.scan_edges(per_chunk, lg.edge_chunks(chunk, w),
                            (FEAT,), h.dtype, remat=True)
        return jnp.sum(out * a["r"]), out

    def unchunked(h, w):
        msg = jnp.tanh(h[lg.edge_src]) * w * (1.0 + h[lg.edge_dst])
        out = lg.aggregate_edges(msg, lg.edge_mask)
        return jnp.sum(out * a["r"]), out

    return through_seam, unchunked


def sum_and_grads(fn, a):
    """(sum onto nodes, its gradient by node rows, by per-edge rows)."""
    (_, out), (g_h, g_w) = jax.value_and_grad(
        fn, argnums=(0, 1), has_aux=True)(a["h"], a["w"])
    return out, g_h, g_w


def assert_seam_matches(got, want):
    for name, g, w in zip(("sum", "d/d node rows", "d/d edge rows"),
                          got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("layout,chunk", SEAM_CASES)
def test_seam_sum_equals_aggregate_edges(rng, layout, chunk):
    (e_cap, e_split), chunk = SEAM_LAYOUTS[layout], SEAM_CHUNKS[chunk]
    a = jax.tree.map(jnp.asarray, seam_arrays(rng, e_cap, e_split))
    lg = seam_graph(a, e_split)
    assert lg.has_frontier_split == (layout == "split")
    # the layout call: src, dst, mask & row_valid first, then the rows
    srcc, dstc, maskc, wc = lg.edge_chunks(chunk, a["w"])
    K, c = srcc.shape
    assert dstc.shape == maskc.shape == (K, c) and wc.shape == (K, c, FEAT)
    if e_cap:
        segments = [36, 60] if layout == "split" else [96]
        per = max(segments) if chunk <= 0 else min(chunk, max(segments))
        assert (K, c) == (sum(-(-n // per) for n in segments), per)
    # no pad row keeps its mask; every real edge keeps its own
    assert int(maskc.sum()) == int(a["mask"].sum())
    assert np.array_equal(np.asarray(wc)[np.asarray(maskc)],
                          np.asarray(a["w"])[np.asarray(a["mask"])])
    # no chunk straddles the split: dst is nondecreasing inside every one
    assert np.all(np.diff(np.asarray(dstc), axis=1) >= 0)
    if layout == "split":
        assert np.any(np.diff(np.asarray(a["dst"])) < 0)

    through_seam, unchunked = seam_and_reference(a, e_split, chunk)
    got, want = sum_and_grads(through_seam, a), sum_and_grads(unchunked, a)
    assert_seam_matches(got, want)
    if e_cap:
        assert np.abs(np.asarray(want[0])).max() > 0.1
        # every sum onto nodes keeps the sorted fast path
        sums = [eqn for eqn in scatter_adds(
            jax.make_jaxpr(through_seam)(a["h"], a["w"]))
            if eqn.invars[0].aval.shape[0] == N_CAP]
        assert sums and all(e.params["indices_are_sorted"] for e in sums)
    else:
        assert not np.asarray(got[0]).any()


@pytest.mark.parametrize("layout,chunk", SEAM_CASES)
def test_seam_sum_under_shard_map(rng, layout, chunk):
    """Four shards with their own rows, one static layout."""
    from jax.sharding import PartitionSpec as P

    (e_cap, e_split), chunk = SEAM_LAYOUTS[layout], SEAM_CHUNKS[chunk]
    a = seam_arrays(rng, e_cap, e_split, shards=4)

    def local(a, forward_only=False):
        a = jax.tree.map(lambda x: x[0], a)
        through_seam, unchunked = seam_and_reference(a, e_split, chunk,
                                                     GRAPH_AXIS)
        if forward_only:
            return through_seam(a["h"], a["w"])[1][None]
        dstc = seam_graph(a, e_split, GRAPH_AXIS).edge_chunks(chunk)[1]
        return jax.tree.map(lambda x: x[None], (
            sum_and_grads(through_seam, a), sum_and_grads(unchunked, a),
            dstc))

    def sharded(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=graph_mesh(4), in_specs=P(GRAPH_AXIS),
            out_specs=P(GRAPH_AXIS), check_vma=False))

    got, want, dstc = sharded(local)(a)
    assert got[0].shape == (4, N_CAP, FEAT)
    assert_seam_matches(got, want)
    assert np.all(np.diff(np.asarray(dstc), axis=-1) >= 0)
    if e_cap:
        # the shards' sums differ: each read its own rows
        assert np.abs(np.asarray(got[0][0] - got[0][1])).max() > 0.1
        sums = [e for e in scatter_adds(jax.make_jaxpr(sharded(
            partial(local, forward_only=True)))(a))
            if e.invars[0].aval.shape[0] == N_CAP]
        assert sums and all(e.params["indices_are_sorted"] for e in sums)
