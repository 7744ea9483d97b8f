"""Fused Pallas kernel suite: golden parity vs the pure-XLA path.

The contract under test: routing through ``kernels/dispatch`` NEVER
changes numbers — energies, forces, stresses, magmoms and training
weight-gradients from the fused dst-tiled kernels (interpret mode on
CPU; the same program compiles on TPU) match the historical pure-XLA
programs to fp32 roundoff, across all four models, packed batches,
padded edges, 1-atom structures and 2-D mesh placements. Plus the
dispatch-layer guarantees: kill switch, sorted-contract gating,
trace-time counters, and the no-materialization property (the fused
path's jaxpr carries no full-size ``(E, width)`` message intermediate).

IMPORTANT idiom: build a SEPARATE potential per kernel mode — the
dispatch decision is trace-time, so reusing one jitted potential across
modes silently re-runs the first mode's executable (exact 0.0 deltas
are the tell of a vacuous comparison).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.kernels import (Gather, KernelCounter, counting,
                                  force_kernel_mode, fused_edge_aggregate,
                                  fused_segment_sum, fused_so2_conv,
                                  pallas_edge_aggregate, pallas_segment_sum,
                                  resolve_kernel_mode)
from distmlip_tpu.kernels.segment import dst_tile_offsets
from distmlip_tpu.ops.segment import (masked_segment_mean,
                                      masked_segment_softmax,
                                      masked_segment_sum)

pytestmark = pytest.mark.pallas


def sorted_segments(rng, e=300, n=37, pad=40):
    """Random dst-sorted ids with repeat-last padding + validity mask."""
    ids = np.sort(rng.integers(0, n, e)).astype(np.int32)
    ids = np.concatenate([ids, np.full(pad, ids[-1], np.int32)])
    mask = np.concatenate([np.ones(e, bool), np.zeros(pad, bool)])
    return jnp.asarray(ids), jnp.asarray(mask), n


# ---------------------------------------------------------------------------
# kernel layer: parity vs ops/segment on synthetic layouts
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_dst_tile_offsets(rng):
    ids, _, n = sorted_segments(rng)
    tile = 8
    offs = np.asarray(dst_tile_offsets(ids, n, tile))
    ids_np = np.asarray(ids)
    for t in range(len(offs) - 1):
        sl = ids_np[offs[t]:offs[t + 1]]
        assert np.all((sl >= t * tile) & (sl < (t + 1) * tile))
    assert offs[0] == 0 and offs[-1] == len(ids_np)


@pytest.mark.tier1
def test_pallas_segment_sum_parity(rng):
    ids, mask, n = sorted_segments(rng)
    for trailing in ((), (5,), (3, 4)):
        data = jnp.asarray(
            rng.normal(size=(len(ids),) + trailing).astype(np.float32))
        ref = masked_segment_sum(data, ids, n, mask,
                                 indices_are_sorted=True)
        out = pallas_segment_sum(data, ids, n, mask, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


@pytest.mark.tier1
def test_pallas_edge_aggregate_parity(rng):
    ids, mask, n = sorted_segments(rng, e=250, n=29, pad=30)
    e = len(ids)
    node = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    w_edge = jnp.asarray(rng.normal(size=(e, 6)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))

    def edge_fn(rows, w, wmat):
        return jax.nn.silu(rows * w) @ wmat

    msg = edge_fn(jnp.take(node, idx, axis=0), w_edge, W)
    ref = masked_segment_sum(msg, ids, n, mask, indices_are_sorted=True)
    out = pallas_edge_aggregate(
        lambda r, w, wmat: edge_fn(r, w, wmat),
        [("gather", node, idx), w_edge], ids, n, mask,
        out_shape=(4,), out_dtype=jnp.float32, consts=(W,), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.tier1
def test_fused_segment_sum_dispatch_and_grad(rng):
    ids, mask, n = sorted_segments(rng)
    data = jnp.asarray(rng.normal(size=(len(ids), 7)).astype(np.float32))

    def loss(d, kernels):
        return jnp.sum(fused_segment_sum(
            d, ids, n, mask, indices_are_sorted=True, kernels=kernels) ** 2)

    v0, g0 = jax.value_and_grad(loss)(data, False)
    v1, g1 = jax.value_and_grad(loss)(data, "interpret")
    assert abs(float(v0) - float(v1)) < 1e-4 * abs(float(v0))
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=1e-5)


@pytest.mark.tier1
def test_fused_edge_aggregate_grads_match_xla():
    """Grads wrt gathered node arrays, per-edge inputs AND hoisted closure
    weights (diff_params=True) through the chunked backward. Local rng +
    scale-relative tolerance: the weight grad sums hundreds of fp32 terms
    in a different order than XLA's reduction, so roundoff scales with
    the grad magnitude, not an absolute constant."""
    lrng = np.random.default_rng(11)
    ids, mask, n = sorted_segments(lrng, e=130, n=17, pad=14)
    e = len(ids)
    node = jnp.asarray(lrng.normal(size=(n, 5)).astype(np.float32))
    per_edge = jnp.asarray(lrng.normal(size=(e, 5)).astype(np.float32))
    W = jnp.asarray(lrng.normal(size=(5, 3)).astype(np.float32))

    def agg(node_, per_edge_, W_, kernels):
        def edge_fn(rows, pe):
            return jnp.tanh(rows + pe) @ W_

        return jnp.sum(fused_edge_aggregate(
            edge_fn, [Gather(node_, jnp.asarray(ids) % n), per_edge_],
            ids, n, mask, kernels=kernels, bwd_chunk=32) ** 2)

    v0, g0 = jax.value_and_grad(agg, argnums=(0, 1, 2))(
        node, per_edge, W, False)
    v1, g1 = jax.value_and_grad(agg, argnums=(0, 1, 2))(
        node, per_edge, W, "interpret")
    assert abs(float(v0) - float(v1)) < 1e-5 * max(1, abs(float(v0)))
    for a, b in zip(g0, g1):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(1.0, float(np.max(np.abs(a))))
        np.testing.assert_allclose(a, b, atol=1e-5 * scale)


@pytest.mark.tier1
def test_fused_edge_aggregate_vmem_budget_pregather(rng):
    """A node array over the VMEM budget is pre-gathered by XLA — same
    numbers, still the fused kernel for the rest of the pipeline."""
    ids, mask, n = sorted_segments(rng, e=90, n=11, pad=6)
    node = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, len(ids)).astype(np.int32))

    def run(budget):
        return fused_edge_aggregate(
            lambda r: r * 2.0, [Gather(node, idx)], ids, n, mask,
            kernels="interpret", vmem_budget=budget)

    ref = masked_segment_sum(2.0 * jnp.take(node, idx, axis=0), ids, n,
                             mask, indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(run(None)), np.asarray(ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(run(8)), np.asarray(ref),
                               atol=1e-5)  # 8 bytes: forces pre-gather


@pytest.mark.tier1
def test_so2_conv_parity_and_grads(rng):
    """Packed per-m GEMMs (the eSCN channel-mixing kernel) vs the XLA
    reference, values and h/W gradients."""
    # a small l_max=2 style layout: m=0 has 3 l-blocks, m=1 has 2, m=2 has 1
    m_idx = {0: (np.array([0, 1, 2]), np.array([], np.int32)),
             1: (np.array([3, 4]), np.array([5, 6])),
             2: (np.array([7]), np.array([8]))}
    S, C, E = 9, 4, 37
    h = jnp.asarray(rng.normal(size=(E, S, C)).astype(np.float32))
    ws = [jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) / d)
          for d in (3 * C, 2 * C, 2 * C, C, C)]

    def loss(h_, ws_, kernels):
        out = fused_so2_conv(h_, list(ws_), m_idx, C, kernels=kernels)
        return jnp.sum(out ** 2), out

    (v0, o0), g0 = jax.value_and_grad(loss, argnums=(0, 1),
                                      has_aux=True)(h, tuple(ws), False)
    (v1, o1), g1 = jax.value_and_grad(loss, argnums=(0, 1),
                                      has_aux=True)(h, tuple(ws),
                                                    "interpret")
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), atol=1e-4)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# ---------------------------------------------------------------------------
# dispatch routing
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_resolve_kernel_mode_routing(monkeypatch):
    monkeypatch.delenv("DISTMLIP_KERNELS", raising=False)
    assert resolve_kernel_mode(False, op="segment_sum") == "xla"
    assert resolve_kernel_mode("interpret", op="segment_sum") == "interpret"
    # backend default on this CPU host is the XLA fallback
    assert resolve_kernel_mode(None, op="segment_sum") == "xla"
    # env kill switch beats everything except the explicit per-object flag
    monkeypatch.setenv("DISTMLIP_KERNELS", "0")
    assert resolve_kernel_mode(None, op="segment_sum") == "xla"
    monkeypatch.setenv("DISTMLIP_KERNELS", "interpret")
    assert resolve_kernel_mode(None, op="segment_sum") == "interpret"
    assert resolve_kernel_mode(False, op="segment_sum") == "xla"
    monkeypatch.setenv("DISTMLIP_KERNELS", "on")
    assert resolve_kernel_mode(None, op="segment_sum") == "pallas"
    # the force context wins over env + object flags (contract checker)
    with force_kernel_mode("xla"):
        assert resolve_kernel_mode("interpret", op="segment_sum") == "xla"
    with pytest.raises(ValueError, match="expected"):
        with force_kernel_mode("bogus"):
            pass
    with pytest.raises(ValueError, match="expected"):
        resolve_kernel_mode("bogus", op="segment_sum")


@pytest.mark.tier1
def test_dispatch_falls_back_off_contract(rng):
    """Unsorted ids and float masks route to XLA even when kernels are
    requested — the dst-tile slicing depends on the sorted contract and
    the chunked backward has no float-mask cotangent."""
    ids, mask, n = sorted_segments(rng, e=50, n=7, pad=6)
    data = jnp.asarray(rng.normal(size=(len(ids), 3)).astype(np.float32))
    with counting() as c:
        fused_segment_sum(data, ids, n, mask, indices_are_sorted=False,
                          kernels="interpret")
    assert (c.pallas, c.xla) == (0, 1)
    with counting() as c:
        fused_edge_aggregate(lambda r: r, [data], ids, n,
                             mask.astype(np.float32),
                             kernels="interpret")
    assert (c.pallas, c.xla) == (0, 1)
    with counting() as c:
        fused_segment_sum(data, ids, n, mask, indices_are_sorted=True,
                          kernels="interpret")
    assert (c.pallas, c.xla) == (1, 0)
    assert c.mode == "pallas" and c.coverage == 1.0


@pytest.mark.tier1
def test_kernel_counter_aggregates():
    c = KernelCounter(pallas=3, xla=1)
    assert c.total == 4 and abs(c.coverage - 0.75) < 1e-9
    assert c.mode == "pallas"
    assert KernelCounter().mode == ""


@pytest.mark.tier1
def test_segment_softmax_mean_sorted_plumbing(rng):
    """The satellite fix: softmax/mean accept indices_are_sorted and the
    hint changes nothing numerically on a sorted layout."""
    ids, mask, n = sorted_segments(rng, e=120, n=13, pad=10)
    logits = jnp.asarray(rng.normal(size=(len(ids),)).astype(np.float32))
    a = masked_segment_softmax(logits, ids, n, mask)
    b = masked_segment_softmax(logits, ids, n, mask,
                               indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    data = jnp.asarray(rng.normal(size=(len(ids), 3)).astype(np.float32))
    a = masked_segment_mean(data, ids, n, mask)
    b = masked_segment_mean(data, ids, n, mask, indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# model golden parity: interpret-mode Pallas vs pure XLA
# ---------------------------------------------------------------------------


def _small_model(name):
    if name == "chgnet":
        from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig

        m = CHGNet(CHGNetConfig(num_species=4, units=16, num_rbf=6,
                                num_blocks=2, cutoff=3.2, bond_cutoff=2.6))
        return m, True, 2.6
    if name == "tensornet":
        from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig

        m = TensorNet(TensorNetConfig(num_species=4, units=16, num_rbf=8,
                                      num_layers=2, cutoff=3.2))
        return m, False, 0.0
    if name == "mace":
        from distmlip_tpu.models import MACE, MACEConfig

        m = MACE(MACEConfig(num_species=4, channels=8, l_max=2, a_lmax=1,
                            hidden_lmax=1, correlation=2,
                            num_interactions=2, num_bessel=5, radial_mlp=8,
                            cutoff=3.2, avg_num_neighbors=12.0))
        return m, False, 0.0
    if name == "escn":
        from distmlip_tpu.models import ESCN, ESCNConfig

        m = ESCN(ESCNConfig(num_species=4, channels=8, l_max=2,
                            num_layers=2, num_bessel=5, num_experts=2,
                            cutoff=3.2, avg_num_neighbors=12.0))
        return m, False, 0.0
    raise ValueError(name)


def _graph_for_model(rng, model, use_bg, bond_r):
    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.partition import build_partitioned_graph, build_plan
    from tests.utils import make_crystal

    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.5,
                                          n_species=2)
    r = model.cfg.cutoff
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], r, bond_r=bond_r)
    plan = build_plan(nl, lattice, [1, 1, 1], 1, r, bond_r, use_bg)
    graph, _ = build_partitioned_graph(plan, nl, species, lattice)
    return graph


def _assert_model_parity(rng, name):
    from distmlip_tpu.parallel import make_potential_fn

    model, use_bg, bond_r = _small_model(name)
    params = model.init(jax.random.PRNGKey(0))
    graph = _graph_for_model(rng, model, use_bg, bond_r)
    outs = {}
    for mode in (False, "interpret"):
        pot = make_potential_fn(model.energy_fn, None, kernels=mode)
        with counting() as c:
            out = pot(params, graph, graph.positions)
        outs[mode] = jax.tree.map(np.asarray, out)
        # the comparison must not be vacuous: the interpret trace must
        # actually route through the Pallas kernels
        if mode == "interpret":
            assert c.pallas > 0 and c.xla == 0, (name, c)
        else:
            assert c.pallas == 0 and c.xla > 0, (name, c)
    e0, e1 = float(outs[False]["energy"]), float(outs["interpret"]["energy"])
    assert abs(e0 - e1) < 1e-5 * max(1.0, abs(e0)), (name, e0, e1)
    np.testing.assert_allclose(outs["interpret"]["forces"],
                               outs[False]["forces"], atol=1e-4)
    np.testing.assert_allclose(outs["interpret"]["stress"],
                               outs[False]["stress"], atol=1e-4)


@pytest.mark.tier1
def test_model_parity_chgnet(rng):
    _assert_model_parity(rng, "chgnet")


@pytest.mark.tier1
def test_model_parity_tensornet(rng):
    _assert_model_parity(rng, "tensornet")


@pytest.mark.tier1
def test_model_parity_mace(rng):
    _assert_model_parity(rng, "mace")


@pytest.mark.tier1
def test_model_parity_escn(rng):
    _assert_model_parity(rng, "escn")


@pytest.mark.tier1
def test_magmom_parity_chgnet(rng):
    """CHGNet magmoms (the fused aux readout) through DistPotential on
    both kernel paths, plus the kernel telemetry surface."""
    from distmlip_tpu.calculators import Atoms, DistPotential
    from tests.utils import make_crystal

    model, _, _ = _small_model("chgnet")
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.5,
                                          n_species=2)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.zeros(100, np.int32)
    smap[1], smap[2] = 0, 1
    res = {}
    for mode in (False, "interpret"):
        pot = DistPotential(model, params, num_partitions=1,
                            species_map=smap, compute_magmom=True,
                            kernels=mode)
        res[mode] = pot.calculate(atoms)
        assert pot.last_stats["kernel_mode"] == (
            "xla" if mode is False else "pallas")
        assert pot.last_stats["kernel_coverage"] == (
            0.0 if mode is False else 1.0)
    assert abs(res[False]["energy"] - res["interpret"]["energy"]) < 1e-4
    np.testing.assert_allclose(res["interpret"]["forces"],
                               res[False]["forces"], atol=1e-4)
    np.testing.assert_allclose(res["interpret"]["magmoms"],
                               res[False]["magmoms"], atol=1e-4)


@pytest.mark.tier1
def test_packed_batch_parity_interpret(rng):
    """Packed B>1 batches (mixed sizes, a 1-atom structure, padded edges)
    through BatchedPotential on both kernel paths."""
    from distmlip_tpu.calculators import Atoms, BatchedPotential
    from tests.test_batched import make_structure

    model, _, _ = _small_model("tensornet")
    params = model.init(jax.random.PRNGKey(1))
    structs = [
        make_structure(rng, reps=(2, 1, 1), a=3.5),
        make_structure(rng, reps=(1, 1, 1), a=3.4),
        Atoms(numbers=np.array([1], np.int32),
              positions=np.array([[2.0, 2.0, 2.0]]),
              cell=np.eye(3) * 4.0),
    ]
    res = {}
    for mode in (False, "interpret"):
        bp = BatchedPotential(model, params, kernels=mode)
        res[mode] = bp.calculate(structs)
        assert bp.last_stats["kernel_mode"] == (
            "xla" if mode is False else "pallas")
    for b in range(len(structs)):
        assert abs(res[False][b]["energy"]
                   - res["interpret"][b]["energy"]) < 1e-4
        np.testing.assert_allclose(res["interpret"][b]["forces"],
                                   res[False][b]["forces"], atol=1e-4)
        np.testing.assert_allclose(res["interpret"][b]["stress"],
                                   res[False][b]["stress"], atol=1e-4)


@pytest.mark.tier1
def test_mesh_placement_parity_interpret(rng):
    """(2, 2) batch x spatial placement with interpret kernels inside
    shard_map matches the pure-XLA mesh program."""
    from distmlip_tpu.calculators import BatchedPotential
    from distmlip_tpu.parallel import device_mesh
    from tests.test_batched import make_structure

    model, _, _ = _small_model("tensornet")
    params = model.init(jax.random.PRNGKey(1))
    # x-wide so each of the 2 slabs exceeds the cutoff
    structs = [make_structure(rng, reps=(4, 1, 1), a=3.5)
               for _ in range(2)]
    res = {}
    for mode in (False, "interpret"):
        bp = BatchedPotential(model, params, mesh=device_mesh(2, 2),
                              kernels=mode)
        res[mode] = bp.calculate(structs)
    for b in range(len(structs)):
        assert abs(res[False][b]["energy"]
                   - res["interpret"][b]["energy"]) < 1e-4
        np.testing.assert_allclose(res["interpret"][b]["forces"],
                                   res[False][b]["forces"], atol=1e-4)


# ---------------------------------------------------------------------------
# the no-materialization property + analysis integration
# ---------------------------------------------------------------------------


def _all_avals(closed_jaxpr):
    from distmlip_tpu.analysis.ir import iter_sites

    for s in iter_sites(closed_jaxpr):
        for v in s.eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield s, aval


@pytest.mark.tier1
def test_no_materialized_edge_messages(rng):
    """THE property the kernels exist for: TensorNet's (E, 3, 3, C) edge
    message tensor exists in the XLA program and does NOT exist anywhere
    in the fused program — in or out of the kernel (in-kernel blocks are
    (BLK, .) sized)."""
    from distmlip_tpu.parallel import make_total_energy

    model, use_bg, bond_r = _small_model("tensornet")
    params = model.init(jax.random.PRNGKey(0))
    graph = _graph_for_model(rng, model, use_bg, bond_r)
    e_cap = int(graph.e_cap)
    C = model.cfg.units
    strain = jnp.zeros((3, 3), jnp.float32)

    def msg_avals(kernels):
        efn = make_total_energy(model.energy_fn, None, kernels=kernels)
        jx = jax.make_jaxpr(efn)(params, graph, graph.positions, strain)
        hits = []
        for _s, aval in _all_avals(jx):
            shape = tuple(aval.shape)
            # the full-size message: leading axis >= e_cap, 9C trailing
            if (shape and shape[0] >= e_cap
                    and int(np.prod(shape[1:], dtype=np.int64)) == 9 * C):
                hits.append(shape)
        return hits

    assert msg_avals(False), "XLA path must materialize the message tensor"
    assert not msg_avals("interpret"), (
        "fused path materialized a full-size (E, 9C) message intermediate")


@pytest.mark.tier1
def test_analysis_walker_sees_through_pallas_call(rng):
    """The contract passes must walk INTO kernel bodies, not skip them:
    eqns with 'pallas_call' in their path exist in a fused trace."""
    ids, mask, n = sorted_segments(rng, e=40, n=5, pad=8)
    data = jnp.asarray(rng.normal(size=(len(ids), 3)).astype(np.float32))

    def run(d):
        return fused_segment_sum(d, ids, n, mask, indices_are_sorted=True,
                                 kernels="interpret")

    jx = jax.make_jaxpr(run)(data)
    from distmlip_tpu.analysis.ir import iter_sites

    in_kernel = [s for s in iter_sites(jx) if "pallas_call" in s.path]
    assert in_kernel, "walker must recurse into pallas_call jaxprs"
    prims = {s.primitive for s in in_kernel}
    assert "dot_general" in prims, (
        "the one-hot MXU accumulate must be visible inside the kernel")


@pytest.mark.tier1
def test_contract_check_kernels_flag_smoke():
    """--kernels on over one model family: the kernel-enabled programs
    trace and every pass stays green (exit 0)."""
    import tools.contract_check as cc

    assert cc.main(["--models", "tensornet", "--kernels", "on",
                    "--programs", "1x1"]) == 0
    assert cc.main(["--models", "tensornet", "--kernels", "off",
                    "--programs", "1x1"]) == 0


# ---------------------------------------------------------------------------
# training: weight grads flow through the fused custom VJPs
# ---------------------------------------------------------------------------


def test_train_grads_flow_and_match(rng):
    """make_total_energy defaults kernels_diff_params=True: loss grads wrt
    model WEIGHTS flow through the chunked kernel VJP (second-order AD —
    the force term differentiates through the position vjp) and match the
    XLA path; the force/stress factories pass False, which must NOT zero
    position grads."""
    from distmlip_tpu.parallel import make_total_energy
    from distmlip_tpu.train import make_loss_fn

    model, use_bg, bond_r = _small_model("tensornet")
    params = model.init(jax.random.PRNGKey(0))
    graph = _graph_for_model(rng, model, use_bg, bond_r)
    targets = {"energy": jnp.float32(-1.0),
               "forces": jnp.zeros(graph.positions.shape, jnp.float32)}
    grads = {}
    for mode in ("xla", "interpret"):
        with force_kernel_mode(mode):
            loss_fn = make_loss_fn(model.energy_fn, None, w_force=1.0)
            _loss, g = jax.jit(jax.value_and_grad(loss_fn))(
                params, graph, graph.positions, targets)
            grads[mode] = jax.tree.map(np.asarray, g)
    leaves0 = jax.tree.leaves(grads["xla"])
    leaves1 = jax.tree.leaves(grads["interpret"])
    total = sum(float(np.abs(x).sum()) for x in leaves0)
    assert total > 0, "weight grads must be nonzero on the training path"
    for a, b in zip(leaves0, leaves1):
        scale = float(np.max(np.abs(a))) + 1e-12
        assert float(np.max(np.abs(a - b))) < 1e-4 * max(scale, 1e-3)

    # sanity: the force-program flag does not break position grads
    with force_kernel_mode("interpret"):
        efn = make_total_energy(model.energy_fn, None,
                                kernels_diff_params=False)
        g_pos = jax.grad(efn, argnums=2)(
            params, graph, graph.positions,
            jnp.zeros((3, 3), jnp.float32))
    assert float(np.abs(np.asarray(g_pos)).sum()) > 0


# ---------------------------------------------------------------------------
# telemetry riding
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_kernel_telemetry_report(tmp_path):
    """StepRecord.kernel_mode/coverage render in the report; the
    kernel_fallback_dominant anomaly needs BOTH low coverage and an
    accelerator (device_memory stats) — CPU runs never flag it."""
    from distmlip_tpu.telemetry import StepRecord
    from distmlip_tpu.telemetry.report import aggregate

    recs = [StepRecord(step=i, kernel_mode="pallas", kernel_coverage=1.0,
                       timings={"total_s": 0.1}) for i in range(3)]
    rep = aggregate(recs)
    assert rep.counters["kernel_modes"] == ["pallas"]
    assert rep.counters["mean_kernel_coverage"] == 1.0
    assert "fused kernels: mode=pallas coverage mean=1.00" in rep.render()
    assert not [a for a in rep.anomalies
                if a.kind == "kernel_fallback_dominant"]

    # an "accelerator" run (device_memory present) mostly on XLA flags
    bad = [StepRecord(step=i, kernel_mode="xla", kernel_coverage=0.0,
                      device_memory={"dev0_bytes_in_use": 1},
                      timings={"total_s": 0.1}) for i in range(3)]
    rep = aggregate(bad)
    kinds = [a.kind for a in rep.anomalies]
    assert "kernel_fallback_dominant" in kinds
    # same records WITHOUT device stats (CPU): no flag
    for r in bad:
        r.device_memory = {}
    rep = aggregate(bad)
    assert "kernel_fallback_dominant" not in [a.kind for a in rep.anomalies]


@pytest.mark.tier1
def test_env_kill_switch_forces_xla(rng, monkeypatch):
    """DISTMLIP_KERNELS=0 beats a kernels=None potential: the trace
    counts zero Pallas dispatches."""
    monkeypatch.setenv("DISTMLIP_KERNELS", "0")
    ids, mask, n = sorted_segments(rng, e=30, n=5, pad=2)
    data = jnp.asarray(rng.normal(size=(len(ids), 2)).astype(np.float32))
    with counting() as c:
        fused_segment_sum(data, ids, n, mask, indices_are_sorted=True)
    assert (c.pallas, c.xla) == (0, 1)
    assert os.environ["DISTMLIP_KERNELS"] == "0"
