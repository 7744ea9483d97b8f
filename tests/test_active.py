"""Active-learning subsystem: ensemble uncertainty lane, replay buffer,
fine-tune trigger + holdout gate, zero-recompile hot-swap with cache-key
roll-forward, deadline load shedding, and the end-to-end
serve -> buffer -> train -> validate -> swap loop.

The e2e contract under test (ISSUE 13 acceptance): a drifted CHGNet
served under ``ActiveLoop`` fills the buffer from high-variance traffic,
fine-tunes back toward committee parity, hot-swaps with
``compile_count`` unchanged and every in-flight Future resolved, and
post-swap variance on the served traffic drops; a FleetRouter swap rolls
the result-cache model id (and the AOT fingerprint) forward so stale
old-weight entries can never serve the new model.
"""

import numpy as np
import pytest

from distmlip_tpu import geometry
from distmlip_tpu.active import (ActiveLoop, EnsembleBatchedPotential,
                                 EscalationPolicy, FineTuneTrigger,
                                 HotSwapError, ReplayBuffer, TriggerPolicy,
                                 hot_swap_engine, hot_swap_router,
                                 params_digest, run_finetune,
                                 variance_score)
from distmlip_tpu.calculators import Atoms, BatchedPotential
from distmlip_tpu.fleet import FleetRouter, ResultCache, install_aot_cache
from distmlip_tpu.fleet.aot import model_fingerprint
from distmlip_tpu.models import PairConfig, PairPotential
from distmlip_tpu.serve import ServeEngine, ServeRejected
from distmlip_tpu.telemetry import Telemetry
from distmlip_tpu.train import TrainConfig

pytestmark = pytest.mark.active


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class CaptureSink:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def close(self):
        pass


def jitter_params(params, scale, seed):
    import jax

    key = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda x: x + scale * jax.random.normal(
            jax.random.fold_in(key, 1), np.shape(x),
            np.asarray(x).dtype)
        if np.issubdtype(np.asarray(x).dtype, np.floating) else x, params)


@pytest.fixture(scope="module")
def pair():
    model = PairPotential(PairConfig(cutoff=4.0))
    return model, model.init()


def make_structure(rng, reps=(2, 1, 1), a=3.6, noise=0.04, species=14):
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), species), positions=cart,
                 cell=lattice)


# ---------------------------------------------------------------------------
# ensemble uncertainty lane
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_ensemble_batched_variance_matches_sequential(rng, pair):
    """The ONE vmapped launch reproduces M sequential member evaluations:
    mean/variance/per-member stacks to fp32 roundoff, and ``calculate``
    (the cheap serving path) is exactly the primary member."""
    model, p0 = pair
    p1, p2 = jitter_params(p0, 0.05, 1), jitter_params(p0, 0.05, 2)
    structs = [make_structure(rng) for _ in range(3)]
    ens = EnsembleBatchedPotential(model, [p0, p1, p2])
    primary = ens.calculate(structs)
    ref = BatchedPotential(model, p0).calculate(structs)
    for a, b in zip(primary, ref):
        assert a["energy"] == pytest.approx(b["energy"], abs=1e-9)
    seq = [BatchedPotential(model, p).calculate(structs)
           for p in (p0, p1, p2)]
    out = ens.calculate_with_variance(structs)
    for b in range(len(structs)):
        e_all = np.array([seq[k][b]["energy"] for k in range(3)])
        f_all = np.stack([seq[k][b]["forces"] for k in range(3)])
        assert out[b]["energy"] == pytest.approx(e_all.mean(), abs=1e-5)
        assert out[b]["energy_var"] == pytest.approx(e_all.var(), abs=1e-5)
        np.testing.assert_allclose(out[b]["forces"], f_all.mean(axis=0),
                                   atol=1e-4)
        np.testing.assert_allclose(out[b]["forces_var"], f_all.var(axis=0),
                                   atol=1e-4)
        assert out[b]["committee_energy"] == pytest.approx(
            e_all[1:].mean(), abs=1e-5)
    assert ens.last_stats["member_count"] == 3
    assert variance_score(out[0]) > 0


@pytest.mark.tier1
def test_ensemble_vmap_adds_zero_collectives():
    """The contract-check pin, asserted as an equality: vmap over stacked
    members adds ZERO collectives to the 2-partition ring program (one
    launch, one set of ppermutes)."""
    import jax
    import jax.numpy as jnp

    from distmlip_tpu.models import TensorNet, TensorNetConfig
    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.parallel import graph_mesh, make_potential_fn
    from distmlip_tpu.parallel.audit import count_collectives
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    cfg = TensorNetConfig(num_species=3, units=8, num_rbf=4, num_layers=1,
                          cutoff=3.2)
    model = TensorNet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    a = make_structure(rng, reps=(4, 2, 2), a=3.5)
    nl = neighbor_list_numpy(a.positions, a.cell, [1, 1, 1], cfg.cutoff)
    plan = build_plan(nl, a.cell, [1, 1, 1], 2, cfg.cutoff, 0.0, False)
    graph, _ = build_partitioned_graph(
        plan, nl, np.zeros(len(a), np.int32), a.cell)
    pfn = make_potential_fn(model.energy_fn, graph_mesh(2))
    single = count_collectives(
        jax.make_jaxpr(pfn)(params, graph, graph.positions))
    stacked = jax.tree.map(lambda p: jnp.stack([p, p]), params)
    vfn = jax.vmap(pfn, in_axes=(0, None, None))
    vmapped = count_collectives(
        jax.make_jaxpr(vfn)(stacked, graph, graph.positions))
    assert sum(single.values()) > 0          # the ring really communicates
    assert dict(vmapped) == dict(single), (vmapped, single)


@pytest.mark.tier1
def test_contract_check_covers_ensemble_programs():
    """tools/contract_check.py traces the ensemble family and stays
    exit 0 with the collective pin in place."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "contract_check", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "contract_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--programs", "ensemble"]) == 0


# ---------------------------------------------------------------------------
# replay buffer
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_buffer_dedup_and_priority_eviction(rng):
    buf = ReplayBuffer(capacity=2)
    s1, s2, s3 = (make_structure(rng) for _ in range(3))
    f = np.zeros((len(s1), 3))
    assert buf.add(s1, 1.0, f, variance=0.5) is not None
    # same structure, wrapped by a lattice vector: SAME canonical key
    wrapped = s1.copy()
    wrapped.positions = wrapped.positions + wrapped.cell[0]
    buf.add(wrapped, 1.1, f, variance=0.2)
    assert len(buf) == 1 and buf.dedup_hits == 1
    entry = next(iter(buf._entries.values()))
    assert entry.variance == 0.5          # dedup keeps the max variance
    assert entry.energy == 1.1            # ...and the freshest label
    buf.add(s2, 2.0, f, variance=0.9)
    # lowest-variance insert on a full buffer is itself the victim
    assert buf.add(s3, 3.0, f, variance=0.1) is None
    assert len(buf) == 2 and buf.evictions == 1
    samples = buf.to_samples()
    assert [s.energy for s in samples] == [2.0, 1.1]  # variance-ordered


@pytest.mark.tier1
def test_buffer_spill_and_reload(rng, tmp_path):
    d = str(tmp_path / "buf")
    buf = ReplayBuffer(capacity=8, directory=d)
    structs = [make_structure(rng) for _ in range(3)]
    for i, s in enumerate(structs):
        buf.add(s, float(i), np.full((len(s), 3), 0.25 * i),
                variance=0.1 * (i + 1), stress=np.eye(3) * i)
    buf.add(structs[0], 9.0, np.zeros((len(structs[0]), 3)), variance=0.05)
    # a fresh process resumes the exact buffer (dedup'd, labels fresh)
    buf2 = ReplayBuffer(capacity=8, directory=d)
    assert len(buf2) == 3
    samples = {round(s.energy, 6) for s in buf2.to_samples()}
    assert samples == {9.0, 1.0, 2.0}
    s0 = [s for s in buf2.to_samples() if s.energy == 2.0][0]
    np.testing.assert_allclose(s0.forces, 0.5, atol=1e-6)
    np.testing.assert_allclose(s0.stress, np.eye(3) * 2, atol=1e-6)
    # corrupt log lines are skipped, not fatal
    with open(tmp_path / "buf" / "buffer_log.jsonl", "a") as f:
        f.write("{corrupt\n")
    assert len(ReplayBuffer(capacity=8, directory=d)) == 3


# ---------------------------------------------------------------------------
# trigger
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_trigger_policies_and_cooldown():
    clock = FakeClock()
    trig = FineTuneTrigger(TriggerPolicy(
        min_buffer=4, interval_s=100.0, variance_drift=2.0,
        drift_window=4, cooldown_s=10.0), clock=clock)
    # an EMPTY buffer never fires, whatever the clock says (nothing to
    # train on), and the interval cadence anchors at construction
    clock.advance(500.0)
    assert trig.due(0) is None
    assert "interval" in trig.due(1)
    trig.note_fired(1)
    assert trig.due(1) is None            # cooldown
    clock.advance(11.0)
    assert trig.due(1) is None            # cooldown over, nothing due yet
    # buffer-size policy counts FRESH entries since the last fine-tune
    assert "buffer_size" in trig.due(5)
    trig.note_fired(5)
    clock.advance(11.0)
    assert trig.due(7) is None            # only 2 fresh
    assert "buffer_size" in trig.due(9)
    # variance drift: first window is the baseline, later windows compare
    for v in (1.0, 1.0, 1.0, 1.0):
        trig.observe_variance(v)
    for v in (3.0, 3.0):
        trig.observe_variance(v)
    assert trig.drift_ratio() == pytest.approx(3.0)
    assert "variance_drift" in trig.due(1)


# ---------------------------------------------------------------------------
# deadline-aware load shedding (ServeEngine satellite)
# ---------------------------------------------------------------------------


@pytest.mark.tier1
@pytest.mark.serve
def test_deadline_shed_expired_requests(rng, pair):
    model, params = pair
    clock = FakeClock()
    engine = ServeEngine(BatchedPotential(model, params), max_batch=4,
                         max_wait_s=0.5, shed_deadlines=True, clock=clock,
                         start=False)
    doomed = engine.submit(make_structure(rng), deadline=0.1)
    live = engine.submit(make_structure(rng), deadline=50.0)
    no_deadline = engine.submit(make_structure(rng))
    clock.advance(1.0)        # doomed's deadline passes IN the queue
    engine.start()
    with pytest.raises(ServeRejected, match="deadline shed"):
        doomed.result(timeout=60)
    assert live.result(timeout=60)["energy"] is not None
    assert no_deadline.result(timeout=60)["energy"] is not None
    assert engine.stats.shed_count == 1
    assert engine.stats.deadline_misses == 0   # shed != missed
    engine.close()


@pytest.mark.tier1
@pytest.mark.serve
def test_deadline_shed_provably_unmeetable(rng, pair):
    """The predictive rule: a deadline tighter than the EWMA batch
    service time cannot be met even from the queue head — shed it."""
    model, params = pair
    clock = FakeClock()
    engine = ServeEngine(BatchedPotential(model, params), max_batch=4,
                         max_wait_s=0.5, shed_deadlines=True, clock=clock,
                         start=False)
    engine._service_ewma = 10.0    # injected drain estimate (fake clock)
    hopeless = engine.submit(make_structure(rng), deadline=2.0)
    fine = engine.submit(make_structure(rng), deadline=100.0)
    clock.advance(1.0)             # past max-wait: the scheduler assembles
    engine.start()
    with pytest.raises(ServeRejected, match="drain rate"):
        hopeless.result(timeout=60)
    assert fine.result(timeout=60)["energy"] is not None
    assert engine.stats.shed_count == 1
    engine.close()


@pytest.mark.serve
def test_shedding_off_by_default_preserves_late_delivery(rng, pair):
    """The historical contract: without shed_deadlines, a missed deadline
    is counted and the result still delivered."""
    model, params = pair
    clock = FakeClock()
    engine = ServeEngine(BatchedPotential(model, params), max_batch=2,
                         max_wait_s=0.5, clock=clock, start=False)
    fut = engine.submit(make_structure(rng), deadline=0.1)
    clock.advance(1.0)
    engine.start()
    assert fut.result(timeout=60)["energy"] is not None
    assert engine.stats.deadline_misses == 1
    assert engine.stats.shed_count == 0
    engine.close()


@pytest.mark.serve
def test_shed_count_rides_telemetry(rng, pair, tmp_path):
    model, params = pair
    clock = FakeClock()
    sink = CaptureSink()
    engine = ServeEngine(BatchedPotential(model, params), max_batch=4,
                         max_wait_s=0.5, shed_deadlines=True, clock=clock,
                         telemetry=Telemetry([sink]), start=False)
    doomed = engine.submit(make_structure(rng), deadline=0.1)
    ok = engine.submit(make_structure(rng))
    clock.advance(1.0)
    engine.start()
    with pytest.raises(ServeRejected):
        doomed.result(timeout=60)
    ok.result(timeout=60)
    engine.drain(timeout=60)
    engine.close()
    serve_recs = [r for r in sink.records if r.kind == "serve_batch"]
    assert serve_recs and serve_recs[-1].shed_count == 1


# ---------------------------------------------------------------------------
# EnsemblePotential telemetry parity (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_ensemble_potential_emits_records_with_member_count(rng, pair):
    from distmlip_tpu.calculators import EnsemblePotential

    model, p0 = pair
    sink = CaptureSink()
    ens = EnsemblePotential(model, [p0, jitter_params(p0, 0.05, 1)],
                            num_partitions=1)
    ens.attach_telemetry(Telemetry([sink]))
    atoms = make_structure(rng)
    res = ens.calculate(atoms)
    assert res["energy_var"] >= 0.0
    assert ens.last_stats["member_count"] == 2
    assert ens.last_stats.get("n_atoms", len(atoms)) == len(atoms)
    recs = [r for r in sink.records if r.kind == "ensemble_calculate"]
    assert len(recs) == 1
    assert recs[0].member_count == 2
    assert recs[0].n_atoms == len(atoms)
    assert recs[0].timings["total_s"] > 0


def test_ensemble_potential_sequential_parity_stats(rng, pair):
    from distmlip_tpu.calculators import EnsemblePotential

    model, p0 = pair
    sink = CaptureSink()
    ens = EnsemblePotential(model, [p0, jitter_params(p0, 0.05, 1)],
                            stacked=False, num_partitions=1)
    ens.attach_telemetry(Telemetry([sink]))
    ens.calculate(make_structure(rng))
    assert ens.last_stats["member_count"] == 2
    kinds = {r.kind for r in sink.records}
    assert "ensemble_calculate" in kinds
    # sequential members emit their own per-member records too
    assert "calculate" in kinds


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_hot_swap_engine_zero_recompile_and_inflight_resolution(rng, pair):
    model, p0 = pair
    p1 = jitter_params(p0, 0.1, 5)
    pot = BatchedPotential(model, p0)
    engine = ServeEngine(pot, max_batch=4, max_wait_s=0.005)
    pool = [make_structure(rng) for _ in range(4)]
    for f in [engine.submit(a) for a in pool]:
        f.result(timeout=60)                      # warm the bucket
    compile_before = engine.compile_count
    # requests queued ACROSS the swap must all resolve
    futs = [engine.submit(a) for a in pool]
    report = hot_swap_engine(engine, p1)
    futs += [engine.submit(a) for a in pool]
    results = [f.result(timeout=60) for f in futs]
    assert len(results) == 8
    assert engine.compile_count == compile_before
    assert report["compile_count"] == compile_before
    # post-swap results ARE the new weights
    ref = BatchedPotential(model, p1).calculate(pool)
    post = [engine.submit(a).result(timeout=60) for a in pool]
    for a, b in zip(post, ref):
        assert a["energy"] == pytest.approx(b["energy"], abs=1e-6)
    assert engine.stats.failed == 0
    engine.close()


@pytest.mark.tier1
def test_hot_swap_rejects_incompatible_tree(rng, pair):
    import jax

    model, p0 = pair
    pot = BatchedPotential(model, p0)
    engine = ServeEngine(pot, max_batch=2, start=False)
    bad = jax.tree.map(lambda x: np.zeros(np.shape(x) + (2,),
                                          np.asarray(x).dtype), p0)
    with pytest.raises(HotSwapError):
        hot_swap_engine(engine, bad)
    # nothing was mutated
    assert pot.params is p0
    engine.close()


@pytest.mark.tier1
@pytest.mark.fleet
def test_router_swap_rolls_cache_keys_stale_entries_never_serve(rng, pair):
    """The stale-entry contract: after a router hot-swap, entries cached
    under the old weights are unreachable — a duplicate submission is
    recomputed with the NEW weights, never served from the old cache."""
    model, p0 = pair
    p1 = jitter_params(p0, 0.1, 6)
    engine = ServeEngine(BatchedPotential(model, p0), max_batch=4,
                         max_wait_s=0.005)
    cache = ResultCache()
    router = FleetRouter([engine], result_cache=cache, model_id="pair")
    atoms = make_structure(rng)
    e_old = router.submit(atoms).result(timeout=60)["energy"]
    # sanity: the duplicate IS a cache hit before the swap
    router.submit(atoms).result(timeout=60)
    assert router.stats.cache_hits == 1
    dispatched_before = router.snapshot()["replicas"]["r0"][
        "dispatched_total"]
    report = hot_swap_router(router, p1)
    assert report["model_id"] != "pair"
    assert report["model_id"] == router.model_id
    assert params_digest(p1) in router.model_id
    e_new = router.submit(atoms).result(timeout=60)["energy"]
    snap = router.snapshot()
    # recomputed on a replica (no stale hit), with the new weights
    assert snap["replicas"]["r0"]["dispatched_total"] == \
        dispatched_before + 1
    assert router.stats.cache_hits == 1
    ref = BatchedPotential(model, p1).calculate([atoms])[0]["energy"]
    assert e_new == pytest.approx(ref, abs=1e-6)
    assert e_new != pytest.approx(e_old, abs=1e-9)
    # the old entry still exists but under the retired key
    assert len(cache) == 2
    router.close()


@pytest.mark.fleet
def test_router_swap_rolls_aot_fingerprint(rng, pair, tmp_path):
    model, p0 = pair
    p1 = jitter_params(p0, 0.1, 7)
    pot = BatchedPotential(model, p0)
    install_aot_cache(pot, str(tmp_path / "aot"))
    engine = ServeEngine(pot, max_batch=2, max_wait_s=0.005)
    router = FleetRouter([engine], result_cache=None, model_id="pair")
    atoms = make_structure(rng)
    router.submit(atoms).result(timeout=60)
    hot_swap_router(router, p1)
    # the AOT key always describes the LIVE model (a pure value swap
    # leaves it unchanged by construction — executables are
    # weight-agnostic — but the invariant is re-derived, not assumed)
    assert pot.aot_cache.fingerprint == model_fingerprint(model, p1)
    # and a rehydrated/warm executable computes with the NEW weights
    e = router.submit(make_structure(rng, noise=0.01)).result(
        timeout=60)["energy"]
    assert np.isfinite(e)
    router.close()


# ---------------------------------------------------------------------------
# fine-tune gate
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_finetune_gate_rejects_worse_model(rng):
    """A fine-tune that cannot improve (LR so hot it diverges) must NOT
    ship: the holdout gate returns params=None."""
    import jax

    from distmlip_tpu.models import TensorNet, TensorNetConfig

    cfg = TensorNetConfig(num_species=2, units=8, num_rbf=4, num_layers=1,
                          cutoff=3.4)
    model = TensorNet(cfg)
    params = model.init(jax.random.PRNGKey(0))
    teacher = BatchedPotential(model, params)
    structs = [make_structure(rng, noise=0.05, species=1)
               for _ in range(6)]
    buf = ReplayBuffer(capacity=16)
    for a, r in zip(structs, teacher.calculate(structs)):
        buf.add(a, r["energy"], r["forces"], variance=1.0)
    import optax

    report = run_finetune(
        model, params, buf.to_samples(), steps=4,
        optimizer=optax.sgd(1e6),        # guaranteed to blow up
        loader_kwargs={"species_fn": lambda z: (z - 1).astype(np.int32),
                       "seed": 3})
    assert not report.shipped and report.params is None
    assert not (report.val_after < report.val_before)


@pytest.mark.tier1
def test_finetune_resume_gate_compares_against_live_weights(rng, tmp_path):
    """Preemption-resume must not launder a bad checkpoint past the
    gate: the holdout baseline is the LIVE serving weights, evaluated
    BEFORE the checkpoint restore — a resumed job whose checkpointed
    state is worse than live must not ship even if it improved a little
    on its own checkpoint."""
    import jax
    import optax

    from distmlip_tpu.models import TensorNet, TensorNetConfig

    cfg = TensorNetConfig(num_species=2, units=8, num_rbf=4, num_layers=1,
                          cutoff=3.4)
    model = TensorNet(cfg)
    live = model.init(jax.random.PRNGKey(0))
    teacher = BatchedPotential(model, live)
    structs = [make_structure(rng, noise=0.05, species=1) for _ in range(6)]
    buf = ReplayBuffer(capacity=16)
    for a, r in zip(structs, teacher.calculate(structs)):
        buf.add(a, r["energy"], r["forces"], variance=1.0)
    samples = buf.to_samples()
    lk = {"species_fn": lambda z: (z - 1).astype(np.int32), "seed": 3}
    ckpt = str(tmp_path / "ft")
    # a "preempted" job that was fine-tuning BAD weights leaves its
    # checkpoint behind (2 of 4 steps done)
    bad = jitter_params(live, 0.5, 9)
    run_finetune(model, bad, samples, steps=2,
                 optimizer=optax.adam(1e-4), checkpoint_dir=ckpt,
                 loader_kwargs=lk)
    # the resumed job serves GOOD live weights: it restores the bad
    # checkpoint, improves slightly on it — and must still be refused
    report = run_finetune(model, live, samples, steps=4,
                          optimizer=optax.adam(1e-4), checkpoint_dir=ckpt,
                          loader_kwargs=lk)
    assert report.resumed_step >= 1
    assert report.val_before < report.val_after   # live beats the candidate
    assert not report.shipped and report.params is None


# ---------------------------------------------------------------------------
# the end-to-end loop (the ISSUE acceptance scenario)
# ---------------------------------------------------------------------------


@pytest.mark.tier1
def test_active_loop_end_to_end_chgnet(rng, tmp_path):
    """Drifted CHGNet under ActiveLoop: high-variance traffic fills the
    buffer, the gated fine-tune pulls it back toward committee parity,
    the hot-swap keeps compile_count unchanged with every in-flight
    Future resolved, and post-swap variance on the served traffic
    drops."""
    import jax

    from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig

    cfg = CHGNetConfig(num_species=3, units=8, num_rbf=4, num_blocks=1,
                       cutoff=3.2, bond_cutoff=2.6)
    model = CHGNet(cfg)
    good = model.init(jax.random.PRNGKey(0))
    drifted = jitter_params(good, 0.4, 1)
    members = [drifted, good, jitter_params(good, 0.01, 2),
               jitter_params(good, 0.01, 3)]
    srng = np.random.default_rng(11)

    def traffic():
        unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5],
                         [0, 0.5, 0.5]])
        frac, lattice = geometry.make_supercell(
            unit, np.eye(3) * 3.8, (2, 2, 1))
        cart = geometry.frac_to_cart(frac, lattice) + srng.normal(
            0, 0.03, (len(frac), 3))
        return Atoms(numbers=srng.integers(1, 4, len(cart)),
                     positions=cart, cell=lattice)

    sink = CaptureSink()
    ens = EnsembleBatchedPotential(model, members, skin=0.3)
    # the batch shapes are this test's, not the scheduler thread's timing:
    # under a max-wait no request reaches, a micro-batch leaves only when
    # max_batch requests are queued or on drain(), so the ten requests
    # form 4 + 4 + 2 and the four in-flight ones the 4 compiled by then
    # (every batch size is a bucket of its own, hence a compile)
    engine = ServeEngine(ens, max_batch=4, max_wait_s=600.0,
                         telemetry=Telemetry([sink]))

    def served_batches():
        return [(r.batch_size, r.bucket_key) for r in sink.records
                if r.kind == "serve_batch"]

    buf = ReplayBuffer(capacity=64, directory=str(tmp_path / "buf"))
    loop = ActiveLoop(
        engine, ens, buf,
        policy=EscalationPolicy(sample_rate=1.0),
        trigger=FineTuneTrigger(TriggerPolicy(min_buffer=6)),
        telemetry=engine.telemetry,
        finetune_kwargs={
            # force-weighted: the variance score is force-based, and the
            # drifted model's dominant error is a big energy offset —
            # without the weight the fine-tune spends its short budget
            # on the offset and the force field barely moves
            "steps": 60, "learning_rate": 5e-3,
            "config": TrainConfig(ema_decay=0.0, w_force=10.0),
            "checkpoint_dir": str(tmp_path / "ft"),
            "loader_kwargs": {
                "species_fn": lambda z: (z - 1).astype(np.int32),
                "use_bond_graph": True, "bond_cutoff": 2.6, "seed": 7}})

    pool = [traffic() for _ in range(10)]
    futs = [loop.submit(a) for a in pool]
    assert engine.drain(timeout=300)
    for f in futs:
        assert np.isfinite(f.result(timeout=300)["energy"])
    warm = served_batches()
    assert sorted(b for b, _ in warm) == [2, 4, 4]
    loop.pump()
    assert len(buf) >= 6                   # high-variance traffic buffered
    var_before = float(np.mean(buf.variances()))
    assert var_before > 0
    compile_before = engine.compile_count

    # in-flight Futures must survive the swap untouched
    inflight = [loop.submit(a) for a in pool[:4]]
    tick = loop.maybe_finetune()
    assert tick is not None and tick["shipped"], tick
    for f in inflight:
        assert np.isfinite(f.result(timeout=300)["energy"])
    (swapped_over,) = served_batches()[len(warm):]
    assert swapped_over in warm and swapped_over[0] == 4
    assert engine.compile_count == compile_before   # ZERO recompiles
    assert loop.stats.swaps == 1 and engine.stats.failed == 0

    # post-swap: the SAME served traffic re-escalates at lower variance
    post = [variance_score(r) for r in ens.calculate_with_variance(pool)]
    assert float(np.mean(post)) < 0.5 * var_before, (
        float(np.mean(post)), var_before)
    # serving now runs the fine-tuned primary (parity with a fresh pot)
    served = loop.submit(pool[0])
    assert engine.drain(timeout=300)
    served = served.result(timeout=300)
    ref = BatchedPotential(model, ens.params).calculate([pool[0]])[0]
    assert served["energy"] == pytest.approx(ref["energy"], abs=1e-5)
    engine.close()

    # telemetry: the active_* records render as the report section
    kinds = {r.kind for r in sink.records}
    assert {"active_escalate", "active_finetune", "active_swap"} <= kinds
    from distmlip_tpu.telemetry.report import aggregate

    rep = aggregate(sink.records)
    act = rep.counters["active"]
    assert act["swaps"] == 1 and act["shipped"] == 1
    assert act["escalation_rate"] == pytest.approx(1.0)
    assert act["member_count"] == 4
    assert act["variance_max"] > 0
    assert "active learning (ActiveLoop)" in rep.render()


def test_active_loop_sampling_policy_and_pending_bound(rng, pair):
    model, p0 = pair
    ens = EnsembleBatchedPotential(model, [p0, jitter_params(p0, 0.05, 1)])
    engine = ServeEngine(ens, max_batch=4, max_wait_s=0.005)
    loop = ActiveLoop(engine, ens,
                      policy=EscalationPolicy(sample_rate=0.0,
                                              max_pending=2))
    pool = [make_structure(rng) for _ in range(3)]
    for f in [loop.submit(a) for a in pool]:
        f.result(timeout=60)
    assert loop.pending_escalations == 0       # rate 0: nothing queued
    for a in pool:
        loop.submit(a, escalate=True).result(timeout=60)
    assert loop.pending_escalations == 2       # bounded, oldest dropped
    assert loop.stats.escalation_dropped == 1
    assert loop.pump() == 2
    assert loop.stats.evaluated == 2
    engine.close()


@pytest.mark.tier1
def test_load_test_active_cli_gate():
    """tools/load_test.py --fleet 2 --active --check: the mid-burst
    hot-swap loses zero requests and triggers zero recompiles."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "load_test", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "load_test.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--fleet", "2", "--active", "--requests", "32",
                   "--max-batch", "4", "--check"])
    assert rc == 0
