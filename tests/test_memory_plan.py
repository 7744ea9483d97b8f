"""Static HBM planner tests: estimator vs the XLA oracle, the
memory_budget pass, and memory-aware autobatching/admission.

Fast subset is tier1-marked; the full 22-program estimator-vs-oracle
sweep (one real XLA compile per program) is slow-marked.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis import (Program, Severity, exit_code, get_passes,
                                   run_passes)
from distmlip_tpu.analysis.memory import (MemoryPlan, analyze_memory,
                                          aval_bytes, oracle_peak_bytes)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

ORACLE_BAND = (0.5, 2.0)        # the acceptance criterion: within 2x


def _pair_graph(rng, nparts=1, reps=(4, 2, 2)):
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.partition import build_partitioned_graph, build_plan
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=reps, a=3.5)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 3.2)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, 3.2, 0.0, False)
    graph, _ = build_partitioned_graph(plan, nl, species, lattice)
    return model, params, graph


# ---------------------------------------------------------------------------
# estimator mechanics (toy fixtures; no model tracing)
# ---------------------------------------------------------------------------


@pytest.mark.memory
@pytest.mark.tier1
def test_plan_shape_and_composition():
    def f(x, w):
        h = jnp.tanh(x @ w)
        return (h @ w).sum()

    x = jnp.ones((256, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(f)(x, w)
    plan = analyze_memory(jaxpr)
    assert isinstance(plan, MemoryPlan)
    # args resident: x (128KiB) + w (64KiB)
    assert plan.arg_bytes == 256 * 128 * 4 + 128 * 128 * 4
    # peak covers at least the args plus one (256,128) temp
    assert plan.peak_bytes >= plan.arg_bytes + 256 * 128 * 4
    assert plan.temp_peak_bytes > 0
    assert plan.n_eqns >= 3
    assert plan.peak_bytes == plan.resident_bytes + plan.temp_peak_bytes


@pytest.mark.memory
@pytest.mark.tier1
def test_donated_input_reuse():
    """A donated input dies at its last use; a held one is resident for
    the whole program — the peaks must differ by about the input size."""
    def f(x):
        y = x * 2.0                 # x's last use: dies here if donated
        z = jnp.tanh(y)
        w = z * 0.5 + 1.0
        return w.sum()

    x = jnp.ones((1024, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(f)(x)
    held = analyze_memory(jaxpr)
    donated = analyze_memory(jaxpr, donated=[0])
    nbytes = 1024 * 256 * 4
    assert held.peak_bytes >= donated.peak_bytes
    # downstream of x's death two same-size temps are transiently live;
    # holding x on top of them costs about one extra buffer
    assert held.peak_bytes - donated.peak_bytes >= nbytes // 2
    # bool-mask spellings (list AND numpy array) are equivalent
    assert analyze_memory(jaxpr, donated=[True]).peak_bytes \
        == donated.peak_bytes
    assert analyze_memory(jaxpr, donated=np.array([True])).peak_bytes \
        == donated.peak_bytes


@pytest.mark.memory
@pytest.mark.tier1
def test_scan_carry_and_ys_residency():
    """A scan charges its stacked ys at the call site and a double-buffered
    carry; the loop body's operands stay held for the whole call."""
    carry_shape = (512, 64)                      # 128 KiB f32
    T = 8

    def step(c, _):
        c = jnp.tanh(c) * 0.5
        return c, c

    def f(c0):
        c, ys = jax.lax.scan(step, c0, jnp.arange(T, dtype=jnp.float32))
        return c.sum() + ys.sum()

    c0 = jnp.ones(carry_shape, jnp.float32)
    jaxpr = jax.make_jaxpr(f)(c0)
    plan = analyze_memory(jaxpr)
    carry_b = int(np.prod(carry_shape)) * 4
    # resident: c0 (arg) + stacked ys (T x carry) + 2x carry double-buffer
    assert plan.peak_bytes >= carry_b + T * carry_b + 2 * carry_b
    # and the scan shows up as a transient window
    assert any(t.primitive == "scan" for t in plan.transients)


@pytest.mark.memory
@pytest.mark.tier1
def test_shard_map_args_scale_per_device():
    """Program args sharded into a shard_map are charged per-device."""
    from jax.sharding import PartitionSpec as P

    from distmlip_tpu.parallel import SPATIAL_AXIS, graph_mesh

    mesh = graph_mesh(4)
    x = jnp.ones((4, 1024, 64), jnp.float32)     # 1 MiB global

    def local(xs):
        return jax.lax.psum((xs * 2.0).sum(), SPATIAL_AXIS)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(SPATIAL_AXIS),),
                       out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(x)
    plan = analyze_memory(jaxpr)
    nbytes = 4 * 1024 * 64 * 4
    # per-device: 1/4 of the global argument (plus rounding slack)
    assert plan.arg_bytes <= nbytes // 4 + 1024
    assert plan.peak_bytes < nbytes          # never charged at global size


@pytest.mark.memory
@pytest.mark.tier1
def test_contributors_carry_sites(rng):
    """Top live-set contributors point at real source sites."""
    from distmlip_tpu.parallel import make_potential_fn

    model, params, graph = _pair_graph(rng)
    pfn = make_potential_fn(model.energy_fn, None)
    jaxpr = jax.make_jaxpr(pfn)(params, graph, graph.positions)
    plan = analyze_memory(jaxpr, top_k=6)
    assert plan.contributors, "a real program has live buffers at peak"
    temps = [c for c in plan.contributors if c.kind == "temp"]
    assert temps, "peak live set of a real program includes temporaries"
    assert any(c.location and str(c.location[0]).endswith(".py")
               for c in temps)
    # rendering is exercised (drives the CLI table + pass messages)
    assert "MiB" in plan.render()


@pytest.mark.memory
@pytest.mark.tier1
def test_aval_bytes():
    x = jnp.ones((3, 5), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a: a + 1.0)(x)
    aval = jaxpr.jaxpr.invars[0].aval
    assert aval_bytes(aval) == 3 * 5 * 4
    assert aval_bytes(object()) == 0


# ---------------------------------------------------------------------------
# memory_budget pass
# ---------------------------------------------------------------------------


def _toy_program(nbytes_scale=1):
    n = 256 * nbytes_scale

    def f(x, w):
        h = jnp.tanh(x @ w)
        g = jnp.concatenate([h, h], axis=1)
        return g.sum()

    x = jnp.ones((n, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)
    return jax.make_jaxpr(f)(x, w)


@pytest.mark.memory
@pytest.mark.tier1
def test_memory_budget_pass_overbudget_errors():
    """Seeded over-budget program: ERROR finding + exit code 3."""
    prog = Program(name="seeded_overbudget", jaxpr=_toy_program(),
                   config={"bytes_limit": 64 * 1024})   # 64 KiB budget
    findings = run_passes(prog, get_passes(["memory_budget"]))
    errs = [f for f in findings if f.severity == Severity.ERROR]
    assert len(errs) == 1
    assert errs[0].rule == "over-budget"
    assert "exceeds" in errs[0].message
    assert exit_code(findings) == 3


@pytest.mark.memory
@pytest.mark.tier1
def test_memory_budget_pass_clean_and_infoline():
    """Generous budget: no gate, but the INFO estimate always reports."""
    prog = Program(name="fits", jaxpr=_toy_program(),
                   config={"bytes_limit": 1 << 30})
    findings = run_passes(prog, get_passes(["memory_budget"]))
    assert exit_code(findings) == 0
    infos = [f for f in findings if f.rule == "peak-estimate"]
    assert len(infos) == 1 and "estimated per-device peak" in infos[0].message
    # no budget at all (CPU, no config): INFO only, never an error
    findings = run_passes(Program(name="nolimit", jaxpr=_toy_program()),
                          get_passes(["memory_budget"]))
    assert exit_code(findings) == 0


@pytest.mark.memory
@pytest.mark.tier1
def test_memory_budget_pass_transient_warning():
    """Fits at steady state, but one loop transient owns > half the
    budget: WARNING, not ERROR."""
    carry = jnp.ones((512, 256), jnp.float32)    # 512 KiB

    def step(c, _):
        return jnp.tanh(c), ()

    def f(c0):
        c, _ = jax.lax.scan(step, c0, jnp.arange(4, dtype=jnp.float32))
        return c.sum()

    jaxpr = jax.make_jaxpr(f)(carry)
    plan = analyze_memory(jaxpr)
    limit = int(plan.peak_bytes / 0.8)           # peak = 80% of budget
    prog = Program(name="transient", jaxpr=jaxpr,
                   config={"bytes_limit": limit})
    findings = run_passes(prog, get_passes(["memory_budget"]))
    assert exit_code(findings) == 0
    warns = [f for f in findings if f.severity == Severity.WARNING]
    assert len(warns) == 1 and warns[0].rule == "large-transient"


@pytest.mark.memory
def test_contract_check_cli_budget_exit_codes(rng):
    """The CLI wiring end to end: a tiny --hbm-budget-gb makes a real
    program exit 3; a generous one exits 0."""
    import contract_check as cc

    args = ["--models", "tensornet", "--programs", "energy[tensornet][1x1]",
            "--passes", "memory_budget"]
    assert cc.main(args + ["--hbm-budget-gb", "0.0005"]) == 3
    assert cc.main(args + ["--hbm-budget-gb", "16"]) == 0


# ---------------------------------------------------------------------------
# estimator vs the XLA oracle
# ---------------------------------------------------------------------------


@pytest.mark.memory
@pytest.mark.tier1
def test_estimator_vs_oracle_fast(rng):
    """Fast band check on two cheap-to-compile real programs."""
    from distmlip_tpu.parallel import make_potential_fn, make_total_energy

    model, params, graph = _pair_graph(rng)
    zero = jnp.zeros((3, 3), jnp.float32)
    for fn, a in ((make_total_energy(model.energy_fn, None),
                   (params, graph, graph.positions, zero)),
                  (make_potential_fn(model.energy_fn, None),
                   (params, graph, graph.positions))):
        jaxpr = jax.make_jaxpr(fn)(*a)
        est = analyze_memory(jaxpr).peak_bytes
        oracle = oracle_peak_bytes(jaxpr)
        assert oracle, "CPU XLA must report memory_analysis"
        ratio = est / oracle
        assert ORACLE_BAND[0] <= ratio <= ORACLE_BAND[1], (
            f"estimate {est} vs oracle {oracle}: {ratio:.2f}x out of band")


@pytest.mark.memory
@pytest.mark.slow
def test_estimator_vs_oracle_all_contract_programs():
    """The acceptance criterion: estimated peak within 2x of XLA's
    memory_analysis totals for EVERY contract-check program (22 programs:
    4 models x {(1,1),(2,1),(2,2)} energy/potential/batched + packed
    batch + DeviceMD stepper). One real CPU compile per program — slow
    lane only."""
    import contract_check as cc

    programs = []
    for name in cc.ALL_MODELS:
        cc._trace_model_programs(name, programs)
    cc._trace_packed_batch(programs)
    cc._trace_device_md(programs)
    assert len(programs) == 22

    out_of_band = []
    no_oracle = []
    for prog in programs:
        est = analyze_memory(prog.jaxpr).peak_bytes
        oracle = oracle_peak_bytes(prog.jaxpr)
        if not oracle:
            no_oracle.append(prog.name)
            continue
        ratio = est / oracle
        if not (ORACLE_BAND[0] <= ratio <= ORACLE_BAND[1]):
            out_of_band.append(f"{prog.name}: {ratio:.2f}x "
                               f"(est {est}, oracle {oracle})")
    assert not no_oracle, f"oracle unavailable for {no_oracle}"
    assert not out_of_band, "estimator out of the 2x band:\n" + \
        "\n".join(out_of_band)


# ---------------------------------------------------------------------------
# memory-aware autobatching
# ---------------------------------------------------------------------------


@pytest.mark.memory
@pytest.mark.tier1
def test_bucket_policy_bytes_model():
    from distmlip_tpu.partition import BucketPolicy

    pol = BucketPolicy()
    assert not pol.bytes_calibrated()
    assert pol.estimate_batch_bytes(100) is None   # uncalibrated: no guess
    pol.calibrate_bytes(128, 10 * 2**20)
    assert pol.bytes_calibrated()
    # exact rung: the calibrated value verbatim
    assert pol.estimate_batch_bytes(100) == 10 * 2**20
    # other rungs: worst coefficient scaled up (monotone in cap)
    big = pol.estimate_batch_bytes(1000)
    assert big > 10 * 2**20
    cap = pol.get("nodes", 1000)
    assert big == int(cap * (10 * 2**20 / 128)) + 1
    # worst-per-rung semantics: smaller recalibration never shrinks it
    pol.calibrate_bytes(128, 1 * 2**20)
    assert pol.estimate_batch_bytes(100) == 10 * 2**20
    pol.calibrate_bytes(128, 20 * 2**20)
    assert pol.estimate_batch_bytes(100) == 20 * 2**20


@pytest.mark.memory
@pytest.mark.tier1
def test_bucket_policy_bytes_model_small_batches_stay_conservative():
    """The resident term (params/consts) does not scale with batch size:
    a single LARGE calibration point must not let small batches estimate
    as nearly-free (the under-admission OOM the budget exists to stop)."""
    from distmlip_tpu.partition import BucketPolicy

    pol = BucketPolicy()
    pol.calibrate_bytes(4096, 8 << 30)         # one big rung, 8 GiB
    # single point: the observed peak is a hard floor below it — a
    # never-measured small batch is not assumed cheaper than anything
    # ever measured
    assert pol.estimate_batch_bytes(100) >= 8 << 30
    # two points: affine fit recovers the resident term, so small rungs
    # estimate resident + k*cap instead of either extreme
    pol2 = BucketPolicy()
    resident, k = 6 << 30, 1 << 20             # 6 GiB resident, 1 MiB/atom
    pol2.calibrate_bytes(1024, resident + k * 1024)
    pol2.calibrate_bytes(4096, resident + k * 4096)
    est = pol2.estimate_batch_bytes(100)       # rung 128
    want = resident + k * 128
    assert abs(est - want) <= want * 0.01
    # and it still refuses to dip below the resident term
    assert est > resident
    # the fit runs through the extreme rungs only: an edge-heavy MIDDLE
    # rung's observed peak is a floor for every larger rung — a bigger
    # batch must never estimate cheaper than a measured smaller one
    pol3 = BucketPolicy()
    pol3.calibrate_bytes(128, 50 * 10**6)
    pol3.calibrate_bytes(384, 150 * 10**6)     # edge-heavy outlier
    pol3.calibrate_bytes(1152, 200 * 10**6)
    est_mid = pol3.estimate_batch_bytes(400)   # uncalibrated rung 640
    assert est_mid >= 150 * 10**6
    # the EXACT-rung path applies the same observed-smaller-rung floor:
    # a lightly-calibrated larger rung never undercuts its edge-heavy
    # smaller sibling
    pol4 = BucketPolicy()
    pol4.calibrate_bytes(128, 50 * 10**6)      # edge-heavy small pack
    pol4.calibrate_bytes(384, 10 * 10**6)      # light larger pack
    assert pol4.estimate_batch_bytes(250) >= 50 * 10**6


@pytest.mark.memory
@pytest.mark.tier1
def test_plan_batch_bytes_budget_never_exceeded(rng):
    """The bytes-budget autobatcher NEVER assembles a batch whose
    estimate exceeds the budget — adversarial random streams."""
    from distmlip_tpu.partition import BucketPolicy
    from distmlip_tpu.serve.scheduler import plan_batch

    pol = BucketPolicy()
    pol.calibrate_bytes(128, 4 * 2**20)       # 32 KiB per capacity atom
    local = np.random.default_rng(7)
    budget = 12 * 2**20
    for _ in range(50):
        sizes = local.integers(8, 520, size=local.integers(1, 30)).tolist()
        plan = plan_batch(sizes, policy=pol, max_batch=16,
                          bytes_budget=budget)
        assert plan.take and plan.take[0] == 0     # head never starved
        assert plan.est_bytes is not None
        if len(plan.take) > 1:
            # the core invariant: a MULTI-request batch is never
            # estimated over budget
            assert plan.est_bytes <= budget, (
                f"sizes={sizes} take={plan.take} est={plan.est_bytes}")
        elif plan.est_bytes > budget:
            # over-budget heads are head-only: flagged (fail) when their
            # rung is measured, unflagged solo probes when extrapolated
            assert plan.take == [0]


@pytest.mark.memory
@pytest.mark.tier1
def test_plan_batch_overbudget_head_flagged():
    from distmlip_tpu.partition import BucketPolicy
    from distmlip_tpu.serve.scheduler import plan_batch

    pol = BucketPolicy()
    pol.calibrate_bytes(128, 4 * 2**20)
    # head of 1000 atoms over a 12 MiB budget on an EXTRAPOLATED
    # estimate: head-only solo probe, NOT flagged (its compile will
    # calibrate the rung; flagging guesses could livelock the lane)
    plan = plan_batch([1000, 16, 16], policy=pol, max_batch=8,
                      bytes_budget=12 * 2**20)
    assert plan.take == [0] and not plan.over_budget
    assert plan.est_bytes > 12 * 2**20
    # same head on its own MEASURED rung: flagged — the engine fails it
    pol.calibrate_bytes(pol.get("nodes", 1000), 40 * 2**20)
    assert pol.has_calibrated_rung(1000)
    plan = plan_batch([1000, 16, 16], policy=pol, max_batch=8,
                      bytes_budget=12 * 2**20)
    assert plan.over_budget and plan.take == [0]
    # same stream, no budget: plain fill, never flagged
    plan = plan_batch([1000, 16, 16], policy=pol, max_batch=8)
    assert not plan.over_budget and len(plan.take) > 1


@pytest.mark.memory
@pytest.mark.tier1
def test_plan_batch_bytes_budget_parity_with_fixed_b(rng):
    """A generous budget reproduces the historical fixed-B fill exactly,
    and no budget at all is byte-identical to the pre-budget planner."""
    from distmlip_tpu.partition import BucketPolicy
    from distmlip_tpu.serve.scheduler import plan_batch

    pol = BucketPolicy()
    pol.calibrate_bytes(128, 4 * 2**20)
    local = np.random.default_rng(11)
    for _ in range(30):
        sizes = local.integers(8, 120, size=local.integers(1, 30)).tolist()
        base = plan_batch(sizes, policy=pol, max_batch=8)
        generous = plan_batch(sizes, policy=pol, max_batch=8,
                              bytes_budget=1 << 40)
        assert base.take == generous.take
        assert base.skipped == generous.skipped
        assert base.total_atoms == generous.total_atoms


@pytest.mark.memory
@pytest.mark.tier1
def test_batched_potential_calibrates_and_reports(rng):
    """A fresh compile calibrates the bytes model and the telemetry
    fields; cache hits reuse the bucket's estimate."""
    from distmlip_tpu.calculators import Atoms, BatchedPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.6)
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart,
                  cell=lattice)
    pot = BatchedPotential(model, params)
    assert pot.hbm_budget_bytes is None       # CPU: no reported limit
    pot.calculate([atoms, atoms.copy()])
    assert pot.last_est_peak_bytes > 0
    assert pot.last_stats["est_peak_bytes"] == pot.last_est_peak_bytes
    assert pot.caps.bytes_calibrated()
    assert pot.estimate_batch_bytes(2 * len(atoms)) > 0
    # warm path (same shapes): the bucket cache still reports the estimate
    first = pot.last_est_peak_bytes
    pot.calculate([atoms, atoms.copy()])
    assert pot.last_est_peak_bytes == first
    # memory_model=False: no calibration trace at all
    pot2 = BatchedPotential(model, params, memory_model=False)
    pot2.calculate([atoms])
    assert pot2.last_est_peak_bytes == 0
    assert not pot2.caps.bytes_calibrated()


@pytest.mark.memory
@pytest.mark.serve
@pytest.mark.tier1
def test_serve_engine_overbudget_admission(rng):
    """A structure whose SOLO estimate exceeds the batched lane's HBM
    budget is rejected at submit (both admission modes); a generous
    budget admits and serves it."""
    from distmlip_tpu.calculators import Atoms, BatchedPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from distmlip_tpu.serve import ServeEngine, ServeRejected
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.6)
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart,
                  cell=lattice)
    pot = BatchedPotential(model, params)
    pot.calculate([atoms])                     # calibrate the bytes model
    est = pot.estimate_batch_bytes(len(atoms))
    assert est and est > 0

    # budget below the solo estimate: reject in BOTH admission modes
    for admission in ("reject", "block"):
        pot.hbm_budget_bytes = est // 2
        eng = ServeEngine(pot, admission=admission, start=False)
        with pytest.raises(ServeRejected, match="HBM budget"):
            eng.submit(atoms)
        assert eng.stats.rejected == 1
        eng.close()

    # generous budget: admitted and served
    pot.hbm_budget_bytes = est * 4
    with ServeEngine(pot) as eng:
        res = eng.submit(atoms).result(timeout=60)
        assert np.isfinite(res["energy"])
    # oversized structures are exempt (they ride the fallback lane — and
    # with none configured they fail with the routing error, not a
    # ServeRejected admission error)
    pot.hbm_budget_bytes = est // 2
    eng = ServeEngine(pot, max_batch_atoms=4, start=True)
    fut = eng.submit(atoms)
    with pytest.raises(ValueError, match="max_batch_atoms"):
        fut.result(timeout=60)
    eng.close()


@pytest.mark.memory
@pytest.mark.serve
@pytest.mark.tier1
def test_serve_engine_overbudget_head_fails_not_dispatches(rng):
    """The pre-calibration admission race: a request admitted before the
    budget/bytes model existed and later becoming an over-budget queue
    head is FAILED by the dispatcher, never run as an over-budget
    batch."""
    from distmlip_tpu.calculators import Atoms, BatchedPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from distmlip_tpu.serve import ServeEngine, ServeRejected
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.6)
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart,
                  cell=lattice)
    pot = BatchedPotential(model, params)
    pot.calculate([atoms])                     # calibrate the bytes model
    est = pot.estimate_batch_bytes(len(atoms))
    assert pot.hbm_budget_bytes is None
    eng = ServeEngine(pot, start=False)
    fut = eng.submit(atoms)                    # admitted: no budget yet
    pot.hbm_budget_bytes = est // 2            # budget appears afterwards
    eng.start()
    with pytest.raises(ServeRejected, match="HBM budget"):
        fut.result(timeout=60)
    # accounting: the request WAS accepted, so it is a failure, not a
    # (second) submit-time reject — rejected+failed must not double-count
    assert eng.stats.failed == 1
    assert eng.stats.rejected == 0
    eng.close()


# ---------------------------------------------------------------------------
# telemetry report: drift flag only with measured stats
# ---------------------------------------------------------------------------


@pytest.mark.memory
@pytest.mark.tier1
def test_report_hbm_drift_needs_measured_stats():
    from distmlip_tpu.telemetry import StepRecord
    from distmlip_tpu.telemetry.report import aggregate

    def rec(step, est, mem):
        return StepRecord(step=step, kind="batched_calculate",
                          timings={"total_s": 0.1},
                          est_peak_bytes=est, device_memory=mem)

    # CPU-style records: estimates but NO measured stats -> never flagged
    rep = aggregate([rec(i, 50 * 2**20, {}) for i in range(4)])
    assert not any(a.kind == "hbm_estimator_drift" for a in rep.anomalies)
    assert rep.counters.get("max_est_peak_bytes") == 50 * 2**20
    assert "hbm_estimator_ratio" not in rep.counters

    # measured stats present and wildly off the estimate -> flagged
    mem = {"dev0_bytes_in_use": 2**20, "dev0_peak_bytes_in_use": 2**20,
           "dev0_bytes_limit": 2**30}
    rep = aggregate([rec(i, 50 * 2**20, dict(mem)) for i in range(4)])
    assert any(a.kind == "hbm_estimator_drift" for a in rep.anomalies)
    assert rep.counters["hbm_estimator_ratio"] == pytest.approx(50.0)
    assert "hbm:" in rep.render()

    # measured stats in band -> ratio reported, no anomaly
    mem_ok = {"dev0_bytes_in_use": 40 * 2**20,
              "dev0_peak_bytes_in_use": 60 * 2**20,
              "dev0_bytes_limit": 2**30}
    rep = aggregate([rec(i, 50 * 2**20, dict(mem_ok)) for i in range(4)])
    assert not any(a.kind == "hbm_estimator_drift" for a in rep.anomalies)
    assert rep.counters["hbm_estimator_ratio"] == pytest.approx(50 / 60)
    assert rep.counters["max_hbm_used_frac"] == pytest.approx(40 / 1024)

    # LOW ratios never flag: peak_bytes_in_use is a process-lifetime
    # high-water mark, so on a mixed run a tiny batched program measured
    # against an earlier big phase's mark proves nothing
    mem_big = {"dev0_bytes_in_use": 2**20,
               "dev0_peak_bytes_in_use": 100 * 2**20,
               "dev0_bytes_limit": 2**30}
    rep = aggregate([rec(i, 1 * 2**20, dict(mem_big)) for i in range(4)])
    assert not any(a.kind == "hbm_estimator_drift" for a in rep.anomalies)
    assert rep.counters["hbm_estimator_ratio"] == pytest.approx(0.01)


@pytest.mark.memory
@pytest.mark.tier1
def test_utils_memory_shared_implementation():
    """The dedup satellite: calculator + report + planner all consume the
    ONE utils/memory implementation."""
    import distmlip_tpu.calculators.calculator as calc_mod
    from distmlip_tpu.utils.memory import (device_bytes_limit,
                                           device_memory_stats,
                                           hbm_usage_frac,
                                           measured_peak_bytes)

    assert calc_mod._hbm_usage_frac is hbm_usage_frac
    assert calc_mod._device_memory_stats is device_memory_stats
    stats = {"dev0_bytes_in_use": 80, "dev0_bytes_limit": 100,
             "dev1_bytes_in_use": 10, "dev1_bytes_limit": 50,
             "dev1_peak_bytes_in_use": 33}
    assert hbm_usage_frac(stats) == pytest.approx(0.8)
    assert device_bytes_limit(stats) == 50
    assert measured_peak_bytes(stats) == 33
    assert hbm_usage_frac({}) is None
    assert device_bytes_limit({}) is None
    assert measured_peak_bytes({}) is None
    # CPU: live lookup degrades to "nothing reported", never raises
    assert device_memory_stats() == {}


@pytest.mark.memory
@pytest.mark.tier1
def test_predictive_prefetch_guard(rng, monkeypatch):
    """The HBM prefetch guard is predictive where a bytes_limit exists:
    high occupancy with a TINY estimated build no longer vetoes; a big
    estimated build does."""
    import distmlip_tpu.calculators.calculator as calc_mod
    import distmlip_tpu.utils.memory as um
    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(3, 2, 2), a=3.6)
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart,
                  cell=lattice)

    def run(limit):
        # device_rebuild=False: the on-device refresh path would skip
        # speculative host builds entirely (by design), and this test is
        # about the HBM guard on the host-prefetch path
        pot = DistPotential(model, params, num_partitions=1, skin=0.6,
                            prefetch_frac=0.0, device_rebuild=False)
        pot.calculate(atoms)
        moved = atoms.copy()
        moved.positions = moved.positions + 0.02
        pot.calculate(moved)       # warm path; prefetch decision happens
        return pot

    # occupancy 0.6 > 1/3 would historically always veto
    monkeypatch.setattr(calc_mod, "_hbm_usage_frac", lambda s=None: 0.6)
    # predictive: huge limit -> the graph adds ~0 frac -> NO veto
    monkeypatch.setattr(um, "device_bytes_limit", lambda s=None: 1 << 50)
    pot = run(1 << 50)
    assert pot.prefetch_skipped_hbm == 0
    assert pot._prefetch is not None
    pot.close()
    # predictive: tiny limit -> the build residency blows the ceiling
    monkeypatch.setattr(um, "device_bytes_limit", lambda s=None: 1024)
    pot = run(1024)
    assert pot.prefetch_skipped_hbm >= 1
    assert pot._prefetch is None
    pot.close()


@pytest.mark.memory
@pytest.mark.tier1
def test_memory_audit_cli_smoke(rng):
    """memory_audit CLI: table + budget gate exit codes (pair-free fast
    path rides the tensornet 1x1 energy program)."""
    import memory_audit as ma

    args = ["--models", "tensornet", "--programs",
            "energy[tensornet][1x1]"]
    assert ma.main(args) == 0
    assert ma.main(args + ["--budget-gb", "0.0005"]) == 3
    assert ma.main(["--budget-gb", "-1"]) == 2
    assert ma.main(["--models", "nope"]) == 2
