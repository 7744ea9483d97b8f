#!/usr/bin/env python
"""Load-test the ServeEngine: closed+open-loop traffic, latency percentiles.

Drives a stream of mixed-size structures through an in-process
``ServeEngine`` and prints ONE JSON line per mode with
p50/p95/p99 latency, structures/sec, batch/bucket occupancy and engine
counters — so serving throughput joins the perf trajectory. With
``--jsonl`` the engine's per-batch StepRecords (and the batched
potential's records) land in a telemetry JSONL renderable by
``tools/telemetry_report.py`` (look for the "serving" section).

``--check`` turns the run into an acceptance gate (used by tests and the
verify flow): requests must complete, the dominant bucket's mean
batch-slot occupancy must reach ``--occupancy-floor`` (default 0.95),
compile count must stay within the BucketPolicy ladder bound, the
scheduler thread must survive (zero isolated faults are NOT required —
poison injection forces some — but the thread must still be serving), and
``drain()`` must leave the queue empty with every Future resolved.
Exit codes: 0 ok, 3 check failed, 2 usage.

``--contracts`` additionally traces the engine's batched potential (the
exact program the scheduler dispatches) and runs every registered
``distmlip_tpu.analysis`` contract pass over the jaxpr — including
``memory_budget``: the serving program's statically estimated peak must
fit the HBM budget (``--hbm-budget-gb``, default: the backend-reported
limit; no gate when neither exists). Combined with ``--check``, an
error-severity finding fails the gate and the summary carries
``est_peak_bytes`` for the estimator-drift trajectory.

``--fleet N`` switches to FLEET mode: an open-loop burst through a
``FleetRouter`` over N in-process ``ServeEngine`` replicas (weighted
tenants, content-addressed result cache, failover). ``--chaos
kill-replica`` kills replica r0 mid-burst; ``--check`` then gates the
chaos contract (every submitted Future resolves, zero stray failures,
p99 under ``--p99-bound-s``), the compile bound (BucketPolicy ladder x
replicas), and the cache contract (duplicate-phase hit rate >=
``--cache-hit-floor`` with ZERO replica dispatches). Exit 3 on
regression — this is the ROADMAP's fleet acceptance gate.

``--active`` (fleet mode only) attaches an ``ActiveLoop``
(distmlip_tpu.active): traffic routes through the loop, a sampled
fraction escalates to the vmapped ensemble evaluator, high-variance
structures land in the replay buffer, and after the cache phase a
SECOND burst runs with a mid-burst zero-recompile HOT-SWAP of perturbed
weights into every live replica. ``--check`` then additionally gates
the swap contract: every swap-burst Future resolves with zero failures,
per-replica compile counts are UNCHANGED across the swap burst (the
pytree swap reuses every executable), the router's cache model-id
rolled forward, and escalations were actually evaluated.

Runs on the backend jax finds; pass ``JAX_PLATFORMS=cpu`` from outside
for the CPU lane (the tests do). Smoke (verify flow):
``JAX_PLATFORMS=cpu python tools/load_test.py --requests 12 --check``
(~seconds with the default pair model) and ``JAX_PLATFORMS=cpu python
tools/load_test.py --fleet 2 --chaos kill-replica --requests 48 --check``.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def make_pool(rng, n_structures: int, species: int = 14):
    """Mixed-size perturbed fcc supercells (16..128 atoms)."""
    from distmlip_tpu import geometry
    from distmlip_tpu.calculators import Atoms

    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    reps_pool = [(1, 1, 1), (2, 1, 1), (2, 2, 1), (2, 2, 2), (3, 1, 1)]
    pool = []
    for i in range(n_structures):
        reps = reps_pool[int(rng.integers(len(reps_pool)))]
        a = float(rng.uniform(3.4, 3.8))
        frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
        cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
            0, 0.05, (len(frac), 3))
        pool.append(Atoms(numbers=np.full(len(cart), species),
                          positions=cart, cell=lattice))
    return pool


def setup_obs(args):
    """Enable the observability hub (+ optional metrics endpoint) when
    the run asks for it. ``--obs auto`` lights up with ``--check`` /
    ``--trace-out`` / ``--metrics-port`` so the acceptance gates always
    measure the instrumented configuration; ``--obs off`` forces the
    uninstrumented baseline (the overhead A/B lever)."""
    want = (args.obs == "on"
            or (args.obs == "auto"
                and (args.check or args.trace_out
                     or args.metrics_port is not None)))
    if not want:
        return None, None
    from distmlip_tpu.obs import MetricsServer, Observability

    hub = Observability.enable()
    server = (MetricsServer(hub.metrics, port=args.metrics_port)
              if args.metrics_port is not None else None)
    return hub, server


def scrape_metrics(server, expected: dict) -> tuple[bool, dict]:
    """One GET /metrics; compare the scraped sample lines against the
    loadgen's own totals (the --metrics-port smoke)."""
    import urllib.request

    from distmlip_tpu.obs import parse_exposition

    body = urllib.request.urlopen(server.url, timeout=10).read().decode()
    vals = parse_exposition(body)
    scraped = {k: vals.get(k, 0.0) for k in expected}
    ok = all(scraped[k] == v for k, v in expected.items())
    return ok, scraped


def trace_summary_block(hub, n_submitted: int, trace_out=None) -> dict:
    """Span-tree conservation + critical-path coverage over the run."""
    from distmlip_tpu.obs.export import (critical_path_summary,
                                         request_trace_summary)

    spans = hub.tracer.spans()
    tsum = request_trace_summary(spans)
    csum = critical_path_summary(spans)
    out = {
        "submitted": n_submitted,
        "request_traces": tsum["requests"],
        "complete": tsum["complete"],
        "terminals": tsum["terminals"],
        "terminal_violations": tsum["terminal_violation_count"],
        "spans_dropped": hub.tracer.spans_dropped,
        "coverage_p50": round(csum.get("coverage_p50", 0.0), 3),
        "queue_dominant": bool(csum.get("queue_dominant", False)),
    }
    if trace_out:
        hub.tracer.write(trace_out)
        out["path"] = trace_out
    return out


def trace_checks(trace: dict) -> dict:
    """The trace_complete + critical-path acceptance gates: every
    submitted request left a CLOSED span tree with exactly one
    future.resolve terminal (span-count conservation across the
    cache-hit/coalesce/failover paths), and the per-request span
    coverage explains >= 90% of the measured request latency."""
    return {
        "trace_complete": (
            trace["request_traces"] == trace["submitted"]
            and trace["complete"] == trace["submitted"]
            and trace["terminal_violations"] == 0
            and trace["spans_dropped"] == 0),
        "trace_critical_path": trace["coverage_p50"] >= 0.9,
    }


def build_model(name: str):
    import jax

    if name == "pair":
        from distmlip_tpu.models import PairConfig, PairPotential

        model = PairPotential(PairConfig(cutoff=4.0))
        return model, model.init()
    if name == "tensornet":
        from distmlip_tpu.models import TensorNet, TensorNetConfig

        model = TensorNet(TensorNetConfig(num_species=95, cutoff=4.5))
        return model, model.init(jax.random.PRNGKey(0))
    raise SystemExit(f"unknown --model {name!r} (pair | tensornet)")


def run(args) -> int:
    import time

    from distmlip_tpu.calculators import BatchedPotential
    from distmlip_tpu.partition import BucketPolicy
    from distmlip_tpu.serve import (ServeEngine, run_closed_loop,
                                    run_open_loop)
    from distmlip_tpu.telemetry import JsonlSink, Telemetry

    rng = np.random.default_rng(args.seed)
    model, params = build_model(args.model)
    pool = make_pool(rng, max(8, args.requests // 4))
    caps = BucketPolicy()
    hub, metrics_server = setup_obs(args)
    telemetry = None
    if args.jsonl:
        telemetry = Telemetry([JsonlSink(args.jsonl,
                                         max_bytes=args.jsonl_max_bytes)])
    budget_bytes = (int(args.hbm_budget_gb * 2**30)
                    if args.hbm_budget_gb else None)
    pot = BatchedPotential(model, params, caps=caps, skin=args.skin,
                           hbm_budget_bytes=budget_bytes)
    engine = ServeEngine(
        pot, max_batch=args.max_batch, max_wait_s=args.max_wait,
        max_queue=args.max_queue, admission=args.admission,
        telemetry=telemetry)

    # poison injection: NaN-position structures must fail ONLY their own
    # Futures (error isolation); submitted mid-stream so they co-batch
    poison_failures = 0
    if args.poison:
        from distmlip_tpu.calculators import Atoms

        poison_futs = []
        for _ in range(args.poison):
            bad = pool[0].copy()
            bad.positions = bad.positions.copy()
            bad.positions[0, 0] = np.nan
            poison_futs.append(engine.submit(bad))

    modes = (("closed", "open") if args.mode == "both" else (args.mode,))
    reports = {}
    rc = 0
    for mode in modes:
        if mode == "closed":
            rep = run_closed_loop(engine, pool, args.requests,
                                  concurrency=args.concurrency)
        else:
            rep = run_open_loop(engine, pool, args.requests,
                                rate_hz=args.rate, rng=rng)
        reports[mode] = rep
        line = {"metric": f"serve_{mode}_loop", **rep.summary(),
                "max_batch": args.max_batch, "model": args.model,
                "compile_count": engine.compile_count}
        dom = engine.stats.dominant_bucket()
        if dom:
            line["dominant_bucket"] = dom[0]
            line["dominant_bucket_occupancy"] = round(dom[1], 3)
        print(json.dumps(line), flush=True)

    if args.poison:
        for f in poison_futs:
            try:
                f.result(timeout=60)
            except Exception:  # noqa: BLE001 - expected: isolated failure
                poison_failures += 1

    drained = engine.drain(timeout=120)
    depth_after_drain = engine.queue_depth
    stats = engine.stats.snapshot()
    t0 = time.perf_counter()
    engine.close()
    close_s = time.perf_counter() - t0

    scraped_ok = scraped = None
    if metrics_server is not None:
        scraped_ok, scraped = scrape_metrics(metrics_server, {
            "distmlip_serve_submitted_total": float(stats["submitted"]),
            "distmlip_serve_completed_total": float(stats["completed"]),
        })
        metrics_server.close()

    summary = {
        "metric": "serve_load_test",
        "requests": sum(r.n_requests for r in reports.values()),
        "ok": sum(r.n_ok for r in reports.values()),
        "failed": sum(r.n_failed for r in reports.values()),
        "rejected": sum(r.n_rejected for r in reports.values()),
        "poison_injected": args.poison,
        "poison_failed": poison_failures,
        "compile_count": engine.compile_count,
        "scheduler_errors": stats["scheduler_errors"],
        "drained": bool(drained),
        "queue_depth_after_drain": depth_after_drain,
        "close_s": round(close_s, 3),
    }
    dom = engine.stats.dominant_bucket()
    if dom:
        summary["dominant_bucket"] = dom[0]
        summary["dominant_bucket_occupancy"] = round(dom[1], 3)
    if telemetry is not None:
        telemetry.close()
        summary["jsonl"] = args.jsonl
    if hub is not None:
        summary["trace"] = trace_summary_block(
            hub, stats["submitted"], trace_out=args.trace_out)
    if scraped is not None:
        summary["metrics_scrape"] = scraped

    contract_errors = None
    est_peak = None
    if args.contracts:
        # static contract audit of the SERVING program: trace the same
        # batched potential the engine dispatches through over a
        # representative packed pool batch and run every registered
        # analysis pass (distmlip_tpu.analysis) — the scheduler must never
        # ship a program that breaks the collective/host-sync/dtype/
        # scatter-hint/memory-budget contracts
        import jax

        from distmlip_tpu.analysis import Program, error_count, run_passes

        if pot._cache is not None:
            # the exact packed graph the engine last dispatched through
            sgraph = pot._cache[0]
        else:
            sgraph = pot._build(pool[:min(len(pool), args.max_batch)])[0]
        jaxpr = jax.make_jaxpr(pot._potential)(
            params, sgraph, sgraph.positions)
        cfg = {"max_total_collectives": 0}
        if budget_bytes is not None:
            cfg["bytes_limit"] = budget_bytes
        findings = run_passes(Program(
            name="serving_program", jaxpr=jaxpr,
            tags=frozenset({"grad"}), config=cfg))
        contract_errors = error_count(findings)
        # the memory_budget pass cached its plan on the config — one walk
        plan = cfg.get("_memory_plan")
        est_peak = plan.peak_bytes if plan is not None else 0
        summary["contract_errors"] = contract_errors
        summary["contract_findings"] = [
            f.render() for f in findings if not f.suppressed][:20]
        summary["est_peak_bytes"] = est_peak

    if args.check:
        # BucketPolicy compile bound: node/edge rungs over the pool's size
        # spread, times the few batch-slot powers of two in play
        n_atoms = [len(a) for a in pool]
        bound = caps.ladder_bound(min(n_atoms),
                                  sum(sorted(n_atoms)[-args.max_batch:]),
                                  args.max_batch)
        checks = {
            # every request completed and the scheduler thread served the
            # whole run (a dead thread would strand Futures/drain forever)
            "all_ok": summary["ok"] == summary["requests"],
            "no_stray_failures": summary["failed"] == 0,
            "poison_isolated": poison_failures == args.poison,
            "occupancy": (dom is not None
                          and dom[1] >= args.occupancy_floor),
            "compile_bound": engine.compile_count <= bound,
            "drained_clean": bool(drained) and depth_after_drain == 0,
        }
        if contract_errors is not None:
            # contracts include memory_budget: the serving program's
            # estimated peak fits the configured/reported HBM budget
            # (no budget known -> the pass only reports, never errors)
            checks["contracts"] = contract_errors == 0
            checks["memory_planned"] = bool(est_peak and est_peak > 0)
        if hub is not None:
            checks.update(trace_checks(summary["trace"]))
        if scraped_ok is not None:
            checks["metrics_scrape"] = scraped_ok
        summary["checks"] = checks
        summary["compile_bound"] = bound
        if not all(checks.values()):
            summary["check"] = "FAIL"
            print(json.dumps(summary), flush=True)
            return 3
        summary["check"] = "ok"
    print(json.dumps(summary), flush=True)
    return rc


def run_fleet(args) -> int:
    """Fleet mode: open-loop burst through a FleetRouter over N in-process
    replicas, optional replica-kill chaos mid-burst, duplicate phase for
    the result-cache gate.

    Phases: (1) submit ``requests // 2`` UNIQUE structures as a burst
    (two tenants, weighted 4:1); with ``--chaos kill-replica``, replica
    r0 is killed after half the burst is in; (2) harvest — every Future
    must resolve; (3) re-submit the same structures (duplicates) — these
    must come back from the content-addressed cache without touching a
    replica. ``--check`` gates: all futures resolved with zero stray
    failures, p99 under ``--p99-bound-s`` (failover included), total
    compile count within the BucketPolicy ladder bound x replicas, and
    duplicate-phase cache hit rate >= ``--cache-hit-floor`` with ZERO new
    replica dispatches. Exit 3 on any regression."""
    import time

    from distmlip_tpu.calculators import BatchedPotential
    from distmlip_tpu.fleet import FleetRouter, ResultCache, TenantConfig
    from distmlip_tpu.partition import BucketPolicy
    from distmlip_tpu.serve import ServeEngine
    from distmlip_tpu.telemetry import JsonlSink, Telemetry

    rng = np.random.default_rng(args.seed)
    model, params = build_model(args.model)
    hub, metrics_server = setup_obs(args)
    telemetry = None
    if args.jsonl:
        telemetry = Telemetry([JsonlSink(args.jsonl,
                                         max_bytes=args.jsonl_max_bytes)])
    policies = [BucketPolicy() for _ in range(args.fleet)]
    # compile telemetry scoped to THIS run: fleet mode doubles as the
    # fresh-vs-aot end-to-end check (obs/profiling.py)
    from distmlip_tpu.obs import profiling as _profiling

    _profiling.reset_compile_log()
    potentials = [
        BatchedPotential(model, params, caps=policies[i], skin=args.skin)
        for i in range(args.fleet)]
    aot_dir = None
    if args.aot == "shared" and args.fleet >= 2:
        import tempfile

        from distmlip_tpu.fleet import install_aot_cache

        aot_dir = tempfile.mkdtemp(prefix="distmlip_aot_")
        for pot in potentials:
            # a dir string -> per-replica cache instances sharing the
            # directory (per-replica rehydrate/export counters)
            install_aot_cache(pot, aot_dir)
    engines = [
        ServeEngine(
            potentials[i],
            max_batch=args.max_batch, max_wait_s=args.max_wait,
            max_queue=args.max_queue, admission="reject",
            telemetry=telemetry)
        for i in range(args.fleet)]
    router = FleetRouter(
        engines,
        result_cache=ResultCache(max_bytes=args.cache_bytes),
        model_id=args.model,
        tenants={"interactive": TenantConfig(weight=4.0),
                 "screening": TenantConfig(weight=1.0)},
        telemetry=telemetry)

    # --active: attach the ActiveLoop — traffic routes through it, a
    # sampled fraction escalates to the vmapped ensemble evaluator
    loop = None
    if args.active:
        import jax

        from distmlip_tpu.active import (ActiveLoop, EnsembleBatchedPotential,
                                         EscalationPolicy, FineTuneTrigger,
                                         ReplayBuffer, TriggerPolicy)

        key = jax.random.PRNGKey(1)
        member = jax.tree.map(
            lambda x: x + 0.05 * jax.random.normal(
                jax.random.fold_in(key, 1), np.shape(x),
                np.asarray(x).dtype)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
            params)
        ensemble = EnsembleBatchedPotential(model, [params, member],
                                            skin=args.skin)
        loop = ActiveLoop(
            router, ensemble, ReplayBuffer(capacity=256),
            policy=EscalationPolicy(sample_rate=0.25),
            # the smoke swaps explicitly mid-burst; keep the trigger out
            trigger=FineTuneTrigger(TriggerPolicy(min_buffer=1 << 30)),
            telemetry=telemetry, seed=args.seed)

    # per-tenant submission ledger (the --metrics-port smoke compares the
    # scraped tenant counters against these) + total submissions (the
    # trace_complete gate compares span trees against this)
    tenant_totals: dict = {}
    n_submitted = 0

    def count_submit(tenant="default"):
        nonlocal n_submitted
        n_submitted += 1
        tenant_totals[tenant] = tenant_totals.get(tenant, 0) + 1

    def fleet_submit(atoms, **kw):
        count_submit(kw.get("tenant", "default"))
        return loop.submit(atoms, **kw) if loop is not None \
            else router.submit(atoms, **kw)

    # phase 1: unique burst (each submission its own perturbed structure)
    base_pool = make_pool(rng, max(8, args.requests // 8))
    n_uniq = max(args.requests // 2, 2)
    n_dup = max(args.requests - n_uniq, 1)
    uniques = []
    for i in range(n_uniq):
        a = base_pool[i % len(base_pool)].copy()
        a.positions = a.positions + rng.normal(0, 0.02, a.positions.shape)
        uniques.append(a)
    # shared-AOT pre-warm: the FIRST replica compiles one bucket FRESH
    # (and exports it to the shared dir); every later replica then
    # REHYDRATES the same bucket — so a --fleet >= 2 run always observes
    # both compile kinds end-to-end. Serialized per replica (drain
    # between) so the export lands before the next replica looks it up.
    # Direct engine submissions count toward the span-conservation gate
    # exactly like the active warm phase below.
    if aot_dir is not None:
        for rep in router.replicas.values():
            if not rep.alive:
                continue
            a = base_pool[0].copy()
            a.positions = a.positions + rng.normal(0, 0.01,
                                                   a.positions.shape)
            n_submitted += 1
            f = rep.engine.submit(a)
            rep.engine.drain(timeout=120)
            f.result(timeout=300)

    futs, t_sub = [], []
    killed = reclaimed = 0
    t0 = time.perf_counter()
    for i, a in enumerate(uniques):
        if args.chaos == "kill-replica" and i == n_uniq // 2 and not killed:
            reclaimed = router.kill_replica("r0")
            killed = 1
        tenant = "interactive" if i % 4 == 0 else "screening"
        t_sub.append(time.perf_counter())
        futs.append(fleet_submit(a, tenant=tenant))
    ok = failed = 0
    lats = []
    for f, ts in zip(futs, t_sub):
        try:
            f.result(timeout=300)
        except Exception:  # noqa: BLE001 - explicit per-request error
            failed += 1
            continue
        ok += 1
        lats.append(time.perf_counter() - ts)
    router.drain(timeout=120)
    dispatched_before_dup = sum(
        r["dispatched_total"]
        for r in router.snapshot()["replicas"].values())
    hits_before_dup = router.cache.hits

    # phase 3: duplicate traffic — must be served by the cache alone
    dup_futs = []
    dup_ok = 0
    for i in range(n_dup):
        count_submit()
        dup_futs.append(router.submit(uniques[i % n_uniq]))
    for f in dup_futs:
        try:
            f.result(timeout=300)
            dup_ok += 1
        except Exception:  # noqa: BLE001
            failed += 1
    snap_dup = router.snapshot()
    dispatched_after_dup = sum(
        r["dispatched_total"] for r in snap_dup["replicas"].values())
    dup_hits = router.cache.hits - hits_before_dup
    hit_rate = dup_hits / max(n_dup, 1)

    # --active phase: a second burst over the (already warm) buckets
    # with a mid-burst hot-swap of perturbed weights — the zero-lost /
    # zero-recompile gate. Distinct property sets keep the pre-swap half
    # off the result cache; the swap's model-id roll keys the post-swap
    # half fresh.
    # wall_s measures the load-test traffic (burst + cache phases) — the
    # active phase's warm-up/swap bursts are timed separately below so
    # --active runs stay comparable with plain fleet runs
    wall_s = time.perf_counter() - t0
    swap_futs = []
    swap_ok = 0
    swap_report = None
    swap_compile_delta = {}
    swap_phase_s = 0.0
    if loop is not None:
        t_active = time.perf_counter()
        loop.pump()                      # evaluate phase-1 escalations
        # The swap burst uses a UNIFORM-size pool (jittered copies of one
        # base cell): every batch a replica can assemble from it is
        # (rung(B * n_atoms), B) for some B <= max_batch — a bucket set
        # small enough to warm EXHAUSTIVELY. Warm it per alive replica
        # with direct engine bursts at EVERY batch size 1..max_batch
        # (drain between bursts pins the assembled B; each B has its own
        # total-atom rung), so the delta below measures only what the
        # swap itself would cost: zero, by the pure-pytree-swap
        # contract, however the router splits the burst.
        swap_pool = []
        for i in range(n_uniq):
            a = base_pool[0].copy()
            a.positions = a.positions + rng.normal(0, 0.01,
                                                   a.positions.shape)
            swap_pool.append(a)
        b_sizes = list(range(1, args.max_batch + 1))
        for rep in router.replicas.values():
            if not rep.alive:
                continue
            for b in b_sizes:
                # direct engine submissions: each still opens its own
                # (engine-rooted) request trace, so they count toward
                # the span-conservation gate like everything else
                warm = []
                for a in swap_pool[:b]:
                    n_submitted += 1
                    warm.append(rep.engine.submit(a))
                rep.engine.drain(timeout=120)
                for f in warm:
                    f.result(timeout=300)
        compile_at_swap = {
            rid: r["compile_count"]
            for rid, r in router.snapshot()["replicas"].items()}
        import jax

        key2 = jax.random.PRNGKey(2)
        new_params = jax.tree.map(
            lambda x: x + 1e-3 * jax.random.normal(
                jax.random.fold_in(key2, 1), np.shape(x),
                np.asarray(x).dtype)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x,
            params)
        for i, a in enumerate(swap_pool):
            if i == max(n_uniq // 4, 1) and swap_report is None:
                # mid-burst: earlier submissions are queued/in flight
                swap_report = loop.swap_now(new_params)
            count_submit()
            swap_futs.append(loop.submit(a))
        if swap_report is None:          # tiny bursts: swap after the loop
            swap_report = loop.swap_now(new_params)
        for f in swap_futs:
            try:
                f.result(timeout=300)
                swap_ok += 1
            except Exception:  # noqa: BLE001
                failed += 1
        router.drain(timeout=120)
        loop.pump()
        swap_compile_delta = {
            rid: r["compile_count"] - compile_at_swap.get(rid, 0)
            for rid, r in router.snapshot()["replicas"].items()}
        swap_phase_s = time.perf_counter() - t_active

    snap = router.snapshot()
    compile_total = sum(r["compile_count"]
                        for r in snap["replicas"].values())
    lats.sort()
    p99 = lats[min(len(lats) - 1, int(0.99 * (len(lats) - 1) + 0.5))] \
        if lats else 0.0
    router.close()
    if telemetry is not None:
        telemetry.close()
    scraped_ok = scraped = None
    if metrics_server is not None:
        expected = {
            f'distmlip_fleet_requests_total{{tenant="{t}"}}': float(n)
            for t, n in sorted(tenant_totals.items())}
        scraped_ok, scraped = scrape_metrics(metrics_server, expected)
        metrics_server.close()

    # compile-telemetry split: the in-process compile log and the metrics
    # registry are two independent observers of the same events — the
    # --check gate below requires them to agree
    kind_counts = _profiling.compile_counts()
    metric_kind_totals: dict = {}
    if hub is not None:
        from distmlip_tpu.obs import parse_exposition

        for line, v in parse_exposition(hub.metrics.render()).items():
            if not line.startswith("distmlip_compiles_total{"):
                continue
            for part in line[line.index("{") + 1:
                             line.index("}")].split(","):
                k, _, val = part.partition("=")
                if k.strip() == "kind":
                    kind = val.strip().strip('"')
                    metric_kind_totals[kind] = (
                        metric_kind_totals.get(kind, 0) + int(v))

    n_atoms = [len(a) for a in uniques]
    bound = args.fleet * policies[0].ladder_bound(
        min(n_atoms), sum(sorted(n_atoms)[-args.max_batch:]),
        args.max_batch)
    summary = {
        "metric": "fleet_load_test",
        "fleet": args.fleet,
        "chaos": args.chaos,
        "requests": n_uniq + n_dup,
        "unique": n_uniq,
        "duplicates": n_dup,
        "ok": ok + dup_ok,
        "failed": failed,
        "reclaimed_on_kill": reclaimed,
        "wall_s": round(wall_s, 3),
        "latency_p99_ms": round(1e3 * p99, 2),
        "compile_count": compile_total,
        "compile_bound": bound,
        "cache_hit_rate": round(hit_rate, 3),
        "dup_dispatches": dispatched_after_dup - dispatched_before_dup,
        "stats": snap["stats"],
        "tenants": snap["tenants"],
        "replicas": snap["replicas"],
        "cache": snap["cache"],
        "compile_events": {
            "kinds": kind_counts,
            "metrics_kinds": metric_kind_totals,
            "aot": ({f"r{i}": pot.aot_cache.stats()
                     for i, pot in enumerate(potentials)}
                    if aot_dir is not None else None),
        },
    }
    if loop is not None:
        summary["active"] = {
            **loop.snapshot(),
            "swap_burst_requests": len(swap_futs),
            "swap_burst_ok": swap_ok,
            "swap_compile_delta": swap_compile_delta,
            "swap_phase_s": round(swap_phase_s, 3),
            "model_id": router.model_id,
        }
    if args.jsonl:
        summary["jsonl"] = args.jsonl
    if hub is not None:
        summary["trace"] = trace_summary_block(
            hub, n_submitted, trace_out=args.trace_out)
    if scraped is not None:
        summary["metrics_scrape"] = scraped
    rc = 0
    if args.check:
        checks = {
            # the chaos contract: every submitted Future resolved, with a
            # result — a killed replica may cost latency, never requests
            "all_resolved": all(f.done() for f in futs + dup_futs),
            "zero_lost": ok + dup_ok == n_uniq + n_dup and failed == 0,
            "p99_bounded": p99 <= args.p99_bound_s,
            "compile_bound": compile_total <= bound,
            # the cache contract: duplicate traffic is served from the
            # content-addressed cache without ANY replica dispatch
            "cache_hit_floor": hit_rate >= args.cache_hit_floor,
            "no_dispatch_on_hits":
                dispatched_after_dup == dispatched_before_dup,
        }
        if args.chaos == "kill-replica":
            checks["failover_observed"] = snap["stats"]["failovers"] >= 1
        if aot_dir is not None:
            # the compile-telemetry contract: a shared-cache fleet run
            # pays BOTH kinds — an executable built in this process on the
            # first replica (compiled, or loaded from jax's persistent
            # cache, which this tool turns on) and an AOT rehydrate on
            # every later one
            checks["compile_kinds_observed"] = (
                kind_counts.get("fresh", 0) + kind_counts.get("cache", 0) > 0
                and kind_counts.get("aot", 0) > 0)
        if hub is not None:
            # the log and the registry saw the same events
            checks["compile_metrics_consistent"] = (
                metric_kind_totals == dict(kind_counts))
        if loop is not None:
            # the hot-swap contract: a mid-burst swap loses ZERO requests
            # and triggers ZERO recompiles on any replica
            checks["active_all_resolved"] = all(f.done() for f in swap_futs)
            checks["active_zero_lost"] = swap_ok == len(swap_futs)
            checks["active_no_swap_recompiles"] = all(
                d == 0 for d in swap_compile_delta.values())
            checks["active_model_id_rolled"] = router.model_id != args.model
            checks["active_escalations_evaluated"] = \
                loop.stats.evaluated > 0
        if hub is not None:
            checks.update(trace_checks(summary["trace"]))
        if scraped_ok is not None:
            checks["metrics_scrape"] = scraped_ok
        summary["checks"] = checks
        if not all(checks.values()):
            summary["check"] = "FAIL"
            rc = 3
        else:
            summary["check"] = "ok"
    print(json.dumps(summary), flush=True)
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=200)
    p.add_argument("--mode", choices=("closed", "open", "both"),
                   default="both")
    p.add_argument("--concurrency", type=int, default=8,
                   help="closed-loop outstanding requests")
    p.add_argument("--rate", type=float, default=0.0,
                   help="open-loop arrival rate in req/s (0 = burst)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-wait", type=float, default=0.02)
    p.add_argument("--max-queue", type=int, default=4096)
    p.add_argument("--admission", choices=("reject", "block"),
                   default="block")
    p.add_argument("--model", default="pair")
    p.add_argument("--skin", type=float, default=0.0)
    p.add_argument("--poison", type=int, default=0,
                   help="inject N NaN-position requests (isolation probe)")
    p.add_argument("--jsonl", default=None,
                   help="write telemetry StepRecords here")
    p.add_argument("--jsonl-max-bytes", type=int, default=None,
                   help="rotate the telemetry JSONL past this size "
                        "(JsonlSink max_bytes; keeps 3 rotated files)")
    p.add_argument("--obs", choices=("auto", "on", "off"), default="auto",
                   help="observability hub (distmlip_tpu.obs): tracing + "
                        "metrics. auto = on whenever --check/--trace-out/"
                        "--metrics-port ask for it; off = uninstrumented "
                        "baseline for the overhead A/B")
    p.add_argument("--trace-out", default=None,
                   help="write the run's Perfetto trace_event JSON here "
                        "(view at ui.perfetto.dev or via "
                        "tools/trace_view.py)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus exposition on this port for "
                        "the run (0 = ephemeral) and scrape it once at "
                        "the end; with --check, the scraped tenant "
                        "counters must match the loadgen totals")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--check", action="store_true",
                   help="assert acceptance criteria; exit 3 on failure")
    p.add_argument("--contracts", action="store_true",
                   help="also run the static contract passes "
                        "(distmlip_tpu.analysis) over the serving program; "
                        "with --check, any error-severity finding fails "
                        "the gate")
    p.add_argument("--occupancy-floor", type=float, default=0.95)
    p.add_argument("--fleet", type=int, default=0,
                   help="run FLEET mode instead: N in-process ServeEngine "
                        "replicas behind a FleetRouter (tenant fairness, "
                        "result cache, failover)")
    p.add_argument("--active", action="store_true",
                   help="fleet mode: attach an ActiveLoop (sampled "
                        "ensemble escalation into a replay buffer) and "
                        "run a second burst with a mid-burst hot-swap; "
                        "--check gates zero lost requests and zero "
                        "recompiles across the swap")
    p.add_argument("--chaos", choices=("none", "kill-replica"),
                   default="none",
                   help="fleet mode: kill replica r0 mid-burst; --check "
                        "then also requires a failover and still zero "
                        "lost requests")
    p.add_argument("--aot", choices=("shared", "off"), default="shared",
                   help="fleet mode: shared on-disk AOT executable cache "
                        "across the replicas (fleet/aot.py) — the first "
                        "replica to compile a bucket exports it, the "
                        "others rehydrate; 'off' = every replica compiles "
                        "its own buckets")
    p.add_argument("--cache-bytes", type=int, default=64 * 2**20,
                   help="fleet mode: result-cache byte bound")
    p.add_argument("--p99-bound-s", type=float, default=60.0,
                   help="fleet mode --check: p99 latency bound (seconds), "
                        "failover included")
    p.add_argument("--cache-hit-floor", type=float, default=0.9,
                   help="fleet mode --check: duplicate-phase result-cache "
                        "hit-rate floor")
    p.add_argument("--hbm-budget-gb", type=float, default=None,
                   help="per-device HBM budget for the batched lane "
                        "(memory-aware autobatching + the --contracts "
                        "memory_budget gate); default: backend-reported "
                        "bytes_limit (none on CPU)")
    args = p.parse_args(argv)
    if args.active and args.fleet < 1:
        print("usage error: --active requires fleet mode (--fleet N)",
              file=sys.stderr)
        return 2
    from distmlip_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        if args.fleet > 0:
            return run_fleet(args)
        return run(args)
    finally:
        from distmlip_tpu.obs import uninstall
        from distmlip_tpu.telemetry.trace import (jax_cache_counts,
                                                  phase_totals)

        uninstall()
        # what ran once (import, runtime builds, each new bucket's first
        # call, jax's stages of every compile), summed by name: buckets
        # compile as the load runs, so the run's end is where set-up ends
        print("[load_test] phases " + json.dumps(
            {**phase_totals(), **jax_cache_counts()}),
            file=sys.stderr, flush=True)


if __name__ == "__main__":
    raise SystemExit(main())
