"""Observability: traces + live metrics + flight recorder on a fleet burst.

Demonstrates the distmlip_tpu.obs subsystem end to end on CPU:

1. one `Observability.enable()` call lights up every layer — no object
   takes a tracer parameter; the fleet/engine instrumentation points
   find the hub at call time;
2. a 2-replica fleet serves a small burst (two tenants, duplicates for
   cache hits) while every request grows its own span tree
   (fleet.submit -> tenancy.admit -> router.route -> engine.queue ->
   batch dispatch -> future.resolve) and the batch spans link back to
   their member requests;
3. the trace is exported as Perfetto `trace_event` JSON (drop it on
   ui.perfetto.dev) and summarized per request by the same critical-path
   code `tools/trace_view.py` uses: queue vs pack vs compile vs device;
4. the metrics registry answers "what is each tenant's p99 RIGHT NOW"
   as Prometheus text exposition — no JSONL replay;
5. the flight recorder captures a timestamped incident directory
   (trace + metrics snapshot) on demand — the same capture an SLO
   burn-rate breach or a replica wedge suspicion triggers by itself.

Run: python examples/12_observability.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import numpy as np  # noqa: E402

from distmlip_tpu import geometry, obs  # noqa: E402
from distmlip_tpu.calculators import Atoms, BatchedPotential  # noqa: E402
from distmlip_tpu.fleet import FleetRouter, ResultCache, TenantConfig  # noqa: E402
from distmlip_tpu.models import PairConfig, PairPotential  # noqa: E402
from distmlip_tpu.partition import BucketPolicy  # noqa: E402
from distmlip_tpu.serve import ServeEngine  # noqa: E402


def make_structure(rng):
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 3.6, (2, 2, 2))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.05, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


def main():
    rng = np.random.default_rng(0)
    out_dir = tempfile.mkdtemp(prefix="distmlip_obs_")

    # -- 1. one call arms all three planes (+ the incident plane) -------
    hub = obs.Observability.enable(
        slo=obs.SLOConfig(latency_s=0.5, objective=0.99),
        flight_dir=os.path.join(out_dir, "incidents"),
        min_interval_s=0.0)
    print(f"observability hub installed; artifacts under {out_dir}")

    model = PairPotential(PairConfig(cutoff=4.0))
    params = model.init()
    router = FleetRouter(
        [ServeEngine(BatchedPotential(model, params, caps=BucketPolicy()),
                     max_batch=4, max_wait_s=0.005, max_queue=4096)
         for _ in range(2)],
        result_cache=ResultCache(), model_id="pair",
        tenants={"interactive": TenantConfig(weight=4.0),
                 "screening": TenantConfig(weight=1.0)})

    # -- 2. a burst with duplicates (cache hits get span trees too) -----
    structs = [make_structure(rng) for _ in range(12)]
    futs = [router.submit(a, tenant="interactive" if i % 4 == 0
                          else "screening")
            for i, a in enumerate(structs)]
    for f in futs:
        f.result(timeout=120)
    router.drain(timeout=60)
    dup_futs = [router.submit(structs[i % len(structs)]) for i in range(8)]
    for f in dup_futs:
        f.result(timeout=120)
    router.close()

    # -- 3. export + per-request critical paths -------------------------
    trace_path = os.path.join(out_dir, "trace.json")
    hub.tracer.write(trace_path)
    spans = hub.tracer.spans()
    tsum = obs.request_trace_summary(spans)
    print(f"\n{tsum['requests']} request span trees, "
          f"{tsum['complete']} complete, "
          f"{tsum['terminals']} future.resolve terminals "
          f"(conserved across cache hits)")
    print(obs.format_critical_path(obs.critical_path_summary(spans)))
    print(f"trace JSON -> {trace_path}  (open ui.perfetto.dev, or run: "
          f"python tools/trace_view.py {trace_path})")

    # -- 4. live metrics: Prometheus exposition, no replay --------------
    print("\nmetrics exposition (tenant/request lines):")
    for line in hub.metrics.render().splitlines():
        if line.startswith(("distmlip_fleet_requests_total",
                            "distmlip_fleet_cache_hits_total",
                            "distmlip_replica_alive")):
            print(f"  {line}")
    lat = hub.metrics.get("distmlip_fleet_request_latency_seconds")
    if lat is not None:
        for tenant in ("interactive", "screening"):
            p99 = lat.labels(tenant=tenant).quantile(0.99)
            print(f"  live p99[{tenant}] <= {1e3 * p99:.1f} ms "
                  f"(log-bucket upper bound)")
    # (serve it live instead: obs.MetricsServer(hub.metrics, port=9090))

    # -- 5. flight recorder: what an SLO breach would leave behind ------
    incident = hub.flight.capture("demo: manual capture")
    print(f"\nincident captured -> {incident}")
    print(f"  contents: {sorted(os.listdir(incident))}")
    print(f"SLO state: {hub.slo.snapshot()}")
    obs.uninstall()


if __name__ == "__main__":
    main()
