"""Fleet serving: 2 replicas, kill one mid-burst, lose ZERO requests.

Demonstrates the distmlip_tpu.fleet subsystem end to end on CPU:

1. two in-process ServeEngine replicas (each its own BatchedPotential)
   behind a FleetRouter with two weighted tenants and a shared
   content-addressed result cache + AOT executable cache;
2. an open-loop burst of screening traffic; replica r0 is KILLED while
   half the burst is still in flight — its queued and in-flight requests
   fail over to r1 and every submitted Future still resolves;
3. duplicate re-submissions come back from the result cache without
   touching a replica (watch the dispatch counters stay flat);
4. a THIRD replica "restarts" from the warm AOT cache and serves its
   first batch with compile_count == 0 (zero recompiles — the cold-start
   story).

Run: python examples/10_fleet.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import numpy as np  # noqa: E402

from distmlip_tpu import geometry  # noqa: E402
from distmlip_tpu.calculators import Atoms, BatchedPotential  # noqa: E402
from distmlip_tpu.fleet import (FleetRouter, ResultCache,  # noqa: E402
                                TenantConfig, install_aot_cache)
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
    model = PairPotential(PairConfig(cutoff=4.0))
    params = model.init()
    aot_dir = tempfile.mkdtemp(prefix="distmlip_aot_")

    def make_engine():
        pot = BatchedPotential(model, params, caps=BucketPolicy())
        install_aot_cache(pot, aot_dir)   # every compile lands on disk
        return ServeEngine(pot, max_batch=4, max_wait_s=0.005,
                           max_queue=4096)

    router = FleetRouter(
        [make_engine(), make_engine()],
        result_cache=ResultCache(max_bytes=64 * 2**20),
        model_id="pair-demo",
        tenants={"interactive": TenantConfig(weight=4.0),
                 "screening": TenantConfig(weight=1.0, rate_hz=500.0)})

    # --- burst + chaos -------------------------------------------------
    structures = [make_structure(rng) for _ in range(24)]
    futures = []
    for i, atoms in enumerate(structures):
        if i == 12:   # half the burst is in: r0 loses its chips
            moved = router.kill_replica("r0")
            print(f"killed replica r0 mid-burst "
                  f"({moved} request(s) failed over to survivors)")
        tenant = "interactive" if i % 4 == 0 else "screening"
        futures.append(router.submit(atoms, tenant=tenant))
    results = [f.result(timeout=120) for f in futures]   # raises if any lost
    print(f"burst: {len(results)}/{len(futures)} futures resolved "
          f"(zero lost), failovers={router.stats.failovers}, "
          f"redispatches={router.stats.redispatches}")

    # --- duplicate traffic: served by the cache, not a chip ------------
    before = router.snapshot()["replicas"]["r1"]["dispatched_total"]
    dup = [router.submit(structures[i % len(structures)])
           for i in range(32)]
    for f, ref in zip(dup, results):
        assert f.result(timeout=60)["energy"] == ref["energy"]
    after = router.snapshot()["replicas"]["r1"]["dispatched_total"]
    print(f"duplicates: 32/32 served, cache hit rate "
          f"{router.cache.hit_rate():.2f}, replica dispatches +"
          f"{after - before} (cache hits touch no chip)")
    # one solo request so the B=1 bucket is compiled + AOT-exported too
    # (the restart below serves a single structure = that exact bucket)
    solo = make_structure(rng)
    router.submit(solo).result(timeout=60)
    router.close()

    # --- cold restart from the warm AOT cache --------------------------
    pot3 = BatchedPotential(model, params, caps=BucketPolicy())
    install_aot_cache(pot3, aot_dir)
    with ServeEngine(pot3, max_batch=4, max_wait_s=0.005) as engine3:
        engine3.submit(solo).result(timeout=60)
        print(f"restarted replica served its first batch with "
              f"compile_count={engine3.compile_count} "
              f"(AOT rehydrated: {pot3.aot_cache.stats()['rehydrated']} "
              f"bucket(s))")
        assert engine3.compile_count == 0


if __name__ == "__main__":
    main()
