"""Serving fleet: multi-replica routing, tenancy, caching, AOT restart.

The production serving layer over :mod:`distmlip_tpu.serve`: N
``ServeEngine`` replicas (in-process for tests and single-host serving;
one process holding one chip each in real deployments) behind a
:class:`FleetRouter` with per-tenant admission quotas and weighted
fairness, a content-addressed :class:`ResultCache` so duplicate
screening traffic never touches a chip, wedge-detecting health monitoring
with zero-request-loss failover (:class:`ReplicaHealth`), and an
:class:`AotExecutableCache` that rehydrates a restarted replica's whole
bucket ladder with zero recompiles.

Quick start::

    from distmlip_tpu.calculators import BatchedPotential
    from distmlip_tpu.fleet import ResultCache, make_fleet

    router = make_fleet(
        2, lambda i: BatchedPotential(model, params),
        aot_cache_dir="/var/cache/distmlip-aot",
        result_cache=ResultCache(max_bytes=256 * 2**20),
        model_id="mace-mp0", precision="float32")
    fut = router.submit(atoms, tenant="interactive", priority=-1)
    result = fut.result()      # survives any single replica dying
    router.close()

Chaos drill / gate: ``python tools/load_test.py --fleet 2
--chaos kill-replica --check``.
"""

from .aot import AotExecutableCache, install_aot_cache, model_fingerprint
from .replica import Replica, ReplicaHealth
from .result_cache import ResultCache, cache_key, structure_key
from .router import FleetError, FleetRouter, FleetStats, make_fleet
from .tenancy import FairScheduler, TenantConfig, TokenBucket

__all__ = [
    "FleetRouter",
    "FleetStats",
    "FleetError",
    "make_fleet",
    "Replica",
    "ReplicaHealth",
    "ResultCache",
    "cache_key",
    "structure_key",
    "TenantConfig",
    "TokenBucket",
    "FairScheduler",
    "AotExecutableCache",
    "install_aot_cache",
    "model_fingerprint",
]
