"""The one-time paths of the potentials are on the phase log: the runtime
build, a graph build's three parts and the four parts of a call that
compiled or built a graph. A steady step logs nothing. The compile log
reads jax's own timing of the call, not the whole first call."""

from __future__ import annotations

import time
from collections import Counter

import numpy as np
import pytest

from distmlip_tpu.calculators import BatchedPotential, DistPotential
from distmlip_tpu.obs import profiling
from distmlip_tpu.telemetry import AggregatingSink, Telemetry, trace
from tests.utils import make_atoms

pytestmark = pytest.mark.tier1

GRAPH = ["distmlip/neighbor_build", "distmlip/partition",
         "distmlip/graph_upload"]
FIRST_CALL = [f"distmlip/first_call.{part}" for part in
              ("prepare", "dispatch", "wait", "results_to_host")]
SKIN = 0.5


@pytest.fixture(scope="module")
def tensornet():
    import jax

    from distmlip_tpu.models import TensorNet, TensorNetConfig

    model = TensorNet(TensorNetConfig(num_species=95, units=8, num_rbf=4,
                                      num_layers=1, cutoff=3.2))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def empty_logs():
    trace.reset_phases()
    profiling.reset_compile_log()
    yield
    trace.reset_phases()
    profiling.reset_compile_log()


def names(log):
    return [p[0] for p in log]


@pytest.mark.parametrize("partitions", [1, 2])
def test_first_call_steady_steps_and_a_rebuild(tensornet, partitions):
    model, params = tensornet
    rng = np.random.default_rng(3)
    atoms = make_atoms(rng, reps=(4, 3, 3) if partitions == 2 else (3, 3, 3))
    agg = AggregatingSink()
    t_made = time.perf_counter()
    # the host rebuild path: a device refresh would compile its own program
    pot = DistPotential(model, params, num_partitions=partitions, skin=SKIN,
                        device_rebuild=False, async_rebuild=False,
                        telemetry=Telemetry([agg]))
    assert names(trace.phases()) == ["distmlip/runtime_build"]
    t0 = time.perf_counter()
    pot.calculate(atoms)
    t1 = time.perf_counter()
    log = trace.phases()
    count = Counter(names(log))
    assert count["distmlip/runtime_build"] == 1
    for name in GRAPH + FIRST_CALL:
        assert count[name] == 1, (name, count)
    for name in ("jax/trace", "jax/lower", "jax/backend_compile"):
        assert count[name] >= 1, (name, count)
    assert not any(n.startswith("distmlip/device_rebuild") for n in count)
    # every phase of the call lies in the call, the build before it
    for name, a, b, _ in log:
        lo = t_made if name == "distmlip/runtime_build" else t0
        assert lo <= a + 2e-3 and a <= b <= t1 + 2e-3, (name, a - t0, b - t0)
    # the four parts tile the call, in order, and hold the graph build
    parts = {p[0]: p for p in log if p[0] in FIRST_CALL}
    for before, after in zip(FIRST_CALL, FIRST_CALL[1:]):
        assert parts[before][2] == parts[after][1]
    prepare = parts[FIRST_CALL[0]]
    assert all(prepare[1] <= a and b <= prepare[2]
               for name, a, b, _ in log if name in GRAPH)
    dispatch = parts[FIRST_CALL[1]]
    assert all(dispatch[1] - 2e-3 <= a and b <= dispatch[2] + 2e-3
               for name, a, b, _ in log if name == "jax/backend_compile")

    # the compile log: jax's stages of this call, not the call
    events = profiling.compile_events()
    assert [e.site for e in events] == ["dist_potential"]
    assert events[0].kind in ("fresh", "cache")
    assert 0.0 < events[0].wall_s <= t1 - t0
    assert not any(e.site == "dist_build" for e in events)
    jax_s = sum(b - a for name, a, b, _ in log
                if name in ("jax/lower", "jax/backend_compile"))
    assert events[0].wall_s >= 0.99 * jax_s
    assert agg.n_records == 1

    # ten steps inside the skin: nothing is logged, by count
    n_phases, n_events = len(trace.phases()), len(events)
    for _ in range(10):
        atoms.positions += rng.normal(0, 1e-3, atoms.positions.shape)
        pot.calculate(atoms)
    assert len(trace.phases()) == n_phases
    assert len(profiling.compile_events()) == n_events
    assert pot.rebuild_count == 1 and agg.n_records == 11

    # past the skin: the graph is built again, nothing compiles
    atoms.positions[0] += 0.5 * SKIN + 0.05
    pot.calculate(atoms)
    again = names(trace.phases()[n_phases:])
    assert again == GRAPH + FIRST_CALL
    assert not any(n.startswith("jax/") for n in again)
    assert pot.rebuild_count == 2
    assert len(profiling.compile_events()) == n_events
    pot.close()


def test_a_steady_step_logs_nothing_without_telemetry_too(tensornet):
    """The guard is on the path every user runs: no hub attached."""
    model, params = tensornet
    rng = np.random.default_rng(4)
    atoms = make_atoms(rng)
    pot = DistPotential(model, params, num_partitions=1, skin=SKIN)
    pot.calculate(atoms)
    assert Counter(names(trace.phases()))[FIRST_CALL[2]] == 1
    assert profiling.compile_events() == []    # the log is the hub's
    n = len(trace.phases())
    for _ in range(10):
        atoms.positions += rng.normal(0, 1e-3, atoms.positions.shape)
        pot.calculate(atoms)
    assert len(trace.phases()) == n
    pot.close()


def test_device_refresh_is_a_phase(tensornet):
    model, params = tensornet
    rng = np.random.default_rng(5)
    atoms = make_atoms(rng)
    pot = DistPotential(model, params, num_partitions=1, skin=SKIN)
    pot.calculate(atoms)
    n = len(trace.phases())
    atoms.positions[0] += 0.5 * SKIN + 0.05
    pot.calculate(atoms)
    assert pot.rebuild_on_device_count == 1
    again = Counter(names(trace.phases()[n:]))
    assert again["distmlip/device_rebuild"] == 1
    assert not any(again[g] for g in GRAPH)
    assert all(again[part] == 1 for part in FIRST_CALL)
    pot.close()


def test_batched_potential_logs_a_new_bucket_not_a_pack(tensornet):
    model, params = tensornet
    rng = np.random.default_rng(6)
    pot = BatchedPotential(model, params)
    assert names(trace.phases()) == ["distmlip/runtime_build"]
    batch = [make_atoms(rng, reps=(2, 2, 2)) for _ in range(2)]
    t0 = time.perf_counter()
    pot.calculate(batch)
    t1 = time.perf_counter()
    count = Counter(names(trace.phases()))
    assert all(count[part] == 1 for part in FIRST_CALL)
    assert count["jax/backend_compile"] >= 1
    event, = profiling.compile_events()
    assert event.site == "batched_bucket"
    assert event.kind in ("fresh", "cache")
    assert 0.0 < event.wall_s <= t1 - t0
    # the same bucket again, other structures: a pack, no phase, no event
    n = len(trace.phases())
    pot.calculate([make_atoms(rng, reps=(2, 2, 2)) for _ in range(2)])
    assert pot.rebuild_count == 2 and pot.compile_count == 1
    assert len(trace.phases()) == n
    assert len(profiling.compile_events()) == 1
