"""Serving demo: async micro-batching engine end to end.

The request/response regime the ServeEngine targets: independent callers
submit single structures (mixed sizes, priorities, deadlines) and the
background scheduler packs them into bucket-aware micro-batches through
one shared BatchedPotential — plus the robustness surface: admission
control, a poison request failing only its own Future, graceful drain.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import threading

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, BatchedPotential
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.serve import ServeEngine, ServeRejected
from distmlip_tpu.telemetry import AggregatingSink, JsonlSink, Telemetry

rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])


def candidate(reps, a=5.4, noise=0.1):
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)


model = TensorNet(TensorNetConfig(num_species=95, cutoff=4.5))
params = model.init(jax.random.PRNGKey(0))

jsonl = "/tmp/serving_demo.jsonl"
telemetry = Telemetry([AggregatingSink(), JsonlSink(jsonl)])
engine = ServeEngine(
    BatchedPotential(model, params),
    max_batch=4,
    max_wait_s=0.02,          # lone requests ship after 20 ms
    max_queue=64, admission="reject",
    telemetry=telemetry,
)

# --- many concurrent callers, mixed sizes and priorities ---------------
pool = [candidate((1, 1, 1)), candidate((2, 1, 1)), candidate((2, 2, 1))]
results = {}


def caller(cid):
    fut = engine.submit(pool[cid % len(pool)],
                        priority=cid % 3 - 1,      # a few urgent (-1) ones
                        deadline=5.0)
    results[cid] = fut.result()


threads = [threading.Thread(target=caller, args=(i,)) for i in range(12)]
for t in threads:
    t.start()
for t in threads:
    t.join()
print(f"12 concurrent callers served: "
      f"E0 = {results[0]['energy']:.4f} eV, "
      f"batches = {engine.stats.batches}, "
      f"compiles = {engine.compile_count}")

# --- a poison request fails ONLY its own Future ------------------------
bad = pool[0].copy()
bad.positions = bad.positions.copy()
bad.positions[0] = np.nan
bad_fut = engine.submit(bad)
good_fut = engine.submit(pool[1])
try:
    bad_fut.result()
except ValueError as e:
    print(f"poison isolated: {e}")
print(f"its batch-mate still served: E = {good_fut.result()['energy']:.4f} eV")

# --- admission control -------------------------------------------------
try:
    tiny = ServeEngine(engine.potential, max_queue=1, start=False)
    tiny.submit(pool[0])
    tiny.submit(pool[0])          # queue full -> ServeRejected
except ServeRejected as e:
    print(f"admission control: {e}")
finally:
    tiny.close()

# --- graceful shutdown -------------------------------------------------
leftovers = [engine.submit(a) for a in pool]
engine.drain()                    # queue empty, every Future resolved
assert all(f.done() for f in leftovers)
engine.close()
telemetry.close()

print("\nper-phase summary (AggregatingSink):")
print(telemetry.sinks[0].summary())
print(f"\nserving section: python tools/telemetry_report.py {jsonl}")
