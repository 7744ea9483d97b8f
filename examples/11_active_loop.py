"""The active-learning subsystem: serve -> buffer -> train -> swap, closed.

What examples/09 does BY HAND (label served traffic, fine-tune a drifted
model, check parity), `distmlip_tpu.active` does as a subsystem:

- an ``EnsembleBatchedPotential`` serves the cheap primary member through
  a ``ServeEngine`` and re-evaluates sampled traffic under every member
  in one vmapped launch (per-structure energy/force variance);
- high-variance structures land, dedup'd, in a persistent
  ``ReplayBuffer`` with their committee labels;
- a ``FineTuneTrigger`` fires the gated fine-tune (Trainer + resumable
  checkpoints; a worse model never ships);
- the winner hot-swaps into the live engine: zero recompiles, zero
  dropped requests.

09 remains the manual-path walkthrough of the training stack itself.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.active import (ActiveLoop, EnsembleBatchedPotential,
                                 EscalationPolicy, FineTuneTrigger,
                                 ReplayBuffer, TriggerPolicy, variance_score)
from distmlip_tpu.calculators import Atoms
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.serve import ServeEngine
from distmlip_tpu.train import TrainConfig

rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])

cfg = TensorNetConfig(num_species=3, units=16, num_rbf=6, num_layers=1,
                      cutoff=3.6)
model = TensorNet(cfg)

# --- the ensemble: a drifted PRIMARY serving live traffic, plus a small
#     committee of reference members (in production: independently
#     trained seeds) -------------------------------------------------------
good = model.init(jax.random.PRNGKey(0))


def perturb(params, scale, seed):
    key = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda p: p + scale * jax.random.normal(
            jax.random.fold_in(key, 1), p.shape, p.dtype)
        if np.issubdtype(np.asarray(p).dtype, np.floating) else p, params)


drifted = perturb(good, 0.08, 1)
ensemble = EnsembleBatchedPotential(
    model, [drifted, good, perturb(good, 0.005, 2), perturb(good, 0.005, 3)],
    skin=0.3)

# --- the serving engine runs the PRIMARY member (cheap path) -------------
engine = ServeEngine(ensemble, max_batch=4, max_wait_s=0.005,
                     shed_deadlines=True)


def structure():
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 3.8, (2, 2, 1))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.03, (len(frac), 3))
    return Atoms(numbers=rng.integers(1, 4, len(cart)), positions=cart,
                 cell=lattice)


# --- the loop: escalate everything (demo), fine-tune at 6 buffered
#     structures, holdout-gate, hot-swap ----------------------------------
buffer_dir = tempfile.mkdtemp(prefix="distmlip-buffer-")
loop = ActiveLoop(
    engine, ensemble, ReplayBuffer(capacity=64, directory=buffer_dir),
    policy=EscalationPolicy(sample_rate=1.0),
    trigger=FineTuneTrigger(TriggerPolicy(min_buffer=6)),
    finetune_kwargs={
        "steps": 40, "learning_rate": 5e-3,
        "config": TrainConfig(ema_decay=0.0, w_force=10.0),
        "checkpoint_dir": tempfile.mkdtemp(prefix="distmlip-ft-"),
        "loader_kwargs": {"species_fn": lambda z: (z - 1).astype(np.int32),
                          "seed": 42}})

pool = [structure() for _ in range(10)]
pre = [variance_score(r) for r in ensemble.calculate_with_variance(pool)]
print(f"pre-swap force variance over served pool: {np.mean(pre):.3e}")

futures = [loop.submit(a) for a in pool]        # same Future contract
for f in futures:
    f.result()
compile_before = engine.compile_count

report = loop.tick()                             # pump + fine-tune + swap
ft = report["finetune"]
print(f"buffer depth {report['buffer_depth']}, fine-tune "
      f"({ft['reason']}): holdout {ft['val_before']:.4f} -> "
      f"{ft['val_after']:.4f}, shipped={ft['shipped']}")

assert ft["shipped"], "the holdout gate refused the candidate"
assert engine.compile_count == compile_before, "swap must not recompile"
post = [variance_score(r) for r in ensemble.calculate_with_variance(pool)]
print(f"post-swap force variance: {np.mean(post):.3e} "
      f"({np.mean(post) / np.mean(pre):.2f}x)")
assert np.mean(post) < np.mean(pre)

snap = loop.snapshot()
print(f"loop stats: {snap['stats']}")
engine.close()
