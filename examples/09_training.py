"""Fine-tune from served data: the serve -> train loop, closed.

The workflow the ROADMAP names: a deployed potential labels structures
(here: `BatchedPotential` playing the teacher — in production, the
ServeEngine's answered requests ARE this dataset), and the training
subsystem fine-tunes a drifted model back to parity on those labels.

The whole training stack is exercised: deterministic packed-batch loader,
gradient accumulation, EMA, dynamic loss scaling, resumable async
checkpoints, and memory-aware micro-batch auto-sizing — all through ONE
jitted step program per accumulation window.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds; pass JAX_PLATFORMS=cpu to stay off a chip.

import numpy as np
import optax

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, BatchedPotential
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.train import Sample, TrainConfig, Trainer

rng = np.random.default_rng(0)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])

cfg = TensorNetConfig(num_species=3, units=16, num_rbf=6, num_layers=1,
                      cutoff=3.6)
model = TensorNet(cfg)

# --- the "production" model serving traffic ------------------------------
served_params = model.init(jax.random.PRNGKey(0))
teacher = BatchedPotential(model, served_params)


def structure(noise, reps=(2, 2, 2)):
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 3.8, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, noise, (len(frac), 3))
    return Atoms(numbers=rng.integers(1, 4, len(cart)), positions=cart,
                 cell=lattice)


# --- label a dataset with the served model (the serve side of the loop) --
# deliberately LONG-TAIL sizes (mostly small cells, a few large): the
# regime where one frozen worst-case capacity wastes most of its padded
# slots, and the cost-model loader's capacity tiers pay off
pool = [structure(0.03 + 0.02 * (i % 3),
                  reps=(2, 2, 2) if i % 2 else (1, 1, 1))
        for i in range(10)]
results = teacher.calculate(pool)
dataset = [Sample(a, float(r["energy"]), np.asarray(r["forces"], np.float32))
           for a, r in zip(pool, results)]
train_set, val_set = dataset[:8], dataset[8:]

# --- a drifted model: the served weights, perturbed ----------------------
drifted = jax.tree.map(
    lambda p: p + 0.08 * jax.random.normal(jax.random.PRNGKey(1), p.shape,
                                           p.dtype)
    if np.issubdtype(np.asarray(p).dtype, np.floating) else p,
    served_params)

# --- fine-tune it back on the served labels (the train side) -------------
ckpt_dir = tempfile.mkdtemp(prefix="distmlip-train-")
trainer = Trainer(
    model.energy_fn, drifted, optax.adam(2e-3), train_set, cfg.cutoff,
    micro_batch_size=2,
    hbm_budget_bytes=1 << 32,           # 4 GiB budget for the demo
    config=TrainConfig(accum_steps=2, ema_decay=0.99, clip_norm=1.0),
    val_samples=val_set, eval_every=4,
    checkpoint_dir=ckpt_dir, checkpoint_every=4,
    # cost-model packing: census the dataset, cluster 2 frozen capacity
    # tiers, bin-pack each epoch to balance edges (train/packing.py) —
    # every tier is priced by the HBM planner before any compile
    loader_kwargs={"species_fn": lambda z: (z - 1).astype(np.int32),
                   "seed": 42, "packing": "cost_model", "num_tiers": 2},
)
print(f"micro_batch={trainer.loader.micro_batch_size}, "
      f"est peak {trainer.est_peak_bytes / 2**20:.1f} MiB "
      f"({len(trainer.tier_peak_bytes)} tier(s)), "
      f"{trainer.steps_per_epoch} steps/epoch")

# padding waste before/after: what the frozen single-cap loader WOULD
# have paid on this long-tail dataset vs what the tiers actually pay
from distmlip_tpu.partition import fixed_caps_for_batches
from distmlip_tpu.train import plan_epoch_naive, predicted_plan_waste

loader = trainer.loader
naive_waste = predicted_plan_waste(
    loader.needs,
    plan_epoch_naive(len(train_set), seed=42, epoch=0, micro_batch_size=2,
                     accum_steps=2),
    {0: fixed_caps_for_batches(loader.needs, 2)})
tiered_waste = predicted_plan_waste(
    loader.needs, loader.epoch_plan(0), loader.tier_caps)
print(f"padding waste: naive single-cap {naive_waste:.2f} -> "
      f"cost-model tiers {tiered_waste:.2f} "
      f"({naive_waste / max(tiered_waste, 1e-9):.1f}x less padding)")

val0 = trainer.evaluate()["loss"]
history = trainer.fit(epochs=8)
val1 = trainer.evaluate()["loss"]
print(f"train loss {history[0]['loss']:.5f} -> {history[-1]['loss']:.5f}, "
      f"val {val0:.5f} -> {val1:.5f} "
      f"(best {trainer.checkpointer.best_metric:.5f})")
assert history[-1]["loss"] < history[0]["loss"]

# --- resume from the newest checkpoint: bitwise continuation -------------
resumed = Trainer(
    model.energy_fn, drifted, optax.adam(2e-3), train_set, cfg.cutoff,
    micro_batch_size=trainer.loader.micro_batch_size,
    config=TrainConfig(accum_steps=2, ema_decay=0.99, clip_norm=1.0),
    checkpoint_dir=ckpt_dir,
    # same packing config: the checkpoint's tier coordinate is VALIDATED
    # against the resumed loader's recomputed plan (drift -> hard error)
    loader_kwargs={"species_fn": lambda z: (z - 1).astype(np.int32),
                   "seed": 42, "packing": "cost_model", "num_tiers": 2},
)
step_no = resumed.restore()
m = resumed.train_step()
print(f"resumed at step {step_no}; next step loss {m['loss']:.5f}")

# --- parity check: fine-tuned forces track the served model --------------
student = BatchedPotential(model, resumed.state.ema_params)
out_t = teacher.calculate(pool[:2])
out_s = student.calculate(pool[:2])
err = max(np.abs(np.asarray(a["forces"]) - np.asarray(b["forces"])).max()
          for a, b in zip(out_t, out_s))
print(f"max |F_teacher - F_student| after fine-tune: {err:.4f} eV/A")
trainer.close()
resumed.close()
