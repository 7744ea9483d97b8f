"""Train graph-parallel: minibatched structures, LR schedule, held-out
eval, checkpoint/resume — the non-toy retrain recipe.

The loss differentiates through the halo exchange, so every chip computes
its slab's contribution and parameter gradients are psum'd — capability the
reference does not have (it is inference-only, README.md:53). This example
is the UMA-endgame training recipe end to end:

  - a dataset of perturbed structures with teacher-generated
    energy/force targets (distillation; swap in DFT labels the same way),
  - minibatches of stacked graphs moved by ONE jitted program per step
    (train.stack_graphs + make_batched_train_step),
  - warmup + cosine LR schedule (optax),
  - held-out validation loss every EVAL_EVERY steps,
  - checkpoint at the midpoint, then a hard resume (fresh params +
    load_train_state) proving the run continues bit-exactly.

Run: python examples/03_train_graph_parallel.py [--steps 500]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds. For the 8-virtual-device CPU mesh:
#   JAX_PLATFORMS=cpu python examples/03_train_graph_parallel.py
jax.config.update("jax_num_cpu_devices", 8)  # read by the CPU backend only

import numpy as np
import optax

from distmlip_tpu import geometry
from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.neighbors import neighbor_list
from distmlip_tpu.parallel import graph_mesh, make_potential_fn
from distmlip_tpu.partition import (CapacityPolicy, build_partitioned_graph,
                                    build_plan)
from distmlip_tpu.train import (load_train_state, make_batched_train_step,
                                make_eval_fn, save_train_state, stack_graphs,
                                stack_targets)

STEPS = int(sys.argv[sys.argv.index("--steps") + 1]) if "--steps" in sys.argv else 500
N_STRUCTS, N_VAL, BATCH = 10, 2, 4
EVAL_EVERY = 50
CKPT = "/tmp/train_state.npz"

rng = np.random.default_rng(2)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
P = min(len(jax.devices()), 2)
mesh = graph_mesh(P) if P > 1 else None
cfg = TensorNetConfig(num_species=8, units=32, num_rbf=8, num_layers=2,
                      cutoff=4.5)
model = TensorNet(cfg)

# teacher: a larger frozen TensorNet provides energy/force labels
teacher_cfg = TensorNetConfig(num_species=8, units=64, num_rbf=12,
                              num_layers=2, cutoff=4.5)
teacher = TensorNet(teacher_cfg)
teacher_params = teacher.init(jax.random.PRNGKey(7))
teacher_fn = make_potential_fn(teacher.energy_fn, mesh, compute_stress=False)

# ---- dataset: N_STRUCTS perturbed supercells under ONE capacity bucket ----
caps = CapacityPolicy()
graphs, positions, targets = [], [], []
for s in range(N_STRUCTS):
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.0, (8, 4, 4))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.04 + 0.02 * (s % 3), (len(frac), 3))
    species = rng.integers(0, 3, len(cart)).astype(np.int32)
    nl = neighbor_list(cart, lattice, [1, 1, 1], cfg.cutoff)
    plan = build_plan(nl, lattice, [1, 1, 1], P, cfg.cutoff)
    graph, host = build_partitioned_graph(plan, nl, species, lattice, caps=caps)
    out = teacher_fn(teacher_params, graph, graph.positions)
    graphs.append(graph)
    positions.append(graph.positions)
    targets.append({"energy": np.float32(out["energy"]),
                    "forces": np.asarray(out["forces"], np.float32)})

train_idx = np.arange(N_STRUCTS - N_VAL)
val_idx = np.arange(N_STRUCTS - N_VAL, N_STRUCTS)
val_graphs = stack_graphs([graphs[i] for i in val_idx])
val_pos = np.stack([positions[i] for i in val_idx])
val_tgt = stack_targets([targets[i] for i in val_idx])

# ---- optimizer with warmup + cosine schedule ----
schedule = optax.warmup_cosine_decay_schedule(
    init_value=1e-4, peak_value=3e-3, warmup_steps=25,
    decay_steps=max(STEPS, 1), end_value=1e-5)
optimizer = optax.adam(schedule)
params = model.init(jax.random.PRNGKey(0))
opt_state = optimizer.init(params)
step_fn = make_batched_train_step(model.energy_fn, mesh, optimizer)
eval_fn = make_eval_fn(model.energy_fn, mesh)

val0 = float(eval_fn(params, val_graphs, val_pos, val_tgt))
print(f"devices={len(jax.devices())} P={P} structures={N_STRUCTS} "
      f"batch={BATCH} steps={STEPS}  val0={val0:.6f}")

for it in range(STEPS):
    batch = rng.choice(train_idx, size=BATCH, replace=False)
    g = stack_graphs([graphs[i] for i in batch])
    pos = np.stack([positions[i] for i in batch])
    tgt = stack_targets([targets[i] for i in batch])
    params, opt_state, loss = step_fn(params, opt_state, g, pos, tgt)
    if (it + 1) % EVAL_EVERY == 0 or it == 0:
        val = float(eval_fn(params, val_graphs, val_pos, val_tgt))
        print(f"step {it + 1:4d}: train {float(loss):.6f}  val {val:.6f}  "
              f"lr {float(schedule(it)):.2e}")
    if it + 1 == STEPS // 2:
        save_train_state(CKPT, params, opt_state, it + 1)
        print(f"checkpoint saved at step {it + 1} -> {CKPT}")
        # hard resume: throw the live state away and restore from disk
        params = model.init(jax.random.PRNGKey(99))  # deliberately wrong
        opt_state = optimizer.init(params)
        params, opt_state, resumed = load_train_state(
            CKPT, params, opt_state)
        print(f"resumed from step {resumed} (fresh process equivalent)")

val_final = float(eval_fn(params, val_graphs, val_pos, val_tgt))
print(f"final: val {val_final:.6f} (from {val0:.6f}, "
      f"{'FELL' if val_final < val0 else 'DID NOT FALL'})")
assert val_final < val0, "validation loss did not improve"
