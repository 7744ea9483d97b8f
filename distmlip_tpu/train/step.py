"""The accumulated mixed-precision train step over packed batches.

One optimizer step = ONE jitted program per accumulation window:

- **packed loss** (:func:`make_packed_loss_fn`) — energy/force/stress
  matching against a block-diagonally packed micro-batch through the
  params-differentiable packed energy program
  (``parallel.make_packed_energy_fn``): inner ``value_and_grad`` over
  positions/strain for forces/stress, outer grad over params for the
  update — the same double-differentiation the legacy recipe uses, now
  over B structures at once, normalized per structure (energy per atom²,
  forces per 3n, mean over real slots);
- **mixed precision** — ``precision="bf16"`` pairs with a model built
  with ``cfg.dtype="bfloat16"`` (every model in the zoo supports it):
  the MODEL casts params to bf16 per forward through its own curated
  ``keep_fp32`` list (species references, readout heads, norms stay
  fp32), grad-side gathers accumulate fp32 (``ops.nn.gather_rows``),
  and the step's master weights / grads / optimizer stay fp32
  throughout — the ``dtype_discipline`` contract (fp32 master weights,
  no half-precision scatter accumulation) is pinned by
  ``tools/contract_check.py`` on the traced train program. On the step
  side the knob selects the loss-scale default (2^15);
- **dynamic loss scaling** — the loss is scaled before the backward,
  grads unscaled after accumulation; a nonfinite global grad norm skips
  the update (params, opt state, EMA, step count all unchanged) and
  halves the scale; ``growth_interval`` consecutive finite steps double
  it (capped). bf16 rarely overflows, fp16-style runs and exploding
  losses are absorbed the same way;
- **gradient accumulation** — ``lax.scan`` over the batch's leading
  accumulation axis: grads and loss components sum in fp32 carries, so
  accumulation N with micro-batch B matches the N*B big-batch step to
  fp32 roundoff (asserted in tests);
- **ZeRO-1 optimizer-state sharding** — with a mesh whose batch axis has
  extent Bm > 1, master params and grads ravel to a (Bm, K) layout whose
  rows shard over the batch axis: every batch row updates ITS shard of
  the optimizer state (adam moments never replicate), then one tiled
  ``all_gather`` rebuilds the full parameter vector. Grad reduction
  itself is the shard_map transpose's psum — the checker budget is
  exactly {psum: grads, all_gather: 1} on the batch axis
  (tools/contract_check.py pins it);
- **EMA** — an exponential moving average of the master weights rides
  the state (applied steps only), the standard eval/serving weight set
  for MLIP training.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import BATCH_AXIS, mesh_shape
from ..parallel.runtime import make_packed_energy_fn


@dataclass(frozen=True)
class TrainConfig:
    """Knobs of the accumulated step (static: baked into the executable)."""

    w_energy: float = 1.0
    w_force: float = 1.0
    w_stress: float = 0.0
    precision: str = "fp32"          # "fp32" | "bf16" compute (master fp32)
    accum_steps: int = 1             # micro-batches per optimizer step
    clip_norm: float = 0.0           # global-norm clip; 0 disables
    ema_decay: float = 0.999         # EMA of master weights; 0 disables
    zero1: Any = "auto"              # True | False | "auto" (mesh batch > 1)
    loss_scale: float | None = None  # None: 2**15 for bf16, 1.0 for fp32
    scale_growth_interval: int = 2000
    scale_factor: float = 2.0
    max_loss_scale: float = 2.0 ** 24
    min_loss_scale: float = 2.0 ** -14

    def __post_init__(self):
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(
                f"precision must be 'fp32' or 'bf16', got "
                f"{self.precision!r}")
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got "
                             f"{self.accum_steps}")

    @property
    def initial_loss_scale(self) -> float:
        if self.loss_scale is not None:
            return float(self.loss_scale)
        return 2.0 ** 15 if self.precision == "bf16" else 1.0


class TrainState(NamedTuple):
    """The full resumable optimizer-step state (a pytree; checkpointed
    whole by train/checkpoint.py, donated whole by the jitted step)."""

    params: Any          # fp32 master weights
    opt_state: Any       # optax state; ZeRO-1: (Bm, K) leaves batch-sharded
    step: Any            # () int32 — APPLIED optimizer steps
    ema_params: Any      # EMA of master weights (== params when disabled)
    loss_scale: Any      # () float32 dynamic loss scale
    good_steps: Any      # () int32 finite steps since last scale change
    rng: Any             # jax PRNG key (reserved for stochastic models)


def resolve_zero1(config: TrainConfig, mesh) -> bool:
    """ZeRO-1 is on when requested, or by default whenever the mesh has a
    batch axis of extent > 1 (sharding over a 1-row axis is a no-op that
    still pays the program plumbing).

    CONSTRAINT: the sharded update runs the optax transformation on each
    row's (Bm, K)-raveled shard independently, which reproduces the
    unsharded step exactly ONLY for elementwise transformations (sgd,
    adam/adamw, rmsprop, schedules — the moment/update math never mixes
    parameters). Transformations that couple across the whole pytree
    (optax.clip_by_global_norm in a chain, lamb's trust ratio, adafactor's
    factored moments) would silently compute their statistics per shard —
    pass ``zero1=False`` for those (global-norm clipping is already a
    step-level knob, ``TrainConfig.clip_norm``, applied BEFORE the
    optimizer on the full gradient).
    """
    has_batch = mesh is not None and BATCH_AXIS in mesh.axis_names
    if config.zero1 != "auto":
        if config.zero1 and not has_batch:
            raise ValueError(
                "zero1=True needs a mesh with a named batch axis to shard "
                "over; pass mesh=device_mesh(B, S) (or leave zero1='auto')")
        return bool(config.zero1)
    return has_batch and mesh_shape(mesh)[0] > 1


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.float32(0.0)


def make_packed_loss_fn(model_energy_fn, mesh=None,
                        config: TrainConfig = TrainConfig(), kernels=None):
    """Loss over ONE packed micro-batch.

    ``(params, graph, targets) -> (loss, components)`` where ``graph`` is
    a ``pack_structures`` super-graph (placement matching ``mesh``) and
    ``targets`` the matching :func:`distmlip_tpu.train.data.pack_targets`
    pytree. ``components`` is a fixed-structure dict of fp32 scalars
    (total + per-term) so it scans/accumulates. Per-structure
    normalization matches the legacy single-structure loss: energy term
    ((E - E*)/n)², force term |F - F*|²/(3n) over owned rows, stress term
    mean over the 9 components; all averaged over the REAL structures in
    the batch.
    """
    energy_fn = make_packed_energy_fn(model_energy_fn, mesh,
                                     diff_params=True, kernels=kernels)
    w_e = float(config.w_energy)
    w_f = float(config.w_force)
    w_s = float(config.w_stress)

    def loss_fn(params, graph, targets):
        f32 = jnp.float32
        # master weights pass through UNCAST: with precision="bf16" the
        # model's own compute-dtype switch (cfg.dtype="bfloat16") casts
        # per forward under its curated keep_fp32 list — a blind cast
        # here would downcast fp32-pinned readout heads and species
        # references the model zoo deliberately protects
        p_c = params
        positions = graph.positions
        B_total = max(graph.batch_parts, 1) * graph.batch_size
        strain0 = jnp.zeros((B_total, 3, 3), dtype=positions.dtype)

        # ONE forward + one backward via vjp: the per-structure energies
        # feed the loss directly and the ones-cotangent pullback is the
        # force/stress backward — no duplicated primal readout (a second
        # value_and_grad forward would leave a DEAD structure-sum psum in
        # the program; collectives never DCE). The strain input joins the
        # vjp only when stress trains — otherwise its transpose would
        # ship dead edge-offset scatter work every step.
        if w_f > 0.0 and w_s > 0.0:
            energies, pullback = jax.vjp(
                lambda pos, s: energy_fn(p_c, graph, pos, s),
                positions, strain0)
            g_pos, g_strain = pullback(jnp.ones_like(energies))
        elif w_f > 0.0:
            energies, pullback = jax.vjp(
                lambda pos: energy_fn(p_c, graph, pos, strain0), positions)
            (g_pos,) = pullback(jnp.ones_like(energies))
            g_strain = None
        elif w_s > 0.0:
            energies, pullback = jax.vjp(
                lambda s: energy_fn(p_c, graph, positions, s), strain0)
            (g_strain,) = pullback(jnp.ones_like(energies))
            g_pos = None
        else:
            energies = energy_fn(p_c, graph, positions, strain0)
            g_pos = g_strain = None

        struct_mask = targets["struct_mask"].astype(f32)
        n_real = jnp.maximum(jnp.sum(struct_mask), 1.0)
        n_atoms = targets["n_atoms"].astype(f32)
        energies = energies.astype(f32)

        e_diff = (energies - targets["energy"].astype(f32)) / n_atoms
        e_term = jnp.sum(struct_mask * e_diff * e_diff) / n_real
        zero = jnp.float32(0.0)
        f_term = s_term = zero
        if w_f > 0.0:
            # owned & real rows carry their structure's flat slot; halo and
            # padded rows carry the B_total sentinel -> weight 0
            slot = targets["atom_slot"]
            owned = slot < B_total
            n_ext = jnp.concatenate([n_atoms, jnp.ones((1,), f32)])
            w_atom = jnp.where(owned, 1.0 / (3.0 * n_ext[slot]), 0.0)
            d = (-g_pos).astype(f32) - targets["forces"].astype(f32)
            f_term = jnp.sum(w_atom[..., None] * d * d) / n_real
        if w_s > 0.0:
            if "stress" not in targets:
                raise ValueError(
                    "w_stress > 0 but the batch carries no stress targets "
                    "(give every Sample a stress, or set w_stress=0)")
            stress = (g_strain.astype(f32)
                      * targets["inv_volume"].astype(f32)[:, None, None])
            ds = stress - targets["stress"].astype(f32)
            s_term = jnp.sum(
                struct_mask[:, None, None] * ds * ds) / (9.0 * n_real)
        loss = w_e * e_term + w_f * f_term + w_s * s_term
        comps = {"loss": loss, "energy": e_term, "force": f_term,
                 "stress": s_term}
        return loss, comps

    return loss_fn


def init_train_state(optimizer, params, mesh=None,
                     config: TrainConfig = TrainConfig(),
                     seed: int = 0) -> TrainState:
    """Fresh state: fp32 master weights, optimizer state (ZeRO-1 layout
    when the placement shards it), EMA mirror, initial loss scale.

    The master weights are COPIES of ``params``: the jitted step donates
    the whole TrainState, and aliasing the caller's arrays into it would
    delete the caller's buffers on the first step (a no-op astype returns
    the same buffer)."""
    params = jax.tree.map(
        lambda x: jnp.array(x, dtype=jnp.float32)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
        else jnp.array(x), params)
    if resolve_zero1(config, mesh):
        flat, _ = ravel_pytree(params)
        bm = mesh_shape(mesh)[0]
        k = -(-flat.size // bm)
        opt_state = optimizer.init(jnp.zeros((bm, k), dtype=flat.dtype))
    else:
        opt_state = optimizer.init(params)
    return TrainState(
        params=params,
        opt_state=opt_state,
        step=jnp.int32(0),
        ema_params=jax.tree.map(jnp.array, params),
        loss_scale=jnp.float32(config.initial_loss_scale),
        good_steps=jnp.int32(0),
        rng=jax.random.PRNGKey(seed),
    )


def _plain_apply(optimizer, grads, opt_state, params):
    # optax's bias-correction scalars (b1 ** count) promote to f64 ONLY
    # under the checker's x64 tracing regime; the runtime's default config
    # keeps the whole update fp32 (audited — tests assert default-config
    # update dtypes are pure fp32)
    # contract: allow(dtype_discipline)
    updates, new_opt = optimizer.update(grads, opt_state, params)
    new_params = jax.tree.map(lambda p, u: p + u, params, updates)
    return new_params, new_opt


def _zero1_apply(optimizer, mesh, grads, opt_state, params):
    """ZeRO-1 sharded update: each batch row owns rows of the (Bm, K)
    raveled master/grad/opt-state layout, updates its shard, and ONE
    tiled all_gather over the batch axis rebuilds the full params."""
    bm = mesh_shape(mesh)[0]
    flat_g, _ = ravel_pytree(grads)
    flat_p, unravel = ravel_pytree(params)
    n = flat_p.size
    k = -(-n // bm)
    pad = bm * k - n
    g2 = jnp.pad(flat_g, (0, pad)).reshape(bm, k)
    p2 = jnp.pad(flat_p, (0, pad)).reshape(bm, k)

    def shard_spec(x):
        return (P(BATCH_AXIS) if getattr(x, "ndim", 0) >= 1
                and x.shape[0] == bm else P())

    opt_specs = jax.tree.map(shard_spec, opt_state)

    def shard_update(g, o, p):
        # g/p: (1, K) — this batch row's shard; optax updates are
        # elementwise, so the sharded step IS the unsharded step on rows.
        # (x64-tracing-only f64 scalars: see _plain_apply)
        # contract: allow(dtype_discipline)
        updates, o2 = optimizer.update(g, o, p)
        p_new = p + updates
        full = jax.lax.all_gather(p_new[0], BATCH_AXIS, axis=0, tiled=False)
        return full, o2

    full_p, new_opt = jax.shard_map(
        shard_update, mesh=mesh,
        in_specs=(P(BATCH_AXIS), opt_specs, P(BATCH_AXIS)),
        out_specs=(P(), opt_specs), check_vma=False)(g2, opt_state, p2)
    new_params = unravel(full_p.reshape(-1)[:n])
    return new_params, new_opt


def make_accum_train_step(model_energy_fn, optimizer, mesh=None,
                          config: TrainConfig = TrainConfig(), kernels=None,
                          donate: bool = True):
    """The jitted accumulated step.

    ``step(state, graphs, targets) -> (state, metrics)`` where
    ``graphs``/``targets`` carry a leading accumulation axis A (a
    ``TrainBatch`` from the loader: ``step(state, batch.graphs,
    batch.targets)``). ``metrics`` is a dict of () fp32/int32 device
    scalars: loss (+components), grad_norm (pre-clip), loss_scale,
    skipped, step. ``donate=True`` donates the input state — the caller
    must not reuse it (the loop checkpoints BEFORE stepping).
    """
    loss_fn = make_packed_loss_fn(model_energy_fn, mesh, config, kernels)
    zero1 = resolve_zero1(config, mesh)
    cfg = config

    def step(state, graphs, targets):
        f32 = jnp.float32
        scale = state.loss_scale
        accum = jax.tree.leaves(graphs)[0].shape[0]

        def scaled_loss(params, graph, tgt):
            loss, comps = loss_fn(params, graph, tgt)
            return loss * scale, comps

        grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)
        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, f32), state.params)
        zero_comps = {"loss": f32(0), "energy": f32(0), "force": f32(0),
                      "stress": f32(0)}

        def micro(carry, xs):
            g_acc, c_acc = carry
            graph, tgt = xs
            (_, comps), grads = grad_fn(state.params, graph, tgt)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(f32), g_acc, grads)
            c_acc = jax.tree.map(lambda a, c: a + c, c_acc, comps)
            return (g_acc, c_acc), None

        (g_sum, c_sum), _ = jax.lax.scan(
            micro, (zero_grads, zero_comps), (graphs, targets))
        inv = 1.0 / (accum * scale)
        grads = jax.tree.map(lambda g: g * inv, g_sum)
        comps = jax.tree.map(lambda c: c / accum, c_sum)

        gnorm = global_norm(grads)
        finite = jnp.isfinite(gnorm)
        # a nonfinite norm poisons every arithmetic path through the
        # update; zero the grads on skipped steps so the (discarded)
        # update computes on clean values and NaNs can't leak through
        # the selects below via 0 * NaN corner cases
        safe = jnp.where(finite, 1.0, 0.0)
        if cfg.clip_norm > 0.0:
            factor = jnp.minimum(
                1.0, cfg.clip_norm / (gnorm + 1e-12)) * safe
        else:
            factor = safe
        grads = jax.tree.map(lambda g: g * factor, grads)

        if zero1:
            new_params, new_opt = _zero1_apply(
                optimizer, mesh, grads, state.opt_state, state.params)
        else:
            new_params, new_opt = _plain_apply(
                optimizer, grads, state.opt_state, state.params)

        def keep(new, old):
            return jax.tree.map(
                lambda a, b: jnp.where(finite, a, b), new, old)

        params = keep(new_params, state.params)
        opt_state = keep(new_opt, state.opt_state)
        if cfg.ema_decay > 0.0:
            decay = f32(cfg.ema_decay)
            ema = jax.tree.map(
                lambda e, p: e + (1.0 - decay) * (p - e),
                state.ema_params, params)
            ema = keep(ema, state.ema_params)
        else:
            ema = params

        interval = jnp.int32(max(cfg.scale_growth_interval, 1))
        good = state.good_steps + 1
        grown = jnp.where(
            good >= interval,
            jnp.minimum(scale * cfg.scale_factor, cfg.max_loss_scale),
            scale)
        new_scale = jnp.where(
            finite, grown,
            jnp.maximum(scale / cfg.scale_factor, cfg.min_loss_scale))
        new_good = jnp.where(finite,
                             jnp.where(good >= interval, 0, good),
                             0).astype(jnp.int32)

        rng, _ = jax.random.split(state.rng)
        new_state = TrainState(
            params=params, opt_state=opt_state,
            step=state.step + finite.astype(jnp.int32),
            ema_params=ema, loss_scale=new_scale, good_steps=new_good,
            rng=rng)
        metrics = {**comps, "grad_norm": gnorm, "loss_scale": new_scale,
                   "skipped": (~finite).astype(jnp.int32),
                   "step": new_state.step}
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(model_energy_fn, mesh=None,
                   config: TrainConfig = TrainConfig(), kernels=None):
    """Held-out evaluation over a stacked batch: ``(params, graphs,
    targets) -> components`` dict of fp32 scalars (mean over the leading
    stack axis). Same loss, no gradient — feed ``state.ema_params`` for
    the EMA eval."""
    loss_fn = make_packed_loss_fn(model_energy_fn, mesh, config, kernels)

    @jax.jit
    def evaluate(params, graphs, targets):
        _, comps = jax.vmap(loss_fn, in_axes=(None, 0, 0))(
            params, graphs, targets)
        return jax.tree.map(jnp.mean, comps)

    return evaluate
