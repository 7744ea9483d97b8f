"""Trainer: the epoch/step loop tying the subsystem together.

``Trainer`` owns the loader (deterministic, prefetching), the jitted
accumulated step, periodic held-out eval (on the EMA weights), resumable
async checkpoints, best-model tracking, and per-step telemetry
(:class:`~distmlip_tpu.telemetry.TrainRecord` riding the shared sinks).

Memory-aware micro-batch sizing: before ANY compile, the candidate step
program is abstractly traced and run through the static HBM planner
(``analysis.memory.analyze_memory`` — the PR 9 machinery), with the
donated ``TrainState`` buffers marked reusable. ``micro_batch_size="auto"``
walks power-of-two candidates downward and picks the largest whose
estimated per-device peak fits ``hbm_budget_frac`` of the budget; an
explicit micro-batch size is still CHECKED and rejected up front when its
estimate exceeds the budget — the OOM surfaces as a ValueError naming the
estimate, not as a dead chip 40 minutes into a run.
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from ..analysis.memory import analyze_memory
from ..telemetry import TrainRecord
from ..utils.memory import device_bytes_limit
from .checkpoint import TrainCheckpointer
from .data import PackedBatchLoader
from .step import (TrainConfig, init_train_state, make_accum_train_step,
                   make_eval_step)


def estimate_step_peak_bytes(step_fn, state, batch) -> int:
    """Static per-device peak estimate of one train-step dispatch: trace
    abstractly (no compile, no chip), mark the donated state's buffers
    reusable, run the buffer-liveness planner."""
    jaxpr = jax.make_jaxpr(step_fn)(state, batch.graphs, batch.targets)
    n_args = len(jaxpr.jaxpr.invars)
    donated = np.zeros(n_args, dtype=bool)
    donated[:len(jax.tree.leaves(state))] = True
    return analyze_memory(jaxpr, donated=donated).peak_bytes


class Trainer:
    """End-to-end training over a labeled dataset of structures.

    Parameters
    ----------
    model_energy_fn, params, optimizer:
        the model's per-shard energy function, its initial parameters
        (master fp32 copies are made), and an optax optimizer — any
        transformation off-mesh; when ZeRO-1 shards the state it must be
        ELEMENTWISE (adam/sgd family; see
        :func:`distmlip_tpu.train.step.resolve_zero1` — global-norm
        clipping belongs in ``TrainConfig.clip_norm``, not the chain).
    samples:
        ``list[train.data.Sample]`` training set.
    cutoff:
        neighbor cutoff for the packed graphs (model cutoff).
    micro_batch_size:
        structures per micro-batch, or ``"auto"`` (fit the HBM budget).
    config:
        :class:`TrainConfig` — loss weights, precision, accumulation,
        clipping, loss-scale dynamics, ZeRO-1 policy.
    mesh:
        2-D device mesh for (batch x spatial) placement of every pack;
        None = single device.
    val_samples / eval_every:
        held-out set and eval cadence in optimizer steps (0 = once per
        epoch). Eval runs on the EMA weights when EMA is enabled.
    checkpoint_dir / checkpoint_every:
        resumable async checkpoints (0 = once per epoch); best-model
        tracking keys on the eval loss.
    hbm_budget_bytes / hbm_budget_frac:
        per-device budget for the static planner gate (default: the
        backend-reported limit; no limit and no explicit budget =>
        the gate is skipped, e.g. CPU test runs).
    """

    def __init__(self, model_energy_fn, params, optimizer, samples,
                 cutoff: float, *, micro_batch_size="auto",
                 config: TrainConfig = TrainConfig(), mesh=None,
                 val_samples=None, eval_every: int = 0,
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int = 0, keep_checkpoints: int = 3,
                 hbm_budget_bytes: int | None = None,
                 hbm_budget_frac: float = 0.8, telemetry=None,
                 seed: int = 0, kernels=None, loader_kwargs: dict | None = None):
        self.config = config
        self.mesh = mesh
        self.telemetry = telemetry
        self.eval_every = int(eval_every)
        self.checkpoint_every = int(checkpoint_every)
        self.history: list[dict] = []
        self.best_val: float | None = None
        lk = dict(loader_kwargs or {})
        lk.setdefault("seed", seed)
        lk.setdefault("accum_steps", config.accum_steps)

        self.state = init_train_state(optimizer, params, mesh, config,
                                      seed=seed)
        self.step_fn = make_accum_train_step(model_energy_fn, optimizer,
                                             mesh, config, kernels=kernels)
        self.eval_fn = make_eval_step(model_energy_fn, mesh, config,
                                      kernels=kernels)

        budget = hbm_budget_bytes
        if budget is None:
            budget = device_bytes_limit()
        self.hbm_budget_bytes = budget
        self.est_peak_bytes = 0
        self.tier_peak_bytes: dict[int, int] = {}
        self.loader = self._size_loader(samples, cutoff, micro_batch_size,
                                        budget, hbm_budget_frac, lk)

        self._val_batch = (self.loader.eval_batch(val_samples)
                          if val_samples else None)
        self.checkpointer = (TrainCheckpointer(checkpoint_dir,
                                               keep=keep_checkpoints)
                             if checkpoint_dir else None)

    # ---- memory-aware micro-batch sizing ----

    def _probe_loader(self, samples, cutoff, B, lk, needs):
        lk = dict(lk)
        # a caller may hand a precomputed dataset census through
        # loader_kwargs (e.g. a naive-vs-cost-model A/B shares
        # one census across two Trainers); an in-sizing-loop census from
        # a previous candidate wins — both are the same dataset property
        needs = needs if needs is not None else lk.pop(
            "precomputed_needs", None)
        lk.pop("precomputed_needs", None)
        return PackedBatchLoader(samples, cutoff, micro_batch_size=B,
                                 precomputed_needs=needs, **lk)

    def _size_loader(self, samples, cutoff, micro_batch_size, budget,
                     frac, lk) -> PackedBatchLoader:
        accum = int(lk.get("accum_steps", 1))
        max_b = max(len(samples) // max(accum, 1), 1)
        # needs are a property of the DATASET, not the batch size —
        # compute once, share across candidate loaders
        probe = None
        needs = None
        if micro_batch_size == "auto":
            b = 1 << int(math.floor(math.log2(max_b)))
            candidates = []
            while b >= 1:
                candidates.append(b)
                b //= 2
        else:
            b = int(micro_batch_size)
            if b > max_b:
                raise ValueError(
                    f"micro_batch_size={b} needs {b * accum} structures "
                    f"per optimizer step but the dataset has "
                    f"{len(samples)}")
            candidates = [b]
        last_est = None
        for b in candidates:
            probe = self._probe_loader(samples, cutoff, b, lk, needs)
            needs = probe.needs
            if budget is None:
                # no limit to gate against (CPU entry point, no explicit
                # budget): take the first candidate, record the estimate
                self.est_peak_bytes = self._estimate(probe)
                return probe
            last_est = self._estimate(probe)
            if last_est <= frac * budget:
                self.est_peak_bytes = last_est
                return probe
            probe.close()
        raise ValueError(
            f"no micro-batch size from {candidates} fits the HBM budget: "
            f"smallest candidate estimates {last_est / 2**20:.1f} MiB "
            f"per device vs budget {frac * budget / 2**20:.1f} MiB "
            f"({frac:.0%} of {budget / 2**30:.2f} GiB) — shrink the "
            f"model/accumulation window or raise hbm_budget_frac")

    def _estimate(self, loader) -> int:
        # price EVERY frozen capacity tier up front (cost-model packing
        # compiles one executable per tier; each must fit the budget, and
        # the gate compares against the most expensive one). The naive
        # loader reports a single tier {0: 0}.
        self.tier_peak_bytes = {}
        for tier, step in sorted(loader.tier_first_steps().items()):
            batch = loader._build(0, step)
            self.tier_peak_bytes[tier] = estimate_step_peak_bytes(
                self.step_fn, self.state, batch)
        return max(self.tier_peak_bytes.values())

    @property
    def compile_count(self) -> int:
        """Train-step executables compiled so far (jit cache entries) —
        pinned <= ``loader.num_tiers`` for the whole run (every tier's
        shapes are frozen; -1 when the jit internals are unavailable)."""
        try:
            return int(self.step_fn._cache_size())
        except Exception:  # noqa: BLE001 - introspection-only surface
            return -1

    # ---- the loop ----

    @property
    def steps_per_epoch(self) -> int:
        return self.loader.steps_per_epoch

    def train_step(self) -> dict:
        """One optimizer step: next batch -> jitted step -> telemetry.
        Returns the host metrics dict (floats)."""
        t0 = time.perf_counter()
        batch = self.loader.next_batch()
        t_data = time.perf_counter() - t0
        cc0 = self.compile_count
        self.state, metrics = self.step_fn(self.state, batch.graphs,
                                           batch.targets)
        m = {k: float(v) for k, v in metrics.items()}  # blocks on device
        dt = time.perf_counter() - t0
        # compile telemetry: a grown jit cache means THIS dispatch traced
        # and compiled a new per-tier executable (wall includes the first
        # execution — indistinguishable at this layer)
        compile_s, compile_kind = 0.0, ""
        if cc0 >= 0 and self.compile_count > cc0:
            from ..obs import profiling as _profiling

            compile_s = dt - t_data
            compile_kind = _profiling.KIND_FRESH
            _profiling.record_compile(
                site="train_step", kind=compile_kind, wall_s=compile_s,
                bucket_key=batch.meta.get(
                    "bucket_key", f"tier={batch.meta.get('tier', 0)}"))
        epoch = int(batch.meta.get("epoch", 0))
        step_no = int(m.pop("step"))
        # cadence keys on the APPLIED-step transition: a nonfinite-skipped
        # step leaves step_no unchanged, and re-firing eval/checkpoint on
        # every retry of the same applied step would hammer exactly the
        # run that is already struggling
        advanced = not m["skipped"]
        tier = int(batch.meta.get("tier", 0))
        m.update(epoch=epoch, examples_per_sec=(
            batch.meta.get("n_structures", 0) / max(dt, 1e-9)),
            tier=tier,
            padding_waste_frac=batch.meta.get("padding_waste_frac", 0.0),
            edge_balance=batch.meta.get("edge_balance", 1.0))

        if self._val_batch is not None and self._due(step_no, batch,
                                                     self.eval_every,
                                                     advanced):
            val = self.evaluate()
            m["val_loss"] = val["loss"]
            if self.checkpointer is not None:
                if self.checkpointer.save_best(self.state, val["loss"],
                                               self.loader.state()):
                    self.best_val = val["loss"]
        if self.checkpointer is not None and self._due(
                step_no, batch, self.checkpoint_every, advanced):
            self.checkpointer.save(self.state, self.loader.state(),
                                   step=step_no)

        if self.telemetry is not None:
            # per-tier executables are priced separately; report the one
            # THIS step dispatched (falling back to the run max) and
            # derive headroom from the SAME estimate so the record stays
            # self-consistent (record.py: 1 - est_peak_bytes / limit)
            tier_est = self.tier_peak_bytes.get(tier, self.est_peak_bytes)
            rec = TrainRecord(
                step=step_no, epoch=epoch,
                timings={"data_s": t_data, "device_s": dt - t_data,
                         "total_s": dt},
                loss=m["loss"], loss_energy=m["energy"],
                loss_force=m["force"], loss_stress=m["stress"],
                val_loss=m.get("val_loss", float("nan")),
                grad_norm=m["grad_norm"], loss_scale=m["loss_scale"],
                skipped=bool(m["skipped"]),
                accum_steps=self.config.accum_steps,
                micro_batch_size=self.loader.micro_batch_size,
                examples_per_sec=m["examples_per_sec"],
                batch_size=batch.meta.get("n_structures", 0),
                n_atoms=batch.meta.get("n_atoms", 0),
                bucket_key=batch.meta.get("bucket_key", ""),
                tier=tier,
                padding_waste_frac=m["padding_waste_frac"],
                edge_balance=m["edge_balance"],
                est_peak_bytes=tier_est,
                hbm_headroom_frac=(
                    1.0 - tier_est / self.hbm_budget_bytes
                    if self.hbm_budget_bytes and tier_est
                    else 0.0),
                compile_s=compile_s,
                compile_kind=compile_kind,
                compiled=bool(compile_kind),
            )
            if self.mesh is not None:
                from ..parallel.mesh import mesh_shape

                bp, sp = mesh_shape(self.mesh)
                rec.mesh_shape = [bp, sp]
                rec.batch_parts, rec.spatial_parts = bp, sp
            self.telemetry.emit(rec)
        self.history.append(m)
        return m

    def _due(self, step_no: int, batch, every: int,
             advanced: bool) -> bool:
        if every > 0:
            # fire once per applied-step TRANSITION (skipped steps repeat
            # the same step_no and must not re-fire)
            return advanced and step_no > 0 and step_no % every == 0
        # per-epoch cadence: fire on the last batch of each epoch (the
        # batch cursor advances even on skipped steps, so this fires once
        # per epoch position)
        return batch.meta.get("step", -1) == self.loader.steps_per_epoch - 1

    def fit(self, epochs: int = 1, steps: int | None = None) -> list[dict]:
        """Run ``steps`` optimizer steps (default: ``epochs`` full passes).
        Returns the per-step metrics history (cumulative across calls)."""
        total = (int(steps) if steps is not None
                 else int(epochs) * self.steps_per_epoch)
        for _ in range(total):
            self.train_step()
        if self.checkpointer is not None:
            self.checkpointer.wait()
        return self.history

    def evaluate(self) -> dict:
        """Held-out loss components on the EMA weights (master weights
        when EMA is off)."""
        if self._val_batch is None:
            raise ValueError("Trainer was built without val_samples")
        params = (self.state.ema_params if self.config.ema_decay > 0.0
                  else self.state.params)
        comps = self.eval_fn(params, self._val_batch.graphs,
                             self._val_batch.targets)
        return {k: float(v) for k, v in comps.items()}

    # ---- checkpoint plumbing ----

    def save_checkpoint(self) -> str:
        if self.checkpointer is None:
            raise ValueError("Trainer was built without checkpoint_dir")
        path = self.checkpointer.save(self.state, self.loader.state())
        self.checkpointer.wait()
        return path

    def restore(self, path: str | None = None) -> int:
        """Resume from ``path`` (default: newest checkpoint): restores the
        full TrainState AND the loader cursor — training continues
        bitwise as if never interrupted. Returns the restored step."""
        if self.checkpointer is None:
            raise ValueError("Trainer was built without checkpoint_dir")
        state, loader_state = self.checkpointer.restore(self.state, path)
        self.state = state
        self.loader.set_state(loader_state)
        return int(state.step)

    def close(self) -> None:
        self.loader.close()
        if self.checkpointer is not None:
            self.checkpointer.wait()
