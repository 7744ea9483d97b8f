"""Batched multi-structure execution: potential + vectorized relax/MD.

``BatchedPotential.calculate(list[Atoms]) -> list[dict]`` evaluates a whole
batch of independent structures in ONE device program over a
block-diagonally packed super-graph (``partition.pack_structures``) — the
TorchSim serving/screening regime (arXiv:2508.06628) where per-structure
dispatch leaves the chip idle between tiny graphs. ``BatchedRelaxer`` and
``BatchedMD`` drive the batch through relaxation (FIRE/GD with
per-structure convergence masking — converged structures freeze in place,
the batch exits when all are done) and fixed-cell MD.

Exactness contract: packing, padding and masking never change results —
per-structure energies/forces/stresses/magmoms match the single-structure
``DistPotential`` path to fp32 roundoff (tests/test_batched.py asserts this
for CHGNet, TensorNet, MACE and eSCN).

Compile behavior: capacities come from a geometric ``BucketPolicy``
(~sqrt(2) steps, configurable), so a stream of varied request sizes
compiles a small fixed executable set instead of one program per novel
(n_atoms, n_edges, B) shape; ``compile_count`` and per-batch bucket
telemetry (bucket id, occupancy, padding waste) track this.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..obs import profiling as _profiling
from ..obs import runtime as obsrt
from ..parallel import make_batched_potential_fn
from ..partition import BucketPolicy, pack_structures
from ..telemetry import StepRecord, annotate, note_dispatch, phase
from ..telemetry.trace import compile_in, listen_to_jax, log_first_call
from ..telemetry.trace import tracing_enabled
from .atoms import (AMU_A2_FS2_TO_EV, EV_A3_TO_GPA, KB, map_species,
                    max_displacement)
from .relax import RelaxResult


class BatchedPotential:
    """Batched potential over a model + parameter pytree (single device).

    Parameters mirror ``DistPotential`` where they apply. The batched path
    is single-partition by design: it targets many SMALL structures per
    step (use ``DistPotential`` for one large halo-partitioned structure).

    ``skin > 0`` enables Verlet graph reuse across ``calculate`` calls: the
    packed graph is rebuilt only when any structure's atoms moved more than
    ``skin/2`` from their build positions (or the structure list changed);
    otherwise only packed positions are re-uploaded. Results are exact
    either way (model envelopes zero skin-shell edges).

    ``caps`` is a ``BucketPolicy`` (geometric capacity ladder); pass a
    custom one to tune ``base``/``growth``/``multiple`` — coarser growth
    means fewer compiles and more padding waste.

    ``device_rebuild`` ("auto" = on for non-bond-graph models): when the
    Verlet cache invalidates but the structure LIST is unchanged (batched
    relax/MD trajectories, repeated serving of the same batch), the packed
    edge arrays are rebuilt ON DEVICE and swapped in place — positions-only
    re-upload, no host repack, no recompile. A capacity overflow falls back
    to the host repack (which may move to the next bucket rung);
    ``DISTMLIP_DEVICE_REBUILD=0`` disables globally.

    ``mesh`` (a ``parallel.device_mesh(batch, spatial)``): run the batch on
    a 2-D (batch x spatial) mesh — structures spread over the batch axis
    AND each structure spatially partitions into ``spatial`` slabs with
    halo exchange on the spatial axis only. The single-device behavior
    (mesh=None) is unchanged. On-device packed refresh is host-side only
    for mesh placements (multi-partition graphs repack on the host).

    Memory-aware autobatching (``hbm_budget_bytes``/``hbm_budget_frac``/
    ``memory_model``): every fresh compile additionally runs the static
    HBM planner (``analysis/memory.analyze_memory`` — one abstract trace,
    no device work) over the just-compiled program and calibrates the
    ``BucketPolicy`` bytes model with the per-device peak estimate
    (cached per shape bucket). ``hbm_budget_bytes`` is the per-device HBM
    budget consumers fill batches toward (``ServeEngine`` admission +
    ``plan_batch``); default: ``hbm_budget_frac`` (0.8) of the backend's
    reported ``bytes_limit``, None on backends reporting none (CPU) —
    budget checks are then skipped. ``memory_model=False`` disables the
    calibration trace entirely. ``last_est_peak_bytes`` /
    ``hbm_headroom_frac`` ride ``last_stats`` and the telemetry records
    so estimator drift vs measured ``bytes_in_use`` is visible.
    """

    def __init__(
        self,
        model,
        params,
        species_map: np.ndarray | None = None,
        compute_stress: bool = True,
        compute_magmom: bool = False,
        caps: BucketPolicy | None = None,
        skin: float = 0.0,
        num_threads: int | None = None,
        device_rebuild: bool | str = "auto",
        mesh=None,
        kernels=None,
        telemetry=None,
        hbm_budget_bytes: int | None = None,
        hbm_budget_frac: float = 0.8,
        memory_model: bool = True,
    ):
        self.model = model
        self.params = params
        self.species_map = species_map
        self.caps = caps or BucketPolicy()
        self.mesh = mesh
        if mesh is not None:
            from ..parallel import mesh_shape

            self.batch_parts, self.spatial_parts = mesh_shape(mesh)
        else:
            self.batch_parts = self.spatial_parts = 1
        self.cutoff = float(model.cfg.cutoff)
        self.bond_cutoff = float(getattr(model.cfg, "bond_cutoff", 0.0))
        self.use_bond_graph = bool(getattr(model.cfg, "use_bond_graph", False))
        self.compute_stress = bool(compute_stress)
        if compute_magmom and not hasattr(model, "energy_and_aux_fn"):
            raise ValueError(
                f"{type(model).__name__} has no energy_and_aux_fn (fused "
                f"sitewise readout); compute_magmom on the batched path is "
                f"a CHGNet-family capability")
        self.compute_magmom = bool(compute_magmom)
        self.skin = float(skin)
        self.num_threads = num_threads
        self.telemetry = telemetry
        # Pallas fused-kernel routing (kernels/dispatch): None = backend
        # default, False = pure XLA, "interpret" = interpreter-mode kernels
        self.kernels = kernels
        listen_to_jax()
        with phase("distmlip/runtime_build"):
            self._potential = make_batched_potential_fn(
                model.energy_and_aux_fn if self.compute_magmom
                else model.energy_fn,
                compute_stress=self.compute_stress, aux=self.compute_magmom,
                mesh=self.mesh, kernels=kernels)
        # last OBSERVED kernel-dispatch tally: jit traces once per shape
        # bucket, so the counter fills on compile steps and stays empty on
        # cache hits — the last nonzero tally describes the executable
        # every subsequent hit runs
        self._kernel_mode = ""
        self._kernel_coverage = 0.0
        self._kernel_ops: dict = {}   # op -> [pallas, xla] call sites
        self._cache = None  # (graph, host, [(numbers, cell, pbc)])
        self.rebuild_count = 0
        # device-resident packed refresh (partition.device_refresh_packed);
        # mesh placements repack on the host (the in-place edge swap is
        # single-partition only)
        if mesh is not None:
            device_rebuild = False
        self.device_rebuild = (True if device_rebuild == "auto"
                               else bool(device_rebuild))
        self.rebuild_on_device_count = 0
        self.rebuild_overflow_count = 0
        self._refresh_spec = None  # (PackedStatic, arrays) for the cache
        self.last_timings: dict[str, float] = {}
        self.last_bucket_key = ""
        self.last_stats: dict = {}
        self._step_counter = 0
        self._last_compile_count = 0
        # compile telemetry of the most recent dispatch (obs/profiling):
        # 0.0/"" on warm steps; "fresh" on a real trace+compile, "aot"
        # when the fleet AOT dispatcher rehydrated the bucket
        self._last_compile_s = 0.0
        self._last_compile_kind = ""
        # memory-aware autobatching: per-device HBM budget + the static
        # planner's calibration (per compiled shape bucket)
        self.memory_model = bool(memory_model)
        if hbm_budget_bytes is None:
            from ..utils.memory import device_bytes_limit

            limit = device_bytes_limit()
            if limit:
                hbm_budget_bytes = int(limit * float(hbm_budget_frac))
        self.hbm_budget_bytes = (int(hbm_budget_bytes)
                                 if hbm_budget_bytes else None)
        self._est_peak_by_bucket: dict[str, int] = {}
        self.last_est_peak_bytes = 0     # 0 = no estimate yet
        self.last_hbm_headroom_frac = 0.0
        # serving: the ServeEngine scheduler thread and direct callers may
        # share one BatchedPotential — serialize calculate() so the Verlet
        # cache (check-then-use) and compile-cache counters stay coherent
        self._lock = threading.RLock()

    def attach_telemetry(self, telemetry) -> None:
        """Same precedence policy as DistPotential: the potential's own
        hub wins; drivers route their ``telemetry=`` kwarg through here."""
        if telemetry is not None and self.telemetry is None:
            self.telemetry = telemetry

    @property
    def compile_count(self) -> int:
        """Distinct XLA executables compiled for the batched potential so
        far — the compile-cache telemetry counter the bucket quantization
        is bounding (one entry per distinct packed shape bucket)."""
        size_fn = getattr(self._potential, "_cache_size", None)
        return int(size_fn()) if size_fn is not None else 0

    def _species(self, numbers: np.ndarray) -> np.ndarray:
        return map_species(numbers, self.species_map)

    def _structures_match(self, structures) -> bool:
        """Cached pack covers the SAME structure list (identity up to
        positions) — the precondition for both skin reuse and the
        positions-only device refresh."""
        if self._cache is None:
            return False
        _, _host, keys = self._cache
        if len(keys) != len(structures):
            return False
        for (numbers0, cell0, pbc0), atoms in zip(keys, structures):
            if not (len(numbers0) == len(atoms)
                    and np.array_equal(numbers0, atoms.numbers)
                    and np.array_equal(cell0, atoms.cell)
                    and np.array_equal(pbc0, atoms.pbc)):
                return False
        return True

    def _cache_valid(self, structures) -> bool:
        if self.skin <= 0.0 or self._cache is None:
            return False
        if not self._structures_match(structures):
            return False
        _, host, _ = self._cache
        # Verlet criterion per structure: every block must stay within
        # the shared skin/2 budget for the packed graph to remain valid
        half = 0.5 * self.skin
        return all(
            max_displacement(atoms.positions, pos0) < half
            for pos0, atoms in zip(host.build_positions, structures))

    def _device_refresh_eligible(self) -> bool:
        from ..neighbors.device import device_rebuild_enabled

        return (self.device_rebuild and self.skin > 0.0
                and not self.use_bond_graph and device_rebuild_enabled())

    def _graph_shardings(self, graph):
        """NamedSharding pytree for a mesh-packed graph (None mesh: default
        placement)."""
        from ..parallel.runtime import graph_shardings

        if self.mesh is None:
            return None
        return graph_shardings(self.mesh, graph)

    def _put_positions(self, host, structures, dtype):
        """Pack + upload positions with the mesh row sharding (or default
        placement on the single-device path)."""
        import jax
        import jax.numpy as jnp

        packed = host.scatter_positions(
            [a.positions.astype(dtype) for a in structures], dtype=dtype)
        if self.mesh is None:
            # jnp.asarray so BOTH paths (host scatter / device refresh)
            # hand the potential identically-placed arrays — mixed
            # numpy/Array inputs would split the jit cache in two
            return jnp.asarray(packed)
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import mesh_row_axes

        return jax.device_put(
            packed, NamedSharding(self.mesh,
                                  PartitionSpec(mesh_row_axes(self.mesh))))

    def _build(self, structures):
        import jax

        with annotate("distmlip/batch_pack"):
            graph, host = pack_structures(
                structures, self.cutoff, self.bond_cutoff,
                self.use_bond_graph, caps=self.caps,
                species_fn=self._species, skin=self.skin,
                num_threads=self.num_threads,
                spatial_parts=self.spatial_parts,
                batch_parts=self.batch_parts)
        with annotate("distmlip/graph_upload"):
            graph = jax.device_put(graph, self._graph_shardings(graph))
        self.rebuild_count += 1
        # refresh spec is built LAZILY on the first refresh attempt: a
        # churning structure stream (every serving batch different) would
        # otherwise pay the per-structure image-grid construction on every
        # repack and never use it
        self._refresh_spec = None
        return graph, host

    def _try_device_refresh(self, structures):
        """Rebuild the cached packed graph's edges ON DEVICE at the current
        positions (structure list unchanged, Verlet budget spent). Returns
        ``(graph, host, positions, rebuild_s)`` — the uploaded packed
        positions are returned so the potential evaluation reuses them
        (one pack + one transfer per step) — or None (overflow -> host
        repack)."""
        import jax.numpy as jnp

        from ..partition import build_packed_refresh_spec, device_refresh_packed

        graph, host, keys = self._cache
        t0 = time.perf_counter()
        dtype = np.asarray(graph.lattice).dtype
        if self._refresh_spec is None:
            # first refresh of this pack: build the spec now (and move its
            # arrays to device once — later refreshes reuse them)
            from ..neighbors.device import _as_device_arrays

            static, arrays = build_packed_refresh_spec(
                host, graph, self.cutoff + self.skin, dtype=dtype)
            self._refresh_spec = (static, _as_device_arrays(arrays))
        with annotate("distmlip/positions_upload"):
            positions = jnp.asarray(host.scatter_positions(
                [a.positions.astype(dtype) for a in structures],
                dtype=dtype))
        static, arrays = self._refresh_spec
        with annotate("distmlip/device_rebuild"):
            graph2, n_edges, overflow = device_refresh_packed(
                static, arrays, graph, positions)
            overflow = bool(overflow)  # one scalar sync gates correctness
        if overflow:
            self.rebuild_overflow_count += 1
            return None
        self.rebuild_count += 1
        self.rebuild_on_device_count += 1
        host.build_positions = [np.asarray(a.positions).copy()
                                for a in structures]
        if host.stats:
            # keep the bucket telemetry truthful after the edge swap
            n_edges = int(n_edges)
            host.stats["n_edges_per_part"] = [n_edges]
            host.stats["edge_occupancy"] = (
                n_edges / graph.e_cap if graph.e_cap else 0.0)
        self._cache = (graph2, host, keys)
        return graph2, host, positions, time.perf_counter() - t0

    def _calibrate_memory(self, graph, positions, structures) -> None:
        """Run the static HBM planner over the just-compiled program and
        record the per-device peak estimate — per shape bucket here (for
        telemetry on cache hits) and on the BucketPolicy bytes model (for
        the scheduler's bytes-budget fill). Best-effort: an analyzer
        fault must never fail the batch."""
        try:
            import jax

            from ..analysis.memory import analyze_memory
            from ..partition.batch import bucket_key

            jaxpr = jax.make_jaxpr(self._potential)(
                self.params, graph, positions)
            plan = analyze_memory(jaxpr)
            self._est_peak_by_bucket[bucket_key(graph)] = plan.peak_bytes
            n_total = sum(len(a) for a in structures)
            if hasattr(self.caps, "calibrate_bytes"):
                self.caps.calibrate_bytes(
                    self.caps.get("nodes", n_total), plan.peak_bytes)
        except Exception:  # noqa: BLE001 - planning must never fail a step
            pass

    def _headroom(self, est_peak_bytes: int, stats: dict | None) -> float:
        """Remaining HBM fraction after the estimated peak, against the
        device limit from the given stats snapshot (or the configured
        budget when the backend reports no limit). 0.0 = unknown."""
        if not est_peak_bytes:
            return 0.0
        from ..utils.memory import device_bytes_limit

        limit = device_bytes_limit(stats or {}) or self.hbm_budget_bytes
        if not limit:
            return 0.0
        return 1.0 - est_peak_bytes / limit

    def estimate_batch_bytes(self, total_atoms: int) -> int | None:
        """Per-device peak-byte estimate for a batch totalling
        ``total_atoms`` atoms, from the calibrated BucketPolicy bytes
        model (None until the first compile calibrates it)."""
        est = getattr(self.caps, "estimate_batch_bytes", None)
        return est(total_atoms) if est is not None else None

    def calculate(self, structures) -> list:
        """Evaluate a batch; returns one result dict per input structure
        (energy eV, forces eV/Å, stress eV/Å^3 ASE sign convention, plus
        magmoms when ``compute_magmom``). Thread-safe: concurrent callers
        (e.g. a ServeEngine scheduler plus a direct caller) serialize on an
        internal lock so the Verlet cache is never torn mid-validation."""
        structures = list(structures)
        if not structures:
            return []
        with self._lock, annotate("distmlip/calculate"):
            return self._calculate_locked(structures)

    def _prepare_batch(self, structures):
        """Build or reuse the packed graph and upload the batch positions —
        the shared front half of every batched evaluation (the single-model
        ``calculate`` and the ensemble evaluator's vmapped pass ride the
        SAME cache/refresh machinery, so an escalation re-evaluation of a
        just-served batch is a cache hit, not a repack). Called under the
        lock; returns ``(graph, host, positions, reused, refreshed,
        rebuild_s, (t0, t1, t2))`` with the phase timestamps the caller
        folds into ``last_timings``."""
        t0 = time.perf_counter()
        reused = self._cache_valid(structures)
        refreshed = False
        rebuild_s = 0.0
        positions = None
        if reused:
            graph, host, _ = self._cache
        else:
            graph = host = None
            if (self._device_refresh_eligible()
                    and self._structures_match(structures)):
                # same structures, positions drifted past skin/2: rebuild
                # the packed edges on device instead of repacking on host
                out = self._try_device_refresh(structures)
                if out is not None:
                    graph, host, positions, rebuild_s = out
                    refreshed = True
            if graph is None:
                graph, host = self._build(structures)
                if self.skin > 0.0:
                    self._cache = (graph, host, [
                        (a.numbers.copy(), a.cell.copy(), a.pbc.copy())
                        for a in structures])
        t1 = time.perf_counter()
        if positions is None:
            dtype = np.asarray(graph.lattice).dtype
            with annotate("distmlip/positions_upload"):
                positions = self._put_positions(host, structures, dtype)
        t2 = time.perf_counter()
        return graph, host, positions, reused, refreshed, rebuild_s, \
            (t0, t1, t2)

    def _calculate_locked(self, structures) -> list:
        with annotate("distmlip/prepare"):
            graph, host, positions, reused, refreshed, rebuild_s, \
                (t0, t1, t2) = self._prepare_batch(structures)
        # when an xprof capture is live, fold the ambient obs trace id
        # into the TraceAnnotation name so the device timeline lines up
        # with the host span tree (name built only when tracing is on —
        # the disabled path stays allocation-free)
        ann_name = "distmlip/batched_potential"
        if tracing_enabled():
            tid = obsrt.current_trace_id()
            if tid is not None:
                ann_name = f"{ann_name}[trace={tid}]"
        cc0 = self.compile_count
        with annotate(ann_name):
            from ..kernels.dispatch import counting

            with annotate("distmlip/dispatch"), counting() as kc:
                note_dispatch(self._potential, self.params, graph, positions)
                out = self._potential(self.params, graph, positions)
            if kc.total:  # a fresh trace happened (new shape bucket)
                self._kernel_mode = kc.mode
                self._kernel_coverage = kc.coverage
                self._kernel_ops = kc.ops
                # new shape bucket: calibrate the bytes model with the
                # static planner's per-device peak for THIS program
                # (host-side abstract trace; once per bucket)
                if self.memory_model:
                    self._calibrate_memory(graph, positions, structures)
            t_dispatched = time.perf_counter()
            with annotate("distmlip/wait"):
                out["energies"].block_until_ready()
            t_waited = time.perf_counter()
            with annotate("distmlip/results_to_host"):
                # flat shard-major slots -> input structure order (identity
                # for the single-shard pack)
                slots = host.structure_slots
                energies = np.asarray(out["energies"],
                                      dtype=np.float64)[slots]
                forces = host.gather_per_structure(np.asarray(out["forces"]))
                strain_grad = np.asarray(out["strain_grad"])[slots]
                if "aux" in out:
                    m = np.asarray(out["aux"]["magmoms"])
                    # the meshless runtime returns shard-local (N_cap,) aux
                    # rows; the mesh runtime the packed (P, N_cap, ...) layout
                    magmoms = host.gather_per_structure(
                        m if self.mesh is not None else m[None])
                else:
                    magmoms = None
        results = []
        for b in range(len(structures)):
            stress = strain_grad[b] / max(host.volumes[b], 1e-30)
            res = {
                "energy": float(energies[b]),
                "free_energy": float(energies[b]),
                "forces": forces[b],
                "stress": stress,
                "stress_GPa": stress * EV_A3_TO_GPA,
            }
            if magmoms is not None:
                res["magmoms"] = magmoms[b]
            results.append(res)
        t3 = time.perf_counter()
        self.last_timings = {
            "neighbor_s": (t1 - t0) - rebuild_s, "partition_s": t2 - t1,
            "device_s": t3 - t2, "total_s": t3 - t0,
        }
        if refreshed:
            self.last_timings["rebuild_s"] = rebuild_s
        self.last_stats = dict(host.stats or {})
        # a reused (skin-cache) graph was packed for the SAME structure
        # list, so its batch stats remain valid; refresh the real-count
        # fields anyway in case the stats dict is shared downstream
        self.last_stats["batch_size"] = len(structures)
        self.last_stats["kernel_mode"] = self._kernel_mode
        self.last_stats["kernel_coverage"] = self._kernel_coverage
        self.last_stats["kernel_ops"] = self._kernel_ops
        self.last_stats["rebuild_count"] = int(not reused)
        self.last_stats["rebuild_on_device"] = int(refreshed)
        self.last_stats["rebuild_overflow_count"] = self.rebuild_overflow_count
        # AOT executable cache (fleet/aot.install_aot_cache): whether this
        # dispatch ran a rehydrated (deserialized) bucket executable
        # instead of a JIT-compiled one
        aot = getattr(self._potential, "last_dispatch_aot", None)
        if aot is not None:
            self.last_stats["aot_rehydrated"] = bool(aot)
        self.last_bucket_key = self.last_stats.get("bucket_key", "")
        # compile telemetry: the AOT dispatcher records its own events
        # (with the true fresh/aot split — don't double-count); a plain
        # jit potential records here when this dispatch grew the
        # executable cache (a real trace+compile; kc.total can't serve —
        # models without fused-dispatch sites count zero on fresh traces)
        self._last_compile_s = 0.0
        self._last_compile_kind = ""
        compiled = self.compile_count > cc0
        if compiled:
            # a new bucket's call is a phase in four parts (a pack alone
            # is not: in serving every batch packs)
            log_first_call(t0, t2, t_dispatched, t_waited, t3)
        if getattr(self._potential, "_records_compiles", False):
            self._last_compile_s = float(getattr(
                self._potential, "last_dispatch_compile_s", 0.0))
            self._last_compile_kind = str(getattr(
                self._potential, "last_dispatch_kind", ""))
        elif compiled:
            # what jax spent on the executable is inside the dispatch;
            # the first run is not
            self._last_compile_s, from_cache = compile_in(t2, t_dispatched)
            self._last_compile_kind = (_profiling.KIND_CACHE if from_cache
                                       else _profiling.KIND_FRESH)
            _profiling.record_compile(
                site="batched_bucket", kind=self._last_compile_kind,
                wall_s=self._last_compile_s,
                bucket_key=self.last_bucket_key)
        # bucket-cached peak estimate (cache hits reuse the compile-time
        # calibration) + headroom against the device limit/budget — ONE
        # backend memory-stats sweep serves both the headroom and the
        # record's device_memory field
        from ..utils.memory import device_memory_stats

        mem_stats = device_memory_stats()
        est = self._est_peak_by_bucket.get(self.last_bucket_key, 0)
        self.last_est_peak_bytes = est
        self.last_hbm_headroom_frac = self._headroom(est, mem_stats)
        self.last_stats["est_peak_bytes"] = est
        self.last_stats["hbm_headroom_frac"] = self.last_hbm_headroom_frac
        self._emit_record(host, len(structures), reused, refreshed, t3 - t0,
                          mem_stats)
        return results

    def _emit_record(self, host, n_structures: int, reused: bool,
                     refreshed: bool, total_s: float,
                     mem_stats: dict | None = None,
                     kind: str = "batched_calculate",
                     member_count: int = 0) -> None:
        self._step_counter += 1
        tel = self.telemetry
        if tel is None or not tel.wants_records():
            return
        cache_size = self.compile_count
        compiled = cache_size > self._last_compile_count
        self._last_compile_count = cache_size
        # correlate with the obs plane: under a ServeEngine dispatch the
        # ambient context is the serve.batch span, so this record and the
        # exported span tree share ids
        ctx = obsrt.current_ctx()
        rec = StepRecord(
            step=self._step_counter, kind=kind, member_count=member_count,
            trace_id=ctx[0] if ctx is not None else "",
            span_id=ctx[1] if ctx is not None else "",
            timings=dict(self.last_timings),
            compile_cache_size=cache_size, compiled=compiled,
            compile_s=self._last_compile_s,
            compile_kind=self._last_compile_kind,
            graph_reused=reused, rebuild=not reused,
            rebuild_count=int(not reused),
            rebuild_on_device=int(refreshed),
            rebuild_overflow_count=self.rebuild_overflow_count,
            structures_per_sec=(n_structures / total_s if total_s > 0
                                else 0.0),
            kernel_mode=self._kernel_mode,
            kernel_coverage=self._kernel_coverage,
            est_peak_bytes=self.last_est_peak_bytes,
            hbm_headroom_frac=self.last_hbm_headroom_frac,
            device_memory=dict(mem_stats or {}),
        )
        import dataclasses

        fields = {f.name for f in dataclasses.fields(StepRecord)}
        for k, v in (host.stats or {}).items():
            # non-field stats (e.g. n_lines) ride extra so asdict-based
            # serialization never silently drops them
            if k in fields:
                setattr(rec, k, v)
            else:
                rec.extra[k] = v
        rec.batch_size = n_structures  # real structures, not padded slots
        rec.aot_rehydrated = bool(self.last_stats.get("aot_rehydrated",
                                                      False))
        tel.emit(rec)


def _segment_ids(n_atoms) -> np.ndarray:
    return np.repeat(np.arange(len(n_atoms)), n_atoms)


def _per_structure_max(per_atom: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Max over each structure's slice of a (N_tot,) array (0 for empty)."""
    B = len(offsets) - 1
    out = np.zeros(B)
    for b in range(B):
        s, e = offsets[b], offsets[b + 1]
        if e > s:
            out[b] = per_atom[s:e].max()
    return out


_BATCH_OPTIMIZERS = ("fire", "gd")


class BatchedRelaxer:
    """Fixed-cell relaxation of a structure batch with per-structure
    convergence masking (the TorchSim batched-FIRE scheme): every iteration
    evaluates the WHOLE batch in one device program, converged structures
    freeze in place (their step is zeroed, their FIRE state stops
    evolving), and the loop exits when all are converged or ``steps`` is
    exhausted. FIRE parameters match ``Relaxer``; ``optimizer="gd"`` is
    plain clipped gradient descent.
    """

    def __init__(
        self,
        potential: BatchedPotential,
        optimizer: str = "fire",
        fmax: float = 0.05,           # eV/Å
        dt_start: float = 0.1,
        dt_max: float = 1.0,
        n_min: int = 5,
        f_inc: float = 1.1,
        f_dec: float = 0.5,
        alpha_start: float = 0.1,
        f_alpha: float = 0.99,
        maxstep: float = 0.2,         # trust radius, Å per component
        gd_step: float = 0.05,        # gd: step = clip(gd_step * forces)
        telemetry=None,
    ):
        if optimizer not in _BATCH_OPTIMIZERS:
            raise ValueError(
                f"optimizer {optimizer!r} not in {_BATCH_OPTIMIZERS}")
        if telemetry is not None:
            potential.attach_telemetry(telemetry)
        self.potential = potential
        self.optimizer = optimizer
        self.fmax = fmax
        self.dt_start, self.dt_max = dt_start, dt_max
        self.n_min, self.f_inc, self.f_dec = n_min, f_inc, f_dec
        self.alpha_start, self.f_alpha = alpha_start, f_alpha
        self.maxstep = maxstep
        self.gd_step = gd_step

    def relax(self, structures, steps: int = 500) -> list:
        """Relax every structure; returns one ``RelaxResult`` per input
        (``nsteps`` is the iteration at which THAT structure converged, or
        the loop count when it didn't)."""
        atoms_list = [a.copy() for a in structures]
        B = len(atoms_list)
        if B == 0:
            return []
        n_atoms = np.array([len(a) for a in atoms_list])
        off = np.concatenate([[0], np.cumsum(n_atoms)])
        sid = _segment_ids(n_atoms)
        n_tot = int(off[-1])
        # vectorized FIRE state: per-atom velocity + per-structure scalars
        v = np.zeros((n_tot, 3))
        dt = np.full(B, self.dt_start)
        alpha = np.full(B, self.alpha_start)
        n_pos = np.zeros(B, dtype=int)
        active = np.ones(B, dtype=bool)
        nsteps = np.zeros(B, dtype=int)

        results = self.potential.calculate(atoms_list)
        it = 0
        for it in range(1, steps + 1):
            f = (np.concatenate([r["forces"] for r in results])
                 if n_tot else np.zeros((0, 3)))
            fmax_b = _per_structure_max(
                np.abs(f).max(axis=1) if n_tot else np.zeros(0), off)
            newly = active & (fmax_b < self.fmax)
            nsteps[newly] = it - 1
            active &= ~newly
            if not active.any():
                break
            step = self._step(f, v, sid, off, dt, alpha, n_pos, active)
            # frozen structures take no step (and keep no velocity)
            step[~active[sid]] = 0.0
            for b in np.nonzero(active)[0]:
                atoms_list[b].positions += step[off[b]:off[b + 1]]
            nsteps[active] = it
            results = self.potential.calculate(atoms_list)

        out = []
        for b in range(B):
            out.append(RelaxResult(
                atoms=atoms_list[b], converged=not active[b],
                nsteps=int(nsteps[b]), energy=results[b]["energy"],
                forces=results[b]["forces"], stress=results[b]["stress"],
            ))
        return out

    def _step(self, f, v, sid, off, dt, alpha, n_pos, active):
        B = len(dt)
        if self.optimizer == "gd":
            step = self.gd_step * f
            return self._clip(step, off)
        # FIRE, vectorized over the batch via per-structure reductions
        p = np.zeros(B)
        np.add.at(p, sid, np.sum(f * v, axis=1))
        uphill = (p <= 0) & active
        downhill = (p > 0) & active
        n_pos[downhill] += 1
        n_pos[uphill] = 0
        grow = downhill & (n_pos > self.n_min)
        dt[grow] = np.minimum(dt[grow] * self.f_inc, self.dt_max)
        alpha[grow] *= self.f_alpha
        dt[uphill] *= self.f_dec
        alpha[uphill] = self.alpha_start
        v[uphill[sid]] = 0.0
        v += dt[sid, None] * f
        # per-structure norms for the velocity mixing
        f2 = np.zeros(B)
        v2 = np.zeros(B)
        np.add.at(f2, sid, np.sum(f * f, axis=1))
        np.add.at(v2, sid, np.sum(v * v, axis=1))
        gn = np.sqrt(f2) + 1e-12
        vn = np.sqrt(v2)
        mix = alpha * vn / gn
        v[:] = (1.0 - alpha)[sid, None] * v + mix[sid, None] * f
        return self._clip(dt[sid, None] * v, off)

    def _clip(self, step, off):
        """Per-structure trust radius: scale each structure's step so its
        largest component stays within ``maxstep``."""
        comp = np.abs(step).max(axis=1) if len(step) else np.zeros(0)
        mx = _per_structure_max(comp, off)
        scale = np.where(mx > self.maxstep,
                         self.maxstep / np.maximum(mx, 1e-30), 1.0)
        sid = _segment_ids(np.diff(off))
        return step * scale[sid, None]


_BATCH_ENSEMBLES = ("nve", "nvt_berendsen", "nvt_langevin")


class BatchedMD:
    """Fixed-cell MD over a structure batch: one velocity-Verlet step per
    device program for the WHOLE batch. Ensembles: ``nve``,
    ``nvt_berendsen`` (per-structure temperature scaling), ``nvt_langevin``
    (BAOAB). Cells stay fixed (no barostats — the batched graph bakes each
    structure's cell into its edge offsets at build time; NPT belongs to
    the single-structure ``MolecularDynamics`` driver).

    ``temperature`` may be a scalar (shared) or a length-B sequence
    (per-structure targets — e.g. a temperature ladder for replica
    screening).
    """

    def __init__(
        self,
        structures,
        potential: BatchedPotential,
        ensemble: str = "nvt_berendsen",
        timestep: float = 1.0,          # fs
        temperature=300.0,              # K, scalar or per-structure
        taut: float | None = None,      # thermostat time constant, fs
        friction: float = 0.01,         # Langevin, 1/fs
        seed: int | None = None,
        telemetry=None,
    ):
        if ensemble not in _BATCH_ENSEMBLES:
            raise ValueError(
                f"ensemble {ensemble!r} not in {_BATCH_ENSEMBLES} "
                f"(batched MD is fixed-cell)")
        if telemetry is not None:
            potential.attach_telemetry(telemetry)
        self.atoms_list = [a.copy() for a in structures]
        self.potential = potential
        self.ensemble = ensemble
        self.dt = float(timestep)
        B = len(self.atoms_list)
        self.t_target = np.broadcast_to(
            np.asarray(temperature, dtype=np.float64), (B,)).copy()
        self.taut = taut if taut is not None else 100.0 * self.dt
        self.friction = friction
        self.rng = np.random.default_rng(seed)
        self.nsteps = 0
        self.n_atoms = np.array([len(a) for a in self.atoms_list])
        self.off = np.concatenate([[0], np.cumsum(self.n_atoms)])
        self.sid = _segment_ids(self.n_atoms)
        self.results = self.potential.calculate(self.atoms_list)

    # ---- packed-array views ----
    def _gather(self, attr) -> np.ndarray:
        return (np.concatenate([getattr(a, attr) for a in self.atoms_list])
                if int(self.off[-1]) else np.zeros((0, 3)))

    def _scatter(self, attr, packed) -> None:
        for b, a in enumerate(self.atoms_list):
            setattr(a, attr, packed[self.off[b]:self.off[b + 1]].copy())

    def _forces(self) -> np.ndarray:
        return (np.concatenate([r["forces"] for r in self.results])
                if int(self.off[-1]) else np.zeros((0, 3)))

    def temperatures(self) -> np.ndarray:
        """Per-structure instantaneous temperatures (K)."""
        B = len(self.atoms_list)
        ke = np.zeros(B)
        v = self._gather("velocities")
        m = np.concatenate([a.masses for a in self.atoms_list]) \
            if int(self.off[-1]) else np.zeros(0)
        np.add.at(ke, self.sid,
                  0.5 * AMU_A2_FS2_TO_EV * m * np.sum(v * v, axis=1))
        dof = np.maximum(3 * self.n_atoms - 3, 1)
        return 2.0 * ke / (dof * KB)

    def step(self) -> None:
        m = (np.concatenate([a.masses for a in self.atoms_list])
             if int(self.off[-1]) else np.zeros(0))
        inv_m = 1.0 / (m[:, None] * AMU_A2_FS2_TO_EV) if len(m) else \
            np.zeros((0, 1))
        v = self._gather("velocities")
        pos = self._gather("positions")
        f = self._forces()
        if self.ensemble == "nvt_langevin":
            # BAOAB splitting, one OU kick mid-step, per-atom noise
            v = v + 0.5 * self.dt * f * inv_m
            pos = pos + 0.5 * self.dt * v
            c1 = np.exp(-self.friction * self.dt)
            sigma = np.sqrt(KB * self.t_target[self.sid]
                            / (m * AMU_A2_FS2_TO_EV))
            v = c1 * v + np.sqrt(1 - c1 ** 2) * sigma[:, None] * \
                self.rng.normal(size=v.shape)
            pos = pos + 0.5 * self.dt * v
            self._scatter("positions", pos)
            self.results = self.potential.calculate(self.atoms_list)
            v = v + 0.5 * self.dt * self._forces() * inv_m
        else:
            v = v + 0.5 * self.dt * f * inv_m
            pos = pos + self.dt * v
            self._scatter("positions", pos)
            self.results = self.potential.calculate(self.atoms_list)
            v = v + 0.5 * self.dt * self._forces() * inv_m
            if self.ensemble == "nvt_berendsen":
                self._scatter("velocities", v)
                t = np.maximum(self.temperatures(), 1e-12)
                lam = np.sqrt(1.0 + (self.dt / self.taut)
                              * (self.t_target / t - 1.0))
                v = v * np.clip(lam, 0.9, 1.1)[self.sid, None]
        self._scatter("velocities", v)
        self.nsteps += 1

    def run(self, steps: int) -> list:
        """Advance the whole batch ``steps`` steps; returns the final
        per-structure result dicts."""
        for _ in range(steps):
            self.step()
        return self.results
