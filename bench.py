"""Benchmark: MD-step throughput (atoms/sec/chip) for MACE on TPU.

Prints ONE JSON line: {"metric", "value", "unit", "device", ...}.

Measures steady-state post-compile MD steps in the framework's production
configuration: Verlet skin-radius graph reuse (BENCH_SKIN, default 0.5 Å) —
host rebuilds amortize across steps exactly as in a real MD run. Set
BENCH_SKIN=0 to time the reference-style rebuild-every-step pipeline
(reference pes.py:50-146). Throughput is divided by the device count.

One process owns the chip: the backend is claimed here, in-process. Without
a TPU the run exits non-zero before measuring anything (a CPU number is
never printed under this metric's name), an exception in the headline
measurement propagates, and the exit code is non-zero if any additive
phase recorded an error.
"""

import json
import os
import sys
import time

import numpy as np

_METRIC = "mace_mp0_md_step_atoms_per_sec_per_chip"


def main() -> int:
    os.environ.setdefault("DISTMLIP_TPU_NUM_THREADS", str(os.cpu_count() or 8))
    from distmlip_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    device = jax.devices()[0]
    identity = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices())}
    if device.platform != "tpu":
        print(f"bench: needs a TPU, but jax found {identity}",
              file=sys.stderr)
        return 2

    from distmlip_tpu import geometry
    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models import MACE, MACEConfig
    from distmlip_tpu.telemetry import AggregatingSink, JsonlSink, Telemetry

    reps = int(os.environ.get("BENCH_REPS", "16"))
    steps = int(os.environ.get("BENCH_STEPS", "5"))
    # bf16 is the production TPU configuration (its error against a
    # float32/highest oracle on the chip is chip_smoke.py's measured band);
    # BENCH_DTYPE=float32 keeps float32 storage — on a TPU its matmuls
    # still run as bf16 passes unless a matmul precision is set
    bench_dtype = os.environ.get("BENCH_DTYPE", "bfloat16")

    # ~4*reps^3 atom perturbed Si-like crystal (16 -> 16384 atoms)
    rng = np.random.default_rng(0)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 3.9, (reps, reps, reps))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, 0.04, (len(frac), 3))
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart, cell=lattice)

    # MACE-MP-0-medium-faithful configuration (the BASELINE.md north-star
    # model): a_lmax = l_max = 3 per PARITY.md — benching a smaller a_lmax
    # would inflate atoms/s by shrinking the CG path set
    # BENCH_REMAT: "1" full remat (default), "0" none, or a checkpoint
    # policy name ("dots" keeps GEMM outputs resident in the backward)
    remat_env = os.environ.get("BENCH_REMAT", "1")
    remat = {"1": True, "0": False}.get(remat_env, remat_env)
    cfg = MACEConfig(
        num_species=95, channels=128, l_max=3,
        a_lmax=int(os.environ.get("BENCH_A_LMAX", "3")), hidden_lmax=1,
        correlation=3, num_interactions=2, num_bessel=8, radial_mlp=64,
        cutoff=5.0, avg_num_neighbors=14.0, remat=remat,
        edge_chunk=int(os.environ.get("BENCH_EDGE_CHUNK", "32768")),
        node_chunk=int(os.environ.get("BENCH_NODE_CHUNK", "4096")),
    )
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # telemetry: per-phase aggregation always; JSONL artifact when
    # BENCH_TELEMETRY_JSONL names a path (feed tools/telemetry_report.py)
    agg = AggregatingSink()
    telemetry = Telemetry([agg])
    jsonl_path = os.environ.get("BENCH_TELEMETRY_JSONL")
    if jsonl_path:
        telemetry.add_sink(JsonlSink(jsonl_path))
    halo_mode = os.environ.get("BENCH_HALO_MODE", "coalesced")
    pot = DistPotential(model, params, num_partitions=len(jax.devices()),
                        compute_stress=True,
                        skin=float(os.environ.get("BENCH_SKIN", "0.5")),
                        compute_dtype=bench_dtype, halo_mode=halo_mode,
                        telemetry=telemetry)
    pot.calculate(atoms)  # compile + warm
    # steady state: perturb positions each step like MD
    times = []
    for _ in range(steps):
        atoms.positions += rng.normal(0, 0.01, atoms.positions.shape)
        t0 = time.perf_counter()
        pot.calculate(atoms)
        times.append(time.perf_counter() - t0)

    # batched-engine throughput (serving regime): structures/sec at batch
    # sizes {1, 8} over small structures through ONE BatchedPotential (its
    # shape-bucketed compile cache covers both batch sizes). Every batched
    # step emits a StepRecord carrying structures_per_sec/bucket_key to the
    # same telemetry sinks (JSONL artifact included). BENCH_BATCHED=0 skips.
    batched_extras = {}
    if os.environ.get("BENCH_BATCHED", "1") != "0":
        try:
            from distmlip_tpu.calculators import BatchedPotential
            from distmlip_tpu.partition import BucketPolicy

            b_reps = int(os.environ.get("BENCH_BATCHED_REPS", "2"))
            b_steps = int(os.environ.get("BENCH_BATCHED_STEPS", "3"))
            frac_b, lat_b = geometry.make_supercell(
                unit, np.eye(3) * 3.9, (b_reps, b_reps, b_reps))
            # pot.model carries the bench compute dtype (bf16 by default)
            bpot = BatchedPotential(
                pot.model, pot.params, caps=BucketPolicy(),
                skin=float(os.environ.get("BENCH_SKIN", "0.5")),
                telemetry=telemetry)
            for B in (1, 8):
                structs = []
                for _ in range(B):
                    cart_b = geometry.frac_to_cart(frac_b, lat_b) + \
                        rng.normal(0, 0.04, (len(frac_b), 3))
                    structs.append(Atoms(numbers=np.full(len(cart_b), 14),
                                         positions=cart_b, cell=lat_b))
                bpot.calculate(structs)  # compile + first pack
                t0 = time.perf_counter()
                for _ in range(b_steps):
                    for a in structs:
                        a.positions += rng.normal(
                            0, 0.01, a.positions.shape)
                    bpot.calculate(structs)
                dt_b = (time.perf_counter() - t0) / max(b_steps, 1)
                batched_extras[f"structures_per_sec_b{B}"] = round(
                    B / dt_b, 2)
            batched_extras["batched_compiles"] = bpot.compile_count
            # static-HBM-planner accuracy on real hardware: predicted
            # per-device peak vs the backend's measured peak residency
            # (the JSONL StepRecords carry the same fields per step, so
            # telemetry_report's hbm_estimator_drift check sees them;
            # this scalar keeps the ratio in the BENCH round artifact)
            from distmlip_tpu.utils.memory import measured_peak_bytes

            est_b = int(getattr(bpot, "last_est_peak_bytes", 0))
            measured_b = measured_peak_bytes()
            if est_b:
                batched_extras["est_peak_bytes"] = est_b
            if est_b and measured_b:
                batched_extras["hbm_est_over_measured"] = round(
                    est_b / measured_b, 3)
        except Exception as e:  # noqa: BLE001 - batched is additive
            batched_extras["batched_error"] = f"{type(e).__name__}: {e}"[:160]

    # serving-engine throughput: open-loop burst (submit everything, then
    # harvest — maximum queueing pressure) through a ServeEngine at
    # max_batch ∈ {1, 8}, requests/sec + p95 latency. Runs in THIS process
    # (one process owns the chip); per-batch StepRecords ride the shared
    # telemetry sinks. BENCH_SERVE=0 skips.
    serve_extras = {}
    if os.environ.get("BENCH_SERVE", "1") != "0":
        try:
            from distmlip_tpu.calculators import BatchedPotential
            from distmlip_tpu.partition import BucketPolicy
            from distmlip_tpu.serve import ServeEngine, run_open_loop

            s_reps = int(os.environ.get("BENCH_SERVE_REPS", "2"))
            n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "24"))
            frac_s, lat_s = geometry.make_supercell(
                unit, np.eye(3) * 3.9, (s_reps, s_reps, s_reps))
            pool = []
            for _ in range(8):
                cart_s = geometry.frac_to_cart(frac_s, lat_s) + \
                    rng.normal(0, 0.04, (len(frac_s), 3))
                pool.append(Atoms(numbers=np.full(len(cart_s), 14),
                                  positions=cart_s, cell=lat_s))
            for B in (1, 8):
                engine = ServeEngine(
                    BatchedPotential(
                        pot.model, pot.params, caps=BucketPolicy(),
                        skin=float(os.environ.get("BENCH_SKIN", "0.5"))),
                    max_batch=B, max_wait_s=0.005, admission="block",
                    telemetry=telemetry)
                run_open_loop(engine, pool, n_req, rate_hz=0.0)  # warm
                rep = run_open_loop(engine, pool, n_req, rate_hz=0.0)
                p95 = rep.latency_percentiles()["p95_s"]
                serve_extras[f"serve_structs_per_sec_b{B}"] = round(
                    rep.structures_per_sec, 2)
                serve_extras[f"serve_p95_ms_b{B}"] = round(1e3 * p95, 2)
                serve_extras[f"serve_compiles_b{B}"] = engine.compile_count
                engine.close()
        except Exception as e:  # noqa: BLE001 - serving is additive
            serve_extras["serve_error"] = f"{type(e).__name__}: {e}"[:160]

    class _MeshPhaseSkipped(Exception):
        """No configured mesh placement fits this host's device count."""

    # 2-D mesh placements: structures/sec for ONE batch of structures
    # across (batch x spatial) placements at EQUAL chip count — e.g. on 8
    # chips, 8x1 (pure batch-parallel), 4x2 and 2x4 (each structure
    # spatially split over 2/4 slabs with halo exchange on the spatial
    # axis). Per-step StepRecords (mesh_shape/spatial_parts fields) ride
    # the shared telemetry sinks. BENCH_MESH=0 skips.
    mesh_extras = {}
    if os.environ.get("BENCH_MESH", "1") != "0":
        try:
            from distmlip_tpu.calculators import BatchedPotential
            from distmlip_tpu.parallel import device_mesh
            from distmlip_tpu.partition import BucketPolicy

            n_dev = len(jax.devices())
            placements = []
            for spec in os.environ.get("BENCH_MESH_PLACEMENTS",
                                       "8,1;4,2;2,4").split(";"):
                b_m, s_m = (int(x) for x in spec.split(","))
                if b_m * s_m <= n_dev:
                    placements.append((b_m, s_m))
            if not placements:
                # its own key, distinct from BENCH_MESH=0 (no mesh_* keys
                # at all) and from mesh_error (a genuine failure): no
                # configured placement fits this host's device count
                mesh_extras["mesh_skipped"] = (
                    f"no placement in BENCH_MESH_PLACEMENTS fits "
                    f"{n_dev} device(s)")
                raise _MeshPhaseSkipped
            m_steps = int(os.environ.get("BENCH_MESH_STEPS", "3"))
            n_struct = int(os.environ.get("BENCH_MESH_STRUCTURES", "8"))
            m_skin = float(os.environ.get("BENCH_SKIN", "0.5"))
            s_max = max((s for _b, s in placements), default=1)
            # slab rule: per-slab width must exceed 2x the build cutoff,
            # so the shared structure pool is sized for the LARGEST S
            r_build = float(model.cfg.cutoff) + m_skin
            reps_x = max(int(np.ceil(2.0 * s_max * r_build / 3.9)) + 1, 4)
            frac_m, lat_m = geometry.make_supercell(
                unit, np.eye(3) * 3.9, (reps_x, 2, 2))
            structs_m = []
            for _ in range(n_struct):
                cart_m = geometry.frac_to_cart(frac_m, lat_m) + \
                    rng.normal(0, 0.04, (len(frac_m), 3))
                structs_m.append(Atoms(numbers=np.full(len(cart_m), 14),
                                       positions=cart_m, cell=lat_m))
            for b_m, s_m in placements:
                mpot = BatchedPotential(
                    pot.model, pot.params, caps=BucketPolicy(), skin=m_skin,
                    mesh=device_mesh(b_m, s_m), telemetry=telemetry)
                mpot.calculate(structs_m)  # compile + first pack
                t0 = time.perf_counter()
                for _ in range(m_steps):
                    for a in structs_m:
                        a.positions += rng.normal(0, 0.01, a.positions.shape)
                    mpot.calculate(structs_m)
                dt_m = (time.perf_counter() - t0) / max(m_steps, 1)
                mesh_extras[f"mesh_structs_per_sec_{b_m}x{s_m}"] = round(
                    n_struct / dt_m, 2)
            mesh_extras["mesh_atoms_per_structure"] = len(frac_m)
        except _MeshPhaseSkipped:
            pass  # mesh_skipped already recorded
        except Exception as e:  # noqa: BLE001 - mesh phase is additive
            mesh_extras["mesh_error"] = f"{type(e).__name__}: {e}"[:160]

    # training subsystem: examples/sec + step time through the accumulated
    # train step (distmlip_tpu.train) at accumulation windows {1, 4} —
    # synthetic labels (throughput, not fitting), per-step TrainRecords
    # ride the shared telemetry sinks (JSONL artifact included), and the
    # static HBM planner's estimate of the step program is recorded.
    # BENCH_TRAIN=0 skips.
    train_extras = {}
    if os.environ.get("BENCH_TRAIN", "1") != "0":
        try:
            import optax

            from distmlip_tpu.calculators import Atoms as _Atoms
            from distmlip_tpu.train import Sample, TrainConfig, Trainer

            n_struct = int(os.environ.get("BENCH_TRAIN_STRUCTURES", "8"))
            t_steps = int(os.environ.get("BENCH_TRAIN_STEPS", "3"))
            t_reps = int(os.environ.get("BENCH_TRAIN_REPS", "3"))
            frac_t, lat_t = geometry.make_supercell(
                unit, np.eye(3) * 3.9, (t_reps, t_reps, t_reps))
            samples_t = []
            for _ in range(n_struct):
                cart_t = geometry.frac_to_cart(frac_t, lat_t) + \
                    rng.normal(0, 0.04, (len(frac_t), 3))
                samples_t.append(Sample(
                    _Atoms(numbers=np.full(len(cart_t), 14),
                           positions=cart_t, cell=lat_t),
                    0.0, np.zeros((len(cart_t), 3), np.float32)))
            train_extras["train_atoms_per_structure"] = len(frac_t)
            for accum in (1, 4):
                if n_struct < 2 * accum:
                    continue
                b_t = max(n_struct // (2 * accum), 1)
                trainer = Trainer(
                    model.energy_fn, pot.params, optax.adam(1e-3),
                    samples_t, float(model.cfg.cutoff),
                    micro_batch_size=b_t,
                    config=TrainConfig(accum_steps=accum),
                    hbm_budget_frac=0.95, telemetry=telemetry,
                    loader_kwargs={"species_fn":
                                   lambda z: np.zeros(len(z), np.int32)})
                trainer.fit(steps=1)  # compile + warm
                t0 = time.perf_counter()
                trainer.fit(steps=t_steps)
                dt_t = (time.perf_counter() - t0) / max(t_steps, 1)
                train_extras[f"train_examples_per_sec_accum{accum}"] = \
                    round(accum * b_t / dt_t, 2)
                train_extras[f"train_step_s_accum{accum}"] = round(dt_t, 4)
                train_extras["train_est_peak_mib"] = round(
                    trainer.est_peak_bytes / 2**20, 1)
                trainer.close()
        except Exception as e:  # noqa: BLE001 - train phase is additive
            train_extras["train_error"] = f"{type(e).__name__}: {e}"[:160]

    # cost-model packing A/B: naive single-cap vs tiered edge-balanced
    # packing on a synthetic LONG-TAIL dataset (lognormal structure
    # sizes) — examples/sec, measured padding_waste_frac and per-tier
    # compile counts land in the round artifact so the BENCH trajectory
    # captures the data-distribution win (CPU dryrun populates the same
    # fields). Small TensorNet: the A/B is data-distribution-bound, not
    # model-bound. BENCH_TRAIN=0 or BENCH_TRAIN_PACKING=0 skips.
    if (os.environ.get("BENCH_TRAIN", "1") != "0"
            and os.environ.get("BENCH_TRAIN_PACKING", "1") != "0"):
        try:
            import optax

            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from pack_audit import synth_longtail_samples

            from distmlip_tpu.models.tensornet import (TensorNet,
                                                       TensorNetConfig)
            from distmlip_tpu.train import Trainer, structure_needs

            n_lt = int(os.environ.get("BENCH_TRAIN_PACKING_STRUCTURES",
                                      "200"))
            lt_steps = int(os.environ.get("BENCH_TRAIN_PACKING_STEPS", "6"))
            b_lt = int(os.environ.get("BENCH_TRAIN_PACKING_BATCH", "8"))
            lt_cut = 3.5
            tiny = TensorNet(TensorNetConfig(
                num_species=4, units=16, num_rbf=6, num_layers=2,
                cutoff=lt_cut))
            p_lt = tiny.init(jax.random.PRNGKey(2))
            samples_lt = synth_longtail_samples(
                n_lt, seed=5, mu=3.0, sigma=1.0, min_atoms=4,
                max_atoms=600)
            needs_lt = structure_needs([s.atoms for s in samples_lt],
                                       lt_cut)
            packing = {}
            for mode, extra_kw in (("naive", {}),
                                   ("cost_model",
                                    {"packing": "cost_model",
                                     "num_tiers": 3})):
                tr = Trainer(
                    tiny.energy_fn, p_lt, optax.adam(1e-3), samples_lt,
                    lt_cut, micro_batch_size=b_lt, hbm_budget_frac=0.95,
                    loader_kwargs={
                        "seed": 1, "precomputed_needs": needs_lt,
                        "species_fn":
                            lambda z: np.zeros(len(z), np.int32),
                        **extra_kw})
                # warm until EVERY tier's first step has run — the
                # measured window must see zero compiles
                tr.fit(steps=max(
                    tr.loader.tier_first_steps().values()) + 1)
                t0 = time.perf_counter()
                hist = tr.fit(steps=lt_steps)[-lt_steps:]
                dt_p = (time.perf_counter() - t0) / max(lt_steps, 1)
                tier_steps = {}
                for h in hist:
                    tier_steps[h["tier"]] = tier_steps.get(
                        h["tier"], 0) + 1
                packing[mode] = {
                    "examples_per_sec": round(b_lt / dt_p, 2),
                    "padding_waste_frac": round(float(np.mean(
                        [h["padding_waste_frac"] for h in hist])), 4),
                    "edge_balance": round(float(min(
                        h["edge_balance"] for h in hist)), 4),
                    "compiles": tr.compile_count,
                    "tiers": tr.loader.num_tiers,
                    "tier_steps": {str(k): v
                                   for k, v in sorted(tier_steps.items())},
                    "tier_est_peak_mib": {
                        str(k): round(v / 2**20, 1)
                        for k, v in sorted(tr.tier_peak_bytes.items())},
                }
                tr.close()
            train_extras["train_packing"] = packing
            w_n = packing["naive"]["padding_waste_frac"]
            w_c = packing["cost_model"]["padding_waste_frac"]
            train_extras["train_padding_waste_naive"] = w_n
            train_extras["train_padding_waste_cost_model"] = w_c
            if w_c > 0:
                train_extras["train_packing_waste_ratio"] = round(
                    w_n / w_c, 2)
            train_extras["train_examples_per_sec_naive"] = \
                packing["naive"]["examples_per_sec"]
            train_extras["train_examples_per_sec_cost_model"] = \
                packing["cost_model"]["examples_per_sec"]
        except Exception as e:  # noqa: BLE001 - packing A/B is additive
            train_extras["train_packing_error"] = \
                f"{type(e).__name__}: {e}"[:160]

    # device-resident MD: steps/sec through DeviceMD with the neighbor
    # rebuild ON DEVICE (in-loop cell list, zero host syncs) vs the host
    # FPIS rebuild at EQUAL skin, plus a rebuilds/sec microbench of the
    # jitted cell-list kernel alone. Per-phase telemetry of the device mode
    # must show no host FPIS time (neighbor_s ~ 0 after the first build).
    # BENCH_DEVICE_MD=0 skips.
    dmd_extras = {}
    if os.environ.get("BENCH_DEVICE_MD", "1") != "0":
        try:
            from distmlip_tpu.calculators import DeviceMD, DistPotential
            from distmlip_tpu.neighbors.device import (build_cell_list_spec,
                                                       device_neighbor_list)
            from distmlip_tpu.telemetry import AggregatingSink as _Agg
            from distmlip_tpu.telemetry import Telemetry as _Tel

            d_reps = int(os.environ.get("BENCH_DEVICE_MD_REPS", "4"))
            d_steps = int(os.environ.get("BENCH_DEVICE_MD_STEPS", "50"))
            d_skin = float(os.environ.get("BENCH_DEVICE_MD_SKIN", "0.3"))
            frac_d, lat_d = geometry.make_supercell(
                unit, np.eye(3) * 3.9, (d_reps, d_reps, d_reps))
            # ONE perturbed configuration shared by both arms: rebuild
            # cadence depends on it, so differing draws would turn the
            # equal-skin A/B into an artifact of the rng
            cart_d = geometry.frac_to_cart(frac_d, lat_d) + \
                rng.normal(0, 0.04, (len(frac_d), 3))
            for mode in ("device", "host"):
                atoms_d = Atoms(numbers=np.full(len(cart_d), 14),
                                positions=cart_d.copy(), cell=lat_d)
                atoms_d.set_maxwell_boltzmann_velocities(
                    600.0, rng=np.random.default_rng(3))
                agg_d = _Agg()
                pot_d = DistPotential(
                    pot.model, pot.params, num_partitions=1, skin=d_skin,
                    device_rebuild=(mode == "device"))
                md = DeviceMD(pot_d, atoms_d, timestep=2.0,
                              device_rebuild=(mode == "device"))
                md.run(5)  # compile + warm (includes the one host build)
                # attach telemetry AFTER warmup so the per-phase breakdown
                # covers only the measured steady state — the acceptance
                # bar for device mode is ~zero host FPIS (neighbor_s) there
                pot_d.telemetry = _Tel([agg_d])
                t0 = time.perf_counter()
                md.run(d_steps)
                dt_d = time.perf_counter() - t0
                dmd_extras[f"device_md_steps_per_sec_{mode}"] = round(
                    d_steps / dt_d, 2)
                dmd_extras[f"device_md_rebuilds_{mode}"] = (
                    f"host={md.rebuilds} device={md.rebuilds_on_device} "
                    f"overflow={md.rebuild_overflows}")
                # host FPIS share of the measured phase table: the device
                # mode's acceptance bar is ~0 here
                dmd_extras[f"device_md_host_fpis_s_{mode}"] = round(
                    agg_d.totals.get("neighbor_s", 0.0), 4)
            # rebuilds/sec: the jitted cell-list kernel alone, steady
            # state. e_cap is sized from the kernel's own exact count (a
            # probe call with a generous cap), and the overflow flag gates
            # the published number — a truncated rebuild must never be
            # timed as a valid one.
            n_d = len(frac_d)
            pos_pad = np.asarray(
                geometry.frac_to_cart(frac_d, lat_d), dtype=np.float32)
            st_p, arr_p = build_cell_list_spec(
                lat_d, [1, 1, 1], 5.5, n_d, n_d, 256 * max(n_d, 128),
                positions=pos_pad)
            probe = device_neighbor_list(st_p, arr_p, pos_pad)
            if bool(probe[4]):
                raise RuntimeError("rebuild microbench probe overflowed")
            e_cap_d = int(int(probe[3]) * 1.2) + 128
            st_d, arr_d = build_cell_list_spec(
                lat_d, [1, 1, 1], 5.5, n_d, n_d, e_cap_d, positions=pos_pad)
            jax.block_until_ready(
                device_neighbor_list(st_d, arr_d, pos_pad)[0])  # compile
            k = int(os.environ.get("BENCH_REBUILD_ITERS", "20"))
            t0 = time.perf_counter()
            for _ in range(k):
                out_d = device_neighbor_list(st_d, arr_d, pos_pad)
            jax.block_until_ready(out_d[0])
            dt_reb = time.perf_counter() - t0
            if bool(out_d[4]):
                dmd_extras["device_rebuild_error"] = "kernel overflow"
            else:
                dmd_extras["device_rebuilds_per_sec"] = round(k / dt_reb, 2)
                dmd_extras["device_rebuild_atoms"] = n_d
        except Exception as e:  # noqa: BLE001 - device-MD bench is additive
            dmd_extras["device_md_error"] = f"{type(e).__name__}: {e}"[:160]

    # --- fused-kernel microbench (PR 8): fused vs unfused edge-aggregate
    # at a sweep of (E, width), MFU via the shared analytic FLOP count so
    # the Pallas win is RECORDED in BENCH_*.json, not asserted.
    # BENCH_KERNELS=0 skips.
    kern_extras = {}
    if os.environ.get("BENCH_KERNELS", "1") != "0":
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from kernel_bench import run_sweep as _kernel_sweep

            k_sizes = [int(s) for s in os.environ.get(
                "BENCH_KERNELS_E", "100000,400000").split(",") if s]
            k_widths = [int(s) for s in os.environ.get(
                "BENCH_KERNELS_W", "64,128").split(",") if s]
            k_iters = int(os.environ.get("BENCH_KERNELS_ITERS", "20"))
            # real Pallas on TPU backends; interpreter kernels are a test
            # lane, not a benchmark — on CPU hosts record the unfused
            # numbers only unless explicitly forced
            on_tpu = jax.default_backend() == "tpu"
            if on_tpu or os.environ.get("BENCH_KERNELS_INTERPRET") == "1":
                kern_extras["kernel_bench"] = _kernel_sweep(
                    k_sizes, k_widths, iters=k_iters, interpret=not on_tpu)
            else:
                kern_extras["kernel_bench"] = {
                    "skipped": "no TPU backend (interpreter kernels are "
                               "not a benchmark; BENCH_KERNELS_INTERPRET=1 "
                               "forces the plumbing smoke)"}
        except Exception as e:  # noqa: BLE001 - kernel bench is additive
            kern_extras["kernel_bench_error"] = (
                f"{type(e).__name__}: {e}"[:160])
    dt = float(np.median(times))
    atoms_per_sec = len(atoms) / dt / max(len(jax.devices()), 1)

    # overlap-pipeline accounting: collective count of the measured mode AND
    # its A/B counterpart (host-side jaxpr traces — no device work), plus
    # the analytic-FLOP mfu for the measured steps
    extras = {"halo_mode": halo_mode, **batched_extras, **serve_extras,
              **mesh_extras, **train_extras, **dmd_extras, **kern_extras}
    try:
        from distmlip_tpu.parallel import make_potential_fn
        from distmlip_tpu.parallel.audit import count_collectives

        graph = pot._cache[0] if pot._cache else None
        if graph is not None:
            for mode in ("coalesced", "legacy"):
                p_mode = make_potential_fn(
                    model.energy_fn, pot.mesh, halo_mode=mode)
                jaxpr = jax.make_jaxpr(p_mode)(pot.params, graph,
                                               graph.positions)
                extras[f"collectives_{mode}"] = sum(
                    count_collectives(jaxpr).values())
    except Exception as e:  # noqa: BLE001 - accounting must not fail the run
        extras["collectives_error"] = str(e)[:120]
    try:
        from distmlip_tpu.utils.flops import mfu as _mfu
        from distmlip_tpu.utils.flops import model_flop_estimate

        stats = (pot._cache[1].stats or {}) if pot._cache else {}
        flops = model_flop_estimate(
            model, len(atoms), sum(stats.get("n_edges_per_part", [])))
        extras["mfu"] = round(
            _mfu(flops, dt, max(len(jax.devices()), 1)), 4)
        extras["flops_per_step"] = float(f"{flops:.3e}")
    except Exception as e:  # noqa: BLE001
        extras["mfu_error"] = str(e)[:120]

    errors = sorted(k for k in extras if k.endswith("_error"))
    print(json.dumps({
        "metric": _METRIC, "value": round(atoms_per_sec, 1),
        "unit": "atoms/s", "device": identity, "dtype": bench_dtype,
        "a_lmax": cfg.a_lmax, **extras}))
    # the structured per-phase breakdown replaces the old hand-formatted
    # pot.last_timings line; the same records went to the JSONL sink when
    # BENCH_TELEMETRY_JSONL is set (render with tools/telemetry_report.py)
    print(f"# n_atoms={len(atoms)} step={dt*1e3:.1f}ms "
          f"rebuilds={pot.rebuild_count} prefetch_hits={pot.prefetch_hits} "
          f"devices={jax.devices()}", file=sys.stderr)
    for line in agg.summary().splitlines():
        print(f"# {line}", file=sys.stderr)
    telemetry.close()
    if errors:
        print(f"bench: phases recorded errors: {errors}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
