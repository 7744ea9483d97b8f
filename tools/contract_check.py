#!/usr/bin/env python
"""Static program-contract checker: trace, run passes, gate CI.

    python tools/contract_check.py [--models chgnet,tensornet,mace,escn,nequip]
        [--programs SUBSTR] [--passes p1,p2] [--kernels {auto,on,off}]
        [--hbm-budget-gb G] [--lint] [--only-lint] [--list-passes]
        [--json] [--verbose]

Builds small test systems, traces the REAL programs the runtime ships —
for every model the forward total-energy and value_and_grad potential at
placements (1,1) single-device, (2,1) graph-parallel ring and the (2,2)
batch x spatial mesh, plus the device-resident DeviceMD chunk stepper and
the single-partition packed-batch program — and runs every registered
:class:`distmlip_tpu.analysis.ContractPass` over each jaxpr. No chip, no
compile: the whole check is abstract tracing on CPU.

Model programs are traced under ``jax.enable_x64`` so f64
leaks stay visible instead of being silently canonicalized to f32 (the
``dtype_discipline`` pass ignores weak-typed python scalars, so a clean
fp32 program stays clean under x64).

``--kernels on`` traces every program with the Pallas fused-kernel
dispatch FORCED on (kernels/dispatch.force_kernel_mode) — the exact
program a TPU run ships, pallas_call bodies included (the jaxpr walker
recurses into them; no chip or compile needed). ``off`` forces the
pure-XLA fallback; ``auto`` (default) leaves the env/backend routing
alone. CI runs both: the contracts must hold on BOTH sides of the
dispatch.

``--hbm-budget-gb G`` states the per-device HBM budget for the
``memory_budget`` pass explicitly (GiB). Without it the pass uses the
backend-reported ``bytes_limit`` — absent on this CPU entry point, so the
pass reports its peak estimate as INFO and gates nothing; with a budget,
a program whose estimated peak exceeds 90% of it is an ERROR (exit 3).

``--lint`` additionally runs the repo-specific AST lint
(:mod:`distmlip_tpu.analysis.lint`) over the package + tools, and chains
``ruff check`` (the generic pycodestyle/pyflakes/isort surface,
``[tool.ruff]`` in pyproject.toml) when ruff is installed — one entry
point for both. ``--only-lint`` skips the (slower) trace stage.

Audited exceptions: ``# contract: allow(<pass-or-rule>)`` on the flagged
source line (or the line above) downgrades that finding to suppressed —
printed, but not gating.

Exit codes: 0 clean, 2 usage error, 3 any unsuppressed ERROR finding.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# multi-device CPU mesh, set before jax initializes (same trick as tests)
_flag = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

ALL_MODELS = ("chgnet", "tensornet", "mace", "escn", "nequip")


def build_system(reps, seed=0, a=3.5, n_species=2):
    import numpy as np

    from distmlip_tpu import geometry

    rng = np.random.default_rng(seed)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.03, (len(frac), 3))
    species = rng.integers(0, n_species, len(frac)).astype(np.int32)
    return cart, lattice, species


def make_model(name):
    """Small-config instance of one of the five real models (plus the LJ
    pair toy used by the DeviceMD program)."""
    import jax

    if name == "chgnet":
        from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig

        model = CHGNet(CHGNetConfig(
            num_species=4, units=16, num_rbf=6, num_blocks=2,
            cutoff=3.2, bond_cutoff=2.6))
        use_bg, bond_r = True, 2.6
    elif name == "tensornet":
        from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig

        model = TensorNet(TensorNetConfig(
            num_species=4, units=16, num_rbf=8, num_layers=2, cutoff=3.2))
        use_bg, bond_r = False, 0.0
    elif name == "mace":
        from distmlip_tpu.models import MACE, MACEConfig

        model = MACE(MACEConfig(
            num_species=4, channels=16, l_max=2, a_lmax=2, hidden_lmax=1,
            correlation=3, num_interactions=2, num_bessel=6, radial_mlp=16,
            cutoff=3.2, avg_num_neighbors=12.0))
        use_bg, bond_r = False, 0.0
    elif name == "escn":
        from distmlip_tpu.models import ESCN, ESCNConfig

        model = ESCN(ESCNConfig(
            num_species=4, channels=16, l_max=2, num_layers=2, num_bessel=6,
            num_experts=4, cutoff=3.2, avg_num_neighbors=12.0))
        use_bg, bond_r = False, 0.0
    elif name == "nequip":
        from distmlip_tpu.models import NequIP, NequIPConfig

        model = NequIP(NequIPConfig(
            num_species=4, irreps=((16, 8, 4),) * 2 + ((16,),), num_bessel=6,
            radial_hidden=(16, 16), cutoff=3.2, cutoff_on=2.8,
            avg_num_neighbors=12.0))
        use_bg, bond_r = False, 0.0
    elif name == "pair":
        from distmlip_tpu.models.pair import PairConfig, PairPotential

        model = PairPotential(PairConfig(cutoff=3.2, kind="lj"))
        use_bg, bond_r = False, 0.0
    else:
        raise SystemExit(f"unknown model {name!r}")
    params = model.init(jax.random.PRNGKey(0))
    return model, params, use_bg, bond_r


def _graph_for(model, use_bg, bond_r, nparts, reps=(4, 2, 2)):
    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    cart, lattice, species = build_system(reps)
    r = model.cfg.cutoff
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], r, bond_r=bond_r)
    plan = build_plan(nl, lattice, [1, 1, 1], nparts, r, bond_r, use_bg)
    graph, _host = build_partitioned_graph(plan, nl, species, lattice)
    return graph


def _packed_graph(model, use_bg, bond_r, batch, spatial_parts=1,
                  batch_parts=1):
    import numpy as np

    from distmlip_tpu.calculators import Atoms
    from distmlip_tpu.partition import pack_structures

    rng = np.random.default_rng(1)
    # wide enough along x that `spatial_parts` slabs each exceed the cutoff
    cart, lattice, species = build_system((max(2 * spatial_parts, 4), 2, 2))
    base = Atoms(numbers=species + 1, positions=cart, cell=lattice)

    def jittered():
        a = base.copy()
        a.positions = a.positions + rng.normal(0, 0.02, a.positions.shape)
        return a

    graph, _host = pack_structures(
        [jittered() for _ in range(batch)], model.cfg.cutoff, bond_r,
        use_bg, species_fn=lambda z: (z - 1).astype("int32"),
        spatial_parts=spatial_parts, batch_parts=batch_parts)
    return graph


def _want_all(_name) -> bool:
    return True


def _trace_model_programs(name, programs_out, want=_want_all):
    """Trace one model's program family across the three placements.

    Forward (total-energy) programs carry the ``forward`` tag so the
    scatter-hint contract bites; value_and_grad potentials are tagged
    ``grad`` (the transposed gather legitimately emits unsorted
    scatter-adds). All are traced under x64 (tag ``x64``).
    ``want(program_name)`` gates each trace BEFORE the work happens, so a
    ``--programs`` filter actually skips tracing, not just reporting.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.parallel import (BATCH_AXIS, device_mesh, graph_mesh,
                                       make_batched_potential_fn,
                                       make_potential_fn, make_total_energy)

    names_22 = (f"batched[{name}][2x2]",)
    wanted_11 = [n for n in (f"energy[{name}][1x1]",
                             f"potential[{name}][1x1]") if want(n)]
    wanted_21 = [n for n in (f"energy[{name}][2x1]",
                             f"potential[{name}][2x1]") if want(n)]
    wanted_22 = [n for n in names_22 if want(n)]
    if not (wanted_11 or wanted_21 or wanted_22):
        return

    model, params, use_bg, bond_r = make_model(name)
    zero_strain = jnp.zeros((3, 3), np.float32)

    # a grad program's replicated strain input transposes to ONE psum over
    # every mesh axis — audited (the batch extent is 1 on all DistPotential
    # placements, so it moves no bytes); see collective_placement docs
    strain_cotangent = {"axis_budget": {BATCH_AXIS: {"psum": 1}}}
    placements = []
    if wanted_11:
        placements.append(
            ("1x1", None, _graph_for(model, use_bg, bond_r, 1),
             {"max_total_collectives": 0}, {}))
    if wanted_21:
        placements.append(
            ("2x1", graph_mesh(2), _graph_for(model, use_bg, bond_r, 2),
             {"forbidden_axes": [BATCH_AXIS]}, strain_cotangent))
    with jax.enable_x64(True):
        for tag, mesh, graph, coll_cfg, grad_cfg in placements:
            mesh_tag = {"mesh"} if mesh is not None else set()
            if want(f"energy[{name}][{tag}]"):
                efn = make_total_energy(model.energy_fn, mesh)
                jx = jax.make_jaxpr(efn)(params, graph, graph.positions,
                                         zero_strain)
                programs_out.append(Program(
                    name=f"energy[{name}][{tag}]", jaxpr=jx,
                    tags=frozenset({"forward", "x64"} | mesh_tag),
                    config=dict(coll_cfg)))
            if want(f"potential[{name}][{tag}]"):
                pfn = make_potential_fn(model.energy_fn, mesh)
                jx = jax.make_jaxpr(pfn)(params, graph, graph.positions)
                programs_out.append(Program(
                    name=f"potential[{name}][{tag}]", jaxpr=jx,
                    tags=frozenset({"grad", "x64"} | mesh_tag),
                    config={**coll_cfg, **grad_cfg}))

        if wanted_22:
            # (2,2): batch x spatial mesh over a 2-structure pack
            mesh22 = device_mesh(2, 2)
            g22 = _packed_graph(model, use_bg, bond_r, batch=2,
                                spatial_parts=2, batch_parts=2)
            bfn = make_batched_potential_fn(model.energy_fn, mesh=mesh22)
            jx = jax.make_jaxpr(bfn)(params, g22, g22.positions)
            programs_out.append(Program(
                name=f"batched[{name}][2x2]", jaxpr=jx,
                tags=frozenset({"grad", "mesh", "x64"}),
                config={"forbidden_axes": [BATCH_AXIS]}))


def _trace_packed_batch(programs_out):
    """Single-partition packed-batch program (B=4): communication-free by
    construction — batching adds structures, not collectives."""
    import jax

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.parallel import make_batched_potential_fn

    model, params, use_bg, bond_r = make_model("tensornet")
    graph = _packed_graph(model, use_bg, bond_r, batch=4)
    bfn = make_batched_potential_fn(model.energy_fn)
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(bfn)(params, graph, graph.positions)
    programs_out.append(Program(
        name="packed_batch[tensornet][B=4]", jaxpr=jx,
        tags=frozenset({"grad", "x64"}),
        config={"max_total_collectives": 0}))


def _trace_ensemble(programs_out, want=_want_all):
    """The vmapped ensemble programs (active/uncertainty.py and
    EnsemblePotential.stacked): vmap over M stacked member param pytrees
    riding the SAME potential program. The pin: batching members adds
    ZERO collectives vs the single-member program — one launch, one set
    of ppermutes — enforced by setting the ensemble program's
    ``max_total_collectives`` to the single-member program's traced
    count (and 0 outright for the single-partition packed-batch
    evaluator, which is communication-free either way)."""
    names = ("ensemble[tensornet][2x1][M=2]",
             "ensemble_batched[tensornet][B=2][M=2]")
    wanted = [n for n in names if want(n)]
    if not wanted:
        return
    import jax
    import jax.numpy as jnp

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.parallel import (BATCH_AXIS, graph_mesh,
                                       make_batched_potential_fn,
                                       make_potential_fn)
    from distmlip_tpu.parallel.audit import count_collectives

    model, params, use_bg, bond_r = make_model("tensornet")
    stacked = jax.tree.map(lambda p: jnp.stack([p, p]), params)
    with jax.enable_x64(True):
        if names[0] in wanted:
            graph = _graph_for(model, use_bg, bond_r, 2)
            pfn = make_potential_fn(model.energy_fn, graph_mesh(2))
            jx_single = jax.make_jaxpr(pfn)(params, graph, graph.positions)
            n_single = sum(count_collectives(jx_single).values())
            vfn = jax.vmap(pfn, in_axes=(0, None, None))
            jx = jax.make_jaxpr(vfn)(stacked, graph, graph.positions)
            programs_out.append(Program(
                name=names[0], jaxpr=jx,
                tags=frozenset({"grad", "mesh", "x64"}),
                config={"forbidden_axes": [BATCH_AXIS],
                        "axis_budget": {BATCH_AXIS: {"psum": 1}},
                        "max_total_collectives": n_single}))
        if names[1] in wanted:
            g = _packed_graph(model, use_bg, bond_r, batch=2)
            bfn = make_batched_potential_fn(model.energy_fn)
            vbfn = jax.vmap(bfn, in_axes=(0, None, None))
            jx = jax.make_jaxpr(vbfn)(stacked, g, g.positions)
            programs_out.append(Program(
                name=names[1], jaxpr=jx,
                tags=frozenset({"grad", "x64"}),
                config={"max_total_collectives": 0}))


def _trace_device_md(programs_out):
    """The DeviceMD chunk stepper with the in-loop neighbor rebuild:
    N steps = ONE device program, mandatory-zero host syncs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.calculators import Atoms, DeviceMD, DistPotential

    model, params, _bg, _br = make_model("pair")
    cart, lattice, _species = build_system((3, 3, 3), a=3.8)
    atoms = Atoms(numbers=np.full(len(cart), 14), positions=cart,
                  cell=lattice)
    pot = DistPotential(model, params, num_partitions=1, skin=0.4)
    md = DeviceMD(pot, atoms, timestep=1.0)
    graph, host, positions = pot._prepare(atoms)
    md._ensure_spec(graph)
    dtype = np.asarray(graph.lattice).dtype
    ref = host.scatter_global(pot._cache[3].astype(dtype), graph.n_cap)
    vel = host.scatter_global(atoms.velocities.astype(dtype), graph.n_cap)
    masses = host.scatter_global(atoms.masses.astype(dtype), graph.n_cap,
                                 fill=1.0)
    jx = jax.make_jaxpr(md._dev_stepper)(
        pot.params, graph, positions, ref, vel, masses, jnp.int32(8),
        jnp.float32(0.0), jnp.float32(0.0))
    programs_out.append(Program(
        name="device_md[pair][1x1]", jaxpr=jx,
        tags=frozenset({"grad", "device_resident"}),
        config={"max_total_collectives": 0}))


def _adam_f32():
    """Adam whose hyperparameters are float32 at the host boundary. The
    programs below are traced under x64, where a Python-float decay makes
    optax's ``1 - decay**count`` a strong float64 — an artifact of the
    tracing regime (production runs with x64 off), which the dtype pass
    would report at optax's own source line."""
    import numpy as np
    import optax

    return optax.adam(np.float32(1e-3), b1=np.float32(0.9),
                      b2=np.float32(0.999))


def _trace_train_step(programs_out, want=_want_all):
    """The accumulated bf16 train-step programs (distmlip_tpu.train):
    lax.scan over 2 micro-batches, fp32 master weights, dynamic loss
    scaling, at (1,1) single-device (communication-free) and on the (2,1)
    batch ring with ZeRO-1 optimizer-state sharding — where the batch
    axis carries EXACTLY the ZeRO-1 budget: the shard_map transpose's
    grad-reduction psums (at most one per param leaf per shard_map'd
    energy program — two of those per micro-step, the forward and the
    force backward) plus ONE tiled all_gather of the updated params.
    Anything else on the batch axis is an ERROR."""
    names = ("train_step[tensornet][1x1]", "train_step[tensornet][2x1]")
    wanted = [n for n in names if want(n)]
    if not wanted:
        return
    import jax
    import numpy as np
    import optax

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.calculators import Atoms
    from distmlip_tpu.parallel import BATCH_AXIS, device_mesh
    from distmlip_tpu.train import (PackedBatchLoader, Sample, TrainConfig,
                                    init_train_state, make_accum_train_step)

    from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig

    # bf16 COMPUTE model (the model's own curated mixed-precision switch)
    # trained with fp32 master weights — the combination the dtype pass
    # must prove clean (no half-precision scatter accumulation anywhere,
    # fp32 optimizer arithmetic)
    model = TensorNet(TensorNetConfig(
        num_species=4, units=16, num_rbf=8, num_layers=2, cutoff=3.2,
        dtype="bfloat16"))
    params = model.init(jax.random.PRNGKey(0))
    accum = 2
    rng = np.random.default_rng(1)
    cart, lattice, species = build_system((4, 2, 2))
    samples = []
    for _ in range(2 * accum):
        pos = cart + rng.normal(0, 0.02, cart.shape)
        samples.append(Sample(
            Atoms(numbers=species + 1, positions=pos, cell=lattice),
            float(rng.normal()),
            rng.normal(0, 0.1, cart.shape).astype(np.float32)))
    optimizer = _adam_f32()
    n_leaves = len(jax.tree.leaves(params))
    zero1_budget = {BATCH_AXIS: {
        "psum": 2 * n_leaves * accum,   # audited grad-reduction allowance
        "all_gather": 1,                # the ZeRO-1 param rebuild
    }}
    placements = (("1x1", None, 1, {"max_total_collectives": 0}),
                  ("2x1", device_mesh(2, 1), 2,
                   {"forbidden_axes": [BATCH_AXIS],
                    "axis_budget": zero1_budget}))
    for tag, mesh, batch_parts, coll_cfg in placements:
        name = f"train_step[tensornet][{tag}]"
        if name not in wanted:
            continue
        cfg = TrainConfig(accum_steps=accum, precision="bf16")
        loader = PackedBatchLoader(
            samples, model.cfg.cutoff, micro_batch_size=2,
            accum_steps=accum,
            species_fn=lambda z: (z - 1).astype("int32"),
            batch_parts=batch_parts, prefetch=0)
        state = init_train_state(optimizer, params, mesh, cfg, seed=0)
        step = make_accum_train_step(model.energy_fn, optimizer, mesh, cfg)
        batch = loader.next_batch()
        loader.close()
        with jax.enable_x64(True):
            jx = jax.make_jaxpr(step)(state, batch.graphs, batch.targets)
        tags = {"grad", "x64", "train"} | ({"mesh"} if mesh else set())
        programs_out.append(Program(
            name=name, jaxpr=jx, tags=frozenset(tags),
            config=dict(coll_cfg)))


def _trace_train_step_tiers(programs_out, want=_want_all):
    """The TIERED cost-model train-step family (PR 15): one accumulated
    step program per frozen capacity tier of a long-tail dataset, traced
    through the same passes and the same collective budget as the
    single-cap program. The pin: tier executables share the collective/
    dtype/memory contracts — adding a capacity tier changes SHAPES, never
    program structure, so per-tier contract drift is an ERROR here."""
    names = ("train_step[tensornet][1x1][tier0]",
             "train_step[tensornet][1x1][tier1]")
    wanted = [n for n in names if want(n)]
    if not wanted:
        return
    import jax
    import numpy as np
    import optax

    from distmlip_tpu.analysis import Program
    from distmlip_tpu.calculators import Atoms
    from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig
    from distmlip_tpu.train import (PackedBatchLoader, Sample, TrainConfig,
                                    init_train_state, make_accum_train_step)

    model = TensorNet(TensorNetConfig(
        num_species=4, units=16, num_rbf=8, num_layers=2, cutoff=3.2,
        dtype="bfloat16"))
    params = model.init(jax.random.PRNGKey(0))
    accum = 2
    rng = np.random.default_rng(2)
    samples = []
    # long-tail: 4 small + 4 large structures so two tiers emerge
    for reps in ((2, 2, 1), (4, 2, 2)):
        cart, lattice, species = build_system(reps)
        for _ in range(4):
            pos = cart + rng.normal(0, 0.02, cart.shape)
            samples.append(Sample(
                Atoms(numbers=species + 1, positions=pos, cell=lattice),
                float(rng.normal()),
                rng.normal(0, 0.1, cart.shape).astype(np.float32)))
    cfg = TrainConfig(accum_steps=accum, precision="bf16")
    optimizer = _adam_f32()
    loader = PackedBatchLoader(
        samples, model.cfg.cutoff, micro_batch_size=2, accum_steps=accum,
        species_fn=lambda z: (z - 1).astype("int32"), prefetch=0,
        packing="cost_model", num_tiers=2)
    state = init_train_state(optimizer, params, None, cfg, seed=0)
    step = make_accum_train_step(model.energy_fn, optimizer, None, cfg)
    firsts = loader.tier_first_steps()
    for tier, first in sorted(firsts.items()):
        name = f"train_step[tensornet][1x1][tier{tier}]"
        if name not in wanted:
            continue
        batch = loader._build(0, first)
        with jax.enable_x64(True):
            jx = jax.make_jaxpr(step)(state, batch.graphs, batch.targets)
        programs_out.append(Program(
            name=name, jaxpr=jx,
            tags=frozenset({"grad", "x64", "train"}),
            config={"max_total_collectives": 0}))
    loader.close()


def run_lint(paths=None):
    """Repo-specific AST lint + ruff (when installed) over the package."""
    from distmlip_tpu.analysis import lint_paths

    paths = paths or [os.path.join(REPO, "distmlip_tpu"),
                      os.path.join(REPO, "tools")]
    findings = lint_paths(paths, package_root=REPO)
    ruff_report = None
    ruff = shutil.which("ruff")
    if ruff is not None:
        proc = subprocess.run(
            [ruff, "check", "--no-cache", *paths], cwd=REPO,
            capture_output=True, text=True)
        ruff_report = {"returncode": proc.returncode,
                       "stdout": proc.stdout.strip()}
    return findings, ruff_report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="contract_check", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--models", default=",".join(ALL_MODELS),
                    help="comma list from {chgnet,tensornet,mace,escn,nequip}")
    ap.add_argument("--programs", default=None,
                    help="only check programs whose name contains SUBSTR")
    ap.add_argument("--passes", default=None,
                    help="comma list of registered passes (default: all)")
    ap.add_argument("--kernels", default="auto",
                    choices=("auto", "on", "off"),
                    help="trace with Pallas fused kernels forced on/off "
                         "(auto: env/backend routing)")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="per-device HBM budget (GiB) for the "
                         "memory_budget pass (default: backend-reported "
                         "bytes_limit; none on CPU)")
    ap.add_argument("--lint", action="store_true",
                    help="also run the AST lint (+ruff when installed)")
    ap.add_argument("--only-lint", action="store_true",
                    help="skip the trace stage, lint only")
    ap.add_argument("--list-passes", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--verbose", action="store_true",
                    help="print INFO findings too")
    try:
        args = ap.parse_args(argv)
        models = tuple(m.strip() for m in args.models.split(",") if m.strip())
        bad = [m for m in models if m not in ALL_MODELS]
        if bad:
            raise ValueError(f"unknown model(s) {bad}; pick from "
                             f"{list(ALL_MODELS)}")
    except SystemExit as e:
        # argparse already printed its usage + error message
        return 0 if e.code in (0, None) else 2
    except ValueError as e:
        print(f"usage error: {e}", file=sys.stderr)
        return 2

    from distmlip_tpu.analysis import (Severity, clear_suppression_cache,
                                       error_count, exit_code,
                                       format_findings, get_passes, REGISTRY,
                                       run_passes, warning_count)

    # suppression comments are cached per file for the process lifetime;
    # a fresh CLI run must re-read them (in-process callers like the tests
    # may have edited sources since the cache filled)
    clear_suppression_cache()

    if args.list_passes:
        for name, cls in REGISTRY.items():
            print(f"{name:<22} {cls.description}")
        return 0

    try:
        passes = get_passes(
            None if args.passes is None
            else [p.strip() for p in args.passes.split(",") if p.strip()])
    except KeyError as e:
        print(f"usage error: {e.args[0]}", file=sys.stderr)
        return 2

    import jax

    jax.config.update("jax_platforms", "cpu")

    report = {"programs": {}, "passes": [p.name for p in passes],
              "kernels": args.kernels}
    all_findings = []

    if not args.only_lint:
        from distmlip_tpu.kernels import force_kernel_mode

        # "on" forces the real (non-interpret) Pallas program — tracing
        # needs no chip; "off" pins the XLA fallback; "auto" leaves the
        # env/backend routing (xla on this CPU entry point)
        forced = {"auto": None, "on": "pallas", "off": "xla"}[args.kernels]
        want = (_want_all if not args.programs
                else (lambda n: args.programs in n))
        programs = []
        with force_kernel_mode(forced):
            for name in models:
                _trace_model_programs(name, programs, want)
            if want("packed_batch[tensornet][B=4]"):
                _trace_packed_batch(programs)
            _trace_ensemble(programs, want)
            if want("device_md[pair][1x1]"):
                _trace_device_md(programs)
            _trace_train_step(programs, want)
            _trace_train_step_tiers(programs, want)
        if args.hbm_budget_gb is not None:
            for prog in programs:
                prog.config.setdefault(
                    "bytes_limit", int(args.hbm_budget_gb * 2**30))
        for prog in programs:
            findings = run_passes(prog, passes)
            all_findings.extend(findings)
            report["programs"][prog.name] = {
                "errors": error_count(findings),
                "warnings": warning_count(findings),
                "findings": [f.render() for f in findings],
            }
            if not args.json:
                shown = findings if args.verbose else [
                    f for f in findings if f.severity != Severity.INFO]
                print(format_findings(
                    shown, header=f"{prog.name}  "
                    f"[errors={error_count(findings)} "
                    f"warnings={warning_count(findings)}]"))

    if args.lint or args.only_lint:
        lint_findings, ruff_report = run_lint()
        all_findings.extend(lint_findings)
        report["lint"] = {
            "errors": error_count(lint_findings),
            "findings": [f.render() for f in lint_findings],
        }
        if not args.json:
            print(format_findings(lint_findings, header="lint"))
        if ruff_report is not None:
            report["lint"]["ruff"] = ruff_report
            if not args.json and ruff_report["returncode"] != 0:
                print("ruff:")
                print(ruff_report["stdout"])
        elif not args.json:
            print("ruff: not installed, skipped (AST lint still ran)")
        if ruff_report is not None and ruff_report["returncode"] != 0:
            # represent ruff failures as one error so the exit gate fires
            report["lint"]["errors"] += 1
            all_findings.append(_ruff_finding(ruff_report))

    n_err = error_count(all_findings)
    n_warn = warning_count(all_findings)
    report["errors"], report["warnings"] = n_err, n_warn
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        n_prog = len(report["programs"])
        print(f"contract check: {n_prog} program(s), {len(passes)} pass(es)"
              f"{', lint' if args.lint or args.only_lint else ''} -> "
              f"{n_err} error(s), {n_warn} warning(s)")
    return exit_code(all_findings)


def _ruff_finding(ruff_report):
    from distmlip_tpu.analysis import Finding, Severity

    return Finding(pass_name="lint", severity=Severity.ERROR,
                   message="ruff check failed:\n" + ruff_report["stdout"],
                   rule="ruff")


if __name__ == "__main__":
    raise SystemExit(main())
