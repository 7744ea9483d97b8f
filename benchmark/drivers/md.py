"""Driver kind ``md``: one large structure under
``MolecularDynamics(atoms, DistPotential(...), ensemble).step()``, one call
per step, as a user of the library runs it.

A mix of this kind holds::

    {"driver": "md",
     "structure": {"kind": "perturbed_fcc", "reps": [16, 16, 8], "a": 3.9,
                   "sigma": 0.04, "number": 14},
     "temperature_k": 300.0, "ensemble": "nve", "timestep_fs": 0.05,
     "skin": 0.5, "warmup_steps": 2, "trace_steps": 3,
     "caps": {"<configuration>": {"nodes": ..., "edges": ...}}}

Structure, velocities and weights come from the seed. ``caps`` fixes the
padded capacities of the graph for a configuration, so that every seed runs
the same executable; without an entry the program's own sticky policy pads,
and seeds may then compile anew. The number of partitions is the cell's
``chips``.

The phases are plain functions of a cell, so the CPU tests drive them at
toy sizes: :func:`set_up`, :func:`run_window`, :func:`release_program`,
:func:`check`. Only ``run.py`` insists on a TPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import tempfile
import time

import numpy as np

from ..harness import compare, device, structures
from ..harness.compilewatch import CompileWatch
from ..reference.common import neighbour_pairs

STRUCTURES = {"perturbed_fcc": structures.perturbed_fcc}
# the reference's arrays are padded to whole buckets (see reference_forces)
ATOM_BUCKET = 2048
EDGE_BUCKET = 65536


@dataclasses.dataclass
class State:
    cell: object
    family: object
    tables: object
    params: dict            # the benchmark's weights, reference layout
    model_cfg: dict         # the configuration's model keywords
    atoms: object
    pot: object
    md: object
    watch: CompileWatch
    devices: list
    setup_compile: dict     # executables / seconds / cache hits of set-up
    build_positions: np.ndarray  # where the neighbour graph was built


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def make_weights(family, model_cfg: dict, tables, seed: int, device):
    """Every leaf in one jitted call on ``device``, float32 as served."""
    import jax

    init = jax.jit(lambda key: family.reference.init_params(
        model_cfg, tables, key))
    with jax.default_device(device):
        return jax.block_until_ready(init(seed_key(seed)))


def build_atoms(traffic: dict, seed: int):
    from distmlip_tpu.calculators import Atoms

    spec = dict(traffic["structure"])
    numbers, positions, cell = STRUCTURES[spec.pop("kind")](seed=seed, **spec)
    velocities = structures.maxwell_boltzmann(
        numbers, float(traffic["temperature_k"]), seed + 1)
    return Atoms(numbers=numbers, positions=positions, cell=cell,
                 velocities=velocities)


def capacity_policy(cell):
    from distmlip_tpu.partition import CapacityPolicy
    from distmlip_tpu.partition.capacity import FixedCaps

    fixed = cell.traffic.get("caps", {}).get(cell.config_name)
    return (FixedCaps(fixed, fallback=CapacityPolicy()) if fixed
            else CapacityPolicy())


class RecordingCaps:
    """A capacity policy that also keeps what each capacity had to hold."""

    def __init__(self, inner):
        self.inner = inner
        self.needed: dict[str, int] = {}

    def get(self, name: str, needed: int) -> int:
        self.needed[name] = max(int(needed), self.needed.get(name, 0))
        return self.inner.get(name, needed)


def host_graph(cell, seed: int):
    """The padded graph of a cell's structure as ``DistPotential`` builds
    it on its first call, on the host only (numpy leaves, no device):
    returns ``(graph, needed)`` with what each capacity had to hold. For
    the tests of shapes across seeds and the compile rehearsal."""
    from distmlip_tpu.neighbors import neighbor_list
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    atoms = build_atoms(cell.traffic, seed)
    r_build = float(cell.config["model"]["cutoff"]) + float(
        cell.traffic["skin"])
    nl = neighbor_list(atoms.positions, atoms.cell, atoms.pbc, r_build)
    plan = build_plan(nl, atoms.cell, atoms.pbc, cell.chips, r_build, 0.0,
                      False)
    caps = RecordingCaps(capacity_policy(cell))
    graph, _ = build_partitioned_graph(
        plan, nl, np.asarray(atoms.numbers, np.int32), atoms.cell, caps=caps)
    return graph, caps.needed


def set_up(cell, seed: int, devices, *, tables_dir: str | None,
           kernels=None) -> State:
    """Everything before the first timed step: structure, weights, the
    potential's first call (which builds the graph and compiles or loads
    the step) and the warm-up steps."""
    from distmlip_tpu.calculators import DistPotential, MolecularDynamics

    from ..harness import spec as spec_

    traffic, config = cell.traffic, cell.config
    family = spec_.load_module(cell, "families", config["family"])
    model_cfg = dict(config["model"])
    watch = CompileWatch()
    with watch.window() as compiled:
        tables = family.reference.Tables(model_cfg, tables_dir)
        params = make_weights(family, model_cfg, tables, seed, devices[0])
        model = family.build_model(model_cfg)
        atoms = build_atoms(traffic, seed)
        build_positions = atoms.positions.copy()
        pot = DistPotential(
            model, family.program_params(params, tables, model),
            num_partitions=len(devices), devices=list(devices),
            skin=float(traffic["skin"]), caps=capacity_policy(cell),
            kernels=kernels, **config["potential"])
        md = MolecularDynamics(atoms, pot, ensemble=traffic["ensemble"],
                               timestep=float(traffic["timestep_fs"]))
        for _ in range(int(traffic["warmup_steps"])):
            md.step()
    return State(cell=cell, family=family, tables=tables, params=params,
                 model_cfg=model_cfg, atoms=atoms, pot=pot, md=md,
                 watch=watch, devices=list(devices), setup_compile=compiled,
                 build_positions=build_positions)


@dataclasses.dataclass
class Window:
    steps: int
    failed: int
    t_first: float          # perf_counter at the first step's start
    t_last: float           # ... at the last step's end
    spans: list             # (name, start, end), host clock, traced run
    compiled: dict          # CompileWatch over the window
    rebuilds: int
    traced_steps: int
    trace: object           # harness.trace.Trace, or None
    # what the last step produced, and what it started from
    prev_positions: np.ndarray = None
    positions: np.ndarray = None
    velocities: np.ndarray = None
    results: dict = None
    # largest displacement since the graph was built, over skin / 2: the
    # graph is rebuilt when this passes 1
    skin_used: float = 0.0


def run_window(state: State, seconds: float, *, trace: bool = False,
               trace_dir: str | None = None) -> Window:
    """Steps until ``seconds`` have passed: a step starts while they have
    not, and the step that was started is finished. With ``trace`` the
    window is the mix's ``trace_steps`` steps at most, all under the
    profiler (writing a trace out takes seconds, which would sit in a
    longer window as a stall), with the program's host annotations on and
    the harness's own spans around ``md.step`` and ``pot.calculate``."""
    md, pot, atoms = state.md, state.pot, state.atoms
    traffic = state.cell.traffic
    spans = []
    traced_steps = int(traffic["trace_steps"]) if trace else 0
    if trace:
        import jax

        from distmlip_tpu.telemetry.trace import set_tracing

        annotation = jax.profiler.TraceAnnotation
        inner = pot.calculate

        def calculate(a):
            t0 = time.perf_counter()
            with annotation("bench/calculate"):
                out = inner(a)
            spans.append(("bench/calculate", t0, time.perf_counter()))
            return out

        pot.calculate = calculate
        set_tracing(True)
        jax.profiler.start_trace(trace_dir)
    else:
        annotation = contextlib.nullcontext
    rebuilds = pot.rebuild_count
    steps = failed = 0
    prev = None
    try:
        with state.watch.window() as compiled:
            t_first = time.perf_counter()
            deadline = t_first + float(seconds)
            t_last = t_first
            while t_last < deadline and not (trace and
                                             steps >= traced_steps):
                prev = atoms.positions.copy()
                t0 = time.perf_counter()
                with annotation("bench/md_step"):
                    md.step()
                t_last = time.perf_counter()
                spans.append(("bench/md_step", t0, t_last))
                steps += 1
                failed += not np.all(np.isfinite(md.results["forces"]))
    finally:
        if trace:
            jax.profiler.stop_trace()
            set_tracing(False)
            del pot.calculate
    trace_obj = None
    if trace:
        from ..harness.trace import Trace

        trace_obj = Trace.from_xplane(trace_dir)
    moved = np.sqrt(((atoms.positions - state.build_positions) ** 2)
                    .sum(axis=1).max())
    return Window(
        steps=steps, failed=int(failed), t_first=t_first, t_last=t_last,
        spans=spans, compiled=compiled,
        rebuilds=pot.rebuild_count - rebuilds,
        traced_steps=min(traced_steps, steps), trace=trace_obj,
        prev_positions=prev, positions=atoms.positions.copy(),
        velocities=atoms.velocities.copy(),
        results={"energy": float(md.results["energy"]),
                 "forces": np.array(md.results["forces"], np.float64)},
        skin_used=float(moved / (0.5 * float(traffic["skin"]))))


def graph_counts(state: State) -> dict:
    """Real atoms and real edges inside the build radius of the live graph
    (host-side stats of the last build)."""
    stats = state.pot.last_stats
    return {"n_atoms": len(state.atoms),
            "n_edges_built": int(sum(stats.get("n_edges_per_part", [0]))),
            "kernel_ops": stats.get("kernel_ops", {})}


def release_program(state: State) -> None:
    """Drop the potential, its graph and its executables, so that the
    reference has the chip's memory to itself."""
    import jax

    state.pot.close()
    state.md = state.pot = None
    gc.collect()
    jax.clear_caches()


def sample_region(positions: np.ndarray, cell: np.ndarray, region: dict,
                  reach: float, seed: int):
    """A slab of the structure to check in place of all of it, drawn from
    the seed: ``(core, cluster, cluster_cell)``. ``core`` are the atoms
    within ``width / 2`` of one of the ``borders`` equally spaced planes
    across ``axis`` (the slab partition's borders, so the halo is in it);
    ``cluster`` those within ``reach`` more, which is all that the forces
    on the core depend on when ``reach`` is twice the model's receptive
    radius. The cluster goes into a box that is open along the axis (a gap
    wider than the cutoff) and periodic across it as the structure is."""
    axis, width = int(region["axis"]), float(region["width"])
    length = float(cell[axis, axis])
    border = (int(seed) % int(region["borders"])) * length / int(
        region["borders"])
    offset = (positions[:, axis] - border + 0.5 * length) % length \
        - 0.5 * length
    cluster = np.flatnonzero(np.abs(offset) < 0.5 * width + reach)
    core = np.flatnonzero(np.abs(offset[cluster]) < 0.5 * width)
    moved = positions[cluster].copy()
    moved[:, axis] = offset[cluster]
    open_cell = np.array(cell, np.float64)
    open_cell[axis, axis] = width + 2.0 * reach + float(region["gap"])
    return core, cluster, moved, open_cell


def reference_forces(state: State, positions: np.ndarray,
                     precisions=("float32",), numbers=None,
                     cell=None) -> dict:
    """``{precision: (energy, forces)}`` of the plain reference at
    ``positions`` (of the whole structure, or of ``numbers`` in ``cell``),
    plus ``"n_edges"``. Each precision runs on a device of its own where
    the cell has several (the calls are dispatched together and then
    awaited), else one after the other on the first."""
    import jax
    import jax.numpy as jnp

    cfg = state.model_cfg
    cell = state.atoms.cell if cell is None else cell
    species = np.asarray(state.atoms.numbers if numbers is None else numbers,
                         np.int32)
    cutoff = float(cfg["cutoff"])
    src, dst, shift = neighbour_pairs(positions, cell, cutoff)
    n_atoms, n_edges = len(positions), len(src)
    # Shapes that do not move with the seed, so that every run after a
    # cell's first finds the reference compiled: ghost atoms without
    # edges, and ghost edges of length exactly the cutoff, where both
    # envelopes and their slopes are zero.
    ghosts = -n_atoms % ATOM_BUCKET
    spare = -n_edges % EDGE_BUCKET
    positions = np.concatenate([positions, np.zeros((ghosts, 3))])
    species = np.concatenate([species, np.full(ghosts, species[0])])
    src = np.concatenate([src, np.zeros(spare, np.int32)])
    dst = np.concatenate([dst, np.zeros(spare, np.int32)])
    shift = np.concatenate([shift, np.tile([cutoff, 0.0, 0.0], (spare, 1))])
    module = state.family.reference
    pending = {}
    for i, precision in enumerate(precisions):
        def total(pos, params, species, src, dst, shift, p=precision):
            return module.site_energies(params, cfg, state.tables, species,
                                        pos, (src, dst, shift),
                                        precision=p).sum()

        target = state.devices[i % len(state.devices)]
        put = lambda x: jax.device_put(x, target)
        with jax.default_matmul_precision("highest"):
            pending[precision] = jax.jit(jax.value_and_grad(total))(
                put(jnp.asarray(positions, jnp.float32)),
                jax.tree.map(put, state.params), put(species), put(src),
                put(dst), put(np.asarray(shift, np.float32)))
    out = {p: (float(e), -np.asarray(g, np.float64)[:n_atoms])
           for p, (e, g) in pending.items()}
    out["n_edges"] = n_edges
    return out


def check(state: State, window: Window, seed: int = 0) -> dict:
    """The comparison that decides ``correct``: what the last timed step
    produced against the plain reference at the same positions, and
    against the same reference computed in the program's own precision,
    which gives the size of that precision's rounding for these weights.
    Where the mix names a ``check_region`` the forces of a slab drawn from
    the seed are compared (see :func:`sample_region`), else every atom's.
    Returns ``{"correct", "compared": [{"name", "value", "limit"}, ...],
    "numbers", "n_edges", "reference"}``."""
    served = state.cell.config["potential"]["compute_dtype"]
    precisions = tuple(dict.fromkeys(("float32", served)))
    region = state.cell.traffic.get("check_region")
    n_atoms = len(state.atoms)
    if region is None:
        rows = np.arange(n_atoms)
        ref = reference_forces(state, window.positions, precisions)
        pick = lambda forces: forces
        n_edges = ref["n_edges"]
    else:
        reach = 2.0 * state.family.receptive_radius(state.model_cfg)
        core, cluster, moved, open_cell = sample_region(
            window.positions, state.atoms.cell, region, reach, seed)
        rows = cluster[core]
        ref = reference_forces(state, moved, precisions,
                               numbers=state.atoms.numbers[cluster],
                               cell=open_cell)
        pick = lambda forces: forces[core]
        # edges of the whole structure, for the operation counts
        n_edges = int(round(ref["n_edges"] * n_atoms / len(cluster)))
    energy, forces = ref["float32"]
    numbers = compare.md_numbers(
        program={"energy": window.results["energy"] if region is None
                 else None,
                 "forces": window.results["forces"][rows],
                 "prev_positions": window.prev_positions[rows],
                 "positions": window.positions[rows],
                 "velocities": window.velocities[rows]},
        reference={"energy": energy, "forces": pick(forces)},
        rounding_forces=pick(ref[served][1]),
        masses=np.asarray(state.atoms.masses)[rows],
        timestep_fs=float(state.cell.traffic["timestep_fs"]))
    numbers["atoms_compared"] = len(rows)
    compared = compare.against_limits(numbers, state.cell.limits)
    return {"correct": all(c["value"] <= c["limit"] for c in compared)
            and window.failed == 0 and window.steps > 0,
            "compared": compared, "numbers": numbers, "n_edges": n_edges,
            "reference": {"energy": energy, "forces": pick(forces),
                          "rounding_forces": pick(ref[served][1]),
                          "region": None if region is None
                          else (moved, state.atoms.numbers[cluster],
                                open_cell, core)}}


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float, workdir: str) -> dict:
    """One run of a cell, as ``run.py`` drives it: returns the end-to-end
    values, the comparison, and the context the per-layer readers read."""
    state = set_up(cell, seed, devices,
                   tables_dir=os.path.join(workdir, "tables"))
    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        window = run_window(state, seconds, trace=trace, trace_dir=trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    memory_peak = device.memory_peak_bytes(devices)
    memory_stats = devices[0].memory_stats()
    counts = graph_counts(state)
    release_program(state)
    t0 = time.perf_counter()
    verdict = check(state, window, seed)
    reference_s = time.perf_counter() - t0
    chips = len(devices)
    window_s = window.t_last - window.t_first
    return {
        "correct": verdict["correct"], "compared": verdict["compared"],
        "attempted": window.steps, "failed": window.failed,
        "memory_peak_bytes": memory_peak,
        "values": {
            "atom_steps_per_s_per_chip":
                counts["n_atoms"] * window.steps / window_s / chips,
            "setup_s": setup_s,
        },
        "notes": {"steps": window.steps, "window_s": window_s,
                  "skin_used": window.skin_used,
                  "reference_s": reference_s, "memory_stats": memory_stats,
                  "numbers": verdict["numbers"],
                  "setup_compile": state.setup_compile,
                  "n_edges": verdict["n_edges"], **counts},
        "run": {
            "cell": cell, "chips": chips, "n_atoms": counts["n_atoms"],
            "n_edges": verdict["n_edges"], "steps": window.steps,
            "window_s": window_s, "spans": window.spans,
            "counters": {"compiles_in_window": window.compiled["executables"],
                         "rebuilds_in_window": window.rebuilds},
            "trace": window.trace, "traced_steps": window.traced_steps,
            "peaks": device.PEAKS[devices[0].device_kind]
            if devices[0].device_kind in device.PEAKS else None,
            "flops_per_step": state.family.step_flops(
                state.model_cfg, state.tables, counts["n_atoms"],
                verdict["n_edges"]),
            "memory_peak_bytes": memory_peak, "model_cfg": state.model_cfg,
            "kernel_ops": counts["kernel_ops"],
            # of one chip's share: the kernel's time is read on one device
            "kernel_work": state.family.kernel_work(
                state.model_cfg, state.tables, counts["n_atoms"] / chips,
                counts["n_edges_built"] / chips),
        },
    }
