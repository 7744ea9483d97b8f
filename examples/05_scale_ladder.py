"""Scale-ladder runs (BASELINE.md progression configs).

Config 2: TensorNet, ~50k-atom electrolyte-like supercell, 4-way graph
partition. On a machine without 4 real chips this runs on a virtual
8-device CPU mesh (slow but exact). Round-2 result (2026-07-29, CPU mesh):
48,668 atoms — 4-way == 1-way to 2.5e-9 eV/atom, dF_max 9.9e-8 eV/Å.

Run: python examples/05_scale_ladder.py [--config 2|3|4|5]
  2: TensorNet ~49k atoms, 4-way    3: MACE ~192k atoms, 8-way
  4: eSCN/UMA ~101k atoms, 8-way (csd + MOLE + chunked Wigner/SO(2))
  5: MACE ~1M atoms, 16-way over a virtual 2-host x 8-chip topology
     (BASELINE config 5 proxy; DISTMLIP_C5_REPS shrinks the box)
Runs on the backend jax finds. On a TPU, configs 3/4/5 run single-chip at
production model shapes in bf16; under ``JAX_PLATFORMS=cpu`` every config
is the virtual-CPU-mesh correctness compare.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# config 5 (the multi-host proxy) needs 16 virtual CPU devices — decided
# BEFORE the backend initializes; the option is read by the CPU backend only
_N_VIRT = 16 if ("--config" in sys.argv
                 and sys.argv[sys.argv.index("--config") + 1] == "5") else 8
jax.config.update("jax_num_cpu_devices", _N_VIRT)
if os.environ.get("JAX_PLATFORMS") == "cpu":
    # XLA-CPU in-process collectives hard-terminate if all shards don't
    # reach a rendezvous within 40 s. 16 serialized virtual shards at 1M
    # atoms ALWAYS trip it, and even 4-way 48k-atom shards do on a loaded
    # host. Raise the deadline for every CPU-mesh run: these are
    # correctness proxies, not perf runs (real TPU collectives have no
    # in-process rendezvous).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_collective_call_terminate_timeout_seconds=100000"
        + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=3600")

import time

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.models import TensorNet, TensorNetConfig


def _print_hbm():
    """Peak device memory (BASELINE.md ladder asks for a memory proof)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        print(f"peak HBM: {peak / 2**30:.2f} GiB "
              f"(in use {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB)")


def compare_partitions(tag, model, params, atoms, smap, P, tol_de, tol_df,
                       baseline=1):
    """P-way vs baseline-way energy/forces compare — the ladder's shared
    check."""
    results = {}
    for n in (P, baseline):
        t0 = time.time()
        pot = DistPotential(model, params, num_partitions=n, species_map=smap)
        results[n] = pot.calculate(atoms)
        print(f"{n}-way: E={results[n]['energy']:.4f} "
              f"({time.time() - t0:.0f}s incl compile)")
    de = abs(results[P]["energy"] - results[baseline]["energy"]) / len(atoms)
    df = np.abs(results[P]["forces"] - results[baseline]["forces"]).max()
    print(f"{P}-way vs {baseline}-way: dE/atom={de:.2e} eV  dF_max={df:.2e} eV/Å")
    assert de < tol_de and df < tol_df
    print(f"CONFIG {tag} PASSED")


def config2():
    cfg = TensorNetConfig(num_species=16, units=64, num_rbf=8, num_layers=2,
                          cutoff=5.0)
    model = TensorNet(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.5, (23, 23, 23))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.05, (len(frac), 3)
    )
    atoms = Atoms(numbers=rng.integers(1, 17, len(cart)), positions=cart,
                  cell=lattice)
    smap = np.concatenate([[0], np.arange(0, 16)]).astype(np.int32)
    print(f"config 2: TensorNet, n_atoms = {len(atoms)}")
    compare_partitions(2, model, params, atoms, smap, 4, 1e-6, 5e-4)


def config3():
    """MACE, ~200k-atom amorphous-SiO2-like box, 8-way partition.

    On the CPU mesh the model is shrunk (channels=32, l_max=2, 1 interaction
    — the partition/halo/capacity machinery still sees the full 200k-atom
    graph); on a TPU it runs the
    MP-0-faithful shape (128ch, l_max=a_lmax=3, correlation 3) in bfloat16
    single-chip — BASELINE.md config 3's memory proof.
    """
    from distmlip_tpu.models import MACE, MACEConfig

    real = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    # beta-cristobalite-ish SiO2: 24-atom cubic cell ~7.16 A, perturbed hard
    unit = np.array([
        [0.0, 0.0, 0.0], [0.5, 0.5, 0.0], [0.5, 0.0, 0.5], [0.0, 0.5, 0.5],
        [0.25, 0.25, 0.25], [0.75, 0.75, 0.25], [0.75, 0.25, 0.75],
        [0.25, 0.75, 0.75],
    ])
    si = unit
    o = (np.concatenate([si + [0.125, 0.125, 0.125],
                         si + [0.875, 0.875, 0.625]]) % 1.0)
    frac_unit = np.concatenate([si, o])
    numbers_unit = np.array([14] * len(si) + [8] * len(o))
    reps = (20, 20, 20)  # 24 * 8000 = 192,000 atoms
    frac, lattice = geometry.make_supercell(frac_unit, np.eye(3) * 7.16, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.12, (len(frac), 3))
    numbers = np.tile(numbers_unit, int(np.prod(reps)))
    atoms = Atoms(numbers=numbers, positions=cart, cell=lattice)
    smap = np.full(15, -1, np.int32)
    smap[8], smap[14] = 0, 1
    print(f"config 3: MACE, n_atoms = {len(atoms)} "
          f"({'MP-0-faithful bf16, real devices' if real else 'small shape, CPU mesh'})")

    if real:
        cfg = MACEConfig(num_species=2, channels=128, l_max=3, a_lmax=3,
                         hidden_lmax=1, correlation=3, num_interactions=2,
                         num_bessel=8, radial_mlp=64, cutoff=6.0,
                         avg_num_neighbors=60.0, dtype="bfloat16")
        model = MACE(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pot = DistPotential(model, params, num_partitions=1, species_map=smap)
        for tag in ("cold", "warm", "warm"):
            t0 = time.time()
            res = pot.calculate(atoms)
            print(f"single-chip {tag}: E={res['energy']:.2f} "
                  f"{time.time() - t0:.2f}s "
                  f"({len(atoms) / (time.time() - t0):.0f} atoms/s)")
        _print_hbm()
        return

    cfg = MACEConfig(num_species=2, channels=32, l_max=2, a_lmax=2,
                     hidden_lmax=1, correlation=3, num_interactions=2,
                     num_bessel=6, radial_mlp=32, cutoff=5.0,
                     avg_num_neighbors=40.0)
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    compare_partitions(3, model, params, atoms, smap, 8, 1e-5, 1e-3)


def config4():
    """UMA/eSCN, ~100k-atom slab-like box, 8-way partition (BASELINE.md
    config 4's family at CPU-mesh-tractable size).

    Exercises the UMA-specific machinery at scale: csd conditioning, MOLE
    expert gating (psum-consistent across partitions), the edge-degree
    embedding, and the edge-chunked Wigner/SO(2) scan (ops/chunk.py) that
    bounds per-edge memory — at this size the unchunked rotated features
    alone would be ~37 GB. On a TPU a single chip runs the same system in
    bfloat16 at l_max=4.
    """
    from distmlip_tpu.models import ESCN, ESCNConfig

    real = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.2, (30, 30, 28))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.06, (len(frac), 3))
    atoms = Atoms(numbers=rng.integers(1, 9, len(cart)), positions=cart,
                  cell=lattice)
    atoms.info = {"charge": 1, "spin": 1, "dataset": 2}
    smap = np.concatenate([[0], np.arange(0, 8)]).astype(np.int32)
    print(f"config 4: eSCN/UMA, n_atoms = {len(atoms)} "
          f"({'bf16 l_max=4, real devices' if real else 'l_max=2, CPU mesh'})")

    if real:
        cfg = ESCNConfig(num_species=8, channels=128, l_max=4, num_layers=2,
                         num_experts=8, cutoff=5.0, avg_num_neighbors=40.0,
                         dtype="bfloat16")
        model = ESCN(cfg)
        params = model.init(jax.random.PRNGKey(0))
        pot = DistPotential(model, params, num_partitions=1, species_map=smap)
        for tag in ("cold", "warm", "warm"):
            t0 = time.time()
            pot.calculate(atoms)
            print(f"single-chip {tag}: {time.time() - t0:.2f}s "
                  f"({len(atoms) / (time.time() - t0):.0f} atoms/s)")
        _print_hbm()
        return

    cfg = ESCNConfig(num_species=8, channels=32, l_max=2, num_layers=2,
                     num_experts=4, cutoff=4.0, avg_num_neighbors=30.0)
    model = ESCN(cfg)
    params = model.init(jax.random.PRNGKey(0))
    compare_partitions(4, model, params, atoms, smap, 8, 1e-5, 1e-3)


def config5():
    """MACE, ~1M-atom H/C/N/O box, 16-way — BASELINE config 5's
    multi-host stretch as a virtual-topology proxy: 16 shards stand in for
    a 2-host x 8-chip slice (the ring ppermute crosses the proxy host
    boundary exactly where DCN would sit; jax.devices() spans hosts by
    construction, so the same program runs unchanged on a real pod
    slice). Validates 16-way == 4-way at the north-star atom count; model
    is CPU-mesh-sized (the real-chip shapes are the benchmark's cells,
    benchmark/configs/).

    On a TPU this becomes the north-star TIMING run instead: the full 1,000,188-atom box through the MP-0-faithful MACE
    (128ch, l_max=a_lmax=3, correlation 3) in bfloat16 on ONE chip, edge-
    chunked per the ROADMAP.md HBM budget, MD-style perturbed warm steps
    (skin reuse), peak HBM printed. DISTMLIP_C5_EDGE_CHUNK /
    DISTMLIP_C5_NODE_CHUNK trim the chunk sizes if the first attempt OOMs."""
    from distmlip_tpu.models import MACE, MACEConfig

    real = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    reps = int(os.environ.get("DISTMLIP_C5_REPS", "63"))
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.0,
                                            (reps, reps, reps))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.05, (len(frac), 3))
    # solvated-protein-ish composition: H-heavy with C/N/O
    numbers = rng.choice([1, 1, 1, 6, 6, 7, 8], size=len(cart))
    atoms = Atoms(numbers=numbers, positions=cart, cell=lattice)
    smap = np.full(9, -1, np.int32)
    smap[1], smap[6], smap[7], smap[8] = 0, 1, 2, 3

    if real:
        print(f"config 5: MACE, n_atoms = {len(atoms)}, SINGLE CHIP "
              f"(MP-0-faithful bf16, north-star timing)")
        cfg = MACEConfig(
            num_species=4, channels=128, l_max=3, a_lmax=3, hidden_lmax=1,
            correlation=3, num_interactions=2, num_bessel=8, radial_mlp=64,
            cutoff=5.0, avg_num_neighbors=40.0, dtype="bfloat16", remat=True,
            edge_chunk=int(os.environ.get("DISTMLIP_C5_EDGE_CHUNK", "32768")),
            node_chunk=int(os.environ.get("DISTMLIP_C5_NODE_CHUNK", "4096")))
        model = MACE(cfg)
        params = model.init(jax.random.PRNGKey(0))
        # async_rebuild=False: a background prefetch would put a SECOND
        # ~1M-atom graph on the chip while the first is live — this config
        # runs within a few % of HBM capacity
        pot = DistPotential(model, params, num_partitions=1, species_map=smap,
                            compute_stress=True, skin=0.5,
                            compute_dtype="bfloat16", async_rebuild=False)
        for tag in ("cold", "warm", "warm", "warm"):
            atoms.positions += rng.normal(0, 0.01, atoms.positions.shape)
            t0 = time.time()
            res = pot.calculate(atoms)
            dt = time.time() - t0
            print(f"single-chip {tag}: E={res['energy']:.2f} {dt:.2f}s "
                  f"({len(atoms) / dt:.0f} atoms/s) "
                  f"rebuilds={pot.rebuild_count}")
        _print_hbm()
        return

    print(f"config 5: MACE, n_atoms = {len(atoms)}, 16-way "
          f"(2-host x 8-chip proxy topology)")

    cfg = MACEConfig(num_species=4, channels=32, l_max=2, a_lmax=2,
                     hidden_lmax=1, correlation=2, num_interactions=2,
                     num_bessel=6, radial_mlp=32, cutoff=5.0,
                     avg_num_neighbors=40.0)
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    compare_partitions(5, model, params, atoms, smap, 16, 1e-5, 1e-3,
                       baseline=4)


if __name__ == "__main__":
    which = "2"
    if "--config" in sys.argv:
        which = sys.argv[sys.argv.index("--config") + 1]
    {"2": config2, "3": config3, "4": config4, "5": config5}[which]()
