"""Simulation-layer tests: DistPotential pipeline, MD ensembles (the
relaxer's are in tests/test_relax.py)."""

import numpy as np
import pytest

from distmlip_tpu import geometry
from distmlip_tpu.calculators import (
    Atoms,
    DistPotential,
    MolecularDynamics,
    TrajectoryObserver,
)
from distmlip_tpu.calculators.md import ENSEMBLES
from distmlip_tpu.models import PairConfig, PairPotential
from tests.utils import lj_potential, make_atoms


@pytest.fixture(scope="module")
def potential():
    return lj_potential()


def test_calculate_basic(rng, potential):
    atoms = make_atoms(rng)
    res = potential.calculate(atoms)
    assert np.isfinite(res["energy"])
    assert res["forces"].shape == (len(atoms), 3)
    assert res["stress"].shape == (3, 3)
    assert potential.last_timings["device_s"] > 0


def test_partition_report(rng, potential):
    rep = potential.partition_report(make_atoms(rng))
    assert "partition 0" in rep and "partition 1" in rep


def test_nve_conserves_energy(rng, potential):
    atoms = make_atoms(rng)
    atoms.set_maxwell_boltzmann_velocities(300.0, rng=rng)
    md = MolecularDynamics(atoms, potential, ensemble="nve", timestep=1.0)
    e0 = md.results["energy"] + atoms.kinetic_energy()
    md.run(50)
    e1 = md.results["energy"] + atoms.kinetic_energy()
    assert abs(e1 - e0) < 5e-3 * len(atoms) ** 0.5  # drift bound


@pytest.mark.parametrize(
    "ensemble", [e for e in ENSEMBLES if e != "nve"]
)
def test_ensembles_run_and_thermostat(rng, ensemble, potential):
    atoms = make_atoms(rng)
    atoms.set_maxwell_boltzmann_velocities(600.0, rng=rng)
    md = MolecularDynamics(
        atoms, potential, ensemble=ensemble, timestep=1.0,
        temperature=300.0, taut=50.0, seed=1,
    )
    md.run(30)
    assert np.isfinite(md.results["energy"])
    assert np.all(np.isfinite(atoms.positions))
    # thermostatted runs should pull T from 600 toward 300
    if ensemble.startswith("nvt"):
        assert atoms.temperature() < 650.0


def test_trajectory_observer(rng, potential, tmp_path):
    atoms = make_atoms(rng)
    obs = TrajectoryObserver(atoms)
    md = MolecularDynamics(
        atoms, potential, ensemble="nvt_berendsen", trajectory=obs,
        logfile=str(tmp_path / "md.log"), loginterval=2,
    )
    md.run(10)
    assert len(obs.energies) == 5
    obs.save(str(tmp_path / "traj.npz"))
    data = np.load(tmp_path / "traj.npz")
    assert data["positions"].shape[0] == 5
    assert (tmp_path / "md.log").read_text().count("\n") == 5


def test_skin_reuse_exact_and_invalidation(rng):
    """skin>0: cache-hit results match rebuild-every-step exactly; cache
    invalidates on displacement > skin/2, cell change, and species change."""
    model = PairPotential(PairConfig(cutoff=3.0, kind="lj"))
    params = {"eps": np.float32(0.1), "sigma": np.float32(2.0)}
    atoms = make_atoms(rng, reps=(4, 3, 3))
    pot0 = DistPotential(model, params, num_partitions=2, skin=0.0)
    pot1 = DistPotential(model, params, num_partitions=2, skin=0.6)
    pos = atoms.positions.copy()
    for _ in range(6):
        pos += rng.normal(0, 0.01, pos.shape)
        a = Atoms(numbers=atoms.numbers, positions=pos, cell=atoms.cell)
        r0 = pot0.calculate(a)
        r1 = pot1.calculate(a)
        assert abs(r0["energy"] - r1["energy"]) < 1e-4
        np.testing.assert_allclose(r0["forces"], r1["forces"], atol=1e-5)
        np.testing.assert_allclose(r0["stress"], r1["stress"], atol=1e-6)
    assert pot1.rebuild_count == 1 and pot0.rebuild_count == 6

    # displacement invalidation: move one atom by > skin/2
    pos2 = pos.copy()
    pos2[0] += [0.4, 0, 0]
    pot1.calculate(Atoms(numbers=atoms.numbers, positions=pos2, cell=atoms.cell))
    assert pot1.rebuild_count == 2

    # cell invalidation: tiny (1e-5 relative) cell change must rebuild
    pot1.calculate(Atoms(numbers=atoms.numbers, positions=pos2,
                         cell=atoms.cell * (1 + 1e-5)))
    assert pot1.rebuild_count == 3


def test_async_rebuild_overlap_matches_sync(rng):
    """The background-prefetched graph must give the same results as
    synchronous rebuilds, and rebuilds during a drifting MD-like run must
    actually be absorbed by the prefetch (prefetch_hits > 0) so the
    rebuild step costs a positions scatter, not a host rebuild
    (VERDICT r4 item 7 — the reference's serial section, pes.py:68-85)."""
    model = PairPotential(PairConfig(cutoff=3.0, kind="lj"))
    params = {"eps": np.float32(0.1), "sigma": np.float32(2.0)}
    atoms = make_atoms(rng, reps=(4, 3, 3))
    pot_async = DistPotential(model, params, num_partitions=2, skin=0.4,
                              async_rebuild=True)
    pot_sync = DistPotential(model, params, num_partitions=2, skin=0.4,
                             async_rebuild=False)
    pos = atoms.positions.copy()
    drift = rng.normal(0, 1.0, pos.shape)
    drift /= np.linalg.norm(drift, axis=1, keepdims=True)
    for _ in range(24):
        pos += 0.02 * drift + rng.normal(0, 0.003, pos.shape)
        a = Atoms(numbers=atoms.numbers, positions=pos, cell=atoms.cell)
        ra = pot_async.calculate(a)
        rs = pot_sync.calculate(a)
        assert abs(ra["energy"] - rs["energy"]) < 1e-4
        np.testing.assert_allclose(ra["forces"], rs["forces"], atol=1e-5)
    assert pot_async.prefetch_hits >= 1, (
        pot_async.prefetch_hits, pot_async.rebuild_count)
    # adoption staleness: a jump far past the prefetch budget must fall
    # back to a fresh build, never serve a stale graph
    pos2 = pos + 5.0
    ra = pot_async.calculate(
        Atoms(numbers=atoms.numbers, positions=pos2, cell=atoms.cell))
    rs = pot_sync.calculate(
        Atoms(numbers=atoms.numbers, positions=pos2, cell=atoms.cell))
    assert abs(ra["energy"] - rs["energy"]) < 1e-4


def test_npt_requires_stress(rng):
    model = PairPotential(PairConfig(cutoff=3.0))
    pot = DistPotential(model, {"eps": np.float32(0.1), "sigma": np.float32(2.0)},
                        num_partitions=1, compute_stress=False)
    atoms = make_atoms(rng, reps=(2, 2, 2))
    with pytest.raises(ValueError, match="compute_stress"):
        MolecularDynamics(atoms, pot, ensemble="npt_berendsen")


def test_ensemble_potential(rng):
    model = PairPotential(PairConfig(cutoff=3.0))
    from distmlip_tpu.calculators import EnsemblePotential

    plist = [{"eps": np.float32(0.1 * (1 + 0.1 * i)), "sigma": np.float32(2.0)}
             for i in range(3)]
    ens = EnsemblePotential(model, plist, num_partitions=2)
    atoms = make_atoms(rng, reps=(2, 2, 2))
    res = ens.calculate(atoms)
    assert res["energies"].shape == (3,)
    assert res["energy_var"] > 0
    assert res["forces"].shape == (len(atoms), 3)
    np.testing.assert_allclose(res["energy"], res["energies"].mean())


def test_auto_partitioning_clamps_to_slab_rule(rng):
    """Default num_partitions=None: all devices, clamped so the planner's
    slab rule holds for the first structure — a small box must not crash
    with PartitionError on the default constructor (review r4 finding)."""
    import jax

    model = PairPotential(PairConfig(cutoff=4.0))
    params = model.init(jax.random.PRNGKey(0))
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * 4.5, (4, 4, 4))
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, 0.05, (len(frac), 3))
    atoms = Atoms(numbers=np.full(len(cart), 1), positions=cart, cell=lattice)
    pot = DistPotential(model, params, skin=0.3)  # AUTO on an 8-device mesh
    res = pot.calculate(atoms)
    # 18 A box, 2*(4.0+0.3) = 8.6 -> P clamped to 2, not 8
    assert pot.num_partitions == 2
    assert np.isfinite(res["energy"])
    # stacked ensemble under AUTO must also construct + run (lazy vmap)
    from distmlip_tpu.calculators import EnsemblePotential

    ens = EnsemblePotential(model, [params, params], skin=0.3)
    out = ens.calculate(atoms)
    assert np.isfinite(out["energy"]) and out["energies"].shape == (2,)
    # vacuum-padded slab: only periodic axes count
    atoms_vac = Atoms(numbers=np.full(len(cart), 1), positions=cart,
                      cell=lattice @ np.diag([1.0, 1.0, 4.0]),
                      pbc=[1, 1, 0])
    pot_vac = DistPotential(model, params, skin=0.3)
    pot_vac.ensure_runtime(atoms_vac)
    assert pot_vac.num_partitions == 2  # clamp from the 18 A periodic axes


def test_stacked_ensemble_matches_sequential(rng):
    """Single-partition ensembles evaluate all members in one vmapped
    program; results must equal the sequential path."""
    import jax

    from distmlip_tpu.calculators import Atoms, EnsemblePotential
    from distmlip_tpu.models import TensorNet, TensorNetConfig

    cfg = TensorNetConfig(num_species=8, units=16, num_rbf=6, num_layers=1,
                          cutoff=3.2)
    model = TensorNet(cfg)
    plist = [model.init(jax.random.PRNGKey(i)) for i in range(3)]
    cart, lattice, species, _ = __import__("tests.conftest", fromlist=["random_cell"]).random_cell(
        rng, n_atoms=24, box=8.0)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    stacked = EnsemblePotential(model, plist, num_partitions=1, stacked=True)
    seq = EnsemblePotential(model, plist, num_partitions=1, stacked=False)
    r1 = stacked.calculate(atoms)
    r2 = seq.calculate(atoms)
    assert abs(r1["energy"] - r2["energy"]) < 1e-5
    np.testing.assert_allclose(r1["forces"], r2["forces"], atol=1e-5)
    np.testing.assert_allclose(r1["energy_var"], r2["energy_var"], rtol=1e-4,
                               atol=1e-8)


@pytest.mark.slow
def test_stacked_ensemble_matches_sequential_multipartition(rng):
    """Multi-partition ensembles also run as ONE vmapped sharded program
    (the vmap batches the whole shard_map'd graph-parallel step); results
    must equal sequential members at P=2."""
    import jax

    from distmlip_tpu.calculators import Atoms, EnsemblePotential
    from distmlip_tpu.models import TensorNet, TensorNetConfig
    from tests.utils import make_crystal

    cfg = TensorNetConfig(num_species=4, units=16, num_rbf=6, num_layers=1,
                          cutoff=3.2)
    model = TensorNet(cfg)
    plist = [model.init(jax.random.PRNGKey(i)) for i in range(3)]
    cart, lattice, species = make_crystal(rng, reps=(5, 3, 3))
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    stacked = EnsemblePotential(model, plist, num_partitions=2)
    assert stacked.stacked  # vmap path is now the multi-partition default
    seq = EnsemblePotential(model, plist, num_partitions=2, stacked=False)
    r1 = stacked.calculate(atoms)
    r2 = seq.calculate(atoms)
    assert abs(r1["energy"] - r2["energy"]) < 1e-5
    np.testing.assert_allclose(r1["forces"], r2["forces"], atol=1e-5)
    np.testing.assert_allclose(r1["energy_var"], r2["energy_var"], rtol=1e-4,
                               atol=1e-8)


def test_uma_predictor_task_routing(rng):
    """UMAPredictor: task name routes the dataset conditioning; different
    tasks give different energies on the same structure."""
    import jax

    from distmlip_tpu.calculators import Atoms, UMAPredictor
    from distmlip_tpu.models import ESCN, ESCNConfig

    cfg = ESCNConfig(num_species=8, channels=8, l_max=1, num_layers=1,
                     num_bessel=4, cutoff=3.2)
    model = ESCN(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species, _ = __import__("tests.conftest", fromlist=["random_cell"]).random_cell(
        rng, n_atoms=20, box=8.0)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    e_omat = UMAPredictor(model, params, task_name="omat",
                          num_partitions=1).calculate(atoms)["energy"]
    e_oc20 = UMAPredictor(model, params, task_name="oc20",
                          num_partitions=1).calculate(atoms)["energy"]
    assert abs(e_omat - e_oc20) > 1e-7
    # explicit atoms.info dataset wins over the task default
    atoms2 = atoms.copy()
    atoms2.info["dataset"] = 2
    e_override = UMAPredictor(model, params, task_name="omat",
                              num_partitions=1).calculate(atoms2)["energy"]
    assert abs(e_override - e_oc20) < 1e-6


def test_out_of_range_system_scalars_raise(rng):
    """Charge/spin/dataset outside the embedding tables must raise instead of
    silently clipping onto the table edge."""
    import jax

    import pytest as _pytest

    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models import ESCN, ESCNConfig

    cfg = ESCNConfig(num_species=8, channels=8, l_max=1, num_layers=1,
                     num_bessel=4, cutoff=3.2)
    model = ESCN(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species, _ = __import__("tests.conftest", fromlist=["random_cell"]).random_cell(
        rng, n_atoms=12, box=8.0)
    pot = DistPotential(model, params, num_partitions=1,
                        species_map=np.arange(0, 10, dtype=np.int32) - 1)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice,
                  info={"charge": 99})
    with _pytest.raises(ValueError, match="charge"):
        pot.calculate(atoms)
    atoms.info = {"dataset": 7}
    with _pytest.raises(ValueError, match="dataset"):
        pot.calculate(atoms)


def test_bfloat16_one_call_switch(rng):
    """DistPotential(compute_dtype='bfloat16') runs end to end; energies and
    forces stay close to fp32 (characterizes the bf16 error)."""
    import jax

    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models import MACE, MACEConfig

    cfg = MACEConfig(num_species=8, channels=16, l_max=2, a_lmax=2,
                     hidden_lmax=1, correlation=3, num_interactions=2,
                     num_bessel=6, radial_mlp=16, cutoff=3.2,
                     avg_num_neighbors=12.0)
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from tests.utils import make_crystal

    cart, lattice, species = make_crystal(rng, reps=(3, 3, 3), n_species=8)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.arange(0, 10, dtype=np.int32) - 1

    r32 = DistPotential(model, params, num_partitions=1,
                        species_map=smap).calculate(atoms)
    r16 = DistPotential(model, params, num_partitions=1, species_map=smap,
                        compute_dtype="bfloat16").calculate(atoms)
    n = len(atoms)
    de_per_atom = abs(r16["energy"] - r32["energy"]) / n
    f_scale = max(np.abs(r32["forces"]).max(), 1e-3)
    df_rel = np.abs(r16["forces"] - r32["forces"]).max() / f_scale
    print(f"bf16 vs fp32: dE={de_per_atom:.2e} eV/atom, "
          f"dF_rel={df_rel:.2e}")
    assert de_per_atom < 5e-3
    assert df_rel < 0.1


def test_compute_dtype_guards(rng):
    """Unsupported models must reject compute_dtype loudly; the global
    set_compute_dtype switch routes into supporting models."""
    import jax

    import distmlip_tpu
    import pytest as _pytest

    from distmlip_tpu.calculators import DistPotential
    from distmlip_tpu.models import PairConfig, PairPotential, TensorNet, TensorNetConfig

    # PairPotential has no compute-dtype support: must reject loudly
    pair = PairPotential(PairConfig(cutoff=3.0))
    with _pytest.raises(ValueError, match="compute"):
        DistPotential(pair, pair.init(), num_partitions=1,
                      compute_dtype="bfloat16")
    model = TensorNet(TensorNetConfig(num_species=4, units=8, num_rbf=4,
                                      num_layers=1))
    params = model.init(jax.random.PRNGKey(0))
    # global switch is ignored (without error) for unsupported models...
    distmlip_tpu.set_compute_dtype("bfloat16")
    try:
        pot_pair = DistPotential(pair, pair.init(), num_partitions=1)
        assert pot_pair.model is pair  # untouched: switch ignored
        # ...and picked up by supporting ones (TensorNet included now)
        pot_tn = DistPotential(model, params, num_partitions=1)
        assert pot_tn.model.cfg.dtype == "bfloat16"
        from distmlip_tpu.models import MACE, MACEConfig

        m = MACE(MACEConfig(num_species=4, channels=8, l_max=1, a_lmax=1,
                            hidden_lmax=1, correlation=2, num_interactions=1,
                            num_bessel=4, radial_mlp=8))
        pot = DistPotential(m, m.init(jax.random.PRNGKey(0)), num_partitions=1)
        assert pot.model.cfg.dtype == "bfloat16"
    finally:
        distmlip_tpu.set_compute_dtype("float32")


def test_device_md_matches_host_md(rng):
    """The device-resident MD loop must reproduce host-driven velocity
    Verlet (same skin-reuse graph, same integrator) and conserve energy."""
    from distmlip_tpu.calculators import (Atoms, DeviceMD, DistPotential,
                                          MolecularDynamics)
    from distmlip_tpu.models import PairConfig, PairPotential

    model = PairPotential(PairConfig(cutoff=3.0, kind="lj"))
    params = {"eps": np.float32(0.05), "sigma": np.float32(2.0)}
    atoms_a = make_atoms(rng, reps=(3, 3, 3), noise=0.03)
    atoms_a.set_maxwell_boltzmann_velocities(300.0,
                                             rng=np.random.default_rng(7))
    atoms_b = atoms_a.copy()

    pot_a = DistPotential(model, params, num_partitions=2, skin=1.0)
    dmd = DeviceMD(pot_a, atoms_a, timestep=1.0)
    dmd.run(25)
    assert dmd.steps_done == 25

    pot_b = DistPotential(model, params, num_partitions=2, skin=1.0)
    hmd = MolecularDynamics(atoms_b, pot_b, ensemble="nve", timestep=1.0)
    hmd.run(25)

    np.testing.assert_allclose(atoms_a.positions, atoms_b.positions,
                               atol=2e-4)
    np.testing.assert_allclose(atoms_a.velocities, atoms_b.velocities,
                               atol=2e-4)
    assert np.isfinite(dmd.results["energy"])


def test_device_md_warm_cache_drift_budget(rng):
    """A skin cache warmed by calculate() at *drifted* positions must not
    double-spend the drift budget: DeviceMD charges drift against the
    graph-BUILD positions, so the trajectory matches a cold-start run."""
    from distmlip_tpu.calculators import (Atoms, DeviceMD, DistPotential,
                                          MolecularDynamics)
    from distmlip_tpu.models import PairConfig, PairPotential

    model = PairPotential(PairConfig(cutoff=3.0, kind="lj"))
    params = {"eps": np.float32(0.05), "sigma": np.float32(2.0)}
    atoms = make_atoms(rng, reps=(3, 3, 3), noise=0.03)
    pot = DistPotential(model, params, num_partitions=2, skin=0.5)
    # warm the cache, then drift atoms close to the skin/2 validity edge
    # WITHOUT re-calculating (cache still "valid" but nearly spent)
    pot.calculate(atoms)
    atoms.positions = atoms.positions + 0.23 / np.sqrt(3)
    atoms.set_maxwell_boltzmann_velocities(300.0,
                                           rng=np.random.default_rng(9))
    atoms_cold = atoms.copy()

    dmd = DeviceMD(pot, atoms, timestep=1.0)
    dmd.run(20)
    assert dmd.steps_done == 20

    pot_cold = DistPotential(model, params, num_partitions=2, skin=0.5)
    hmd = MolecularDynamics(atoms_cold, pot_cold, ensemble="nve",
                            timestep=1.0)
    hmd.run(20)
    np.testing.assert_allclose(atoms.positions, atoms_cold.positions,
                               atol=2e-4)


def test_device_md_thermostat_and_rebuild(rng):
    """Berendsen NVT on device pulls T toward the target; a small skin
    forces mid-run rebuilds and the step count still completes."""
    from distmlip_tpu.calculators import Atoms, DeviceMD, DistPotential

    from distmlip_tpu.models import PairConfig, PairPotential

    model = PairPotential(PairConfig(cutoff=3.0, kind="lj"))
    params = {"eps": np.float32(0.05), "sigma": np.float32(2.0)}
    atoms = make_atoms(rng, reps=(3, 3, 3), noise=0.03)
    atoms.set_maxwell_boltzmann_velocities(600.0,
                                           rng=np.random.default_rng(8))
    pot = DistPotential(model, params, num_partitions=2, skin=0.3)
    dmd = DeviceMD(pot, atoms, timestep=1.0, temperature=300.0, taut=25.0)
    dmd.run(60)
    assert dmd.steps_done == 60
    assert dmd.rebuilds >= 1
    assert atoms.temperature() < 650.0


@pytest.mark.parametrize("family", ["tensornet", "chgnet"])
def test_bfloat16_switch_tensornet_chgnet(rng, family):
    """bf16 one-call switch for the matgl-family models: runs end to end
    with bounded deviation from fp32."""
    import jax

    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models import (CHGNet, CHGNetConfig, TensorNet,
                                     TensorNetConfig)
    from tests.utils import make_crystal

    if family == "tensornet":
        model = TensorNet(TensorNetConfig(num_species=8, units=16, num_rbf=6,
                                          num_layers=2, cutoff=3.4))
    else:
        model = CHGNet(CHGNetConfig(num_species=8, units=16, num_rbf=6,
                                    num_angle=4, num_blocks=2, cutoff=3.4,
                                    bond_cutoff=2.8))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(3, 3, 3), n_species=8)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    smap = np.arange(0, 10, dtype=np.int32) - 1
    r32 = DistPotential(model, params, num_partitions=1,
                        species_map=smap).calculate(atoms)
    r16 = DistPotential(model, params, num_partitions=1, species_map=smap,
                        compute_dtype="bfloat16").calculate(atoms)
    de = abs(r16["energy"] - r32["energy"]) / len(atoms)
    f_scale = max(np.abs(r32["forces"]).max(), 1e-3)
    df = np.abs(r16["forces"] - r32["forces"]).max() / f_scale
    assert de < 1e-2, de
    assert df < 0.15, df


