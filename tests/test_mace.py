"""MACE model physics + distributed equivalence."""

import jax
import numpy as np
import pytest

from distmlip_tpu.models import MACE, MACEConfig
from tests.utils import make_crystal, run_potential

CFG = MACEConfig(
    num_species=4, channels=16, l_max=2, a_lmax=2, hidden_lmax=1,
    correlation=3, num_interactions=2, num_bessel=6, radial_mlp=16,
    cutoff=3.2, avg_num_neighbors=12.0,
)
MODEL = MACE(CFG)


@pytest.fixture(scope="module")
def params():
    return MODEL.init(jax.random.PRNGKey(0))


def test_distributed_matches_single_device(rng, params):
    cart, lattice, species = make_crystal(rng, reps=(7, 4, 4))
    e1, f1, s1 = run_potential(MODEL.energy_fn, params, cart, lattice, species, CFG.cutoff, 1)
    e4, f4, s4 = run_potential(MODEL.energy_fn, params, cart, lattice, species, CFG.cutoff, 4)
    assert np.abs(f1).max() > 1e-3  # non-degeneracy guard
    assert abs(e1 - e4) < 1e-4 * max(1.0, abs(e1))
    np.testing.assert_allclose(f1, f4, atol=1e-4)
    np.testing.assert_allclose(s1, s4, atol=1e-5)


def test_rotation_invariance(rng, params):
    """The acid test of the SO(3) stack: energy invariant, forces covariant."""
    cart, lattice, species = make_crystal(rng, reps=(3, 3, 3))
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    e1, f1, _ = run_potential(MODEL.energy_fn, params, cart, lattice, species, CFG.cutoff, 1)
    e2, f2, _ = run_potential(
        MODEL.energy_fn, params, cart @ q, lattice @ q, species, CFG.cutoff, 1
    )
    assert abs(e1 - e2) < 5e-4 * max(1.0, abs(e1))
    np.testing.assert_allclose(f1 @ q, f2, atol=2e-4)


def test_higher_order_terms_contribute(rng, params):
    """Correlation-3 paths must change the energy (w3 zeroed vs not)."""
    import copy

    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2))
    # amplify w3 in both runs: init magnitudes leave the cubic term near
    # fp32 resolution (the cutoff envelope shrinks near-cutoff edges)
    p1 = copy.deepcopy(params)
    for inter in p1["interactions"]:
        for l, wts in inter["product"].items():
            wts["w3"] = wts["w3"] * 100.0
    e1, _, _ = run_potential(MODEL.energy_fn, p1, cart, lattice, species,
                             CFG.cutoff, 1, compute_stress=False)
    p0 = copy.deepcopy(p1)
    for inter in p0["interactions"]:
        for l, wts in inter["product"].items():
            wts["w3"] = wts["w3"] * 0.0
    e2, _, _ = run_potential(MODEL.energy_fn, p0, cart, lattice, species,
                             CFG.cutoff, 1, compute_stress=False)
    assert abs(e1 - e2) > 1e-4


def test_forces_match_finite_difference(rng, params):
    jax.config.update("jax_enable_x64", True)
    try:
        cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), noise=0.08)
        cart = cart.astype(np.float64)

        def energy(c):
            e, f, _ = run_potential(
                MODEL.energy_fn,
                jax.tree.map(lambda x: jax.numpy.asarray(x, jax.numpy.float64), params),
                c, lattice, species, CFG.cutoff, 1, compute_stress=False,
                dtype=np.float64,
            )
            return e, f

        _, forces = energy(cart)
        h = 1e-5
        for atom, ax in [(0, 0), (9, 1), (17, 2)]:
            cp, cm = cart.copy(), cart.copy()
            cp[atom, ax] += h
            cm[atom, ax] -= h
            ep, _ = energy(cp)
            em, _ = energy(cm)
            f_fd = -(ep - em) / (2 * h)
            np.testing.assert_allclose(forces[atom, ax], f_fd, rtol=1e-4, atol=1e-8)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_energy_smooth_at_cutoff(rng, params):
    lattice = np.eye(3) * 20.0
    species = np.zeros(3, np.int32)
    es = []
    for d in np.linspace(CFG.cutoff - 0.02, CFG.cutoff + 0.02, 9):
        cart = np.array([[5.0, 5.0, 5.0], [5.0 + d, 5.0, 5.0], [5.0, 6.8, 5.0]])
        e, _, _ = run_potential(MODEL.energy_fn, params, cart, lattice, species,
                                CFG.cutoff, 1, compute_stress=False)
        es.append(e)
    assert np.ptp(es) < 2e-3


def test_zbl_pair_repulsion(rng):
    """ZBL: strongly repulsive at short range, smooth at its own cutoff,
    and exactly zero beyond the covalent-radii sum."""
    from distmlip_tpu.models.pair import COVALENT_RADII, zbl_edge_energy
    import jax.numpy as jnp

    cfg = MACEConfig(
        num_species=4, channels=8, l_max=1, a_lmax=1, hidden_lmax=1,
        correlation=2, num_interactions=1, num_bessel=4, radial_mlp=8,
        cutoff=3.2, avg_num_neighbors=6.0, zbl=True,
        atomic_numbers=(14, 14, 8, 8),
    )
    import dataclasses

    model = MACE(cfg)
    model_nozbl = MACE(dataclasses.replace(cfg, zbl=False))
    params = model.init(jax.random.PRNGKey(0))
    lattice = np.eye(3) * 20.0
    species = np.zeros(2, np.int32)

    def zbl_at(dd):
        """Isolated ZBL contribution: energy with minus without the term
        (the learned potential's own slope would swamp a raw-ptp check)."""
        cart = np.array([[5.0, 5.0, 5.0], [5.0 + dd, 5.0, 5.0]])
        e_on, _, _ = run_potential(model.energy_fn, params, cart, lattice,
                                   species, cfg.cutoff, 1, compute_stress=False)
        e_off, _, _ = run_potential(model_nozbl.energy_fn, params, cart,
                                    lattice, species, cfg.cutoff, 1,
                                    compute_stress=False)
        return e_on - e_off

    r_max = 2 * COVALENT_RADII[14]
    assert zbl_at(0.6) - zbl_at(1.2) > 10.0      # strongly repulsive
    # smooth (continuous) across the ZBL cutoff
    es = [zbl_at(d) for d in np.linspace(r_max - 0.02, r_max + 0.02, 7)]
    assert np.ptp(es) < 1e-4
    # edge-level: exact zero beyond r_max
    v = zbl_edge_energy(jnp.asarray([14]), jnp.asarray([14]),
                        jnp.asarray([r_max + 0.01]))
    assert float(v[0]) == 0.0

    # aggregation parity: upstream ScaleShiftMACE scale-shifts the SUM of
    # interaction and pair energies (mace/models.py:131,174-175), so the
    # isolated ZBL contribution must scale linearly with `scale`
    zbl_1 = zbl_at(0.8)
    params2 = {**params, "scale": params["scale"] * 2.0}
    cart = np.array([[5.0, 5.0, 5.0], [5.8, 5.0, 5.0]])
    e_on2, _, _ = run_potential(model.energy_fn, params2, cart, lattice,
                                species, cfg.cutoff, 1, compute_stress=False)
    e_off2, _, _ = run_potential(model_nozbl.energy_fn, params2, cart,
                                 lattice, species, cfg.cutoff, 1,
                                 compute_stress=False)
    np.testing.assert_allclose(e_on2 - e_off2, 2.0 * zbl_1, rtol=1e-5)


def test_multihead_readout(rng):
    """Heads must be independent: changing head-1 params leaves head 0
    unchanged; selecting head 1 changes the energy."""
    import dataclasses

    cfg = dataclasses.replace(CFG, num_heads=2)
    model = MACE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params["species_ref"]["w"] = params["species_ref"]["w"].at[1].set(3.0)
    params["shift"] = params["shift"].at[1].set(-1.0)
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2))
    e0, _, _ = run_potential(model.energy_fn, params, cart, lattice, species,
                             cfg.cutoff, 1, compute_stress=False)
    m1 = MACE(dataclasses.replace(cfg, head=1))
    e1, _, _ = run_potential(m1.energy_fn, params, cart, lattice, species,
                             cfg.cutoff, 1, compute_stress=False)
    assert abs(e0 - e1) > 1.0
    # head-0 energy must not depend on head-1 columns
    p2 = jax.device_get(params)
    p2["species_ref"]["w"] = np.array(p2["species_ref"]["w"])
    p2["species_ref"]["w"][1] = 99.0
    e0b, _, _ = run_potential(model.energy_fn, p2, cart, lattice, species,
                              cfg.cutoff, 1, compute_stress=False)
    assert abs(e0 - e0b) < 1e-6


CHUNKED = dict(edge_chunk=96, node_chunk=17)


@pytest.mark.parametrize("nparts, reps, other", [
    (1, (3, 3, 3), dict(edge_chunk=0, node_chunk=0)),
    (1, (3, 3, 3), dict(CHUNKED, remat=False)),
    (4, (7, 4, 4), dict(CHUNKED, remat=False)),
], ids=["unchunked", "no-remat", "no-remat-4-parts"])
def test_edge_node_chunking_matches_unchunked(rng, params, nparts, reps,
                                              other):
    """K>1 edge-chunked density projection AND node-chunked symmetric
    contraction (remat scan paths) must reproduce the unchunked forward
    exactly — guards the per-chunk padding, the T-factorized projection,
    and the scan accumulation. Against the same chunks at ``remat=False``,
    on one part and four: the chunk bodies are the only checkpoints
    (PR 35), and energy, forces and stress still come through the scans,
    the halo exchange and the node scan to float32 round-off."""
    import dataclasses

    cart, lattice, species = make_crystal(rng, reps=reps)
    m_un = MACE(dataclasses.replace(CFG, **other))
    m_ch = MACE(dataclasses.replace(CFG, **CHUNKED))
    assert m_ch.cfg.remat is True
    e0, f0, s0 = run_potential(m_un.energy_fn, params, cart, lattice, species,
                               CFG.cutoff, nparts)
    e1, f1, s1 = run_potential(m_ch.energy_fn, params, cart, lattice, species,
                               CFG.cutoff, nparts)
    assert np.abs(f0).max() > 1e-3
    assert abs(e0 - e1) < 1e-5 * max(1.0, abs(e0))
    np.testing.assert_allclose(f0, f1, atol=1e-5)
    np.testing.assert_allclose(s0, s1, atol=1e-7)
