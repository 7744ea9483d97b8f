"""The plain references against the program at toy width on the CPU
(float32 both sides), the weights carried across the two bases of the
symmetric couplings, the blocked sums against plain ones, and the control:
the reference in the precision below the configuration's fails the limits
the program keeps."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.drivers import md
from benchmark.harness import compare, spec
from benchmark.reference import common, so3
from benchmark.reference import mace as ref_mace


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("tables"))


def toy_run(tmp_path, tables_dir, family, chips=1, reps=(3, 3, 3), **kw):
    root = toy.make_root(str(tmp_path), family, reps=reps, chips=chips, **kw)
    cell = spec.load_cell("toy-md", root)
    state = md.set_up(cell, 11, jax.devices()[:chips], tables_dir=tables_dir,
                      kernels="interpret" if family == "mace" else None)
    window = md.run_window(state, 1e-6)  # one whole step
    md.release_program(state)
    return state, window


@pytest.mark.parametrize("family, chips, reps", [
    ("mace", 1, (3, 3, 3)), ("tensornet", 1, (3, 3, 3)),
    ("mace", 4, (12, 3, 3)), ("tensornet", 4, (12, 3, 3))])
def test_program_agrees_with_reference(tmp_path, tables_dir, family, chips,
                                       reps):
    """Neighbour graph, partition and halo, forward, kernels (interpreted)
    and the backward that gives forces, against the reference's own cell
    list and plain forward. Positions have moved since the graph was
    built, under a skin that holds."""
    state, window = toy_run(tmp_path, tables_dir, family, chips, reps)
    assert window.steps == 1 and window.rebuilds == 0
    assert window.skin_used > 0
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    for number in verdict["compared"]:
        assert number["value"] < 5e-5, number


@pytest.mark.parametrize("family", ["mace", "tensornet"])
def test_control_fails_where_the_program_passes(tmp_path, tables_dir, family):
    """bfloat16 program against the float32 reference, and the control
    (the reference in float8 in the program's place) against the same:
    the control's force error is over three times the program's."""
    state, window = toy_run(tmp_path, tables_dir, family,
                            compute_dtype="bfloat16", limits=toy.SERVED)
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    program = verdict["numbers"]
    forces = md.reference_forces(
        state, window.positions,
        ("float8_e4m3fn",))["float8_e4m3fn"][1]
    reference = verdict["reference"]
    control = (compare.relative(forces, reference["forces"])
               / compare.relative(reference["rounding_forces"],
                                  reference["forces"]))
    limit = toy.SERVED["force_err_vs_rounding"]
    print(family, program, control)
    assert 0.3 < program["force_err_vs_rounding"] < limit < control
    assert control > 3 * program["force_err_vs_rounding"]


def test_weights_cross_an_orthogonal_change_of_basis(tables_dir):
    """``program_params`` turns the product weights by U_program^T
    U_reference. With the program's basis mixed by a random rotation the
    model is the same function; with a basis of another space it is not."""
    from benchmark.families import mace as family

    cfg = toy.TOY_MODELS["mace"]
    tables = ref_mace.Tables(cfg, tables_dir)
    params = ref_mace.init_params(cfg, tables, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)

    class Model:
        prod_U = {}
    for l, by_nu in tables.u.items():
        Model.prod_U[l] = {}
        for nu, u in by_nu.items():
            q, _ = np.linalg.qr(rng.normal(size=(u.shape[-1],) * 2))
            Model.prod_U[l][nu] = u @ q
    turned = family.program_params(params, tables, Model)
    w = params["interactions"][0]["product"]["1"]["w2"]
    w_t = turned["interactions"][0]["product"]["1"]["w2"]
    u, u_t = tables.u[1][2], Model.prod_U[1][2]
    assert not np.allclose(w, w_t, atol=1e-3)
    np.testing.assert_allclose(np.einsum("...k,skc->...sc", u, w),
                               np.einsum("...k,skc->...sc", u_t, w_t),
                               atol=1e-5)


def test_coupling_tables_are_equivariant():
    rng = np.random.default_rng(3)
    rot = so3._random_rotation(rng)
    d1, d2 = so3.wigner_d(1, rot), so3.wigner_d(2, rot)
    cg = so3.clebsch_gordan(1, 1, 2)
    turned = np.einsum("xa,yb,zc,abc->xyz", d1, d1, d2, cg)
    np.testing.assert_allclose(turned, cg, atol=1e-10)
    u = so3.symmetric_basis((0, 1), 1, 2)
    k = u.shape[-1]
    gram = u.reshape(-1, k).T @ u.reshape(-1, k)
    np.testing.assert_allclose(gram, np.eye(k), atol=1e-10)
    np.testing.assert_allclose(u, np.swapaxes(u, 0, 1), atol=1e-12)


def test_blocked_sums_equal_plain_ones():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(37, 3)), jnp.float32)
    dst = jnp.asarray(np.sort(rng.integers(0, 5, 37)), jnp.int32)
    fn = lambda x: jnp.tanh(x) * 2.0
    plain = common.blocked_segment_sum(fn, (rows,), dst, 5, None)
    for block in (8, 37, 64):
        got = common.blocked_segment_sum(fn, (rows,), dst, 5, block)
        np.testing.assert_allclose(got, plain, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(common.blocked(fn, (rows,), block),
                                   fn(rows), rtol=1e-6)
    grad = jax.grad(lambda r: common.blocked_segment_sum(
        fn, (r,), dst, 5, 8).sum())(rows)
    np.testing.assert_allclose(
        grad, jax.grad(lambda r: fn(r).sum())(rows), rtol=1e-5)


def test_neighbour_pairs_against_brute_force():
    rng = np.random.default_rng(2)
    cell = np.diag([9.0, 11.0, 13.0])
    pos = rng.random((60, 3)) * np.diag(cell) + 20.0  # out of the box too
    src, dst, shift = common.neighbour_pairs(pos, cell, 4.0)
    vec = pos[dst] - pos[src] + shift
    assert np.all(np.linalg.norm(vec, axis=1) < 4.0)
    delta = pos[None, :, :] - pos[:, None, :]
    delta -= np.round(delta / np.diag(cell)) * np.diag(cell)
    dist = np.linalg.norm(delta, axis=-1)
    expected = (dist < 4.0) & ~np.eye(60, dtype=bool)
    assert len(src) == expected.sum()
    assert expected[src, dst].all()
    np.testing.assert_allclose(vec, delta[src, dst], atol=1e-9)
    with pytest.raises(ValueError):
        common.neighbour_pairs(pos, np.diag([7.0, 11.0, 13.0]), 4.0)


def test_a_slab_drawn_from_the_seed_stands_for_the_whole(tmp_path, tables_dir):
    """The four-chip cell compares the forces of a slab around a partition
    border against the reference on the cluster they depend on. On a toy
    cell long enough for the cluster to be a part of it, those forces are
    the whole reference's, and the check still reads a broken exchange."""
    region = {"axis": 0, "width": 7.8, "borders": 4, "gap": 6.0}
    state, window = toy_run(tmp_path, tables_dir, "mace", 4, (24, 3, 3),
                            check_region=region)
    whole = md.reference_forces(state, window.positions)["float32"][1]
    for seed in (0, 1, 2 ** 31 + 2):
        verdict = md.check(state, window, seed)
        assert verdict["correct"], verdict["compared"]
        moved, _, _, core = verdict["reference"]["region"]
        assert 0 < len(core) < len(moved) < len(state.atoms)
        assert verdict["numbers"]["atoms_compared"] == len(core)
        reach = 2.0 * 2 * 5.0
        core_idx, cluster, _, _ = md.sample_region(
            window.positions, state.atoms.cell, region, reach, seed)
        np.testing.assert_allclose(verdict["reference"]["forces"],
                                   whole[cluster[core_idx]], atol=2e-5,
                                   rtol=1e-4)
    # forces altered inside the slab only are read, outside it are not
    broken = window.results["forces"].copy()
    rows = cluster[core_idx]
    broken[rows] *= 1.5
    window.results["forces"] = broken
    assert not md.check(state, window, 2 ** 31 + 2)["correct"]
