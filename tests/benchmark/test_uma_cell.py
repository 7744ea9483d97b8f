"""The cell ``uma-md-1c`` and its family ``escn`` (eSCN-MD, the backbone of
UMA): the plain reference on its own (its Wigner blocks, its free angle, a
global rotation, blocks of edges, finite differences, the expert merge),
the operation and byte counts, the cell's files, and its step compiled for
a described v5e. The program against the reference: ``test_uma_program.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.families import escn as family
from benchmark.harness import spec, structures
from benchmark.reference import common, so3
from benchmark.reference import escn as ref
from test_compile_v5e import HBM_BYTES, compile_step, topo  # noqa: F401
from test_flops import contraction_flops

toy.TOY_MODELS.setdefault("escn", {
    "max_num_elements": 100, "sphere_channels": 8, "hidden_channels": 8,
    "edge_channels": 8, "lmax": 2, "mmax": 2, "num_layers": 2,
    "num_distance_basis": 8, "basis_width_scalar": 2.0, "cutoff": 5.0,
    "avg_degree": 42.0, "num_experts": 4, "num_charges": 25,
    "charge_min": -12, "num_spins": 10, "num_datasets": 5,
    "system": {"charge": 0, "spin": 0, "dataset": 0}})
CFG = toy.TOY_MODELS["escn"]
TABLES = ref.Tables(CFG)


def two_species(numbers):
    return np.where(np.arange(len(numbers)) % 3 == 0, 8, 14).astype(np.int32)


@pytest.fixture(scope="module")
def small():
    """27 cells of perturbed fcc, two species, with the reference's edges
    and weights."""
    numbers, positions, cell = structures.perturbed_fcc(
        (3, 3, 3), 3.9, 0.04, 14, seed=0)
    src, dst, shift = common.neighbour_pairs(positions, cell, CFG["cutoff"])
    params = ref.init_params(CFG, TABLES, jax.random.PRNGKey(0))
    return {"species": jnp.asarray(two_species(numbers)), "cell": cell,
            "positions": jnp.asarray(positions, jnp.float32),
            "edges": (jnp.asarray(src), jnp.asarray(dst),
                      jnp.asarray(shift, jnp.float32)), "params": params}


def _total(params, species, positions, edges, free_angle, edge_block=None):
    with jax.default_matmul_precision("highest"):
        return ref.site_energies(params, CFG, TABLES, species, positions,
                                 edges, free_angle=free_angle,
                                 edge_block=edge_block).sum()


# one compilation serves every test of the reference on its own
WHOLE = jax.jit(jax.value_and_grad(_total, argnums=2))


def energy(small, positions=None, edges=None, free_angle=None, fn=WHOLE):
    edges = small["edges"] if edges is None else edges
    if free_angle is None:
        free_angle = jnp.zeros(len(edges[0]), edges[2].dtype)
    return fn(small["params"], small["species"],
              small["positions"] if positions is None else positions,
              edges, free_angle)


# ---- the reference on its own ---------------------------------------------

def test_wigner_blocks_are_rotations_of_the_harmonics():
    """Orthogonal, and Y_l(frame r) = D^l Y_l(r) for this package's own
    harmonics: the blocks take lab coefficients into the edge frame."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(7, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    u[0] = [0.0, 0.0, 1.0]   # along an axis too
    frames = ref.edge_frames(jnp.asarray(u),
                             jnp.asarray(rng.uniform(0, 6.28, 7)))
    np.testing.assert_allclose(np.asarray(frames)[:, 2], u, atol=1e-6)
    np.testing.assert_allclose(np.linalg.det(np.asarray(frames)), 1.0,
                               atol=1e-5)
    r = rng.normal(size=(7, 3))
    r /= np.linalg.norm(r, axis=1, keepdims=True)
    turned = np.einsum("eij,ej->ei", np.asarray(frames), r)
    for l, d in enumerate(ref.wigner_blocks(TABLES, frames)):
        d = np.asarray(d, np.float64)
        np.testing.assert_allclose(
            np.einsum("epq,erq->epr", d, d),
            np.broadcast_to(np.eye(2 * l + 1), d.shape), atol=2e-6)
        np.testing.assert_allclose(
            so3.spherical_harmonics(l, turned),
            np.einsum("epq,eq->ep", d, so3.spherical_harmonics(l, r)),
            atol=1e-5)


def test_energy_does_not_depend_on_the_free_angle_or_a_global_rotation(small):
    base, slope = energy(small)
    rng = np.random.default_rng(1)
    n_edges = len(small["edges"][0])
    angle = jnp.asarray(rng.uniform(0, 2 * np.pi, n_edges), jnp.float32)
    assert float(energy(small, free_angle=angle)[0]) == pytest.approx(
        float(base), abs=2e-5)
    q = jnp.asarray(so3._random_rotation(rng), jnp.float32)
    src, dst, shift = small["edges"]
    turned, turned_slope = energy(small, positions=small["positions"] @ q.T,
                                  edges=(src, dst, shift @ q.T))
    assert float(turned) == pytest.approx(float(base), abs=2e-5)
    # and it does depend on where the atoms are; forces turn with them
    assert float(jnp.abs(slope).max()) > 1e-3
    np.testing.assert_allclose(turned_slope, slope @ q.T, atol=2e-6)


def test_reference_in_blocks_equals_the_reference_whole(small):
    whole, g_whole = energy(small)
    blocks = jax.jit(jax.value_and_grad(_total, argnums=2),
                     static_argnames="edge_block")
    for block in (1000,):
        got, g = energy(small, fn=lambda *a: blocks(*a, edge_block=block))
        assert float(got) == pytest.approx(float(whole), abs=2e-5)
        np.testing.assert_allclose(g, g_whole, atol=2e-7, rtol=1e-4)


def test_reference_forces_against_finite_differences(small):
    with jax.enable_x64():
        to64 = lambda t: jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float64)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
        s64 = {**small, "params": to64(small["params"]),
               "positions": to64(small["positions"]),
               "edges": to64(small["edges"])}
        _, grad = energy(s64)
        h = 1e-4
        for atom, axis in ((0, 0), (17, 1)):
            up = s64["positions"].at[atom, axis].add(h)
            down = s64["positions"].at[atom, axis].add(-h)
            numeric = (energy(s64, positions=up)[0]
                       - energy(s64, positions=down)[0]) / (2 * h)
            assert float(grad[atom, axis]) == pytest.approx(
                float(numeric), rel=1e-4, abs=1e-8)


def test_every_expert_is_mixed_as_written(small):
    """The gate's softmax weighs all experts, and the energy is the one of
    a single-expert model holding the merged matrices."""
    params = small["params"]
    csd = ref.linear(params["csd"]["mix"], jnp.concatenate([
        params["csd"]["charge"]["w"][12], params["csd"]["spin"]["w"][0],
        params["csd"]["dataset"]["w"][0]]), lambda x: x)
    coefficients = ref.expert_coefficients(params, small["species"], csd)
    assert coefficients.shape == (CFG["num_experts"],)
    assert float(coefficients.sum()) == pytest.approx(1.0, abs=1e-6)
    assert float(coefficients.min()) > 0.01
    one = {**CFG, "num_experts": 1}
    merged = {k: v for k, v in params.items() if k != "mole_gate"}
    merged["blocks"] = [
        {**layer, "so2_1": {**ref.merge_experts(layer["so2_1"], coefficients),
                            "rad": layer["so2_1"]["rad"]},
         "so2_2": ref.merge_experts(layer["so2_2"], coefficients)}
        for layer in params["blocks"]]
    assert (jax.tree.structure(merged) == jax.tree.structure(
        jax.eval_shape(lambda k: ref.init_params(one, ref.Tables(one), k),
                       jax.random.PRNGKey(0))))
    with jax.default_matmul_precision("highest"):
        single = ref.site_energies(merged, one, ref.Tables(one),
                                   small["species"], small["positions"],
                                   small["edges"], edge_block=None).sum()
    assert float(single) == pytest.approx(float(energy(small)[0]), abs=2e-5)


# ---- the counts -----------------------------------------------------------

def test_step_flops_against_the_jaxpr(small):
    def total(pos):
        return ref.site_energies(small["params"], CFG, TABLES,
                                 small["species"], pos, small["edges"],
                                 edge_block=None).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(total))(small["positions"])
    counted = contraction_flops(jaxpr.jaxpr)
    ours = family.step_flops(CFG, TABLES, len(small["species"]),
                             len(small["edges"][0]))
    assert ours == pytest.approx(counted, rel=0.03), (ours, counted)
    # edges carry the step: twice the edges, nearly twice the operations
    more = family.step_flops(CFG, TABLES, len(small["species"]),
                             2 * len(small["edges"][0]))
    assert 1.9 < more / ours < 2.0


def test_published_size_needs_what_the_issue_reckoned():
    cell = spec.load_cell("uma-md-1c")
    cfg = cell.config["model"]
    tables = ref.Tables(cfg)
    per_atom = family.step_flops(cfg, tables, 8192, 8192 * 54) / 8192
    assert 1.2e9 < per_atom < 2.2e9   # ISSUE 28: about 1.6 GFLOP an atom
    params = jax.eval_shape(lambda k: ref.init_params(cfg, tables, k),
                            jax.random.PRNGKey(0))
    so2 = sum(np.prod(x.shape) for layer in params["blocks"]
              for conv in ("so2_1", "so2_2")
              for name, x in layer[conv].items() if name in ("m0", "m1",
                                                              "m2"))
    assert so2 == pytest.approx(145e6, rel=0.02)      # "150M total"
    assert so2 / cfg["num_experts"] == pytest.approx(4.5e6, rel=0.02)


def test_segment_sum_bytes_follow_its_shapes():
    work = family.kernel_work(CFG, TABLES, n_atoms=100, n_edges_built=5000)
    width = 9 * CFG["sphere_channels"]
    scans = CFG["num_layers"] + 1
    assert work["segment_sum"]["bytes"] == scans * (
        2 * width * (5000 + 100) + 4 * 5000)
    assert work["segment_sum"]["flops"] == scans * 5000 * width


# ---- the cell's files -----------------------------------------------------

def test_cell_loads_from_files():
    """What ``test_spec.test_cell_loads_from_files`` asks of a cell, less
    its list of the two families the benchmark began with."""
    cell = spec.load_cell("uma-md-1c")
    assert cell.traffic["driver"] == "md" and cell.chips == 1
    assert cell.config["family"] == "escn" and cell.config["reduced"] == {}
    model = cell.config["model"]
    assert (model["sphere_channels"], model["hidden_channels"],
            model["edge_channels"]) == (128, 128, 128)
    assert (model["lmax"], model["mmax"], model["num_layers"],
            model["num_experts"], model["cutoff"]) == (2, 2, 4, 32, 6.0)
    assert cell.config["potential"]["compute_dtype"] == "bfloat16"
    assert cell.traffic["structure"]["reps"] == [16, 16, 8]
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "atom_steps_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"model.edge_rotation_ms_per_step.md",
                     "model.expert_mix_ms_per_step.md",
                     "kernel.segment_sum_roofline.uma.md", "model.mfu.md",
                     "model.unattributed_share.md"}
    assert "kernel.segment_sum_roofline.md" not in names
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        assert callable(read) and params["reader"]
    assert set(cell.limits) == {"force_err_vs_rounding", "kick_rel_err"}
    spec.load_module(cell, "families", "escn")
    built = family.build_model(model)
    assert built.cfg.num_experts == 32 and built.cfg.edge_chunk == 32768
    # the reference draws its weights in the program's own tree
    tables = ref.Tables(model)
    assert (jax.tree.structure(jax.eval_shape(
        lambda k: ref.init_params(model, tables, k), jax.random.PRNGKey(0)))
        == jax.tree.structure(jax.eval_shape(built.init,
                                             jax.random.PRNGKey(0))))
    with pytest.raises(ValueError):
        family.build_model({**model, "system": {"charge": 1}})


def test_new_stages_read_nothing_from_a_program_without_them():
    """The parent's stage tables know neither ``edge_rotation`` nor
    ``expert_mix``: the reader then sums nothing, and does not raise."""
    from benchmark.readers import stage_time

    split = stage_time.by_label({"fusion.1": 10, "fusion.2": 30},
                                {"fusion.1": ("edge_message", "forward")})
    assert sum(ns for (stage, _), ns in split.items()
               if stage == "expert_mix") == 0


@pytest.mark.slow  # 130-210 s on this CPU: a step of 79,000 instructions
def test_step_compiles_for_v5e(topo, monkeypatch):  # noqa: F811
    """The published size, 8,192 atoms, on a described v5e: XLA:TPU and
    Mosaic (``segment_sum`` at 1,152-wide rows) take it, and it fits."""
    from jax.experimental.compilation_cache import compilation_cache

    cell = spec.load_cell("uma-md-1c")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = compile_step(cell, topo, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    print(f"uma-md-1c: arguments {memory.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes) < HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()
