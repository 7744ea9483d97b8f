"""The cell ``sevennet-md-1c`` and its family ``nequip`` (NequIP at the sizes
of SevenNet-0): the plain reference on its own (rotation, translation and
what a reflection does to a parity-free path set, finite differences,
blocks of edges, the cross-product path, the envelope's two ends), the
parameter count on both sides, the operation and byte counts, the cell's
files, and the edge sum's kernel compiled for a described v5e at the widths
the message has. The program against the reference:
``test_sevennet_program.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.families import nequip as family
from benchmark.harness import spec, structures
from benchmark.reference import common, so3
from benchmark.reference import nequip as ref
from test_compile_v5e import HBM_BYTES, topo  # noqa: F401
from test_flops import contraction_flops

CELL = "sevennet-md-1c"
# message blocks of 72, 80 and 44 columns, each padded to one lane tile (384
# columns a middle layer's message); edge_chunk 512: several chunks at 27
# cells
toy.TOY_MODELS.setdefault("nequip", {
    "num_species": 95, "channels": [8, 4, 2], "l_max": 2,
    "num_convolutions": 5, "num_bessel": 8, "radial_hidden": [8, 8],
    "cutoff": 5.0, "cutoff_on": 4.5, "avg_num_neighbors": 42.0,
    "edge_chunk": 512})
CFG = toy.TOY_MODELS["nequip"]
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
    return {"species": jnp.asarray(two_species(numbers)), "cell": cell,
            "positions": jnp.asarray(positions, jnp.float32),
            "edges": (jnp.asarray(src), jnp.asarray(dst),
                      jnp.asarray(shift, jnp.float32)),
            "params": ref.init_params(CFG, TABLES, jax.random.PRNGKey(0))}


def _total(params, species, positions, edges, cfg=CFG, tables=TABLES,
           edge_block=None):
    with jax.default_matmul_precision("highest"):
        return ref.site_energies(params, cfg, tables, species, positions,
                                 edges, edge_block=edge_block).sum()


# one compilation serves every test of the reference on its own
WHOLE = jax.jit(jax.value_and_grad(_total, argnums=2))


def energy(small, positions=None, edges=None, fn=WHOLE):
    return fn(small["params"], small["species"],
              small["positions"] if positions is None else positions,
              small["edges"] if edges is None else edges)


# ---- the reference on its own ---------------------------------------------

def test_rotation_and_translation_leave_the_energy_a_reflection_does_not(
        small):
    """A proper rotation and a translation leave the energy and turn the
    forces. A reflection CHANGES the energy: every irrep is even and every
    triangle-allowed path couples, so features of degree 1 are pseudovectors
    as well as vectors, and from the third convolution on a pseudoscalar
    reaches the scalars. That is the published path set (``is_parity:
    False``), not a fault to repair; with two convolutions nothing odd has
    reached a scalar yet and the reflection changes nothing."""
    base, slope = energy(small)
    rng = np.random.default_rng(1)
    q = jnp.asarray(so3._random_rotation(rng), jnp.float32)
    src, dst, shift = small["edges"]
    turned, turned_slope = energy(
        small, positions=small["positions"] @ q.T + 0.37,
        edges=(src, dst, shift @ q.T))
    assert float(turned) == pytest.approx(float(base), abs=2e-5)
    assert float(jnp.abs(slope).max()) > 1e-3
    np.testing.assert_allclose(turned_slope, slope @ q.T, atol=2e-6)
    # a lattice is all but its own mirror image: a gas of 40 atoms is not
    pos = rng.uniform(0.0, 11.0, (40, 3))
    src, dst, shift = common.neighbour_pairs(pos, np.eye(3) * 11.0,
                                             CFG["cutoff"])
    species = jnp.asarray(two_species(np.zeros(40)))
    mirror = np.diag([1.0, 1.0, -1.0])
    for convolutions, changes in ((2, False), (3, True), (5, True)):
        cfg = {**CFG, "num_convolutions": convolutions}
        tables = ref.Tables(cfg)
        params = ref.init_params(cfg, tables, jax.random.PRNGKey(0))
        gas = jax.jit(lambda p, s: _total(
            params, species, p, (jnp.asarray(src), jnp.asarray(dst), s), cfg,
            tables))
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        here, there = gas(f32(pos), f32(shift)), gas(f32(pos @ mirror),
                                                     f32(shift @ mirror))
        relative = abs(float(there) - float(here)) / abs(float(here))
        assert (relative > 1e-5) if changes else (relative < 1e-6), (
            convolutions, float(here), float(there))


def test_reference_in_blocks_equals_the_reference_whole(small):
    whole, g_whole = energy(small)
    blocks = jax.jit(jax.value_and_grad(
        lambda *a: _total(*a, edge_block=1000), argnums=2))
    got, g = energy(small, fn=blocks)
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


def test_the_odd_path_is_the_cross_product():
    """``clebsch_gordan(1, 1, 1)`` is the Levi-Civita symbol up to a
    factor, in this package's (x, y, z) order of the l = 1 components:
    the path (1, 1, 1) couples two vectors to their cross product, which a
    parity filter (l_in + l_Y + l_out even) would drop."""
    levi = np.zeros((3, 3, 3))
    for i, j, k in ((0, 1, 2), (1, 2, 0), (2, 0, 1)):
        levi[i, j, k], levi[j, i, k] = 1.0, -1.0
    cg = so3.clebsch_gordan(1, 1, 1)
    factor = cg[0, 1, 2]
    assert abs(factor) == pytest.approx(np.sqrt(3.0 / 6.0))  # sum C^2 = 3
    np.testing.assert_allclose(cg, factor * levi, atol=1e-12)
    assert (1, 1, 1) in TABLES.paths[1] and (1, 1, 1) not in TABLES.paths[0]
    # 3 paths out of scalars, 15 between full layers, 3 into scalars
    assert [len(p) for p in TABLES.paths] == [3, 15, 15, 15, 3]
    odd = [p for p in TABLES.paths[1] if sum(p) % 2]
    assert sorted(odd) == [(1, 1, 1), (1, 2, 2), (2, 1, 2), (2, 2, 1)]


def test_envelope_is_smooth_at_both_ends():
    """A dimer: the force is continuous across ``cutoff_on`` (the switch
    starts with zero slope), and an edge of the cutoff's length or more
    contributes nothing, energy or force (the harness pads the edge list
    with such edges)."""
    params = ref.init_params(CFG, TABLES, jax.random.PRNGKey(2))
    species = jnp.asarray([14, 8], jnp.int32)
    edges = (jnp.asarray([0, 1]), jnp.asarray([1, 0]),
             jnp.zeros((2, 3), jnp.float32))
    dimer = jax.jit(jax.value_and_grad(lambda d: _total(
        params, species, jnp.asarray([[0.0, 0.0, 0.0], [1.0, 0.0, 0.0]]) * d,
        edges)))
    on, rc = CFG["cutoff_on"], CFG["cutoff"]
    (_, below), (_, above) = dimer(on - 1e-3), dimer(on + 1e-3)
    assert abs(float(below)) > 1e-4
    assert float(above) == pytest.approx(float(below), rel=2e-2)
    alone = dimer(rc + 1.0)[0]
    for d in (rc - 1e-3, rc, rc + 1e-3):
        e, slope = dimer(d)
        assert float(e) == pytest.approx(float(alone), abs=1e-6)
        assert abs(float(slope)) < 1e-4
    assert abs(float(dimer(on)[0]) - float(alone)) > 1e-4


# ---- the counts -----------------------------------------------------------

PUBLISHED = {**{k: v for k, v in CFG.items() if k != "edge_chunk"},
             "channels": [128, 64, 32], "radial_hidden": [64, 64],
             "num_species": 89}


def test_published_sizes_count_842440_parameters_on_both_sides():
    """SevenNet-0's published count at its 89 species, to the last digit:
    embedding 11,392; first convolution 115,200; three middle ones 207,360
    each; last 85,504; readout 8,256; 8 Bessel frequencies."""
    tables = ref.Tables(PUBLISHED)
    params = jax.eval_shape(lambda k: ref.init_params(PUBLISHED, tables, k),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(tree))
    assert [size(layer) for layer in params["layers"]] == [
        115200, 207360, 207360, 207360, 85504]
    assert size(params["embedding"]) == 11392
    assert size(params["readout"]) == 8256
    assert ref.count_weights(params) == 842440
    model = family.build_model(PUBLISHED)
    ours = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert size({k: v for k, v in ours.items() if k != "rescale"}) == 842440
    # and the harness's weights arrive in the program's own tree
    mapped = jax.eval_shape(
        lambda p: family.program_params(p, tables, model), params)
    assert jax.tree.structure(mapped) == jax.tree.structure(ours)
    assert jax.tree.leaves(mapped) == jax.tree.leaves(ours)


def test_step_flops_against_the_jaxpr(small):
    def total(pos):
        return ref.site_energies(small["params"], CFG, TABLES,
                                 small["species"], pos, small["edges"],
                                 edge_block=None).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(total))(small["positions"])
    counted = contraction_flops(jaxpr.jaxpr)
    n_atoms, n_edges = len(small["species"]), len(small["edges"][0])
    ours = family.step_flops(CFG, TABLES, n_atoms, n_edges)
    assert ours == pytest.approx(counted, rel=0.03), (ours, counted)
    # edges carry the step: twice the edges, nearly twice the operations
    more = family.step_flops(CFG, TABLES, n_atoms, 2 * n_edges)
    assert 1.9 < more / ours < 2.0


def test_published_size_needs_what_the_issue_reckoned():
    cell = spec.load_cell(CELL)
    cfg = cell.config["model"]
    tables = ref.Tables(cfg)
    per_atom = family.step_flops(cfg, tables, 24576, 24576 * 42) / 24576
    # ISSUE 34: about 1.2 MFLOP an edge, 53 MFLOP an atom at 42 neighbours
    assert 40e6 < per_atom < 70e6
    edge_only = (family.step_flops(cfg, tables, 0, 1000) / 1000)
    radial = 2 * 2 * (8 * 64 + 64 * 64 + 64 * 960) * 3 + 2 * 2 * (
        8 * 64 + 64 * 64 + 64 * 384) + 2 * 2 * (8 * 64 + 64 * 64 + 64 * 224)
    assert 0.7 < radial / edge_only < 0.9    # the radial MLPs: four fifths


def test_segment_sum_bytes_follow_the_real_widths():
    cfg = spec.load_cell(CELL).config["model"]
    tables = ref.Tables(cfg)
    widths = [1152, 3136, 3136, 3136, 224]
    work = family.kernel_work(cfg, tables, n_atoms=100, n_edges_built=5000)
    assert work["segment_sum"]["bytes"] == sum(
        2 * w * (5000 + 100) + 4 * 5000 for w in widths)
    assert work["segment_sum"]["flops"] == sum(5000 * w for w in widths)
    # the program pads each input degree's block to whole lane tiles
    model = family.build_model(cfg)
    assert [t["width"] for t in model.tables] == [1152, 3200, 3200, 3200, 384]
    assert [t["n_radial"] for t in model.tables] == [384, 960, 960, 960, 224]


# ---- the cell's files -----------------------------------------------------

def test_cell_loads_from_files():
    """What ``test_spec.test_cell_loads_from_files`` asks of a cell, less
    its list of the two families the benchmark began with."""
    cell = spec.load_cell(CELL)
    assert cell.traffic["driver"] == "md" and cell.chips == 1
    assert cell.config["family"] == "nequip" and cell.config["reduced"] == {}
    model = cell.config["model"]
    assert model["channels"] == [128, 64, 32]
    assert model["radial_hidden"] == [64, 64]
    assert (model["l_max"], model["num_convolutions"], model["num_bessel"],
            model["cutoff"], model["cutoff_on"]) == (2, 5, 8, 5.0, 4.5)
    assert cell.config["potential"]["compute_dtype"] == "bfloat16"
    assert cell.traffic["structure"]["reps"] == [16, 16, 24]
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "atom_steps_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"model.node_gate_ms_per_step.md",
                     "model.radial_mlp_ms_per_step.md",
                     "kernel.segment_sum_roofline.7net.md", "model.mfu.md",
                     "model.unattributed_share.md",
                     "model.edge_message_ms_per_step.md"}
    assert not names & {"kernel.segment_sum_roofline.md",
                        "kernel.segment_sum_roofline.uma.md",
                        "model.edge_rotation_ms_per_step.md",
                        "model.line_message_ms_per_step.md"}
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        assert callable(read) and params["reader"]
    assert set(cell.limits) == {"force_err_vs_rounding", "kick_rel_err"}
    assert cell.config_name in cell.traffic["caps"]
    spec.load_module(cell, "drivers", cell.traffic["driver"])
    spec.load_module(cell, "families", "nequip")
    built = family.build_model(model)
    assert built.cfg.irreps == ((128, 64, 32),) * 4 + ((128,),)
    assert (built.cfg.edge_chunk, built.cfg.remat) == (32768, True)
    assert family.receptive_radius(model) == 25.0
    # whole chunks: the edge capacity is 37 chunks of 32,768
    assert cell.traffic["caps"][cell.config_name]["edges"] % 32768 == 0


def test_new_stage_reads_nothing_from_a_program_without_it():
    """The parent's stage tables know no ``node_gate``: the reader then
    sums nothing, and does not raise."""
    from benchmark.readers import stage_time

    split = stage_time.by_label({"fusion.1": 10, "fusion.2": 30},
                                {"fusion.1": ("edge_message", "forward")})
    assert sum(ns for (stage, _), ns in split.items()
               if stage == "node_gate") == 0


# ---- for a described v5e --------------------------------------------------

def _lower_edge_sum(one, width):
    from distmlip_tpu.kernels.segment import pallas_segment_sum_into

    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=one)
    return jax.jit(pallas_segment_sum_into).lower(
        shaped((25600, width), jnp.bfloat16),
        shaped((32768, width), jnp.bfloat16), shaped((32768,), jnp.int32),
        shaped((32768,), jnp.bool_))


@pytest.mark.parametrize("width", [1152, 3200, 384])
def test_edge_sum_kernel_compiles_at_the_padded_widths(topo, width):  # noqa: F811
    """One chunk of 32,768 message rows into the cell's (25,600, W)
    accumulator, at the widths the program lays the message out in."""
    from jax.sharding import SingleDeviceSharding

    compiled = _lower_edge_sum(SingleDeviceSharding(topo.devices[0]),
                               width).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mosaic_refuses_the_message_at_its_own_width(topo):  # noqa: F811
    """3,136 columns (24.5 lane tiles) a middle layer's message has: the
    kernel's block copies need whole tiles. Hence ``models/nequip.py`` pads
    each input degree's block with zero columns to whole tiles, 3,200. If
    this compiles one day, the padding can go."""
    from jax.sharding import SingleDeviceSharding

    with pytest.raises(Exception, match="aligned to tiling"):
        _lower_edge_sum(SingleDeviceSharding(topo.devices[0]),
                        3136).compile()


@pytest.mark.slow  # 40 s on this CPU; the kernel's widths are tier-1 above
def test_step_compiles_for_v5e(topo, monkeypatch):  # noqa: F811
    """The published size, 24,576 atoms, on a described v5e: XLA:TPU and
    Mosaic take it, it fits, and it is over an eighth of the chip."""
    import dataclasses

    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from benchmark.drivers import md
    from distmlip_tpu.parallel import make_potential_fn

    cell = spec.load_cell(CELL)
    cfg = cell.config["model"]
    model = family.build_model(cfg)
    model = type(model)(dataclasses.replace(
        model.cfg, dtype=cell.config["potential"]["compute_dtype"]))
    tables = ref.Tables(cfg)
    params = jax.eval_shape(lambda k: family.program_params(
        ref.init_params(cfg, tables, k), tables, model),
        jax.random.PRNGKey(0))
    graph, _ = md.host_graph(cell, 0)
    one = SingleDeviceSharding(topo.devices[0])
    shaped = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                            sharding=one)
    graph_s = jax.tree.map(shaped, graph)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        compiled = make_potential_fn(model.energy_fn, None).lower(
            jax.tree.map(shaped, params), graph_s,
            graph_s.positions).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    print(f"{CELL}: arguments {memory.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    assert 0.125 * HBM_BYTES < peak < HBM_BYTES
    # five edge sums, forward and recomputed
    assert compiled.as_text().count("tpu_custom_call") >= 5
