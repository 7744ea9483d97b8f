"""The cell ``chgnet-md-1c`` and its family ``chgnet``: the plain reference
on its own (a hand-built line graph, a global rotation and translation,
blocks of lines and edges, finite differences, the harness's ghost edges, a
table that overflows), the operation counts, the cell's files, its own host
graph with bonds across seeds, and its step compiled for a described v5e.
The program against the reference: ``test_chgnet_program.py``.

``md.host_graph`` and ``test_compile_v5e.compile_step`` build with bond
radius 0.0 and no bond graph and belong to the benchmark as it stands, so
this cell's graph and compile are made here (:func:`bond_host_graph`,
:func:`compile_bond_step`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.drivers import md
from benchmark.families import chgnet as family
from benchmark.harness import spec, structures
from benchmark.reference import chgnet as ref
from benchmark.reference import common
from test_compile_v5e import HBM_BYTES, topo  # noqa: F401
from test_flops import contraction_flops
from test_shapes_across_seeds import SEEDS, signature

toy.TOY_MODELS.setdefault("chgnet", {
    "num_species": 95, "units": 8, "num_rbf": 5, "num_angle": 2,
    "num_blocks": 3, "cutoff": 5.0, "bond_cutoff": 3.0,
    "shared_bond_weights": "both", "reference_max_bonds": 13})
CFG = toy.TOY_MODELS["chgnet"]
CELL = "chgnet-md-1c"


def two_species(numbers):
    return np.where(np.arange(len(numbers)) % 3 == 0, 8, 14).astype(np.int32)


@pytest.fixture(scope="module")
def small():
    """27 cells of perturbed fcc, two species, with the reference's edges
    and weights."""
    numbers, positions, cell = structures.perturbed_fcc(
        (3, 3, 3), 3.9, 0.04, 14, seed=0)
    src, dst, shift = common.neighbour_pairs(positions, cell, CFG["cutoff"])
    tables = ref.Tables(CFG)
    params = ref.init_params(CFG, tables, jax.random.PRNGKey(0))
    return {"species": jnp.asarray(two_species(numbers)), "cell": cell,
            "positions": jnp.asarray(positions, jnp.float32),
            "edges": (jnp.asarray(src), jnp.asarray(dst),
                      jnp.asarray(shift, jnp.float32)),
            "params": params, "tables": tables}


def _total(params, species, positions, edges, tables, cfg=CFG, **blocks):
    with jax.default_matmul_precision("highest"):
        return ref.site_energies(params, cfg, tables, species, positions,
                                 edges, **blocks).sum()


def energy(small, positions=None, edges=None, **kw):
    fn = jax.jit(jax.value_and_grad(
        lambda p, pos, e: _total(p, small["species"], pos, e,
                                 small["tables"], **kw), argnums=1))
    return fn(small["params"],
              small["positions"] if positions is None else positions,
              small["edges"] if edges is None else edges)


# ---- the reference on its own ---------------------------------------------

def test_hand_built_line_graph():
    """Three atoms in an L: A - B 2.5 A, B - C 2.5 A, A - C 3.54 A, so two
    bonds (four directed), six directed edges, and two lines, both at B
    (A -> B with B -> C, C -> B with B -> A; a bond with its own reverse is
    no line), each with a right angle."""
    positions = jnp.asarray([[0.0, 0, 0], [2.5, 0, 0], [2.5, 2.5, 0]])
    src = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    dst = jnp.asarray([1, 2, 0, 2, 0, 1], jnp.int32)
    vec = positions[dst] - positions[src]
    d = jnp.linalg.norm(vec, axis=-1)
    is_bond = d <= CFG["bond_cutoff"]
    assert int(is_bond.sum()) == 4
    bonds_in, bonds_out, has_out, is_line, overflow = ref.line_graph(
        src, dst, is_bond, 3, 4)
    assert not bool(overflow)
    assert np.asarray(has_out).sum(axis=1).tolist() == [1, 2, 1]
    assert np.asarray(is_line).sum(axis=(1, 2)).tolist() == [0, 2, 0]
    theta = ref.angle(vec[bonds_in][:, :, None], d[bonds_in][:, :, None],
                      vec[bonds_out][:, None, :], d[bonds_out][:, None, :])
    np.testing.assert_allclose(np.asarray(theta)[np.asarray(is_line)],
                               [np.pi / 2, np.pi / 2], atol=1e-6)
    pairs = {(int(src[bonds_in[1, p]]), int(dst[bonds_out[1, q]]))
             for p, q in zip(*np.nonzero(np.asarray(is_line[1])))}
    assert pairs == {(0, 2), (2, 0)}
    # and what an evaluation reports to its tables
    tables = ref.Tables(CFG)
    params = ref.init_params(CFG, tables, jax.random.PRNGKey(1))
    ref.site_energies(params, CFG, tables, jnp.asarray([14, 8, 14]),
                      positions, (src, dst, jnp.zeros((6, 3))))
    jax.effects_barrier()
    assert tables.found == {"n_edges": 6, "n_bonds": 4, "n_lines": 2,
                            "overflow": False}
    # collinear bonds: theta stops short of pi where arccos has no slope
    straight = ref.angle(jnp.asarray([1.0, 0, 0]), 1.0,
                         jnp.asarray([1.0, 0, 0]), 1.0)
    assert np.pi - 2e-3 < float(straight) < np.pi


def test_fcc_has_twelve_bonds_and_132_lines_an_atom(small):
    energy(small)
    jax.effects_barrier()
    n = len(small["species"])
    assert small["tables"].found == {
        "n_edges": len(small["edges"][0]), "n_bonds": 12 * n,
        "n_lines": 132 * n, "overflow": False}


def test_rotation_and_translation(small):
    base, slope = energy(small)
    assert float(jnp.abs(slope).max()) > 1e-3   # it depends on the atoms
    rng = np.random.default_rng(1)
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    q = jnp.asarray(q * np.sign(np.linalg.det(q)), jnp.float32)
    src, dst, shift = small["edges"]
    turned, turned_slope = energy(
        small, positions=(small["positions"] + 0.37) @ q.T,
        edges=(src, dst, shift @ q.T))
    assert float(turned) == pytest.approx(float(base), abs=3e-5)
    np.testing.assert_allclose(turned_slope, slope @ q.T, atol=3e-6)


def test_reference_in_blocks_equals_the_reference_whole(small):
    whole, g_whole = energy(small, edge_block=None, atom_block=None)
    got, g = energy(small, edge_block=1000, atom_block=25)
    assert float(got) == pytest.approx(float(whole), abs=2e-5)
    np.testing.assert_allclose(g, g_whole, atol=3e-7, rtol=1e-4)


def test_ghost_edges_at_the_cutoff_change_nothing(small):
    """The harness pads the edges with ghosts of length exactly the cutoff
    between atom 0 and itself: not in the graph, though the embedded edge
    feature has a bias."""
    base, slope = energy(small)
    src, dst, shift = small["edges"]
    pad = 777
    ghosts = (jnp.concatenate([src, jnp.zeros(pad, src.dtype)]),
              jnp.concatenate([dst, jnp.zeros(pad, dst.dtype)]),
              jnp.concatenate([shift, jnp.tile(jnp.asarray(
                  [[CFG["cutoff"], 0.0, 0.0]], shift.dtype), (pad, 1))]))
    padded, padded_slope = energy(small, edges=ghosts)
    assert float(padded) == pytest.approx(float(base), abs=1e-5)
    np.testing.assert_allclose(padded_slope, slope, atol=1e-7)
    jax.effects_barrier()
    assert small["tables"].found["n_edges"] == len(src)


def test_a_table_that_overflows_is_not_a_smaller_graph(small):
    """Twelve bonds an atom into eleven slots: NaN energies and forces
    (``compare.against_limits`` reads a NaN as over every limit)."""
    tight = {**CFG, "reference_max_bonds": 11}
    total, slope = energy(small, cfg=tight)
    assert np.isnan(float(total)) and np.isnan(np.asarray(slope)).all()
    jax.effects_barrier()
    assert small["tables"].found["overflow"] is True


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


def test_the_guarded_float8_rounding_is_the_harness_rounding():
    """``ref.rounder`` holds the scaled tensor to the 8-bit type's range
    before it converts (on the chip the harness's came out NaN at the
    cell's size): on values in range, forward and cotangent, it is
    ``common.rounder`` to the last bit, and the other precisions are
    ``common``'s own."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 33)) ** 3,
                    jnp.float32)
    for precision in common.PRECISIONS:
        ours, theirs = ref.rounder(precision), common.rounder(precision)
        np.testing.assert_array_equal(ours(x), theirs(x))
        g = jax.grad(lambda t: (ours(t) * x[::-1]).sum())(x)
        g_ref = jax.grad(lambda t: (theirs(t) * x[::-1]).sum())(x)
        np.testing.assert_array_equal(g, g_ref)
    coarse = ref.rounder("float8_e4m3fn")(x)
    assert 0.005 < float(jnp.abs(coarse - x).max() / jnp.abs(x).max()) < 0.07


def test_sitewise_readout_is_taken_before_the_last_atom_conv(small):
    with jax.default_matmul_precision("highest"):
        energies, sites = ref.site_energies(
            small["params"], CFG, small["tables"], small["species"],
            small["positions"], small["edges"], with_sites=True)
        fewer = {**CFG, "num_blocks": CFG["num_blocks"] - 1}
        cut = {**small["params"],
               "atom_conv": small["params"]["atom_conv"][:-1],
               "bond_conv": small["params"]["bond_conv"][:-1]}
        # one block fewer: its LAST atom conv is this model's last but one,
        # and the bond conv that followed it moves no atom feature
        _, before = ref.site_energies(
            cut, fewer, ref.Tables(fewer), small["species"],
            small["positions"], small["edges"], with_sites=True)
    assert sites.shape == energies.shape and float(sites.min()) >= 0.0
    assert not np.allclose(sites, before, atol=1e-4)


# ---- the counts -----------------------------------------------------------

def test_step_flops_against_the_jaxpr(small):
    """The reference computes every slot of its K x K tables, the count is
    of real bonds and lines: the formula is held to the jaxpr with the
    slots in the real rows' place, the real rows to what the evaluation
    reports."""
    def total(pos):
        return ref.site_energies(small["params"], CFG, small["tables"],
                                 small["species"], pos, small["edges"],
                                 edge_block=None, atom_block=None).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(total))(small["positions"])
    counted = contraction_flops(jaxpr.jaxpr)
    n, n_edges = len(small["species"]), len(small["edges"][0])
    k = CFG["reference_max_bonds"]
    slots = ref.Tables(CFG)
    slots.found = {"n_edges": n_edges, "n_bonds": n * k, "n_lines": n * k * k}
    ours = family.step_flops(CFG, slots, n, n_edges)
    assert ours == pytest.approx(counted, rel=0.03), (ours, counted)
    # with the real rows: lines carry the step
    energy(small)
    real = family.step_flops(CFG, small["tables"], n, n_edges)
    assert real < ours
    doubled = ref.Tables(CFG)
    doubled.found = {**small["tables"].found,
                     "n_lines": 2 * small["tables"].found["n_lines"]}
    assert 1.5 < family.step_flops(CFG, doubled, n, n_edges) / real < 2.0
    with pytest.raises(RuntimeError):
        family.step_flops(CFG, ref.Tables(CFG), n, n_edges)
    assert family.kernel_work(CFG, slots, n, n_edges) == {}


def test_published_size():
    """89 species, 64 channels, 31 radial functions, max_f 4, 4 blocks give
    406,047 parameters through the program's own initialiser (the layout of
    ``tests/test_convert_chgnet.py::test_mptrj_shaped_dict_converts``; the
    v0.3.0 release counts 412,525 with its own angular basis), and the
    reference's tree maps onto the program's leaf for leaf."""
    cell = spec.load_cell(CELL)
    cfg = cell.config["model"]
    model = family.build_model({**cfg, "num_species": 89})
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 406_047
    model = family.build_model(cfg)
    tables = ref.Tables(cfg)
    mapped = jax.eval_shape(
        lambda k: family.program_params(ref.init_params(cfg, tables, k),
                                        tables, model), jax.random.PRNGKey(0))
    own = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mapped) == jax.tree.structure(own)
    assert ([x.shape for x in jax.tree.leaves(mapped)]
            == [x.shape for x in jax.tree.leaves(own)])
    # 1.07 TFLOP a step at 54 edges, 12 bonds, 132 lines an atom; the lines
    # are 70 % of it
    n = 8192
    tables.found = {"n_edges": 54 * n, "n_bonds": 12 * n, "n_lines": 132 * n}
    whole = family.step_flops(cfg, tables, n, 54 * n)
    assert 0.9e12 < whole < 1.2e12
    tables.found = {"n_edges": 54 * n, "n_bonds": 0, "n_lines": 0}
    assert 0.6 < 1.0 - family.step_flops(cfg, tables, n, 54 * n) / whole < 0.8
    assert family.receptive_radius(cfg) == 4 * 6.0 + 3 * 3.0


# ---- the cell's files -----------------------------------------------------

def test_cell_loads_from_files():
    """What ``test_spec.test_cell_loads_from_files`` asks of a cell, less
    its list of the two families the benchmark began with."""
    cell = spec.load_cell(CELL)
    assert cell.traffic["driver"] == "md" and cell.chips == 1
    assert cell.config["family"] == "chgnet" and cell.config["reduced"] == {}
    model = cell.config["model"]
    assert (model["units"], model["num_rbf"], model["num_angle"],
            model["num_blocks"], model["cutoff"], model["bond_cutoff"],
            model["shared_bond_weights"]) == (64, 31, 4, 4, 6.0, 3.0, "both")
    assert cell.config["potential"]["compute_dtype"] == "bfloat16"
    assert cell.traffic["structure"]["reps"] == [16, 16, 8]
    assert cell.traffic["trace_steps"] == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "atom_steps_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"model.line_message_ms_per_step.md",
                     "model.angle_update_ms_per_step.md",
                     "model.bond_graph_prep_ms_per_step.md", "model.mfu.md",
                     "model.unattributed_share.md",
                     "model.backward_share.md", "device.idle_share.md"}
    assert not {n for n in names if "roofline" in n or "rotation" in n}
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        assert callable(read) and params["reader"]
    assert set(cell.limits) == {"force_err_vs_rounding", "kick_rel_err"}
    for entry in cell.limits.values():
        assert entry["lower"] < entry["limit"] < entry["upper"]
    assert set(cell.traffic["caps"][cell.config_name]) == {
        "nodes", "edges", "halo", "bonds", "lines", "bond_map"}
    spec.load_module(cell, "families", "chgnet")
    built = family.build_model(model)
    assert built.cfg.use_bond_graph and built.cfg.bond_update_hidden is None
    assert not hasattr(built.cfg, "reference_max_bonds")


def test_new_stages_read_nothing_from_a_program_without_them():
    """The parent's CHGNet has no scope of its own: its stage tables know
    only what the dispatcher and the halo open. The reader then sums
    nothing under the new names, and does not raise."""
    from benchmark.readers import stage_time

    split = stage_time.by_label({"fusion.1": 10, "fusion.2": 30},
                                {"fusion.1": ("edge_message", "forward")})
    for stage in ("line_message", "angle_update", "line_geometry",
                  "bond_map"):
        assert sum(ns for (s, _), ns in split.items() if s == stage) == 0


# ---- the cell's own graph, with bonds -------------------------------------

def bond_host_graph(cell, seed: int):
    """``md.host_graph`` for a configuration with a bond graph: the padded
    graph as ``DistPotential._build_graph`` builds it on its first call
    (bond radius = bond cutoff + skin), on the host only."""
    from distmlip_tpu.neighbors import neighbor_list
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    atoms = md.build_atoms(cell.traffic, seed)
    skin = float(cell.traffic["skin"])
    r_build = float(cell.config["model"]["cutoff"]) + skin
    b_build = float(cell.config["model"]["bond_cutoff"]) + skin
    nl = neighbor_list(atoms.positions, atoms.cell, atoms.pbc, r_build,
                       bond_r=b_build)
    plan = build_plan(nl, atoms.cell, atoms.pbc, cell.chips, r_build,
                      b_build, True)
    caps = md.RecordingCaps(md.capacity_policy(cell))
    graph, host = build_partitioned_graph(
        plan, nl, np.asarray(atoms.numbers, np.int32), atoms.cell, caps=caps)
    return graph, caps.needed, host.stats


def test_shapes_with_bonds_do_not_depend_on_the_seed():
    cell = spec.load_cell(CELL)
    fixed = cell.traffic["caps"][cell.config_name]
    seen = set()
    for seed in SEEDS:
        graph, needed, stats = bond_host_graph(cell, seed)
        assert graph.has_bond_graph
        seen.add((signature(graph), graph.b_cap))
        assert set(fixed) <= set(needed)
        for cap in fixed:
            assert needed[cap] <= 0.97 * fixed[cap], (cap, needed[cap])
        # every capacity the builder asked for is one the mix fixes, or
        # holds nothing on one chip
        assert all(need == 0 for cap, need in needed.items()
                   if cap not in fixed), needed
        assert (stats["n_bonds_per_part"], stats["n_lines_per_part"]) == (
            [12 * 8192], [132 * 8192])
    assert len(seen) == 1
    assert {k: needed[k] for k in fixed} == {
        "nodes": 8192, "edges": 638976, "halo": 0, "bonds": 98304,
        "lines": 1081344, "bond_map": 98304}


def compile_bond_step(cell, topo, monkeypatch):  # noqa: F811
    """``test_compile_v5e.compile_step`` for this cell: its graph with
    bonds, the reference's weights through ``program_params``."""
    from jax.sharding import SingleDeviceSharding

    from distmlip_tpu.parallel import make_potential_fn

    model = family.build_model(cell.config["model"])
    model = type(model)(dataclasses.replace(
        model.cfg, dtype=cell.config["potential"]["compute_dtype"]))
    tables = ref.Tables(cell.config["model"], None)
    params = jax.eval_shape(
        lambda key: family.program_params(ref.init_params(
            cell.config["model"], tables, key), tables, model),
        jax.random.PRNGKey(0))
    graph, _, _ = bond_host_graph(cell, 0)
    one = SingleDeviceSharding(topo.devices[0])

    shaped = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                            sharding=one)
    graph_s = jax.tree.map(shaped, graph)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    potential = make_potential_fn(model.energy_fn, None)
    return potential.lower(jax.tree.map(shaped, params), graph_s,
                           graph_s.positions).compile()


def test_step_compiles_for_v5e(topo, monkeypatch):  # noqa: F811
    """The published size, 8,192 atoms with 1.08M lines and no remat, on a
    described v5e: XLA:TPU takes it, it fits, no Pallas call is in it."""
    from jax.experimental.compilation_cache import compilation_cache

    cell = spec.load_cell(CELL)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = compile_bond_step(cell, topo, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    print(f"{CELL}: arguments {memory.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    # over a quarter of the chip: the size a deployment would hold
    assert 0.25 * HBM_BYTES < peak < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()
