"""The cell ``dimenet-pp-md-1c`` and its family ``dimenet`` (DimeNet++ at the
sizes of PyG's ``from_qm9_pretrained``): the plain reference on its own (42
neighbours and 1,722 triplets an atom, a global rotation and translation,
blocks of centre atoms, finite differences, the harness's ghost edges, a
table that overflows, its roots against the program's, the rounding twins
and the float8 clip), the parameter count on both sides, the operation
counts, the cell's files, its own host graph with bonds across seeds, and
its step compiled for a described v5e. The program against the reference:
``test_dimenet_program.py``.

``md.host_graph`` and ``test_compile_v5e.compile_step`` build with bond
radius 0.0 and no bond graph and belong to the benchmark as it stands, so
this cell's graph and compile are made here, as ``test_chgnet_cell.py``
makes CHGNet's.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from benchmark.drivers import md
from benchmark.families import dimenet as family
from benchmark.harness import spec, structures
from benchmark.reference import chgnet, common
from benchmark.reference import dimenet as ref
from test_compile_v5e import HBM_BYTES, topo  # noqa: F401
from test_flops import contraction_flops
from test_shapes_across_seeds import SEEDS, signature

CELL = "dimenet-pp-md-1c"
toy.TOY_MODELS.setdefault("dimenet", {
    "num_species": 95, "hidden_channels": 16, "out_emb_channels": 32,
    "int_emb_size": 8, "basis_emb_size": 4, "num_blocks": 2,
    "num_spherical": 7, "num_radial": 6, "cutoff": 5.0,
    "envelope_exponent": 5, "num_before_skip": 1, "num_after_skip": 2,
    "num_output_layers": 3, "reference_max_neighbours": 64})
CFG = toy.TOY_MODELS["dimenet"]
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


def energy(small, positions=None, edges=None, cfg=CFG, tables=TABLES,
           **kw):
    def total(params, pos, e):
        with jax.default_matmul_precision("highest"):
            return ref.site_energies(params, cfg, tables, small["species"],
                                     pos, e, **kw).sum()

    return jax.jit(jax.value_and_grad(total, argnums=1))(
        small["params"],
        small["positions"] if positions is None else positions,
        small["edges"] if edges is None else edges)


# ---- the reference on its own ---------------------------------------------

def test_fcc_has_42_neighbours_and_1722_triplets_an_atom(small):
    energy(small)
    jax.effects_barrier()
    n = len(small["species"])
    assert TABLES.found == {"n_edges": 42 * n, "n_triplets": 42 * 41 * n,
                            "overflow": False}


def test_rotation_and_translation(small):
    base, slope = energy(small)
    assert float(jnp.abs(slope).max()) > 1e-2
    rng = np.random.default_rng(1)
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    q = jnp.asarray(q * np.sign(np.linalg.det(q)), jnp.float32)
    src, dst, shift = small["edges"]
    turned, turned_slope = energy(
        small, positions=(small["positions"] + 0.37) @ q.T,
        edges=(src, dst, shift @ q.T))
    assert float(turned) == pytest.approx(float(base), rel=5e-6)
    # float32 round-off in another frame: 1e-6 of the forces' norm
    assert np.linalg.norm(turned_slope - slope @ q.T) < \
        1e-5 * np.linalg.norm(slope)


def test_reference_in_blocks_equals_the_reference_whole(small):
    whole, g_whole = energy(small, atom_block=None)
    got, g = energy(small, atom_block=25)
    assert float(got) == pytest.approx(float(whole), rel=1e-6)
    np.testing.assert_allclose(g, g_whole, atol=2e-5)


def test_ghost_edges_at_the_cutoff_change_nothing(small):
    """The harness pads the edges with ghosts of length exactly the cutoff
    between atom 0 and itself: not in the graph, in no triplet table."""
    base, slope = energy(small)
    src, dst, shift = small["edges"]
    pad = 777
    ghosts = (jnp.concatenate([src, jnp.zeros(pad, src.dtype)]),
              jnp.concatenate([dst, jnp.zeros(pad, dst.dtype)]),
              jnp.concatenate([shift, jnp.tile(jnp.asarray(
                  [[CFG["cutoff"], 0.0, 0.0]], shift.dtype), (pad, 1))]))
    padded, padded_slope = energy(small, edges=ghosts)
    assert float(padded) == pytest.approx(float(base), rel=1e-6)
    np.testing.assert_allclose(padded_slope, slope, atol=1e-6)
    jax.effects_barrier()
    assert TABLES.found["n_edges"] == len(src)
    assert not TABLES.found["overflow"]


def test_a_table_that_overflows_is_not_a_smaller_graph(small):
    """42 edges an atom into 41 slots: NaN energies and forces
    (``compare.against_limits`` reads a NaN as over every limit)."""
    tight = {**CFG, "reference_max_neighbours": 41}
    total, slope = energy(small, cfg=tight)
    assert np.isnan(float(total)) and np.isnan(np.asarray(slope)).all()


def test_right_angles_leave_the_forces_finite(small):
    """The unperturbed lattice has triplets at exactly 90 degrees (cos
    theta = 0.0 in float32), where the slope of cos ** 0 is NaN; the
    cell's perturbed lattice met one in about a fifth of its seeds on the
    chip (PR 38)."""
    numbers, positions, cell = structures.perturbed_fcc(
        (3, 3, 3), 3.9, 0.0, 14, seed=0)
    src, dst, shift = common.neighbour_pairs(positions, cell, CFG["cutoff"])
    _, slope = energy(small, positions=jnp.asarray(positions, jnp.float32),
                      edges=(jnp.asarray(src), jnp.asarray(dst),
                             jnp.asarray(shift, jnp.float32)))
    assert np.isfinite(np.asarray(slope)).all()


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
                float(numeric), rel=1e-5, abs=1e-8)


def test_roots_and_bases_are_the_programs_by_another_route():
    """The reference's roots (sign changes of the finite trigonometric sum
    on a grid) and normalisers against the program's (bisection between
    the roots of the order below, upward recurrence); its j_l and P_l
    against the program's in float32."""
    from distmlip_tpu.ops import radial

    z, norms = radial.spherical_bessel_table(7, 6)
    np.testing.assert_allclose(TABLES.roots, z, rtol=1e-12)
    np.testing.assert_allclose(TABLES.norms, norms, rtol=1e-10)
    x = jnp.linspace(0.5, 1.0, 501)
    arg = jnp.asarray(z, jnp.float32)[None] * x[:, None, None]
    ours = jnp.stack([ref._jl_finite(jnp, l, arg[:, l]) for l in range(7)],
                     axis=1)
    theirs = radial.spherical_bessel_jl(7, arg)
    scale = jnp.abs(theirs).max(axis=0)
    assert float((jnp.abs(ours - theirs) / scale).max()) < 1e-5
    cos = jnp.linspace(-1.0, 1.0, 41)
    powers = cos[:, None] ** jnp.arange(7, dtype=jnp.float32)
    legendre = powers @ jnp.asarray(TABLES.legendre, jnp.float32).T
    scale = np.sqrt((2 * np.arange(7) + 1) / (4 * np.pi))
    np.testing.assert_allclose(legendre * scale,
                               radial.legendre_rows(cos, 7), atol=2e-6)


def test_rounding_twins_and_the_float8_clip():
    """The twins are ``chgnet.rounder``'s: bfloat16 rounds to 8 bits of
    mantissa, float8 holds the scaled tensor to e4m3's range before it
    converts (a division can land past 448 on the chip and e5m2's
    cotangent then has an infinity), so a tensor and its cotangent stay
    finite, and float32 is the identity."""
    assert ref.rounder is chgnet.rounder
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 33)) ** 5,
                    jnp.float32)
    assert bool((ref.rounder("float32")(x) == x).all())
    half = ref.rounder("bfloat16")(x)
    assert 0 < float(jnp.abs(half - x).max() / jnp.abs(x).max()) < 2 ** -8
    fp8 = ref.rounder("float8_e4m3fn")
    coarse = fp8(x)
    g = jax.grad(lambda t: (fp8(t) * x[::-1] * 1e30).sum())(x)
    assert bool(jnp.isfinite(coarse).all()) and bool(jnp.isfinite(g).all())
    assert 0.005 < float(jnp.abs(coarse - x).max() / jnp.abs(x).max()) < 0.07


# ---- the counts -----------------------------------------------------------

def test_published_size_counts_1885830_on_both_sides():
    cell = spec.load_cell(CELL)
    cfg = cell.config["model"]
    model = family.build_model(cfg)
    tables = ref.Tables(cfg)
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    own = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mapped = jax.eval_shape(
        lambda k: family.program_params(ref.init_params(cfg, tables, k),
                                        tables, model), jax.random.PRNGKey(0))
    assert count(own) == count(mapped) == 1_885_830
    assert jax.tree.structure(mapped) == jax.tree.structure(own)
    assert ([x.shape for x in jax.tree.leaves(mapped)]
            == [x.shape for x in jax.tree.leaves(own)])
    assert not hasattr(model.cfg, "reference_max_neighbours")
    assert model.cfg.bond_cutoff == model.cfg.cutoff == 5.0
    assert family.receptive_radius(cfg) == 5 * 5.0
    # 42 edges and 1,722 triplets an atom: 1.2 TFLOP a step with the
    # cotangents, the triplets 16 % of it (the edge linears are the rest)
    n = 8192
    tables.found = {"n_edges": 42 * n, "n_triplets": 1722 * n}
    whole = family.step_flops(cfg, tables, n, 42 * n)
    assert 1.1e12 < whole < 1.3e12
    tables.found = {"n_edges": 42 * n, "n_triplets": 0}
    assert 0.1 < 1 - family.step_flops(cfg, tables, n, 42 * n) / whole < 0.2


def test_step_flops_against_the_jaxpr(small):
    """The reference computes every slot of its K x K tables, the count is
    of real triplets: the formula is held to the jaxpr with the slots in
    the real triplets' place."""
    def total(pos):
        return ref.site_energies(small["params"], CFG, TABLES,
                                 small["species"], pos, small["edges"],
                                 atom_block=None).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(total))(small["positions"])
    counted = contraction_flops(jaxpr.jaxpr)
    n, n_edges = len(small["species"]), len(small["edges"][0])
    k = CFG["reference_max_neighbours"]
    slots = ref.Tables(CFG)
    slots.found = {"n_edges": n_edges, "n_triplets": n * k * k}
    ours = family.step_flops(CFG, slots, n, n_edges)
    assert ours == pytest.approx(counted, rel=0.03), (ours, counted)
    energy(small)
    real = family.step_flops(CFG, TABLES, n, n_edges)
    assert real < ours
    with pytest.raises(RuntimeError):
        family.step_flops(CFG, ref.Tables(CFG), n, n_edges)
    assert family.kernel_work(CFG, slots, n, n_edges) == {}


# ---- the cell's files -----------------------------------------------------

def test_cell_loads_from_files():
    """What ``test_spec.test_cell_loads_from_files`` asks of a cell, less
    its list of the two families the benchmark began with."""
    cell = spec.load_cell(CELL)
    assert cell.traffic["driver"] == "md" and cell.chips == 1
    assert cell.config["family"] == "dimenet" and cell.config["reduced"] == {}
    model = cell.config["model"]
    assert (model["hidden_channels"], model["out_emb_channels"],
            model["int_emb_size"], model["basis_emb_size"],
            model["num_blocks"], model["num_spherical"], model["num_radial"],
            model["cutoff"], model["envelope_exponent"],
            model["num_before_skip"], model["num_after_skip"],
            model["num_output_layers"]) == (128, 256, 64, 8, 4, 7, 6, 5.0, 5,
                                            1, 2, 3)
    assert cell.config["potential"]["compute_dtype"] == "bfloat16"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "atom_steps_per_s_per_chip"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"model.triplet_basis_ms_per_step.md",
                     "model.triplet_message_ms_per_step.md",
                     "model.edge_update_ms_per_step.md",
                     "model.bond_graph_prep_ms_per_step.md", "model.mfu.md",
                     "model.unattributed_share.md", "device.idle_share.md"}
    assert not {n for n in names if "roofline" in n}
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        assert callable(read) and params["reader"]
    assert set(cell.limits) == {"force_err_vs_rounding", "kick_rel_err"}
    for entry in cell.limits.values():
        assert entry["lower"] < entry["limit"] < entry["upper"]
    assert set(cell.traffic["caps"][cell.config_name]) == {
        "nodes", "edges", "halo", "bonds", "lines", "bond_map"}
    spec.load_module(cell, "families", "dimenet")


def test_new_stages_read_nothing_from_a_program_without_them():
    """The parent has no DimeNet++: its stage tables know none of the three
    stages, and the reader sums nothing under them and does not raise."""
    from benchmark.readers import stage_time

    split = stage_time.by_label({"fusion.1": 10, "fusion.2": 30},
                                {"fusion.1": ("edge_message", "forward")})
    for stage in ("triplet_basis", "triplet_message", "edge_update"):
        assert sum(ns for (s, _), ns in split.items() if s == stage) == 0


# ---- the cell's own graph, with every edge a bond -------------------------

def bond_host_graph(cell, seed: int):
    """``md.host_graph`` for a configuration with a bond graph: the padded
    graph as ``DistPotential._build_graph`` builds it on its first call
    (bond radius = bond cutoff + skin = cutoff + skin), on the host."""
    from distmlip_tpu.neighbors import neighbor_list
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    atoms = md.build_atoms(cell.traffic, seed)
    r_build = float(cell.config["model"]["cutoff"]) + float(
        cell.traffic["skin"])
    nl = neighbor_list(atoms.positions, atoms.cell, atoms.pbc, r_build,
                       bond_r=r_build)
    plan = build_plan(nl, atoms.cell, atoms.pbc, cell.chips, r_build,
                      r_build, True)
    caps = md.RecordingCaps(md.capacity_policy(cell))
    graph, host = build_partitioned_graph(
        plan, nl, np.asarray(atoms.numbers, np.int32), atoms.cell, caps=caps)
    return graph, caps.needed, host.stats


def test_shapes_with_bonds_do_not_depend_on_the_seed():
    """Every seed's graph fits the fixed capacities with room (each need at
    most 0.97 of its capacity), every built edge is a bond, and the table
    has 53 slabs: the lattice's largest in-degree at 5.5 A is 52 over these
    seeds (51 lines into a bond), and two slabs more hold an in-degree of
    54, where one atom in about 800 seeds would reach 53."""
    cell = spec.load_cell(CELL)
    fixed = cell.traffic["caps"][cell.config_name]
    seen = set()
    for seed in SEEDS:
        graph, needed, stats = bond_host_graph(cell, seed)
        assert graph.has_bond_graph
        seen.add((signature(graph), graph.b_cap))
        for cap in fixed:
            assert needed[cap] <= 0.97 * fixed[cap] or needed[cap] <= 128, \
                (cap, needed[cap])
        assert all(need == 0 for cap, need in needed.items()
                   if cap not in fixed), needed
        assert stats["n_bonds_per_part"] == stats["n_edges_per_part"]
        assert stats["line_slots"] == 53
        assert 0.83 < stats["line_table_fill"] < 0.87
        assert 17.4e6 < stats["n_lines"] < 17.5e6
    assert len(seen) == 1


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
    compiled = potential.lower(jax.tree.map(shaped, params), graph_s,
                               graph_s.positions).compile()
    return compiled, graph


@pytest.mark.slow  # minutes on this CPU; the jaxpr test is tier-1
def test_step_compiles_for_v5e(topo, monkeypatch):  # noqa: F811
    """The published size, 8,192 atoms with 20.9M table slots, on a
    described v5e: XLA:TPU takes it, it fits, and no buffer of the compiled
    step runs over the slots wider than one number (the index and mask
    columns): the triplet work lives in the scan's slab rows."""
    from jax.experimental.compilation_cache import compilation_cache

    cell = spec.load_cell(CELL)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled, graph = compile_bond_step(cell, topo, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    print(f"{CELL}: arguments {memory.argument_size_in_bytes / 1e9:.2f} "
          f"GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert peak < HBM_BYTES
    slots, rows = graph.line_src.shape[-1], graph.b_cap
    over_slots = {}
    for dtype, dims in re.findall(r"\b(\w+)\[([\d,]+)\]",
                                  compiled.as_text()):
        dims = tuple(int(d) for d in dims.split(","))
        if slots in dims or (slots // rows, rows) == dims[:2]:
            over_slots.setdefault(dtype, set()).add(dims)
    # line_src and line_mask, whole ([1, slots], [slots]) and by slab
    # ([K, b_cap]); nothing of a float type
    print(over_slots)
    assert set(over_slots) == {"s32", "pred"}, over_slots
    assert all(dims in {(1, slots), (slots,), (slots // rows, rows)}
               for found in over_slots.values() for dims in found)
