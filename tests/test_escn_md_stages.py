"""ESCNMD's energy-and-forces step at jaxpr level: the MOLE expert axis is
collapsed once a step outside every loop, every equation of the model
carries a stage (the twin of the benchmark's ``model.unattributed_share.md``),
the products that build Wigner blocks run at ``COORD_PRECISION`` where that
can show, and a model that is handed the merged weights gives the energy of
the one that merges them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.analysis.ir import iter_sites
from distmlip_tpu.calculators import Atoms, DistPotential
from distmlip_tpu.geometry import (COORD_PRECISION, frac_to_cart,
                                   make_supercell)
from distmlip_tpu.kernels.so3 import wigner_n_cols
from distmlip_tpu.models import ESCNMD, ESCNMDConfig
from distmlip_tpu.ops.so3_e3nn import wigner_blocks_from_edges
from distmlip_tpu.telemetry import STAGES
from distmlip_tpu.telemetry.stages import stage_of

LOOPS = ("scan", "while")


def config(**kw):
    return ESCNMDConfig(**{**dict(
        max_num_elements=20, sphere_channels=8, lmax=2, mmax=2, num_layers=2,
        hidden_channels=8, edge_channels=8, num_distance_basis=8, cutoff=3.5,
        avg_degree=12.0, edge_chunk=256, num_experts=4), **kw})


def atoms_of(nparts=1):
    rng = np.random.default_rng(7)
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = make_supercell(unit, np.eye(3) * 3.9, (3 * nparts, 2, 2))
    cart = frac_to_cart(frac, lattice) + rng.normal(0, 0.03, (len(frac), 3))
    numbers = np.where(np.arange(len(cart)) % 3 == 0, 8, 14)
    return Atoms(numbers=numbers, positions=cart, cell=lattice)


def step_sites(cfg, nparts=1, with_graph=False, **kw):
    model = ESCNMD(cfg)
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.3, **kw)
    graph, _, positions = pot._prepare(atoms_of(nparts))
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    sites = list(iter_sites(jaxpr))
    return (sites, graph) if with_graph else sites


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_expert_axis_is_collapsed_once_outside_every_loop(dtype):
    """Every equation under ``expert_mix`` sits outside every scan and
    while, the four experts' weights (a leading axis of 4 on a rank-3
    array) never enter a loop, and the scans multiply by plain matrices."""
    cfg = config(dtype=dtype)
    sites = step_sites(cfg)
    mixed = [s for s in sites if stage_of(s.stack) == "expert_mix"]
    assert mixed and {"reduce_sum", "mul"} <= {s.primitive for s in mixed}
    assert any(s.primitive == "exp" for s in mixed)       # the softmax
    inside = [(s.primitive, s.path) for s in mixed
              if any(p in LOOPS for p in s.path)]
    assert not inside
    scans = [s for s in sites if s.primitive == "scan"]
    assert len(scans) >= cfg.num_layers + 1   # and their transposes
    for site in scans:
        stacked = [v.aval.shape for v in site.eqn.invars
                   if len(v.aval.shape) == 3
                   and v.aval.shape[0] == cfg.num_experts
                   and v.aval.shape[1] > cfg.num_experts]
        assert not stacked, stacked
    looped_dots = [s for s in sites if s.primitive == "dot_general"
                   and any(p in LOOPS for p in s.path)]
    assert looped_dots
    assert all(stage_of(s.stack) in ("edge_message", "edge_rotation",
                                     "radial_mlp", "edge_aggregate")
               for s in looped_dots)


@pytest.mark.parametrize("nparts, kernels", [(1, None), (2, "interpret")])
def test_every_equation_of_the_model_carries_a_stage(nparts, kernels):
    sites = step_sites(config(dtype="bfloat16"), nparts, kernels=kernels)
    model = [s for s in sites if "model_energy" in s.stack]
    assert len(model) > 200
    bare = sorted({(s.primitive, s.stack) for s in model
                   if stage_of(s.stack) is None})
    assert not bare, bare[:10]
    seen = {stage_of(s.stack) for s in model}
    # ESCNMD has no pair repulsion, no bond graph and no NequIP gate; one
    # partition has no halo
    expected = set(STAGES) - {
        "pair_repulsion", "line_geometry", "line_message", "angle_update",
        "bond_map", "node_gate"} - ({"halo"} if nparts == 1 else set())
    assert expected <= seen, expected - seen
    if kernels == "interpret":
        calls = [s for s in model if s.primitive == "pallas_call"]
        assert calls and all(stage_of(s.stack) == "edge_aggregate"
                             for s in calls)


@pytest.mark.parametrize("nparts", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_edge_message_addresses_rows_by_index_only_on_nodes(dtype, nparts):
    """Between the two rotations the coefficients are flat per-m pieces cut
    by static slices: under ``edge_message`` nothing is written by index
    (no ``scatter``, no ``scatter-mul``) and the only rows read by index are
    the model's own ``hn[src]`` / ``hn[dst]`` (flat ``S * C`` rows of the
    node array), with the scatter-adds onto nodes that transpose them. The l-major ``(E, 9, c)``
    layout addressed through index lists counted 48 ``scatter``, 12
    ``scatter-mul``, 66 ``scatter-add`` and 117 rank-3 ``gather`` here
    (PR 28's tree, this config), each a loop of whole-array updates on the
    chip."""
    cfg = config(dtype=dtype)
    sites, graph = step_sites(cfg, nparts, with_graph=True)
    msg = [s for s in sites if stage_of(s.stack) == "edge_message"]
    assert any(s.primitive == "dot_general" for s in msg)
    assert not [s for s in msg if s.primitive in ("scatter", "scatter-mul")]
    node_shape = (graph.n_cap, cfg.sphere_dim * cfg.sphere_channels)
    adds = [s.eqn.outvars[0].aval.shape for s in msg
            if s.primitive == "scatter-add"]
    gathers = [s.eqn.invars[0].aval.shape for s in msg
               if s.primitive == "gather"]
    # two a layer; forward and recompute read, the backward adds
    assert adds == [node_shape] * (2 * cfg.num_layers), adds
    assert gathers == [node_shape] * (4 * cfg.num_layers), gathers


def test_rotation_rows_pass_through_the_kernels_alone():
    """At a whole lane tile of channels with the kernel path forced, every
    equation under ``edge_rotation`` inside the scans that touches a row of
    channels is a rotation kernel: no ``concatenate``, ``gather``,
    ``scatter``, product or elementwise pass over an ``(E_c, .., c)``
    operand (the envelope multiplies the ``(E_c, 35)`` block columns).
    What else sits there builds the blocks and hands them over, on arrays
    of at most 35 lanes. Every equation still carries a stage."""
    cfg = config(dtype="bfloat16", sphere_channels=128, hidden_channels=128,
                 num_layers=1, num_experts=1)
    sites = step_sites(cfg, kernels="interpret")
    model = [s for s in sites if "model_energy" in s.stack]
    assert not [s.primitive for s in model if stage_of(s.stack) is None]
    rot = [s for s in model if stage_of(s.stack) == "edge_rotation"
           and any(p in LOOPS for p in s.path)
           and "pallas_call" not in s.path]     # not the kernels' bodies
    n_cols = wigner_n_cols(cfg.lmax)

    def rows(s):
        """Shapes of the rank >= 2 operands and results a whole lane tile
        wide or wider: rows of channels."""
        return [v.aval.shape for v in [*s.eqn.invars, *s.eqn.outvars]
                if len(getattr(v.aval, "shape", ())) >= 2
                and v.aval.shape[-1] >= cfg.sphere_channels]

    wide = [s for s in rot if rows(s)]
    wrappers = {"pallas_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
                "checkpoint", "remat", "pjit", "jit", "closed_call"}
    # besides the kernels: the columns' cotangent leaves its kernel as one
    # float32 lane tile, of which the 35 columns are cut
    cuts = [s for s in wide if s.primitive not in wrappers]
    assert {s.primitive for s in cuts} <= {"slice"}, cuts
    assert all(rows(s) == [(cfg.edge_chunk, 128)]
               and s.eqn.outvars[0].aval.shape == (cfg.edge_chunk, n_cols)
               for s in cuts)
    assert "pallas_call" in {s.primitive for s in wide}
    calls = [s for s in rot if s.primitive == "pallas_call"]
    # the edge-degree scan: out, and backward the columns' cotangent (no
    # cotangent reaches its radial rows' producer but through the rows);
    # a layer: in + out forward and recomputed, four passes backward
    assert len(calls) >= 2 + 8 * cfg.num_layers, len(calls)
    small = {s.primitive for s in rot if not rows(s)}
    assert "dot_general" in small    # the blocks' own X J X J products
    pallas = [s for s in model if s.primitive == "pallas_call"]
    assert {stage_of(s.stack) for s in pallas} == {"edge_rotation",
                                                   "edge_aggregate"}


def block_products(sites):
    """The contractions that build Wigner blocks: under ``edge_rotation``,
    5 x 5 or 3 x 3 on both sides (a rotation has features on one)."""
    return [s for s in sites if s.primitive == "dot_general"
            and stage_of(s.stack) == "edge_rotation"
            and all(v.aval.shape[-1] <= 5 and v.aval.shape[-2] <= 5
                    for v in s.eqn.invars)]


def test_wigner_block_products_run_at_coord_precision_where_it_can_show():
    """A float32 matmul is one bfloat16 pass on a TPU unless a precision is
    set: the products X(a) J X(b) J that build each edge's block carry
    ``COORD_PRECISION`` by default and in a float32 model. A bfloat16 model
    casts every block to bfloat16 where it uses it, so the extra passes buy
    nothing there (3.2 % of the uma-md-1c step for the same force error, my
    chip runs, PR 28): its products take the ambient precision."""
    rhat = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3)),
                       jnp.float32)
    rhat = rhat / jnp.linalg.norm(rhat, axis=1, keepdims=True)
    want = jax.lax.Precision(COORD_PRECISION)
    for gamma in (None, jnp.zeros(5)):
        jaxpr = jax.make_jaxpr(
            lambda r: wigner_blocks_from_edges(2, r, gamma))(rhat)
        dots = [s for s in iter_sites(jaxpr) if s.primitive == "dot_general"]
        assert dots
        for site in dots:
            assert set(np.ravel(site.eqn.params["precision"])) == {want}
    full = block_products(step_sites(config(dtype="float32")))
    assert full
    assert all(set(np.ravel(s.eqn.params["precision"])) == {want}
               for s in full)
    served = block_products(step_sites(config(dtype="bfloat16")))
    assert len(served) == len(full)
    assert all(s.eqn.params["precision"] is None for s in served)


def test_merged_weights_give_the_energy_of_the_model_that_merges_them():
    """The parent mixed the experts at every use, inside the scans; the
    same numbers come from a one-expert model holding the merged matrices
    (gate and merge redone here in numpy, float64)."""
    cfg = config()
    model = ESCNMD(cfg)
    params = model.init(jax.random.PRNGKey(3))
    atoms = atoms_of()
    out = DistPotential(model, params, num_partitions=1).calculate(atoms)

    p64 = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    lin = lambda p, x: x @ p["w"].T + p["b"]
    silu = lambda x: x / (1.0 + np.exp(-x))
    csd = lin(p64["csd"]["mix"], np.concatenate([
        p64["csd"]["charge"]["w"][0 - cfg.charge_min],
        p64["csd"]["spin"]["w"][0], p64["csd"]["dataset"]["w"][0]]))
    composition = p64["sphere_embedding"]["w"][atoms.numbers].mean(axis=0)
    logits = lin(p64["mole_gate"]["lin2"], silu(lin(
        p64["mole_gate"]["lin1"], np.concatenate([composition, csd]))))
    mole = np.exp(logits - logits.max())
    mole /= mole.sum()
    assert mole.min() > 0.01   # every expert counts

    merged = {k: v for k, v in params.items() if k != "mole_gate"}
    merged["blocks"] = [
        {**blk, **{conv: {
            k: (jnp.asarray(np.einsum("k,kab->ab", mole, np.asarray(
                w, np.float64)), jnp.float32) if np.ndim(w) == 3 else w)
            for k, w in blk[conv].items()} for conv in ("so2_1", "so2_2")}}
        for blk in params["blocks"]]
    single = ESCNMD(dataclasses.replace(cfg, num_experts=1))
    assert (jax.tree.structure(single.init(jax.random.PRNGKey(0)))
            == jax.tree.structure(merged))
    ref = DistPotential(single, merged, num_partitions=1).calculate(atoms)
    assert abs(out["energy"] - ref["energy"]) / len(atoms) < 1e-6
    scale = np.abs(ref["forces"]).max()
    np.testing.assert_allclose(out["forces"], ref["forces"],
                               atol=2e-4 * scale)


LOOP_HLO = """HloModule jit_f

%body (s: (s32[], u32[8,9,4], u32[8,1,4])) -> (s32[], u32[8,9,4], u32[8,1,4]) {
  %s = (s32[], u32[8,9,4], u32[8,1,4]) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %buf = u32[8,9,4] get-tuple-element(%s), index=1
  %row = u32[8,1,4] get-tuple-element(%s), index=2
  %moved = u32[8,1,4] copy(%row)
  %dus = u32[8,9,4] dynamic-update-slice(%buf, %moved, %i, %i, %i)
  ROOT %t = (s32[], u32[8,9,4], u32[8,1,4]) tuple(%i, %dus, %row)
}

%cond (s: (s32[], u32[8,9,4], u32[8,1,4])) -> pred[] {
  %s = (s32[], u32[8,9,4], u32[8,1,4]) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  ROOT %lt = pred[] compare(%i, %i), direction=LT
}

ENTRY %main (x: u32[8,9,4], y: u32[8,1,4]) -> u32[8,9,4] {
  %x = u32[8,9,4] parameter(0)
  %y = u32[8,1,4] parameter(1)
  %z = s32[] constant(0)
  %made = u32[8,1,4] add(%y, %y), metadata={op_name="jit(f)/jvp(edge_rotation)/add"}
  %into = u32[8,9,4] dynamic-update-slice(%x, %made, %z, %z, %z)
  %st = (s32[], u32[8,9,4], u32[8,1,4]) tuple(%z, %into, %y)
  %loop = (s32[], u32[8,9,4], u32[8,1,4]) while(%st), condition=%cond, body=%body, metadata={op_name="jit(f)/transpose(jvp(model))/edge_message/scatter"}
  ROOT %out = u32[8,9,4] get-tuple-element(%loop), index=1
}
"""


def test_an_update_without_metadata_takes_its_stage_from_update_or_loop():
    """What the compiler makes of ``y.at[:, rows, :].set(w)``: a ``while``
    over the rows (it keeps the scatter's metadata) whose body (whole-array
    updates, copies) has none; and an update written into a buffer whose
    first operand has no stage but whose second has. ``uma-md-1c`` read
    10.6 % unattributed before these two rules (my chip run, PR 28)."""
    from distmlip_tpu.telemetry.stages import stage_table

    rows = {r["head"].split(" = ")[0]: r for r in stage_table(LOOP_HLO)}
    assert (rows["%into"]["stage"], rows["%into"]["pass"],
            rows["%into"]["inherited"]) == ("edge_rotation", "forward", True)
    for name in ("%dus", "%moved", "%lt"):
        assert (rows[name]["stage"], rows[name]["pass"],
                rows[name]["inherited"]) == ("edge_message", "backward", True)
    assert rows["%loop"]["stage"] == "edge_message"
