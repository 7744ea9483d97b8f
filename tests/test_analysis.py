"""Contract-pass framework tests: seeded violations + clean real models.

Each seeded fixture is a deliberately bad program per registered pass — a
hidden psum on the batch axis, a host callback inside a while_loop body, a
float64 closure leak, an unhinted scatter-add on a forward program, a
giant baked-in constant, a dead collective — and must be caught with the
right severity and scope location, driving the CLI's exit-code convention
(``exit_code == 3``). The clean-run tests trace the four real models'
(1,1) programs (the full placement family runs in the ``slow`` lane and
``tools/contract_check.py``) and must come back error-free.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distmlip_tpu.analysis import (Program, Severity, error_count, exit_code,
                                   get_passes, ir, lint_file, run_passes,
                                   warning_count)

pytestmark = pytest.mark.contracts



def _findings(pass_name, findings):
    return [f for f in findings if f.pass_name == pass_name]


# ---------------------------------------------------------------------------
# seeded violations: one deliberately bad program per pass
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_seeded_hidden_batch_axis_psum():
    """A psum sneaking onto the batch axis of the 2-D mesh violates the
    zero-cross-batch-communication invariant: ERROR, exit 3."""
    from jax.sharding import PartitionSpec as P

    from distmlip_tpu.parallel import BATCH_AXIS, device_mesh

    mesh = device_mesh(2, 2)

    @jax.jit
    def bad(x):
        def local(v):
            return jax.lax.psum(v, BATCH_AXIS)

        return jax.shard_map(local, mesh=mesh,
                         in_specs=P(BATCH_AXIS), out_specs=P())(x)

    jaxpr = jax.make_jaxpr(bad)(jnp.ones((4, 3), jnp.float32))
    findings = run_passes(
        Program(name="seeded_batch_psum", jaxpr=jaxpr,
                config={"forbidden_axes": [BATCH_AXIS]}),
        get_passes(["collective_placement"]))
    errs = [f for f in _findings("collective_placement", findings)
            if f.severity == Severity.ERROR]
    assert errs and errs[0].rule == "forbidden-axis"
    assert "batch" in errs[0].message
    assert errs[0].program == "seeded_batch_psum"
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_seeded_callback_in_while_loop():
    """A pure_callback inside a while_loop body stalls the device on the
    host EVERY iteration: ERROR with the loop in the scope path."""

    @jax.jit
    def bad(x):
        def body(c):
            y = jax.pure_callback(
                lambda v: np.asarray(v, np.float32),
                jax.ShapeDtypeStruct((), jnp.float32), c)
            return y + 1.0

        return jax.lax.while_loop(lambda c: c < 10.0, body, x)

    jaxpr = jax.make_jaxpr(bad)(jnp.float32(0.0))
    findings = run_passes(Program(name="seeded_callback", jaxpr=jaxpr),
                          get_passes(["host_sync"]))
    errs = [f for f in _findings("host_sync", findings)
            if f.severity == Severity.ERROR]
    assert errs, findings
    assert any("while" in f.path for f in errs), [f.path for f in errs]
    assert errs[0].rule == "loop"
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_seeded_callback_device_resident_program():
    """In a device_resident-tagged program (the DeviceMD chunk contract)
    even a loop-free callback is an ERROR — mandatory zero."""

    @jax.jit
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v, np.float32),
            jax.ShapeDtypeStruct((), jnp.float32), x) + 1.0

    jaxpr = jax.make_jaxpr(bad)(jnp.float32(0.0))
    findings = run_passes(
        Program(name="seeded_resident", jaxpr=jaxpr,
                tags=frozenset({"device_resident"})),
        get_passes(["host_sync"]))
    assert error_count(findings) >= 1
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_seeded_f64_leak():
    """An un-cast np.float64 closure array promotes the device path to f64
    under x64 tracing: both the aval walk and the const scan must fire."""

    leak = np.random.default_rng(0).normal(size=(8, 3))  # float64 host array

    def bad(x):
        return jnp.sum(x * leak)

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(bad)(jnp.ones((8, 3), jnp.float32))
    findings = run_passes(
        Program(name="seeded_f64", jaxpr=jaxpr,
                tags=frozenset({"x64"})),
        get_passes(["dtype_discipline"]))
    rules = {f.rule for f in _findings("dtype_discipline", findings)
             if f.severity == Severity.ERROR}
    assert "f64-aval" in rules, findings
    assert "f64-const" in rules, findings
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_seeded_unhinted_scatter_add():
    """A forward-program segment_sum without indices_are_sorted=True falls
    off the TPU scatter fast path: ERROR, located at the call site."""
    idx = jnp.array([0, 1, 1, 2], jnp.int32)

    def bad(v):
        return jax.ops.segment_sum(v, idx, num_segments=4)

    jaxpr = jax.make_jaxpr(bad)(jnp.ones((4, 2), jnp.float32))
    findings = run_passes(
        Program(name="seeded_scatter", jaxpr=jaxpr,
                tags=frozenset({"forward"})),
        get_passes(["scatter_hints"]))
    errs = [f for f in _findings("scatter_hints", findings)
            if f.severity == Severity.ERROR]
    assert errs and errs[0].rule == "unhinted-add"
    assert errs[0].location and errs[0].location[0].endswith(
        "test_analysis.py")
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_seeded_unhinted_scatter_grad_program_exempt():
    """The SAME unsorted scatter in a grad-tagged program is legitimate
    (transposed gather) — the pass only runs on forward programs."""
    idx = jnp.array([0, 1, 1, 2], jnp.int32)

    def bad(v):
        return jax.ops.segment_sum(v, idx, num_segments=4)

    jaxpr = jax.make_jaxpr(bad)(jnp.ones((4, 2), jnp.float32))
    findings = run_passes(
        Program(name="grad_prog", jaxpr=jaxpr, tags=frozenset({"grad"})),
        get_passes(["scatter_hints"]))
    assert not findings
    assert exit_code(findings) == 0


@pytest.mark.tier1
def test_seeded_giant_baked_const():
    """An 8 MiB array closed over instead of passed as an argument ships
    with (and can recompile) the executable: ERROR past 4 MiB."""
    giant = jnp.asarray(np.zeros((1024, 1024, 2), np.float32))  # 8 MiB

    def bad(x):
        return jnp.sum(x + giant)

    jaxpr = jax.make_jaxpr(bad)(jnp.ones((1024, 1024, 2), jnp.float32))
    findings = run_passes(Program(name="seeded_const", jaxpr=jaxpr),
                          get_passes(["recompile_hazard"]))
    errs = [f for f in _findings("recompile_hazard", findings)
            if f.severity == Severity.ERROR]
    assert errs and errs[0].rule == "giant-const"
    assert "8.0 MiB" in errs[0].message
    assert exit_code(findings) == 3
    # raising the threshold per program (audited static table) clears it
    ok = run_passes(
        Program(name="seeded_const_ok", jaxpr=jaxpr,
                config={"const_error_bytes": 16 * 1024 * 1024}),
        get_passes(["recompile_hazard"]))
    assert exit_code(ok) == 0


@pytest.mark.tier1
def test_seeded_dead_collective():
    """A collective with no path to a program output escapes every cost
    model: WARNING (dead arithmetic stays INFO)."""
    from jax.sharding import PartitionSpec as P

    from distmlip_tpu.parallel import SPATIAL_AXIS, device_mesh

    mesh = device_mesh(1, 2)

    @jax.jit
    def bad(x):
        def local(v):
            dead = jax.lax.psum(v, SPATIAL_AXIS)  # noqa: F841 - seeded
            return v * 2.0

        return jax.shard_map(local, mesh=mesh,
                         in_specs=P(SPATIAL_AXIS), out_specs=P(SPATIAL_AXIS))(x)

    jaxpr = jax.make_jaxpr(bad)(jnp.ones((4, 3), jnp.float32))
    findings = run_passes(Program(name="seeded_dead", jaxpr=jaxpr),
                          get_passes(["dead_compute"]))
    warns = [f for f in _findings("dead_compute", findings)
             if f.severity == Severity.WARNING]
    assert any("psum" in f.message for f in warns), findings
    # dead compute is a health contract, not a gate
    assert exit_code(findings) == 0


@pytest.mark.tier1
def test_suppression_comment_downgrades_finding():
    """# contract: allow(<pass>) on the flagged line keeps the finding
    visible but non-gating — and only at that location."""
    idx = jnp.array([0, 1, 1, 2], jnp.int32)

    def audited(v):
        # contract: allow(scatter_hints)
        return jax.ops.segment_sum(v, idx, num_segments=4)

    jaxpr = jax.make_jaxpr(audited)(jnp.ones((4, 2), jnp.float32))
    findings = run_passes(
        Program(name="audited", jaxpr=jaxpr, tags=frozenset({"forward"})),
        get_passes(["scatter_hints"]))
    assert findings and all(f.suppressed for f in findings)
    assert exit_code(findings) == 0


# ---------------------------------------------------------------------------
# pass plumbing
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_registry_covers_the_contract_surface():
    from distmlip_tpu.analysis import REGISTRY

    assert {"collective_placement", "host_sync", "dtype_discipline",
            "scatter_hints", "recompile_hazard",
            "dead_compute"} <= set(REGISTRY)
    assert len(get_passes()) >= 6
    with pytest.raises(KeyError):
        get_passes(["no_such_pass"])


@pytest.mark.tier1
def test_walker_paths_and_scopes():
    """iter_sites must recurse into control-flow sub-jaxprs with the
    enclosing primitive stack on every site."""

    @jax.jit
    def f(x):
        def body(c):
            return jax.lax.cond(c[0] > 0, lambda v: v * 2, lambda v: v, c)

        return jax.lax.fori_loop(0, 3, lambda i, c: body(c), x)

    jaxpr = jax.make_jaxpr(f)(jnp.ones((2,), jnp.float32))
    paths = {s.path for s in ir.iter_sites(jaxpr)}
    # fori_loop with a static trip count traces as scan on this jax build
    assert any("scan" in p or "while" in p for p in paths), paths
    assert any("cond" in p for p in paths), paths


@pytest.mark.tier1
def test_audit_shim_is_the_walker():
    """parallel/audit.py is a compatibility shim over analysis.ir — same
    objects, not a fork."""
    from distmlip_tpu.parallel import audit

    assert audit.count_collectives is ir.count_collectives
    assert audit.COLLECTIVE_PRIMS is ir.COLLECTIVE_PRIMS
    assert audit.collectives_by_axis is ir.collectives_by_axis


@pytest.mark.tier1
def test_edge_to_bond_scatter_rides_the_sorted_fast_path(rng):
    """The fix the scatter_hints pass drove: edge_to_bond's bond-map
    scatter carries indices_are_sorted=True (bond_map_bond is ascending
    by construction); bond_to_edge stays an audited exception."""
    from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig
    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.parallel import make_total_energy
    from distmlip_tpu.partition import build_partitioned_graph, build_plan
    from tests.utils import make_crystal

    model = CHGNet(CHGNetConfig(num_species=4, units=8, num_rbf=4,
                                num_blocks=1, cutoff=3.2, bond_cutoff=2.6))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(2, 2, 2), a=3.5)
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], 3.2, bond_r=2.6)
    plan = build_plan(nl, lattice, [1, 1, 1], 1, 3.2, 2.6, True)
    graph, _host = build_partitioned_graph(plan, nl, species, lattice)
    efn = make_total_energy(model.energy_fn, None)
    jaxpr = jax.make_jaxpr(efn)(params, graph, graph.positions,
                                jnp.zeros((3, 3), jnp.float32))
    findings = run_passes(
        Program(name="chgnet_fwd", jaxpr=jaxpr,
                tags=frozenset({"forward"})),
        get_passes(["scatter_hints"]))
    # the only unhinted scatter left is bond_to_edge, and it is suppressed
    live = [f for f in findings if not f.suppressed]
    assert not live, live
    assert exit_code(findings) == 0


@pytest.mark.tier1
def test_total_gates_count_eqns_like_count_collectives():
    """The total-ceiling/parity gates count every collective EQN once —
    a psum over BOTH mesh axes is one collective, not two, so pinning
    expected_total_collectives to a count_collectives reference (the
    halo_audit --batch gate) can never spuriously fail."""
    from jax.sharding import PartitionSpec as P

    from distmlip_tpu.parallel import (BATCH_AXIS, SPATIAL_AXIS, device_mesh)

    mesh = device_mesh(2, 2)

    @jax.jit
    def f(x):
        def local(v):
            return jax.lax.psum(v, (BATCH_AXIS, SPATIAL_AXIS))

        return jax.shard_map(local, mesh=mesh,
                         in_specs=P(BATCH_AXIS, SPATIAL_AXIS),
                         out_specs=P())(x)

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.float32))
    assert sum(ir.count_collectives(jaxpr).values()) == 1
    findings = run_passes(
        Program(name="two_axis_psum", jaxpr=jaxpr,
                config={"expected_total_collectives": 1,
                        "max_total_collectives": 1}),
        get_passes(["collective_placement"]))
    assert error_count(findings) == 0, findings


@pytest.mark.tier1
def test_ppermute_count_is_alias_robust():
    """Ring-parity gates must see the permute under either primitive name
    (ppermute vs collective_permute across jax builds) — never a vacuous
    0 == 0 pass."""
    assert ir.ppermute_count({"ppermute": 3}) == 3
    assert ir.ppermute_count({"collective_permute": 2}) == 2
    assert ir.ppermute_count({"ppermute": 1, "collective_permute": 1}) == 2
    assert ir.ppermute_count({"psum": 4}) == 0


@pytest.mark.tier1
def test_chgnet_ring_program_has_no_dead_collectives():
    """The fix the dead_compute pass drove: the last bond block's b
    re-exchange + angle update fed nothing — a dead ppermute shipping real
    bytes every step on the 2-partition ring (XLA can't DCE a collective).
    It is now skipped, and the pass that found it stays silent."""
    import tools.contract_check as cc
    from distmlip_tpu.parallel import graph_mesh, make_total_energy

    model, params, use_bg, bond_r = cc.make_model("chgnet")
    graph = cc._graph_for(model, use_bg, bond_r, 2)
    efn = make_total_energy(model.energy_fn, graph_mesh(2))
    jaxpr = jax.make_jaxpr(efn)(params, graph, graph.positions,
                                jnp.zeros((3, 3), jnp.float32))
    findings = run_passes(Program(name="chgnet_ring_fwd", jaxpr=jaxpr),
                          get_passes(["dead_compute"]))
    warns = [f for f in findings if f.severity == Severity.WARNING]
    assert not warns, "\n".join(f.render() for f in warns)


# ---------------------------------------------------------------------------
# AST lint
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_lint_catches_host_pull_and_wallclock(tmp_path):
    src = tmp_path / "models" / "bad.py"
    src.parent.mkdir()
    src.write_text(
        "import time\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "def energy(params, lg, pos):\n"
        "    t0 = time.time()\n"
        "    e = jnp.sum(pos)\n"
        "    scale = float(jnp.max(pos))\n"
        "    return e * scale + 0 * t0\n"
    )
    findings = lint_file(str(src), package_root=str(tmp_path))
    rules = {f.rule for f in findings}
    assert "DML001" in rules, findings   # float(jnp...) in hot module
    assert "DML002" in rules, findings   # time.time() in a device fn
    assert exit_code(findings) == 3


@pytest.mark.tier1
def test_lint_unused_import_and_reexport_idiom(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "import os\n"
        "import sys as sys\n"           # re-export idiom: not flagged
        "from math import cos\n"
        "\n"
        "__all__ = ['cos']\n"           # __all__ re-export: not flagged
    )
    findings = lint_file(str(src))
    assert [f for f in findings if f.rule == "F401"]
    names = {f.message for f in findings if f.rule == "F401"}
    assert any("'os'" in m for m in names)
    assert not any("sys" in m or "cos" in m for m in names), findings


@pytest.mark.tier1
def test_lint_package_is_clean():
    """The shipped package must pass its own AST lint."""
    from distmlip_tpu.analysis import lint_paths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = [f for f in lint_paths(
        [os.path.join(root, "distmlip_tpu")], package_root=root)
        if not f.suppressed]
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# clean-run over the real models
# ---------------------------------------------------------------------------

def _clean_model_programs(name):
    import tools.contract_check as cc
    from distmlip_tpu.parallel import make_potential_fn, make_total_energy


    model, params, use_bg, bond_r = cc.make_model(name)
    g1 = cc._graph_for(model, use_bg, bond_r, 1)
    with jax.enable_x64(True):
        efn = make_total_energy(model.energy_fn, None)
        jx_e = jax.make_jaxpr(efn)(params, g1, g1.positions,
                                   jnp.zeros((3, 3), np.float32))
        pfn = make_potential_fn(model.energy_fn, None)
        jx_p = jax.make_jaxpr(pfn)(params, g1, g1.positions)
    return [
        Program(name=f"energy[{name}][1x1]", jaxpr=jx_e,
                tags=frozenset({"forward", "x64"}),
                config={"max_total_collectives": 0}),
        Program(name=f"potential[{name}][1x1]", jaxpr=jx_p,
                tags=frozenset({"grad", "x64"}),
                config={"max_total_collectives": 0}),
    ]


@pytest.mark.tier1
@pytest.mark.parametrize("model_name", ["chgnet", "tensornet"])
def test_clean_run_fast_models(model_name):
    for prog in _clean_model_programs(model_name):
        findings = run_passes(prog)
        assert error_count(findings) == 0, "\n".join(
            f.render() for f in findings)
        assert exit_code(findings) == 0


@pytest.mark.parametrize("model_name", ["mace", "escn", "nequip"])
def test_clean_run_equivariant_models(model_name):
    for prog in _clean_model_programs(model_name):
        findings = run_passes(prog)
        assert error_count(findings) == 0, "\n".join(
            f.render() for f in findings)


@pytest.mark.slow
def test_contract_check_cli_full_clean():
    """The full CLI — five models x three placements + DeviceMD + packed
    batch, every registered pass — exits 0 on the clean tree."""
    import tools.contract_check as cc

    assert cc.main([]) == 0


@pytest.mark.tier1
def test_contract_check_cli_usage_errors():
    import tools.contract_check as cc

    assert cc.main(["--models", "nope"]) == 2
    assert cc.main(["--passes", "no_such_pass", "--only-lint"]) == 2
    assert cc.main(["--bogus-flag"]) == 2      # argparse rejection
    assert cc.main(["--help"]) == 0
    assert cc.main(["--list-passes"]) == 0


@pytest.mark.tier1
def test_contract_audit_survives_broken_pass(rng, monkeypatch):
    """StepRecord telemetry: a contract pass raising (e.g. jax param drift
    breaking one pass's introspection) must degrade to findings-unknown,
    NOT zero the already-computed collective tally."""
    import distmlip_tpu.analysis as analysis
    from distmlip_tpu.calculators import Atoms, DistPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from tests.utils import make_crystal

    model = PairPotential(PairConfig(cutoff=3.2))
    params = model.init(jax.random.PRNGKey(0))
    cart, lattice, species = make_crystal(rng, reps=(4, 2, 2), a=3.5)
    atoms = Atoms(numbers=species + 1, positions=cart, cell=lattice)
    pot = DistPotential(model, params, num_partitions=2, skin=0.4)
    pot.calculate(atoms)

    def boom(*a, **k):
        raise RuntimeError("pass exploded")

    monkeypatch.setattr(analysis, "run_passes", boom)
    n, errs, warns, kmode, kcov, est = pot._contract_audit()
    assert n > 0, "collective tally must survive a broken pass"
    assert (errs, warns) == (0, 0)
    # the kernel-dispatch tally rides the same trace and must survive too
    assert kmode in ("pallas", "xla") and 0.0 <= kcov <= 1.0
    # ...and so does the static HBM plan (computed before the passes run)
    assert est > 0


@pytest.mark.tier1
def test_device_md_stepper_program_is_contract_clean(rng):
    """The device-resident contract, end to end on the REAL stepper: the
    traced DeviceMD chunk must carry zero host syncs and zero collectives."""
    import tools.contract_check as cc

    programs = []
    cc._trace_device_md(programs)
    (prog,) = programs
    assert prog.tagged("device_resident")
    findings = run_passes(prog)
    assert error_count(findings) == 0, "\n".join(
        f.render() for f in findings)
