"""The program (``DimeNetPP`` through ``DistPotential`` and
``MolecularDynamics``) against the plain reference of ``dimenet`` at toy
width on the CPU: float32 on one and on four virtual devices (the bond
halo exchange of the geometry once a step and of ``s`` once a block), the
four planted faults of ``reference/dimenet.py`` read outside the
tolerance, bfloat16 against the reference's own bfloat16 rounding and the
float8 control, and the step's jaxpr: the triplet sum is a scan over the
in-line table's slabs with a checkpointed body and a ``(b_cap, 64)``
carry, and no 64- or 128-wide array over all slots exists in it.

The toy structure has two species; the driver's atom bucket is set to 1 (no
ghost atoms), so energies compare too.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

import toy
from benchmark.drivers import md
from benchmark.harness import compare, spec
from benchmark.reference import dimenet as ref
from test_dimenet_cell import two_species  # registers TOY_MODELS["dimenet"]

# float32 program against the float32 reference: the sound program reads
# 2.1e-6 on both numbers (one and four partitions: the two sides sum the
# same products in another order); the smallest of the four faults below
# reads 100 times that or more. 2e-5 lies ten times from the first;
# ``toy.TIGHT``'s 1e-3 would pass a fault that moves the forces by 0.1 %.
FLOAT32_TOLERANCE = 2e-5
LIMITS = {"force_rel_err": FLOAT32_TOLERANCE,
          "kick_rel_err": FLOAT32_TOLERANCE}


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("tables"))


def toy_run(patch, tmp, tables_dir, chips=1, reps=(3, 3, 3), limits=LIMITS,
            **kw):
    root = toy.make_root(str(tmp), "dimenet", reps=reps, chips=chips,
                         limits=limits, **kw)
    cell = spec.load_cell("toy-md", root)
    real = md.build_atoms

    def build_atoms(traffic, seed):
        atoms = real(traffic, seed)
        atoms.numbers = two_species(atoms.numbers)
        return atoms

    patch.setattr(md, "build_atoms", build_atoms)
    patch.setattr(md, "ATOM_BUCKET", 1)
    # 4,536 real edges and 3,656 ghosts of exactly the cutoff, not 61,000
    patch.setattr(md, "EDGE_BUCKET", 8192)
    state = md.set_up(cell, 11, jax.devices()[:chips], tables_dir=tables_dir)
    stats = dict(state.pot.last_stats)
    window = md.run_window(state, 1e-6)  # one whole step
    md.release_program(state)
    return state, window, stats


@pytest.fixture(scope="module")
def sound(tmp_path_factory, tables_dir):
    """One step of the float32 program on one device, for the test of
    agreement and for the four faults, which differ in the reference."""
    with pytest.MonkeyPatch.context() as patch:
        yield toy_run(patch, tmp_path_factory.mktemp("sound"), tables_dir)


def agrees(run):
    state, window, stats = run
    assert len(set(state.atoms.numbers.tolist())) == 2
    assert window.steps == 1 and window.rebuilds == 0
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    assert [c["limit"] for c in verdict["compared"]] == [FLOAT32_TOLERANCE] * 2
    assert verdict["numbers"]["energy_err_per_atom"] < 1e-5
    # every built edge is a bond, and the in-line table holds the lines of
    # the bond with the most (the largest in-degree at 5.5 A, less one)
    assert stats["n_bonds_per_part"] == stats["n_edges_per_part"]
    assert stats["line_slots"] >= 42 and 0.5 < stats["line_table_fill"] < 1
    return stats


def test_program_agrees_with_reference(sound):
    """Neighbour graph with bonds at the cutoff, the bond rows, the triplet
    scan with the factored basis and the backward that gives forces,
    against the reference's own cell list, per-atom triplet tables and
    42-wide basis per triplet: float32 on both sides."""
    agrees(sound)


def test_four_partitions_agree_with_reference(monkeypatch, tmp_path,
                                              tables_dir):
    """Slab partition; the halo bond rows of the geometry and of ``s`` by
    the bond halo exchange; triplets whose centre is a halo atom."""
    stats = agrees(toy_run(monkeypatch, tmp_path, tables_dir, chips=4,
                           reps=(12, 3, 3)))
    assert sum(stats["bond_halo_send_per_part"]) > 0


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_fault_reads_outside_the_tolerance(monkeypatch, sound, fault):
    """CHGNet's sign of cos theta, backtracking triplets, the spherical
    rows n-major, the last block's triplet sum dropped: the sound program
    against a reference that has the fault."""
    monkeypatch.setattr(ref, "site_energies", functools.partial(
        ref.site_energies, faults=(fault,)))
    state, window, _ = sound
    verdict = md.check(state, window)
    print(fault, verdict["compared"])
    assert not verdict["correct"]
    forces = {c["name"]: c["value"] for c in verdict["compared"]}[
        "force_rel_err"]
    assert forces > 10 * FLOAT32_TOLERANCE


def test_control_fails_where_the_program_passes(monkeypatch, tmp_path,
                                                tables_dir):
    """bfloat16 program against the float32 reference in units of the
    reference's own bfloat16 rounding, and the control (the reference in
    float8 in the program's place) against the same."""
    state, window, _ = toy_run(monkeypatch, tmp_path, tables_dir,
                               compute_dtype="bfloat16", limits=toy.SERVED)
    verdict = md.check(state, window)
    assert verdict["correct"], verdict["compared"]
    program = verdict["numbers"]
    forces = md.reference_forces(
        state, window.positions, ("float8_e4m3fn",))["float8_e4m3fn"][1]
    reference = verdict["reference"]
    control = (compare.relative(forces, reference["forces"])
               / compare.relative(reference["rounding_forces"],
                                  reference["forces"]))
    limit = toy.SERVED["force_err_vs_rounding"]
    print(program, control)
    assert 0.3 < program["force_err_vs_rounding"] < limit < control
    assert control > 3 * program["force_err_vs_rounding"]


# ---- the triplet scan in the step's jaxpr ----------------------------------

def _walk(jaxpr, path=()):
    """(equation, the primitives of the loops and calls it sits in)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, path + (eqn.primitive.name,))


@pytest.mark.parametrize("nparts", [1, 4])
def test_the_triplet_sum_is_a_scan_over_slabs(nparts):
    """Per interaction block one forward scan of K steps whose carry is
    ``(b_cap, 64)`` float32, and its backward, whose body is the one
    checkpoint; no value of the step is 64 or 128 wide over all K x b_cap
    slots (what the compiled step for a described v5e shows at the cell's
    size: ``test_dimenet_cell.py``). Only ``line_src`` and ``line_mask``
    are that long."""
    from distmlip_tpu.calculators import Atoms, DistPotential

    from benchmark.families import dimenet as family
    from benchmark.harness import structures

    model = family.build_model(toy.TOY_MODELS["dimenet"])
    pot = DistPotential(model, model.init(jax.random.PRNGKey(0)),
                        num_partitions=nparts, skin=0.5)
    numbers, positions, cell = structures.perturbed_fcc(
        (3 * nparts, 3, 3), 3.9, 0.04, 14, seed=0)
    graph, _, positions = pot._prepare(Atoms(
        numbers=two_species(numbers), positions=positions, cell=cell))
    blocks = model.cfg.num_blocks
    jaxpr = jax.make_jaxpr(pot._potential)(pot.params, graph, positions)
    K, rows = graph.line_src.shape[-1] // graph.b_cap, graph.b_cap
    slots = K * rows
    eqns = list(_walk(jaxpr.jaxpr))
    scans = [e for e, _ in eqns if e.primitive.name == "scan"
             and e.params["length"] == K]
    assert len(scans) == 2 * blocks
    carries = {tuple(v.aval.shape) + (str(v.aval.dtype),)
               for e in scans for v in e.outvars[:1]}
    assert (rows, 8, "float32") in carries
    remats = [e for e, path in eqns if e.primitive.name == "remat2"]
    assert len(remats) == blocks and all("scan" in p for e, p in eqns
                                         if e in remats)
    # every value that runs over the slots: line_src and line_mask whole,
    # stacked over the partitions or by slab, and nothing of a float type
    over_slots = {}
    for eqn, _ in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            # (float0: the cotangent an integer input has, no array)
            if (slots in shape or shape[:2] == (K, rows)) and \
                    aval.dtype != jax.dtypes.float0:
                over_slots.setdefault(str(aval.dtype), set()).add(shape)
    assert set(over_slots) == {"int32", "bool"}, over_slots
    assert all(int(np.prod(shape)) in (slots, nparts * slots)
               for found in over_slots.values() for shape in found)
