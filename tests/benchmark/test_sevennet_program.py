"""The program (``NequIP`` through ``DistPotential`` and
``MolecularDynamics``) against the plain reference of ``nequip`` at toy
width on the CPU: float32 on one and on four virtual devices (four halo
exchanges of unequal-width rows), through the interpreted kernel with a
scalar block of a whole lane tile (the published layout's lane-aligned
repeat), three faults that have to read outside the tolerance, and bfloat16 against the reference's own bfloat16
rounding and the float8 control.

The toy structure has two species; the driver's atom bucket is set to 1 (no
ghost atoms), so energies compare too: an atom without edges has the energy
of its self-connections, not zero.
"""

from __future__ import annotations

import functools

import jax
import pytest

import toy
from benchmark.drivers import md
from benchmark.harness import compare, spec
from benchmark.reference import nequip as ref
from test_sevennet_cell import two_species  # registers TOY_MODELS["nequip"]

# float32 program against the float32 reference: the sound program reads
# 1.7e-6 to 1.8e-6 on both numbers (one and four partitions, XLA and the
# interpreted kernel: the two sides sum the same products in another order);
# the smallest of the three faults below, the cross-product path dropped,
# reads 5.1e-4 at five convolutions. 2e-5 lies an order from the first and
# 25 times from the second; ``toy.TIGHT``'s 1e-3 would pass that fault.
FLOAT32_TOLERANCE = 2e-5
LIMITS = {"force_rel_err": FLOAT32_TOLERANCE,
          "kick_rel_err": FLOAT32_TOLERANCE}


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("tables"))


def toy_run(patch, tmp, tables_dir, chips=1, reps=(3, 3, 3), kernels=None,
            limits=LIMITS, channels=None, **kw):
    if channels is not None:
        patch.setitem(toy.TOY_MODELS, "nequip", {
            **toy.TOY_MODELS["nequip"], "channels": channels})
    root = toy.make_root(str(tmp), "nequip", reps=reps, chips=chips,
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
    state = md.set_up(cell, 11, jax.devices()[:chips], tables_dir=tables_dir,
                      kernels=kernels)
    stats = dict(state.pot.last_stats)
    widths = [t["width"] for t in state.pot.model.tables]
    window = md.run_window(state, 1e-6)  # one whole step
    md.release_program(state)
    return state, window, stats, widths


@pytest.fixture(scope="module")
def sound(tmp_path_factory, tables_dir):
    """One step of the float32 program on one device, for the test of
    agreement and for the three faults, which differ in the reference."""
    with pytest.MonkeyPatch.context() as patch:
        yield toy_run(patch, tmp_path_factory.mktemp("sound"), tables_dir)


def agrees(run, kernel_sites, message_widths=(128, 384, 384, 384, 384)):
    state, window, stats, widths = run
    assert len(set(state.atoms.numbers.tolist())) == 2
    assert window.steps == 1 and window.rebuilds == 0
    verdict = md.check(state, window)
    print(verdict["compared"])
    assert verdict["correct"], verdict["compared"]
    assert [c["limit"] for c in verdict["compared"]] == [FLOAT32_TOLERANCE] * 2
    assert verdict["numbers"]["energy_err_per_atom"] < 1e-5
    # blocks of 72, 80 and 44 columns (8, 4 and 2 in the last layer), each
    # padded to one lane tile
    assert widths == list(message_widths)
    # five edge sums a step, one a convolution
    assert stats["kernel_ops"]["segment_sum_into"] == kernel_sites
    return stats


def test_program_agrees_with_reference(sound):
    """Neighbour graph, the flat node rows, the coupling as products over
    a padded message row, the chunked scan, the gate and the backward that
    gives forces, against the reference's own cell list and one einsum a
    path: float32 on both sides."""
    agrees(sound, [0, 5])


def test_interpreted_kernel_and_the_lane_aligned_repeat(
        monkeypatch, tmp_path, tables_dir):
    """128 scalar channels, as published: their block (9 x 128 columns out
    of scalars, 128 in the last layer) needs no padding and its channels
    repeat by ``jnp.tile``, where the 4- and 2-wide degrees (and every
    degree of the other toys) repeat by a one-hot product. The edge sums
    run through the interpreted Pallas kernel."""
    agrees(toy_run(monkeypatch, tmp_path, tables_dir, kernels="interpret",
                   channels=[128, 4, 2]),
           [5, 0], message_widths=(1152, 1408, 1408, 1408, 384))


def test_four_partitions_agree_with_reference(monkeypatch, tmp_path,
                                              tables_dir):
    """Slab partition and four halo exchanges of the flat rows (14 numbers
    a node here, 480 at the published widths; 8 after the embedding needs
    none, the last convolution's none either)."""
    stats = agrees(toy_run(monkeypatch, tmp_path, tables_dir, chips=4,
                           reps=(12, 3, 3)), [0, 5])
    assert sum(stats["halo_send_per_part"]) > 0


@pytest.mark.parametrize("fault", ["gates", "odd_path", "self_connection"])
def test_a_fault_reads_outside_the_tolerance(monkeypatch, sound, fault):
    """Gate scalars left unactivated, the path (1, 1, 1) dropped, ``Lin_sc``
    left out: the sound program against a reference that lacks the piece
    (1.2e-1, 5.1e-4 and 1.2e3 on the forces)."""
    monkeypatch.setattr(ref, "site_energies", functools.partial(
        ref.site_energies, faults=(fault,)))
    state, window, _, _ = sound
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
    state, window, _, _ = toy_run(monkeypatch, tmp_path, tables_dir,
                                  compute_dtype="bfloat16",
                                  limits=toy.SERVED)
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
