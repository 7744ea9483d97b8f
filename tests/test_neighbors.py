"""Neighbor-search correctness: numpy linked-cell and native C++ vs brute force."""

import numpy as np
import pytest

from distmlip_tpu.neighbors import (
    neighbor_list,
    neighbor_list_brute,
    neighbor_list_numpy,
)
from tests.conftest import random_cell


def _assert_same(a, b):
    a, b = a.sorted_copy(), b.sorted_copy()
    assert a.num_edges == b.num_edges
    np.testing.assert_array_equal(a.src, b.src)
    np.testing.assert_array_equal(a.dst, b.dst)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    np.testing.assert_allclose(a.distances, b.distances, atol=1e-10)
    np.testing.assert_array_equal(a.bond_mask, b.bond_mask)


@pytest.mark.parametrize("impl", ["numpy", "native"])
@pytest.mark.parametrize(
    "n_atoms,box,r", [(20, 6.0, 2.5), (60, 9.0, 3.5), (12, 3.0, 2.9)]
)
def test_vs_brute_force(rng, impl, n_atoms, box, r):
    cart, lattice, _, pbc = random_cell(rng, n_atoms=n_atoms, box=box, jitter=1.0)
    fn = neighbor_list_numpy if impl == "numpy" else neighbor_list
    got = fn(cart, lattice, pbc, r, bond_r=r * 0.6)
    want = neighbor_list_brute(cart, lattice, pbc, r, bond_r=r * 0.6)
    _assert_same(got, want)


@pytest.mark.parametrize("impl", ["numpy", "native"])
def test_unwrapped_inputs(rng, impl):
    """Offsets must be reported relative to the unwrapped input coordinates."""
    cart, lattice, _, pbc = random_cell(rng, n_atoms=30, box=7.0)
    shift = rng.integers(-3, 4, (30, 3)) @ lattice
    fn = neighbor_list_numpy if impl == "numpy" else neighbor_list
    nl = fn(cart + shift, lattice, pbc, 3.0)
    # every edge: |cart[dst] + offsets@lattice - cart[src]| == distance
    moved = cart + shift
    vec = moved[nl.dst] + nl.offsets @ lattice - moved[nl.src]
    np.testing.assert_allclose(np.linalg.norm(vec, axis=1), nl.distances, atol=1e-9)


@pytest.mark.parametrize("impl", ["numpy", "native"])
def test_self_image_small_cell(rng, impl):
    """Cell smaller than cutoff: atoms must neighbor their own images."""
    cart = np.array([[0.5, 0.5, 0.5]])
    lattice = np.eye(3) * 2.0
    fn = neighbor_list_numpy if impl == "numpy" else neighbor_list
    nl = fn(cart, lattice, [1, 1, 1], 2.5)
    want = neighbor_list_brute(cart, lattice, [1, 1, 1], 2.5)
    _assert_same(nl, want)
    assert nl.num_edges > 0
    assert np.all(nl.src == 0) and np.all(nl.dst == 0)


@pytest.mark.parametrize("impl", ["numpy", "native"])
def test_nonperiodic_axes(rng, impl):
    cart, lattice, _, _ = random_cell(rng, n_atoms=25, box=6.0)
    pbc = np.array([1, 1, 0])
    fn = neighbor_list_numpy if impl == "numpy" else neighbor_list
    got = fn(cart, lattice, pbc, 3.0)
    want = neighbor_list_brute(cart, lattice, pbc, 3.0)
    _assert_same(got, want)
    assert np.all(got.offsets[:, 2] == 0)


def test_symmetry(rng):
    """Directed edge set is symmetric: (i,j,o) <-> (j,i,-o)."""
    cart, lattice, _, pbc = random_cell(rng, n_atoms=40, box=8.0)
    nl = neighbor_list_numpy(cart, lattice, pbc, 3.0)
    fwd = set(map(tuple, np.c_[nl.src, nl.dst, nl.offsets]))
    rev = set(map(tuple, np.c_[nl.dst, nl.src, -nl.offsets]))
    assert fwd == rev


@pytest.mark.parametrize("impl", ["numpy", "native"])
def test_out_of_cell_on_free_axis(impl):
    """Atoms outside the cell along a non-periodic axis must keep their edges
    (free axes are never wrapped, so such positions are legal input)."""
    cart = np.array([[3.0, 3.0, 9.5], [3.0, 3.0, 7.5]])
    lattice = np.eye(3) * 6.0
    pbc = [1, 1, 0]
    fn = neighbor_list_numpy if impl == "numpy" else neighbor_list
    got = fn(cart, lattice, pbc, 3.0)
    want = neighbor_list_brute(cart, lattice, pbc, 3.0)
    _assert_same(got, want)
    assert got.num_edges == 2


def test_empty_system_native_matches_fallback():
    import numpy as _np

    nl = neighbor_list(_np.zeros((0, 3)), _np.eye(3) * 5.0, [1, 1, 1], 3.0)
    assert nl.num_edges == 0
