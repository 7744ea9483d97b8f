"""Padded capacities and executable shapes of each cell are the same for
seeds 0 to 11 and for a seed past 2**31, at the real size (host-side graph
build only, no device): every seed is served from the compile cache."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from benchmark.drivers import md
from benchmark.harness import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEEDS = list(range(12)) + [2 ** 31 + 17]


def signature(graph) -> tuple:
    static = (graph.num_partitions, graph.shifts, graph.n_cap, graph.e_cap,
              graph.e_split)
    leaves = tuple((np.shape(x), str(np.asarray(x).dtype))
                   for x in jax.tree.leaves(graph))
    return static, leaves


@pytest.mark.parametrize("name", CELLS)
def test_shapes_do_not_depend_on_the_seed(name):
    cell = spec.load_cell(name)
    fixed = cell.traffic["caps"][cell.config_name]
    seen = set()
    for seed in SEEDS:
        graph, needed = md.host_graph(cell, seed)
        seen.add(signature(graph))
        # room left in every fixed capacity: a seed that needed more would
        # fail the run rather than compile anew
        for cap, need in needed.items():
            assert need <= 0.97 * fixed[cap] or need <= 128, (cap, need)
    assert len(seen) == 1


def test_seed_gives_the_same_inputs():
    cell = spec.load_cell(CELLS[0])
    a, b = md.build_atoms(cell.traffic, 5), md.build_atoms(cell.traffic, 5)
    c = md.build_atoms(cell.traffic, 6)
    assert np.array_equal(a.positions, b.positions)
    assert np.array_equal(a.velocities, b.velocities)
    assert not np.array_equal(a.positions, c.positions)
    k1, k2 = md.seed_key(2 ** 31 + 5), md.seed_key(5)
    assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))
