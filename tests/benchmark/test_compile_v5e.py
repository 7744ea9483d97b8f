"""Each cell's step, compiled at the cell's own size for a described TPU
v5e (no chip attached): what Mosaic or XLA:TPU would refuse on the chip is
refused here, at no chip time. The only file that describes a topology
(on-chip-measurement guide, section 2): the call is made inside a
module-scoped fixture, never at import.

The program asks ``jax.default_backend()`` to choose Pallas or XLA per
operation; here that is the CPU, so the test answers "tpu" for it while the
step is traced, which gives the routing the chip gets.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from benchmark.drivers import md
from benchmark.harness import spec

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_step(cell, topo, monkeypatch):
    """The compiled energy-and-forces program of a cell on described
    devices, from the graph its seed-0 structure pads to."""
    from distmlip_tpu.parallel import graph_mesh, make_potential_fn
    from distmlip_tpu.parallel.runtime import graph_shardings

    family = spec.load_module(cell, "families", cell.config["family"])
    model = family.build_model(cell.config["model"])
    model = type(model)(dataclasses.replace(
        model.cfg, dtype=cell.config["potential"]["compute_dtype"]))
    tables = family.reference.Tables(cell.config["model"], None)
    params = jax.eval_shape(
        lambda key: family.reference.init_params(
            cell.config["model"], tables, key), jax.random.PRNGKey(0))
    graph, _ = md.host_graph(cell, 0)
    devices = topo.devices[:cell.chips]
    if cell.chips == 1:
        mesh = None
        one = SingleDeviceSharding(devices[0])
        shardings = jax.tree.map(lambda _: one, graph)
    else:
        mesh = graph_mesh(cell.chips, devices)
        shardings = graph_shardings(mesh, graph)
    replicated = (SingleDeviceSharding(devices[0]) if mesh is None
                  else NamedSharding(mesh, jax.sharding.PartitionSpec()))

    def shaped(x, sharding):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    graph_s = jax.tree.map(shaped, graph, shardings)
    params_s = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype,
                                       sharding=replicated), params)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    potential = make_potential_fn(model.energy_fn, mesh)
    return potential.lower(params_s, graph_s, graph_s.positions).compile()


@pytest.fixture(scope="module")
def compiled_cache():
    return {}


@pytest.mark.parametrize("name", [
    "mace-md-1c", "tensornet-md-1c",
    pytest.param("mace-md-4c", marks=pytest.mark.slow)])
def test_step_compiles_for_v5e(name, topo, monkeypatch):
    cell = spec.load_cell(name)
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = compile_step(cell, topo, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes)
    print(f"{name}: arguments {memory.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB")
    assert peak < HBM_BYTES
    if cell.config["family"] == "mace":
        assert "tpu_custom_call" in text  # the Pallas segment_sum is there
    if cell.chips > 1:
        assert "collective-permute" in text
