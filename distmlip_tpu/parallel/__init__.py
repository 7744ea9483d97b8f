from .mesh import (BATCH_AXIS, GRAPH_AXIS, SPATIAL_AXIS, device_mesh,
                   graph_mesh, mesh_shape)
from .halo import LocalGraph, local_graph_from_stacked
from .runtime import (make_total_energy, make_potential_fn,
                      make_batched_potential_fn, make_packed_energy_fn,
                      graph_in_specs, graph_row_axes)
from .audit import (collective_counts, collectives_by_axis,
                    count_collectives, ppermutes_by_scope)

__all__ = [
    "BATCH_AXIS",
    "SPATIAL_AXIS",
    "GRAPH_AXIS",
    "device_mesh",
    "mesh_shape",
    "graph_mesh",
    "LocalGraph",
    "local_graph_from_stacked",
    "make_total_energy",
    "make_potential_fn",
    "make_batched_potential_fn",
    "make_packed_energy_fn",
    "graph_in_specs",
    "graph_row_axes",
    "collective_counts",
    "collectives_by_axis",
    "count_collectives",
    "ppermutes_by_scope",
]
