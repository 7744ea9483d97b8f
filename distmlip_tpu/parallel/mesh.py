"""Device-mesh helpers: the named 2-D ``Mesh(("batch", "spatial"))``.

The parallel runtime addresses ONE named mesh with two axes:

- ``"spatial"`` — graph parallelism (slab s of a structure lives at spatial
  coordinate s; the halo exchange rides ``ppermute`` over this axis only).
- ``"batch"`` — data parallelism over packed structure batches. The batch
  axis NEVER carries a collective: batched structures are block-diagonal,
  so the only cross-device traffic a placement needs is the spatial halo
  ring inside each batch row (``tools/halo_audit.py --mesh B,S`` asserts
  this at the jaxpr level).

One executable family serves every placement on the same mesh: B
structures x 1 slab (pure batch-parallel), 1 structure x S slabs (the
historical 1-D ring, now addressed by axis name on the spatial sub-axis),
and B x S (each packed structure itself spatially partitioned).
``graph_mesh(P)`` remains as the 1-structure entry point and now returns a
``(1, P)`` 2-D mesh, so existing ``PartitionSpec(GRAPH_AXIS)`` programs run
unchanged. Multi-host meshes work as before: ``jax.devices()`` spans hosts
and slab adjacency maps onto ICI/DCN neighbor links.

The overlap-aware halo pipeline — the coalesced exchange
(parallel/halo.py) and the interior/frontier edge split
(partition/graph.py) — pays off when XLA lowers ``ppermute`` to an async
collective-permute start/done pair and schedules independent compute
between the two. The TPU compiler does both by default (chip_smoke.py's
MD-4 phase counts the pairs in the optimised HLO), so this package sets no
XLA flags.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

BATCH_AXIS = "batch"
SPATIAL_AXIS = "spatial"
# historical name for the graph-parallel axis; now an alias of the spatial
# sub-axis of the 2-D mesh so existing PartitionSpec(GRAPH_AXIS) code keeps
# addressing the ring by name
GRAPH_AXIS = SPATIAL_AXIS


def device_mesh(batch: int = 1, spatial: int = 1, devices=None) -> Mesh:
    """The named 2-D ``Mesh(("batch", "spatial"))`` of ``batch * spatial``
    devices.

    Device (b, s) holds spatial slab s of batch shard b. Spatial neighbors
    are adjacent in device order, so on a TPU slice the halo ``ppermute``
    rides ICI neighbor links within each batch row; batch rows never talk
    to each other (no batch-axis collectives by construction).
    """
    devices = list(devices if devices is not None else jax.devices())
    batch, spatial = int(batch), int(spatial)
    if batch < 1 or spatial < 1:
        raise ValueError(
            f"mesh axes must be >= 1, got batch={batch} spatial={spatial}")
    need = batch * spatial
    if need > len(devices):
        raise ValueError(
            f"Requested a {batch}x{spatial} mesh ({need} devices) but only "
            f"{len(devices)} devices are available.")
    return Mesh(np.array(devices[:need]).reshape(batch, spatial),
                (BATCH_AXIS, SPATIAL_AXIS))


def mesh_shape(mesh: Mesh) -> tuple[int, int]:
    """``(batch, spatial)`` sizes of a mesh. Meshes without an explicit
    batch axis (a user-built 1-D spatial mesh) report batch=1; a missing
    spatial axis reports spatial=1 (pure batch-parallel mesh)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get(BATCH_AXIS, 1)), int(sizes.get(SPATIAL_AXIS, 1))


def mesh_row_axes(mesh: Mesh | None):
    """Mesh axes a graph's leading partition axis should shard over on
    ``mesh``: both named axes when the mesh carries a batch axis (even of
    size 1 — replicating rows over an unmentioned axis would add spurious
    gradient-transpose psums on it), else the spatial axis alone (a
    user-built 1-D spatial mesh)."""
    if mesh is None:
        return SPATIAL_AXIS
    if BATCH_AXIS in mesh.axis_names:
        return (BATCH_AXIS, SPATIAL_AXIS)
    return SPATIAL_AXIS


def graph_mesh(num_partitions: int | None = None, devices=None) -> Mesh:
    """A ``(1, P)`` mesh for pure graph parallelism (1 structure x P slabs).

    Historically this was the 1-D ``("gp",)`` mesh; it is now the batch=1
    slice of the named 2-D mesh, so single-structure programs and B x S
    placements share one mesh family (``PartitionSpec(GRAPH_AXIS)`` keeps
    addressing the spatial ring by name).
    """
    devices = list(devices if devices is not None else jax.devices())
    if num_partitions is None:
        num_partitions = len(devices)
    return device_mesh(1, num_partitions, devices)
