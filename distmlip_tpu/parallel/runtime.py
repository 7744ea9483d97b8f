"""Graph-parallel potential runtime.

Builds jitted energy / (energy, forces, stress) functions from a model's
per-shard energy function. Forces come from ``jax.grad`` of the sharded
total energy — JAX transposes the halo-exchange ``ppermute`` into the
reverse collective, reproducing the reference's autograd-through-device-
copies force flow (reference pes.py:121-124, models.py:181-193) without any
hand-written backward.

Model contract:
    model_energy_fn(params, lg: LocalGraph, positions) -> per-atom energies
with shape (N_cap,); padded rows may hold garbage — the runtime masks them.

Fused site readout (``aux=True``): the model function instead returns
``(e_atoms, aux)`` where ``aux`` is a pytree of per-atom arrays (leading
axis N_cap — e.g. CHGNet magmoms). The aux rides the SAME forward pass as
the energy (``jax.value_and_grad(..., has_aux=True)``), so sitewise
quantities cost no second forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..geometry import COORD_PRECISION, apply_strain
from ..partition.graph import PartitionedGraph
from ..telemetry import scope
from .halo import local_graph_from_stacked
from .mesh import (BATCH_AXIS, GRAPH_AXIS, SPATIAL_AXIS, mesh_row_axes,
                   mesh_shape)


def graph_row_axes(graph: PartitionedGraph):
    """Mesh axes the graph's leading partition axis shards over.

    A 2-D-placed graph (``batch_parts > 1``) factors its leading axis as
    (batch, spatial) row-major and shards over BOTH named axes jointly;
    every other graph (single structure, or a packed batch confined to one
    batch row) shards over the spatial axis only and REPLICATES over any
    batch axis the mesh has — which is what lets an oversized request run
    on the spatial sub-axis of the same serving mesh.
    """
    return (BATCH_AXIS, SPATIAL_AXIS) if graph.batch_parts > 1 \
        else SPATIAL_AXIS


def graph_in_specs(graph: PartitionedGraph, axes=None) -> PartitionedGraph:
    """A pytree of PartitionSpecs matching ``graph``'s treedef.

    Per-partition arrays shard their leading P axis over ``axes`` (default
    ``graph_row_axes(graph)`` — the spatial axis, or (batch, spatial)
    jointly for 2-D-placed packed graphs; the runtime passes
    ``mesh_row_axes(mesh)`` so rows never replicate over a present batch
    axis); halo tables (S, P, H) shard axis 1; lattice and scalars
    replicate.
    """
    import dataclasses

    axes = graph_row_axes(graph) if axes is None else axes
    row, table, rep = P(axes), P(None, axes), P()
    return dataclasses.replace(
        graph,
        positions=row, species=row, node_mask=row, owned_mask=row,
        edge_src=row, edge_dst=row, edge_offset=row, edge_mask=row,
        halo_send_idx=table, halo_send_mask=table, halo_recv_idx=table,
        lattice=rep, n_total_nodes=rep,
        system=None if graph.system is None else {k: rep for k in graph.system},
        line_src=row, line_mask=row, line_count=row, bond_center=row,
        center_in=row, bond_order=row, bond_rank=row, redirect_bits=row,
        bond_map_edge=row, bond_map_bond=row, bond_map_mask=row,
        bond_halo_send_idx=table, bond_halo_send_mask=table,
        bond_halo_recv_idx=table,
        struct_id=None if graph.struct_id is None else row,
    )


def graph_shardings(mesh: Mesh, graph: PartitionedGraph):
    """NamedSharding pytree placing ``graph`` on ``mesh``.

    One definition of placement identity for every lane (DistPotential,
    BatchedPotential): per-partition rows shard over ``mesh_row_axes(mesh)``
    (so rows never replicate over a present batch axis), halo tables shard
    axis 1, scalars replicate — exactly the in_specs the runtime's
    shard_map programs consume.
    """
    from jax.sharding import NamedSharding

    specs = graph_in_specs(graph, mesh_row_axes(mesh))
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_total_energy(model_energy_fn, mesh: Mesh | None, aux: bool = False,
                      kernels=None, kernels_diff_params: bool = True):
    """Sharded total-energy fn: (params, graph, positions, strain) -> scalar
    (or (scalar, aux_pytree) with ``aux=True``).

    ``kernels_diff_params`` defaults True (training-safe: loss grads flow
    into model weights through the fused-kernel custom VJPs); the
    force/stress factories below pass False — they differentiate
    positions/strain only, and False keeps the kernel path free of
    weight-cotangent compute and mesh psums (kernels/dispatch).

    ``positions`` is (P, N_cap, 3); only owned rows are read — halo rows are
    refreshed in-jit by the halo exchange so that gradients flow back to the
    owning partition. ``strain`` is a (3, 3) symmetric strain applied to
    positions and lattice (for stress). With ``aux=True`` the model fn must
    return ``(e_atoms, aux)``; aux leaves keep their per-partition leading
    layout ((P, N_cap, ...) outside the shard_map).
    """
    def local_energy(params, strain, graph_local, positions):
        axis = GRAPH_AXIS if mesh is not None else None
        if not kernels_diff_params:
            # force/stress program: no param grads are ever requested, but
            # the fused kernels' custom VJPs mark every primal perturbed —
            # any param-bound cotangent they emit (embedding tables, node
            # features of the first layer) would cross the shard_map
            # boundary as a replicated-input psum that plain XLA AD never
            # ships. Cut ALL of them here, inside the shard-local fn.
            params = jax.lax.stop_gradient(params)
        lg, _ = local_graph_from_stacked(
            graph_local, axis, kernels=kernels,
            kernels_diff_params=kernels_diff_params)
        dtype = positions.dtype
        with scope("edge_geometry"), scope("apply_strain"):
            pos, lg.lattice = apply_strain(
                positions[0], lg.lattice.astype(dtype), strain.astype(dtype)
            )
        pos = lg.halo_exchange(pos)
        with scope("model_energy"):
            out = model_energy_fn(params, lg, pos)
        with scope("readout"):
            if aux:
                e_atoms, aux_out = out
                aux_out = jax.tree.map(lambda a: a[None], aux_out)
                return lg.owned_sum(e_atoms.reshape(-1, 1)), aux_out
            return lg.owned_sum(out.reshape(-1, 1))

    if mesh is None:
        def total_energy(params, graph, positions, strain):
            if graph.num_partitions != 1:
                raise ValueError(
                    f"mesh=None requires a single-partition graph, got "
                    f"P={graph.num_partitions}; pass mesh=graph_mesh(P)."
                )
            return local_energy(params, strain, graph, positions)
        return total_energy

    def total_energy(params, graph, positions, strain):
        axes = mesh_row_axes(mesh)
        out_specs = (P(), P(axes)) if aux else P()
        sharded = jax.shard_map(
            local_energy,
            mesh=mesh,
            in_specs=(P(), P(), graph_in_specs(graph, axes), P(axes)),
            out_specs=out_specs,
            check_vma=False,
        )
        return sharded(params, strain, graph, positions)

    return total_energy


def make_potential_fn(model_energy_fn, mesh: Mesh | None,
                      compute_stress: bool = True, aux: bool = False,
                      kernels=None):
    """Jitted (params, graph, positions) -> dict(energy, forces, stress).

    forces: (P, N_cap, 3) — per-partition owned rows (reassemble with
    HostGraphData.gather_owned); stress: (3, 3) in eV/Å^3, dE/deps / V.
    With ``aux=True`` (fused site readout) the model fn returns
    ``(e_atoms, aux)`` and the result dict gains an ``"aux"`` pytree of
    (P, N_cap, ...) per-atom outputs computed on the SAME forward pass.
    """
    total_energy = make_total_energy(model_energy_fn, mesh, aux=aux,
                                     kernels=kernels,
                                     kernels_diff_params=False)

    @jax.jit
    def potential(params, graph, positions):
        strain = jnp.zeros((3, 3), dtype=positions.dtype)
        grad_fn = jax.value_and_grad(
            total_energy,
            argnums=(2, 3) if compute_stress else 2,
            has_aux=aux,
        )
        with scope("energy_and_grad"):
            val, grads = grad_fn(params, graph, positions, strain)
        energy, aux_out = val if aux else (val, None)
        if compute_stress:
            g_pos, g_strain = grads
            with scope("stress"):
                vol = jnp.abs(jnp.linalg.det(graph.lattice.astype(
                    jnp.float64 if graph.lattice.dtype == jnp.float64
                    else positions.dtype)))
                stress = g_strain / vol
        else:
            g_pos = grads
            stress = jnp.zeros((3, 3), dtype=positions.dtype)
        out = {"energy": energy, "forces": -g_pos, "stress": stress}
        if aux:
            out["aux"] = aux_out
        return out

    return potential


def make_packed_energy_fn(model_energy_fn, mesh: Mesh | None = None,
                          diff_params: bool = True, kernels=None):
    """Per-structure energies of a packed batch, params-DIFFERENTIABLE.

    ``(params, graph, positions, strain) -> (B_total,)`` energies, where
    ``graph`` is a :func:`distmlip_tpu.partition.pack_structures` pack
    (``mesh=None`` requires the single-partition pack; a 2-D mesh accepts
    the matching (batch x spatial) placement) and ``strain`` is the
    per-structure ``(B_total, 3, 3)`` symmetric strain.

    This is the TRAINING counterpart of
    :func:`make_batched_potential_fn`'s internal energy program: with
    ``diff_params=True`` (default) parameter gradients flow — the loss
    factories in :mod:`distmlip_tpu.train.step` differentiate it twice
    (inner positions/strain grad for forces/stress, outer params grad for
    the update). Not jitted here: callers embed it inside their own jitted
    step (one program per accumulation window).
    """
    energy = _packed_energy(
        _local_batched_energy(model_energy_fn, aux=False, kernels=kernels,
                              diff_params=diff_params),
        mesh, "make_packed_energy_fn")

    def packed_energy(params, graph, positions, strain):
        return energy(params, strain, graph, positions)[0]

    return packed_energy


def _packed_energy(local_energy, mesh: Mesh | None, who: str):
    """``local_energy`` (see ``_local_batched_energy``) over a whole packed
    graph: ``(params, strain, graph, positions) -> (energies (B_total,),
    aux)``, on the single-partition pack (``mesh=None``: no collectives)
    or under ``shard_map`` on the 2-D mesh the graph was packed for. The
    mesh's axes are checked here, the graph's placement at trace time;
    ``who`` names the public factory in the messages."""
    if mesh is None:
        def energy(params, strain, graph, positions):
            if graph.num_partitions != 1 or graph.batch_size < 1:
                raise ValueError(
                    f"{who}(mesh=None) requires a single-partition packed "
                    f"graph (got P={graph.num_partitions}, "
                    f"batch_size={graph.batch_size}); build it with "
                    "pack_structures(), or pass the 2-D mesh the graph "
                    "was packed for.")
            return local_energy(params, strain, graph, positions)
        return energy

    # the shard_map addresses BOTH named axes (strain/energies shard over
    # "batch"); a user-built mesh missing either name would only fail deep
    # inside jax's axis resolution at first trace
    missing = [ax for ax in (BATCH_AXIS, SPATIAL_AXIS)
               if ax not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"{who} needs a mesh with named axes "
            f"({BATCH_AXIS!r}, {SPATIAL_AXIS!r}); this mesh "
            f"{tuple(mesh.axis_names)} lacks {missing} — build it with "
            f"parallel.device_mesh(batch, spatial).")
    mesh_bp, mesh_sp = mesh_shape(mesh)

    def local(params, strain, graph_local, positions):
        energies, aux_out = local_energy(params, strain, graph_local,
                                         positions)
        # restore the leading shard axis so aux rows concat back to the
        # packed (P, N_cap, ...) layout
        return energies, jax.tree.map(lambda a: a[None], aux_out)

    def energy(params, strain, graph, positions):
        if graph.batch_size < 1 or graph.struct_id is None:
            raise ValueError(
                f"{who} requires a packed graph (batch_size >= 1); build "
                "it with pack_structures().")
        if graph.batch_parts != mesh_bp or graph.spatial_size != mesh_sp:
            raise ValueError(
                f"graph placement {graph.batch_parts}x{graph.spatial_size} "
                f"does not match the {mesh_bp}x{mesh_sp} mesh; pack with "
                f"batch_parts={mesh_bp}, spatial_parts={mesh_sp}.")
        axes = mesh_row_axes(mesh)
        row = P(axes)
        # strain shards over batch only: every spatial slab of a batch row
        # sees its row's (B_local, 3, 3) slice
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(BATCH_AXIS), graph_in_specs(graph, axes), row),
            out_specs=(P(BATCH_AXIS), row), check_vma=False,
        )(params, strain, graph, positions)

    return energy


def _local_batched_energy(model_energy_fn, aux, kernels=None,
                          diff_params=False):
    """Shard-local batched energy: strain -> halo exchange -> model ->
    per-structure readout. Shared by the single-device packed path and the
    2-D mesh path (where it runs inside shard_map with the spatial axis
    bound).

    ``diff_params=False`` (the batched INFERENCE engine) stop-gradients the
    params — grads are positions/strain only, and the stop keeps the fused
    kernels' custom VJPs free of weight-cotangent compute and mesh psums
    (see make_total_energy). The training path passes True so loss
    gradients flow into the model weights through the same packed program
    (train/step.py)."""

    def local_energy(params, strain, graph_local, positions):
        # graph_local: per-shard (1, ...) slices (or the whole P=1 graph on
        # the meshless path); strain: (B_local, 3, 3) — this batch shard's
        # slots only
        axis = SPATIAL_AXIS if graph_local.spatial_size > 1 else None
        if not diff_params:
            # batched inference engine: grads are positions/strain only —
            # cut param-bound kernel-VJP cotangents before the mesh
            # boundary (see make_total_energy)
            params = jax.lax.stop_gradient(params)
        lg, _ = local_graph_from_stacked(graph_local, axis, kernels=kernels,
                                         kernels_diff_params=diff_params)
        B = graph_local.batch_size
        dtype = positions.dtype
        pos = positions[0]
        sid = lg.struct_id
        with scope("edge_geometry"), scope("apply_strain"):
            # per-structure symmetric strain: x_i -> x_i @ (I + eps_{s(i)});
            # Cartesian edge offsets deform with their structure's cell.
            # Padded and halo rows have sid == B — the gather clamps them
            # onto the last real slot, which is harmless (padded rows are
            # masked; halo rows are overwritten by the exchange below with
            # their owner's strained coordinates).
            sym = 0.5 * (strain + jnp.swapaxes(strain, -1, -2)).astype(dtype)
            defm = jnp.eye(3, dtype=dtype)[None, :, :] + sym      # (B, 3, 3)
            pos = jnp.einsum("ni,nij->nj", pos, defm[sid],
                             precision=COORD_PRECISION)
            esid = sid[lg.edge_dst]  # edge's structure (dst rows are real)
            lg.edge_offset = jnp.einsum(
                "ei,eij->ej", lg.edge_offset.astype(dtype), defm[esid],
                precision=COORD_PRECISION)
        # spatially partitioned structures refresh their halo rows from the
        # owning slab (strained above); a no-op on S=1 placements
        pos = lg.halo_exchange(pos)
        with scope("model_energy"):
            out = model_energy_fn(params, lg, pos)
        e_atoms, aux_out = out if aux else (out, None)
        with scope("readout"), scope("batched_readout"):
            # segment_sum onto batch slots + psum over the SPATIAL axis
            # only — the batch axis never carries a collective
            energies = lg.structure_sum(e_atoms.reshape(-1).astype(dtype))
        return energies, aux_out

    return local_energy


def make_batched_potential_fn(model_energy_fn, compute_stress: bool = True,
                              aux: bool = False, mesh: Mesh | None = None,
                              kernels=None):
    """Jitted batched potential over a block-diagonally packed graph.

    ``(params, graph, positions) -> dict`` where ``graph`` is a
    ``PartitionedGraph`` built by
    :func:`distmlip_tpu.partition.pack_structures` (``batch_size`` slots
    per batch shard, ``struct_id`` per node, Cartesian edge offsets,
    identity lattice):

    - ``energies``: (B_total,) per-structure energies, where ``B_total =
      batch_parts * batch_size`` (flat slot order: shard-major) — ONE
      ``segment_sum(e_atoms, struct_id)`` readout per shard, ``psum``'d
      over the spatial axis (padded rows carry the sentinel slot and are
      dropped); empty slots read 0.
    - ``forces``: (P, N_cap, 3) packed per-atom forces from ONE
      ``value_and_grad`` through the whole super-graph. The blocks share no
      edges, so d(sum_b E_b)/dx_i = dE_{struct(i)}/dx_i exactly — batching
      introduces no cross-terms.
    - ``strain_grad``: (B_total, 3, 3) dE_b/d(strain_b) — each structure
      gets its OWN symmetric strain applied to its positions and
      (Cartesian) edge offsets; divide by per-structure volume on the host
      for stress.
    - ``aux`` (``aux=True``): the model's fused per-atom outputs (packed
      (P, N_cap, ...) layout, slice per structure on the host).

    ``mesh=None`` (default) is the historical single-device path: it
    requires ``P == 1`` and traces NO collectives, so collective counts are
    independent of B (``tools/halo_audit.py --batch`` asserts this).

    With a 2-D ``mesh`` (:func:`distmlip_tpu.parallel.device_mesh`) the
    packed graph may itself be (batch x spatial)-sharded: rows shard over
    ``("batch", "spatial")`` jointly, each packed structure's slabs ride
    the halo ``ppermute`` over the SPATIAL axis only, and per-structure
    energies psum over spatial — the batch axis carries ZERO collectives
    by construction (``tools/halo_audit.py --mesh B,S`` asserts this).
    One executable family covers pure batch-parallel (B x 1), the 1-D ring
    (1 x S) and the mixed B x S placement.
    """
    energy = _packed_energy(
        _local_batched_energy(model_energy_fn, aux, kernels=kernels),
        mesh, "make_batched_potential_fn")

    def batched_energy(params, strain, graph, positions):
        energies, aux_out = energy(params, strain, graph, positions)
        return jnp.sum(energies), (energies, aux_out)

    @jax.jit
    def potential(params, graph, positions):
        B_total = graph.batch_parts * graph.batch_size
        strain = jnp.zeros((B_total, 3, 3), dtype=positions.dtype)
        grad_fn = jax.value_and_grad(
            batched_energy, argnums=(3, 1) if compute_stress else 3,
            has_aux=True)
        with scope("energy_and_grad"):
            (_, (energies, aux_out)), grads = grad_fn(
                params, strain, graph, positions)
        if compute_stress:
            g_pos, g_strain = grads
        else:
            g_pos = grads
            g_strain = jnp.zeros((B_total, 3, 3), dtype=positions.dtype)
        out = {"energies": energies, "forces": -g_pos,
               "strain_grad": g_strain}
        if aux:
            out["aux"] = aux_out
        return out

    return potential
