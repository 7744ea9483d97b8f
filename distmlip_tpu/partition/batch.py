"""Block-diagonal multi-structure packing.

``pack_structures`` concatenates B independent neighbor graphs into ONE
single-partition ``PartitionedGraph`` super-graph so a whole batch of small
structures evaluates in one device program — the TorchSim batching regime
(arXiv:2508.06628): for MLIP serving/screening workloads the chip is idle
between tiny graphs, and padding many structures into one computation is
worth 1-2 orders of magnitude of throughput.

Packing layout (all offsets cumulative over structures, real entries first,
one shared padding tail per array):

  nodes:  [ atoms_0 | atoms_1 | ... | pad ]            struct_id = b per row
  edges:  [ edges_0 | edges_1 | ... | pad ]            dst-sorted per block
  bonds:  [ bonds_0 | ... | pad ]
  lines:  slot-major in-line table over the packed bond rows (below)

The existing padding contract is preserved exactly, so all models run
unchanged on the packed ``LocalGraph``:

- per-structure edge blocks are dst-sorted and node ids only grow with the
  structure offset, so the CONCATENATED ``edge_dst`` is globally
  nondecreasing — the ``indices_are_sorted=True`` segment-sum fast path
  holds for the whole super-array;
- the lines of all blocks form ONE in-line table over the packed bond rows
  (``partition/graph.line_table``): slot ``k * b_cap + b`` is the k-th line
  into bond row ``b``, ``K`` the largest in-degree in the batch, so a line's
  destination and centre are its position and only ``line_src`` is an index
  array; ``bond_center`` holds each bond row's centre atom, and the centre
  tables (``partition/graph.center_table``) the same lines by centre atom;
- padded ``dst`` rows repeat the last real value (in-bounds, nondecreasing);
  padded rows are masked so they contribute 0;
- ``e_split == e_cap``: the packed layout is unsplit (single partition has
  no frontier edges).

Heterogeneous cells are handled by baking edge image offsets to CARTESIAN
at pack time (``shift @ cell_b``) and setting the graph lattice to the
identity — ``LocalGraph.edge_vectors`` then reproduces each structure's own
periodic geometry, and the batched runtime strains offsets per structure
through ``struct_id`` for per-structure stress.

Exactness: packing is a relabeling of B disjoint graphs plus masked
padding. No message path crosses a block boundary, so per-structure
energies/forces/stresses match the single-structure path to fp32 roundoff
(asserted across all four model families in tests/test_batched.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..neighbors import neighbor_list
from .capacity import (LINE_SLOTS, BucketPolicy, FixedCaps, freeze_caps,
                       line_table_cap)
from .graph import (PartitionedGraph, bond_orders, build_partitioned_graph,
                    center_table, empty_center_tables, expand_shift_tables,
                    line_slots_needed, line_table, line_table_stats,
                    live_mask)
from .partitioner import build_plan
from .plan import PartitionPlan


def bucket_key(graph: PartitionedGraph) -> str:
    """Stable id of a packed graph's compiled-shape bucket: every static
    dimension that feeds the jitted program's input shapes (node/edge/bond
    capacity rungs, batch slots, and the (batch, spatial) placement). Two
    packed batches with the same key reuse the same XLA executable."""
    key = (f"n{graph.n_cap}_e{graph.e_cap}_B{graph.batch_size}")
    if graph.has_bond_graph:
        key += (f"_b{graph.b_cap}_l{graph.line_src.shape[-1]}"
                f"_m{graph.bond_map_edge.shape[-1]}")
    if graph.spatial_parts > 0:
        # 2-D mesh placement: the (batch, spatial) factorization selects a
        # distinct executable family even at equal caps
        key += f"_m{graph.batch_parts}x{graph.spatial_size}"
    return key


@dataclass
class PackedHostData:
    """Host companions of a packed graph needed for scatter/reassembly."""

    node_offsets: np.ndarray        # (B+1,) cumulative real-atom offsets
    n_atoms: np.ndarray             # (B,) real atoms per structure
    volumes: np.ndarray             # (B,) cell volumes (stress division)
    n_cap: int
    batch_size: int                 # padded slot count (>= B real)
    stats: dict | None = None       # telemetry: occupancy/waste/bucket
    # build-time positions per structure (Verlet skin cache validity)
    build_positions: list = field(default_factory=list)
    # per-structure cells/pbc captured at pack time — the device edge
    # refresh (device_refresh_packed) rebuilds each block's neighbor list
    # with its own periodic geometry without re-touching the structures
    cells: list = field(default_factory=list)
    pbcs: list = field(default_factory=list)

    @property
    def num_structures(self) -> int:
        return len(self.n_atoms)

    @property
    def structure_slots(self) -> np.ndarray:
        """(B,) flat energy/strain slot of each structure in the runtime's
        ``energies`` output (identity for the single-shard pack; the mesh
        pack maps structure i onto shard-major slots)."""
        return np.arange(self.num_structures, dtype=np.int64)

    def scatter_positions(self, positions_list, dtype=np.float32) -> np.ndarray:
        """Pack per-structure (n_b, 3) position arrays into (1, N_cap, 3)."""
        return self.scatter_per_atom(positions_list, dtype=dtype)

    def scatter_per_atom(self, arrays, dtype=np.float32) -> np.ndarray:
        """Pack per-structure per-atom arrays (n_b, ...) of a shared
        trailing shape into the graph's padded (1, N_cap, ...) layout
        (padded rows zero). Positions, force targets, per-atom labels —
        anything node-aligned packs through here."""
        trail = np.shape(np.asarray(arrays[0]))[1:]
        out = np.zeros((1, self.n_cap) + trail, dtype=dtype)
        for b, arr in enumerate(arrays):
            s = self.node_offsets[b]
            out[0, s:s + len(arr)] = arr
        return out

    def atom_slots(self) -> np.ndarray:
        """(1, N_cap) int32 flat energy-slot of each node row; padded rows
        carry the ``batch_slots`` sentinel (one past the last slot) so a
        slot-indexed gather can be masked/clamped uniformly. Aligns
        per-atom arrays with the runtime's flat ``energies`` output."""
        out = np.full((1, self.n_cap), self.batch_size, dtype=np.int32)
        for b in range(self.num_structures):
            out[0, self.node_offsets[b]:self.node_offsets[b + 1]] = b
        return out

    def gather_per_structure(self, packed: np.ndarray) -> list:
        """Slice a (1, N_cap, ...) packed per-atom array into per-structure
        (n_b, ...) views."""
        arr = np.asarray(packed)[0]
        return [arr[self.node_offsets[b]:self.node_offsets[b + 1]]
                for b in range(self.num_structures)]


_default_buckets = BucketPolicy()


def _batch_system(structures, system: dict | None) -> dict:
    """Resolve the batch-wide conditioning dict (see pack_structures)."""
    if system is not None:
        return system
    systems = []
    for atoms in structures:
        info = getattr(atoms, "info", {}) or {}
        systems.append({
            "charge": int(info.get("charge", 0)),
            "spin": int(info.get("spin", 0)),
            "dataset": int(info.get("dataset", 0)),
        })
    if any(s != systems[0] for s in systems[1:]):
        raise ValueError(
            "pack_structures: structures carry conflicting charge/spin/"
            "dataset conditioning; batch structures with identical "
            "system scalars (or pass system= explicitly)")
    return systems[0]


def pack_structures(
    structures,
    cutoff: float,
    bond_cutoff: float = 0.0,
    use_bond_graph: bool = False,
    caps: BucketPolicy | None = None,
    species_fn=None,
    dtype=np.float32,
    skin: float = 0.0,
    system: dict | None = None,
    num_threads: int | None = None,
    spatial_parts: int = 1,
    batch_parts: int = 1,
) -> tuple[PartitionedGraph, PackedHostData]:
    """Pack a list of ``Atoms`` into one block-diagonal PartitionedGraph.

    ``caps`` (default: a shared ``BucketPolicy``) quantizes every capacity
    to a geometric ladder so a stream of varied batch shapes compiles a
    small fixed executable set. ``species_fn`` maps atomic numbers to model
    species indices (default: identity). ``skin`` builds the neighbor
    graphs at ``cutoff + skin`` for Verlet reuse (model envelopes zero the
    skin-shell edges, so results are unchanged).

    ``system`` conditioning scalars are REPLICATED across the batch
    (one ()-shaped int per key); structures carrying conflicting
    ``atoms.info`` conditioning raise rather than silently aliasing.

    ``spatial_parts``/``batch_parts`` select the 2-D mesh placement: with
    either > 1 the batch packs for a ``(batch_parts, spatial_parts)``
    ``device_mesh`` — structures assign contiguously to ``batch_parts``
    shards, each structure is spatially partitioned into ``spatial_parts``
    slabs with its own halo ring, and the result is a
    (batch x spatial)-sharded super-graph (leading axis ``batch_parts *
    spatial_parts``, see ``pack_structures_mesh``). The default (1, 1) is
    the historical single-device pack.
    """
    if spatial_parts > 1 or batch_parts > 1:
        return pack_structures_mesh(
            structures, cutoff, bond_cutoff=bond_cutoff,
            use_bond_graph=use_bond_graph, caps=caps, species_fn=species_fn,
            dtype=dtype, skin=skin, system=system, num_threads=num_threads,
            spatial_parts=spatial_parts, batch_parts=batch_parts)
    if not structures:
        raise ValueError("pack_structures needs at least one structure")
    caps = caps or _default_buckets
    species_fn = species_fn or (lambda z: np.asarray(z, dtype=np.int32))
    r_build = cutoff + skin
    b_build = (bond_cutoff + skin) if use_bond_graph else 0.0

    # conditioning scalars must agree across the batch: the packed graph
    # carries ONE replicated system dict (models read it per-graph). An
    # explicit system= override skips the consistency check — the caller
    # has chosen the batch-wide conditioning.
    system = _batch_system(structures, system)

    B = len(structures)
    b_slots = caps.get_small(B) if hasattr(caps, "get_small") else B

    # --- per-structure single-partition plans (dst-sorted per block) ---
    blocks = []
    for atoms in structures:
        nl = neighbor_list(atoms.positions, atoms.cell, atoms.pbc, r_build,
                           bond_r=b_build, num_threads=num_threads)
        plan = build_plan(nl, atoms.cell, atoms.pbc, 1, r_build, b_build,
                          use_bond_graph)
        cell = np.asarray(atoms.cell, dtype=np.float64)
        input_cart = nl.wrapped_cart + nl.shift @ cell
        ne = len(plan.src_local[0])
        perm = np.argsort(plan.dst_local[0], kind="stable")
        inv = np.empty(ne, dtype=np.int64)
        inv[perm] = np.arange(ne)
        blk = {
            "n": len(atoms),
            "pos": input_cart,
            "species": species_fn(atoms.numbers),
            "src": plan.src_local[0][perm],
            "dst": plan.dst_local[0][perm],
            # bake image offsets to Cartesian: per-structure cells never
            # reach the device, geometry rides the offsets
            "off": (plan.edge_offsets[0][perm].astype(np.float64) @ cell),
            "vol": abs(np.linalg.det(cell)),
        }
        if use_bond_graph:
            blk.update({
                "nb": int(plan.bond_markers[0][-1]),
                "line_src": plan.line_src[0],
                "line_dst": plan.line_dst[0],
                "line_center": plan.line_center_local[0],
                "bm_edge": inv[plan.bond_mapping_edge[0]],
                "bm_bond": plan.bond_mapping_bond[0],
            })
        blocks.append(blk)

    node_off = np.concatenate([[0], np.cumsum([b["n"] for b in blocks])])
    n_tot = int(node_off[-1])
    e_tot = int(sum(len(b["src"]) for b in blocks))
    n_cap = caps.get("nodes", n_tot)
    e_cap = caps.get("edges", e_tot)

    positions = np.zeros((1, n_cap, 3), dtype=dtype)
    species = np.zeros((1, n_cap), dtype=np.int32)
    node_mask = np.zeros((1, n_cap), dtype=bool)
    # padded rows point one past the last slot: the per-structure
    # segment_sum readout (num_segments == batch_size) drops them
    struct_id = np.full((1, n_cap), b_slots, dtype=np.int32)
    edge_src = np.zeros((1, e_cap), dtype=np.int32)
    edge_dst = np.zeros((1, e_cap), dtype=np.int32)
    edge_offset = np.zeros((1, e_cap, 3), dtype=dtype)
    edge_mask = np.zeros((1, e_cap), dtype=bool)

    ni = ei = 0
    for b, blk in enumerate(blocks):
        n, ne = blk["n"], len(blk["src"])
        positions[0, ni:ni + n] = blk["pos"]
        species[0, ni:ni + n] = blk["species"]
        node_mask[0, ni:ni + n] = True
        struct_id[0, ni:ni + n] = b
        edge_src[0, ei:ei + ne] = blk["src"] + ni
        edge_dst[0, ei:ei + ne] = blk["dst"] + ni
        edge_offset[0, ei:ei + ne] = blk["off"]
        edge_mask[0, ei:ei + ne] = True
        ni += n
        ei += ne
    # padding contract: dst repeats the last real value (nondecreasing,
    # in-bounds); src stays 0 and the mask zeroes the message
    edge_dst[0, ei:] = edge_dst[0, ei - 1] if ei else 0
    assert np.all(np.diff(edge_dst[0]) >= 0), "packed edge_dst must be sorted"

    if use_bond_graph:
        bond_off = np.concatenate([[0], np.cumsum([b["nb"] for b in blocks])])
        b_tot = int(bond_off[-1])
        m_tot = int(sum(len(b["bm_edge"]) for b in blocks))
        b_cap = caps.get("bonds", b_tot)
        m_cap = caps.get("bond_map", m_tot)
        slabs = line_table_cap(
            caps, line_slots_needed([b["line_dst"] for b in blocks]), m_tot,
            b_cap) // max(b_cap, 1)
        bm_edge = np.zeros((1, m_cap), dtype=np.int32)
        bm_bond = np.zeros((1, m_cap), dtype=np.int32)
        bm_mask = np.zeros((1, m_cap), dtype=bool)
        ei = mi = 0
        for blk, bi in zip(blocks, bond_off):
            nm = len(blk["bm_edge"])
            bm_edge[0, mi:mi + nm] = blk["bm_edge"] + ei
            bm_bond[0, mi:mi + nm] = blk["bm_bond"] + bi
            bm_mask[0, mi:mi + nm] = True
            ei += len(blk["src"])
            mi += nm
        # one table over the packed bond rows (block offsets on the ids)
        lines = (np.concatenate([b["line_src"] + o
                                 for b, o in zip(blocks, bond_off)]),
                 np.concatenate([b["line_dst"] + o
                                 for b, o in zip(blocks, bond_off)]),
                 np.concatenate([b["line_center"] + o
                                 for b, o in zip(blocks, node_off)]))
        line_src, line_count, bond_center = (
            x[None] for x in line_table(*lines, b_cap, slabs))
        center_tables = dict(zip(
            ("center_in", "redirect_bits"),
            (x[None] for x in center_table(*lines, b_cap, n_cap, slabs))))
    else:
        b_cap = slabs = 0
        line_src = line_count = bond_center = np.zeros((1, 0),
                                                       dtype=np.int32)
        center_tables = empty_center_tables(1, n_cap)
        bm_edge = bm_bond = np.zeros((1, 0), dtype=np.int32)
        bm_mask = np.zeros((1, 0), dtype=bool)

    graph = PartitionedGraph(
        num_partitions=1,
        shifts=(),
        has_bond_graph=use_bond_graph,
        n_cap=n_cap,
        e_cap=e_cap,
        b_cap=b_cap,
        e_split=e_cap,  # unsplit: single partition has no frontier
        batch_size=b_slots,
        positions=positions,
        species=species,
        node_mask=node_mask,
        owned_mask=node_mask.copy(),  # single partition: every real row owned
        struct_id=struct_id,
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_offset=edge_offset,
        edge_mask=edge_mask,
        halo_send_idx=np.zeros((1, 1, 0), dtype=np.int32),
        halo_send_mask=np.zeros((1, 1, 0), dtype=bool),
        halo_recv_idx=np.full((1, 1, 0), n_cap, dtype=np.int32),
        # identity lattice: edge offsets are already Cartesian, and the
        # batched runtime strains them per structure via struct_id
        lattice=np.eye(3, dtype=dtype),
        n_total_nodes=np.int32(n_tot),
        line_src=line_src,
        line_mask=live_mask(line_count, slabs),
        line_count=line_count,
        bond_center=bond_center,
        **center_tables,
        **bond_orders(bond_center),
        bond_map_edge=bm_edge,
        bond_map_bond=bm_bond,
        bond_map_mask=bm_mask,
        bond_halo_send_idx=np.zeros((1, 1, 0), dtype=np.int32),
        bond_halo_send_mask=np.zeros((1, 1, 0), dtype=bool),
        bond_halo_recv_idx=np.full((1, 1, 0), b_cap, dtype=np.int32),
        system={k: np.int32(v) for k, v in system.items()},
    )
    host = PackedHostData(
        node_offsets=node_off,
        n_atoms=np.array([b["n"] for b in blocks]),
        volumes=np.array([b["vol"] for b in blocks]),
        n_cap=n_cap,
        batch_size=b_slots,
        build_positions=[np.asarray(a.positions).copy() for a in structures],
        cells=[np.asarray(a.cell, dtype=np.float64).copy()
               for a in structures],
        pbcs=[np.asarray(a.pbc).copy() for a in structures],
        stats=packed_stats(graph, B),
    )
    return graph, host


# ---------------------------------------------------------------------------
# 2-D mesh packing: (batch_parts x spatial_parts) placements on one mesh
# ---------------------------------------------------------------------------


def _cat(arrs, dtype=np.int64, width: int | None = None):
    """Concatenate a possibly empty list of 1-D/2-D arrays (typed empty
    result when the list is empty)."""
    arrs = [a for a in (np.asarray(x) for x in arrs) if len(a)]
    if not arrs:
        shape = (0,) if width is None else (0, width)
        return np.zeros(shape, dtype=dtype)
    return np.concatenate(arrs).astype(dtype, copy=False)


def _pair_list(lists, section_fn, p: int, kind: str, q: int) -> np.ndarray:
    """Send ("to") / recv ("from") local-index list of partition p against
    peer q — explicit lists for block plans, marker sections for slab
    plans. Both sides are ordered by global id (slot-aligned exchange)."""
    if lists is not None:
        return np.asarray(lists[p].get(q, np.zeros(0, np.int64)),
                          dtype=np.int64)
    s_, e_ = section_fn(p, kind, q)
    return np.arange(s_, e_, dtype=np.int64)


def _plan_pair(plan, p: int, kind: str, q: int) -> np.ndarray:
    return _pair_list(plan.halo_send if kind == "to" else plan.halo_recv,
                      plan.section, p, kind, q)


def _plan_bond_pair(plan, p: int, kind: str, q: int) -> np.ndarray:
    return _pair_list(
        plan.bond_halo_send if kind == "to" else plan.bond_halo_recv,
        plan.bond_section, p, kind, q)


class _MergedNeighborData:
    """``nl`` shim for ``build_partitioned_graph`` over a merged shard:
    positions are already input-frame Cartesian and image offsets are baked
    into the (Cartesian) edge offsets, so the shim reports zero shifts and
    the graph lattice is the identity."""

    def __init__(self, input_cart):
        self.wrapped_cart = np.asarray(input_cart, dtype=np.float64).reshape(
            -1, 3)
        self.shift = np.zeros_like(self.wrapped_cart)


def _merge_shard(items, S: int, use_bond_graph: bool, b_slots: int):
    """Merge per-structure S-partition plans into ONE shard-level plan.

    Local node order per merged partition: ``[owned(struct 0) | owned(1) |
    ... | halo(struct 0) | halo(1) | ...]`` — owned rows stay a prefix
    (the ``owned_counts`` contract) and the owned-row ``struct_id`` is
    nondecreasing (sorted per-structure segment-sum readout). Bond nodes
    follow the same layout. Per-structure halo pair lists concatenate in
    structure order on BOTH sides, so the ring exchange stays
    slot-aligned. Edge image offsets are baked to Cartesian with each
    structure's own cell.

    Returns ``(plan, nl_shim, species, struct_slot, layout)``:
    ``struct_slot[s]`` maps every real local row of partition s to its
    shard-local batch slot (halo rows carry the ``b_slots`` sentinel);
    ``layout[k][s] = (owned_start, owned_count, owned_global_ids)`` places
    structure k's owned rows for host scatter/gather.
    """
    K = len(items)
    gbase = np.concatenate(
        [[0], np.cumsum([it["n"] for it in items])]).astype(np.int64)
    n_tot = int(gbase[-1])
    plan = PartitionPlan(
        num_partitions=S, axis=0,
        walls=np.zeros(max(S - 1, 0)),
        node_part=_cat([it["plan"].node_part for it in items],
                       dtype=np.int32),
        nodes_to_partition=np.full(n_tot, -1, dtype=np.int64),
        halo_send=[{} for _ in range(S)],
        halo_recv=[{} for _ in range(S)],
        has_bond_graph=use_bond_graph,
    )
    if use_bond_graph:
        plan.bond_halo_send = [{} for _ in range(S)]
        plan.bond_halo_recv = [{} for _ in range(S)]
    species = _cat([it["species"] for it in items], dtype=np.int32)
    input_cart = np.concatenate(
        [np.asarray(it["input_cart"], dtype=np.float64).reshape(-1, 3)
         for it in items]) if K else np.zeros((0, 3))
    struct_slot = []
    layout = [[None] * S for _ in range(K)]

    for s in range(S):
        oc = [int(it["plan"].owned_counts[s]) for it in items]
        nt = [int(it["plan"].node_markers[s][-1]) for it in items]
        O = np.concatenate([[0], np.cumsum(oc)]).astype(np.int64)
        H = np.concatenate(
            [[0], np.cumsum([t - o for t, o in zip(nt, oc)])]).astype(
                np.int64)
        OC, NT = int(O[-1]), int(O[-1] + H[-1])

        def map_local(k, idx, oc=oc, O=O, H=H, OC=OC):
            idx = np.asarray(idx, dtype=np.int64)
            return np.where(idx < oc[k], O[k] + idx,
                            OC + H[k] + (idx - oc[k]))

        plan.global_ids.append(_cat(
            [it["plan"].global_ids[s][:oc[k]] + gbase[k]
             for k, it in enumerate(items)]
            + [it["plan"].global_ids[s][oc[k]:] + gbase[k]
               for k, it in enumerate(items)]))
        # marker vector: only owned (m[1+P]) and total (m[-1]) are read
        # for merged plans (kind "block" — halo lists are explicit)
        plan.node_markers.append(np.concatenate(
            [[0], np.full(S + 1, OC), np.full(S, NT)]).astype(np.int64))
        e_base = np.concatenate(
            [[0], np.cumsum([len(it["plan"].src_local[s])
                             for it in items])]).astype(np.int64)
        plan.src_local.append(_cat(
            [map_local(k, it["plan"].src_local[s])
             for k, it in enumerate(items)], dtype=np.int32))
        plan.dst_local.append(_cat(
            [map_local(k, it["plan"].dst_local[s])
             for k, it in enumerate(items)], dtype=np.int32))
        plan.edge_offsets.append(_cat(
            [np.asarray(it["plan"].edge_offsets[s], dtype=np.float64)
             @ it["cell"] for k, it in enumerate(items)],
            dtype=np.float64, width=3))
        plan.edge_ids.append(np.arange(int(e_base[-1]), dtype=np.int64))
        slot = np.concatenate([
            np.repeat(np.arange(K, dtype=np.int32),
                      np.asarray(oc, dtype=np.int64))
            if K else np.zeros(0, np.int32),
            np.full(NT - OC, b_slots, dtype=np.int32)])
        struct_slot.append(slot)
        for k, it in enumerate(items):
            layout[k][s] = (int(O[k]), oc[k],
                            np.asarray(it["plan"].global_ids[s][:oc[k]],
                                       dtype=np.int64))
        for q in range(S):
            if q == s:
                continue
            send = _cat([map_local(k, _plan_pair(it["plan"], s, "to", q))
                         for k, it in enumerate(items)])
            recv = _cat([map_local(k, _plan_pair(it["plan"], s, "from", q))
                         for k, it in enumerate(items)])
            if len(send):
                plan.halo_send[s][q] = send
            if len(recv):
                plan.halo_recv[s][q] = recv

        if use_bond_graph:
            boc = [int(it["plan"].bond_markers[s][1 + S]) for it in items]
            bnt = [int(it["plan"].bond_markers[s][-1]) for it in items]
            BO = np.concatenate([[0], np.cumsum(boc)]).astype(np.int64)
            BH = np.concatenate(
                [[0], np.cumsum([t - o for t, o in zip(bnt, boc)])]).astype(
                    np.int64)
            BOC, BNT = int(BO[-1]), int(BO[-1] + BH[-1])

            def map_bond(k, idx, boc=boc, BO=BO, BH=BH, BOC=BOC):
                idx = np.asarray(idx, dtype=np.int64)
                return np.where(idx < boc[k], BO[k] + idx,
                                BOC + BH[k] + (idx - boc[k]))

            plan.bond_markers.append(np.concatenate(
                [[0], np.full(S + 1, BOC), np.full(S, BNT)]).astype(
                    np.int64))
            plan.line_src.append(_cat(
                [map_bond(k, it["plan"].line_src[s])
                 for k, it in enumerate(items)], dtype=np.int32))
            plan.line_dst.append(_cat(
                [map_bond(k, it["plan"].line_dst[s])
                 for k, it in enumerate(items)], dtype=np.int32))
            plan.line_center_local.append(_cat(
                [map_local(k, it["plan"].line_center_local[s])
                 for k, it in enumerate(items)], dtype=np.int32))
            plan.bond_mapping_edge.append(_cat(
                [np.asarray(it["plan"].bond_mapping_edge[s],
                            dtype=np.int64) + e_base[k]
                 for k, it in enumerate(items)]))
            plan.bond_mapping_bond.append(_cat(
                [map_bond(k, it["plan"].bond_mapping_bond[s])
                 for k, it in enumerate(items)], dtype=np.int32))
            for q in range(S):
                if q == s:
                    continue
                send = _cat(
                    [map_bond(k, _plan_bond_pair(it["plan"], s, "to", q))
                     for k, it in enumerate(items)])
                recv = _cat(
                    [map_bond(k, _plan_bond_pair(it["plan"], s, "from", q))
                     for k, it in enumerate(items)])
                if len(send):
                    plan.bond_halo_send[s][q] = send
                if len(recv):
                    plan.bond_halo_recv[s][q] = recv

    return plan, _MergedNeighborData(input_cart), species, struct_slot, \
        layout


@dataclass
class MeshPackedHostData:
    """Host companions of a (batch x spatial)-packed graph.

    Same surface as ``PackedHostData`` where the batched calculators need
    it (``scatter_positions`` / ``gather_per_structure`` / ``volumes`` /
    ``build_positions`` / ``stats``), plus the placement geometry. A
    structure's atoms live as owned rows spread over its shard's S spatial
    partitions; ``layout[i]`` lists ``(p, start, count, global_ids)`` row
    blocks (p = shard * S + slab, global partition row).
    """

    spatial_parts: int
    batch_parts: int
    batch_size: int              # structure SLOTS per batch shard
    per_shard: int               # real structures per shard (last may have fewer)
    n_cap: int
    n_atoms: np.ndarray          # (B,) real atoms per structure
    volumes: np.ndarray          # (B,) cell volumes (stress division)
    layout: list                 # [i] -> [(p, start, count, gids), ...]
    stats: dict | None = None
    build_positions: list = field(default_factory=list)
    cells: list = field(default_factory=list)
    pbcs: list = field(default_factory=list)

    @property
    def num_structures(self) -> int:
        return len(self.n_atoms)

    @property
    def structure_slots(self) -> np.ndarray:
        """(B,) flat slot of each structure in the runtime's shard-major
        ``energies``/``strain_grad`` outputs."""
        i = np.arange(self.num_structures, dtype=np.int64)
        return (i // self.per_shard) * self.batch_size + (i % self.per_shard)

    def scatter_positions(self, positions_list, dtype=np.float32) -> np.ndarray:
        """Pack per-structure (n_b, 3) positions into (P, N_cap, 3) owned
        rows (halo rows are refreshed in-jit by the spatial exchange)."""
        return self.scatter_per_atom(positions_list, dtype=dtype)

    def scatter_per_atom(self, arrays, dtype=np.float32) -> np.ndarray:
        """Pack per-structure per-atom arrays (n_b, ...) of a shared
        trailing shape into owned rows of the (P, N_cap, ...) layout
        (halo + padded rows zero). Same surface as
        ``PackedHostData.scatter_per_atom``."""
        P = self.spatial_parts * self.batch_parts
        trail = np.shape(np.asarray(arrays[0]))[1:]
        out = np.zeros((P, self.n_cap) + trail, dtype=dtype)
        for i, arr in enumerate(arrays):
            arr = np.asarray(arr)
            for p, start, count, gids in self.layout[i]:
                out[p, start:start + count] = arr[gids]
        return out

    def atom_slots(self) -> np.ndarray:
        """(P, N_cap) int32 FLAT (shard-major) energy-slot of each owned
        node row; halo and padded rows carry the total-slot sentinel
        ``batch_parts * batch_size``. The mesh counterpart of
        ``PackedHostData.atom_slots`` — aligns per-atom arrays with the
        runtime's flat ``energies``/``strain_grad`` outputs."""
        P = self.spatial_parts * self.batch_parts
        total = self.batch_parts * self.batch_size
        out = np.full((P, self.n_cap), total, dtype=np.int32)
        slots = self.structure_slots
        for i in range(self.num_structures):
            for p, start, count, _gids in self.layout[i]:
                out[p, start:start + count] = slots[i]
        return out

    def gather_per_structure(self, packed: np.ndarray) -> list:
        """Reassemble a (P, N_cap, ...) owned-row array into per-structure
        (n_b, ...) arrays in each structure's own atom order."""
        arr = np.asarray(packed)
        res = []
        for i in range(self.num_structures):
            out = np.zeros((int(self.n_atoms[i]),) + arr.shape[2:],
                           dtype=arr.dtype)
            for p, start, count, gids in self.layout[i]:
                out[gids] = arr[p, start:start + count]
            res.append(out)
        return res


def pack_structures_mesh(
    structures,
    cutoff: float,
    bond_cutoff: float = 0.0,
    use_bond_graph: bool = False,
    caps: BucketPolicy | None = None,
    species_fn=None,
    dtype=np.float32,
    skin: float = 0.0,
    system: dict | None = None,
    num_threads: int | None = None,
    spatial_parts: int = 1,
    batch_parts: int = 1,
) -> tuple[PartitionedGraph, MeshPackedHostData]:
    """Pack B structures for a ``(batch_parts, spatial_parts)`` mesh.

    Structures assign contiguously to ``batch_parts`` shards (structure i
    -> shard ``i // ceil(B / batch_parts)``); within a shard every
    structure is spatially partitioned into ``spatial_parts`` slabs via
    the standard planner and the slabs merge block-diagonally per spatial
    partition (``_merge_shard``). The result is ONE ``PartitionedGraph``
    whose leading axis is ``batch_parts * spatial_parts`` (shard-major),
    sharded by the runtime over the 2-D mesh's ("batch", "spatial") axes.

    Exactness is inherited: per shard this is the same relabel-plus-pad
    the planner/packer already guarantee, and shards never share rows or
    edges — so energies/forces/stresses match the single-device reference
    to fp32 roundoff at EVERY placement (tests/test_mesh2d.py asserts this
    for all four model families).

    Static-shape discipline: every shard builds against ``FixedCaps``
    (cross-shard maxima quantized ONCE through ``caps``) and halo tables
    expand onto the union shift set, so all shards share one program.
    Shards left empty by B < batch_parts pack zero structures (masked
    slots) — the placement still runs, it just wastes those rows.
    """
    if not structures:
        raise ValueError("pack_structures_mesh needs at least one structure")
    S, Bp = int(spatial_parts), int(batch_parts)
    if S < 1 or Bp < 1:
        raise ValueError(
            f"spatial_parts/batch_parts must be >= 1, got {S}/{Bp}")
    caps = caps or _default_buckets
    species_fn = species_fn or (lambda z: np.asarray(z, dtype=np.int32))
    r_build = cutoff + skin
    b_build = (bond_cutoff + skin) if use_bond_graph else 0.0
    system = _batch_system(structures, system)
    B = len(structures)
    per_shard = -(-B // Bp)  # ceil
    b_slots = (caps.get_small(per_shard) if hasattr(caps, "get_small")
               else per_shard)

    items = []
    for atoms in structures:
        nl = neighbor_list(atoms.positions, atoms.cell, atoms.pbc, r_build,
                           bond_r=b_build, num_threads=num_threads)
        plan = build_plan(nl, atoms.cell, atoms.pbc, S, r_build, b_build,
                          use_bond_graph)
        cell = np.asarray(atoms.cell, dtype=np.float64)
        items.append({
            "plan": plan,
            "cell": cell,
            "n": len(atoms),
            "input_cart": nl.wrapped_cart + nl.shift @ cell,
            "species": species_fn(atoms.numbers),
            "vol": abs(np.linalg.det(cell)),
        })

    shards = [items[b * per_shard:(b + 1) * per_shard] for b in range(Bp)]
    merged = [_merge_shard(sh, S, use_bond_graph, b_slots) for sh in shards]

    # cross-shard worst-case capacities, quantized ONCE: every shard's
    # build must land on identical static shapes
    needs: dict[str, int] = {}

    def _need(name, val):
        needs[name] = max(needs.get(name, 0), int(val))

    for mplan, _nl, _sp, _slots, _lay in merged:
        _need("nodes", max(int(m[-1]) for m in mplan.node_markers))
        _need("edges", max(len(e) for e in mplan.edge_ids))
        _need("halo", max(
            (len(v) for d in mplan.halo_send for v in d.values()),
            default=0))
        if use_bond_graph:
            _need("bonds", max(int(m[-1]) for m in mplan.bond_markers))
            _need(LINE_SLOTS, line_slots_needed(mplan.line_dst))
            _need("bond_map", max(len(x) for x in mplan.bond_mapping_edge))
            _need("bond_halo", max(
                (len(v) for d in mplan.bond_halo_send for v in d.values()),
                default=0))
    # ``lines``: the shards' in-line tables, the batch's largest in-degree
    # over the bond rows a shard computes, in whole slabs of the frozen b_cap
    slots = needs.pop(LINE_SLOTS, 0)
    fixed = FixedCaps(freeze_caps(caps, needs, slots), fallback=caps)

    graphs = []
    for mplan, nl_shim, species, _slots, _lay in merged:
        g, _host = build_partitioned_graph(
            mplan, nl_shim, species, np.eye(3), caps=fixed, dtype=dtype,
            system=system, frontier_split=False)
        graphs.append(g)

    # equalize ring shifts across shards (union), then stack shard-major
    import dataclasses

    all_shifts = tuple(sorted(set().union(
        *[set(g.shifts) for g in graphs]))) if graphs else ()
    for i, g in enumerate(graphs):
        if tuple(g.shifts) == all_shifts:
            continue
        rep = {
            "shifts": all_shifts,
            "halo_send_idx": expand_shift_tables(
                g.halo_send_idx, g.shifts, all_shifts, 0),
            "halo_send_mask": expand_shift_tables(
                g.halo_send_mask, g.shifts, all_shifts, False),
            "halo_recv_idx": expand_shift_tables(
                g.halo_recv_idx, g.shifts, all_shifts, g.n_cap),
        }
        if use_bond_graph:
            rep.update(
                bond_halo_send_idx=expand_shift_tables(
                    g.bond_halo_send_idx, g.shifts, all_shifts, 0),
                bond_halo_send_mask=expand_shift_tables(
                    g.bond_halo_send_mask, g.shifts, all_shifts, False),
                bond_halo_recv_idx=expand_shift_tables(
                    g.bond_halo_recv_idx, g.shifts, all_shifts, g.b_cap))
        graphs[i] = dataclasses.replace(g, **rep)

    g0 = graphs[0]
    struct_id = np.full((Bp * S, g0.n_cap), b_slots, dtype=np.int32)
    for b, (_plan, _nl, _sp, slots_list, _lay) in enumerate(merged):
        for s in range(S):
            arr = slots_list[s]
            struct_id[b * S + s, :len(arr)] = arr

    def cat0(name):
        return np.concatenate([getattr(g, name) for g in graphs], axis=0)

    def cat1(name):
        return np.concatenate([getattr(g, name) for g in graphs], axis=1)

    graph = PartitionedGraph(
        num_partitions=Bp * S,
        shifts=all_shifts,
        has_bond_graph=use_bond_graph,
        n_cap=g0.n_cap,
        e_cap=g0.e_cap,
        b_cap=g0.b_cap,
        e_split=g0.e_split,
        batch_size=b_slots,
        spatial_parts=S,
        positions=cat0("positions"),
        species=cat0("species"),
        node_mask=cat0("node_mask"),
        owned_mask=cat0("owned_mask"),
        struct_id=struct_id,
        edge_src=cat0("edge_src"),
        edge_dst=cat0("edge_dst"),
        edge_offset=cat0("edge_offset"),
        edge_mask=cat0("edge_mask"),
        halo_send_idx=cat1("halo_send_idx"),
        halo_send_mask=cat1("halo_send_mask"),
        halo_recv_idx=cat1("halo_recv_idx"),
        lattice=np.eye(3, dtype=dtype),
        n_total_nodes=np.int32(sum(it["n"] for it in items)),
        line_src=cat0("line_src"),
        line_mask=cat0("line_mask"),
        line_count=cat0("line_count"),
        bond_center=cat0("bond_center"),
        **{name: cat0(name) for name in (
            "center_in", "bond_order", "bond_rank", "redirect_bits")},
        bond_map_edge=cat0("bond_map_edge"),
        bond_map_bond=cat0("bond_map_bond"),
        bond_map_mask=cat0("bond_map_mask"),
        bond_halo_send_idx=cat1("bond_halo_send_idx"),
        bond_halo_send_mask=cat1("bond_halo_send_mask"),
        bond_halo_recv_idx=cat1("bond_halo_recv_idx"),
        system={k: np.int32(v) for k, v in system.items()},
    )

    layout = []
    for i in range(B):
        b, j = divmod(i, per_shard)
        _plan, _nl, _sp, _slots, shard_layout = merged[b]
        layout.append([
            (b * S + s,) + shard_layout[j][s][:2] + (shard_layout[j][s][2],)
            for s in range(S)])
    host = MeshPackedHostData(
        spatial_parts=S,
        batch_parts=Bp,
        batch_size=b_slots,
        per_shard=per_shard,
        n_cap=g0.n_cap,
        n_atoms=np.array([it["n"] for it in items]),
        volumes=np.array([it["vol"] for it in items]),
        layout=layout,
        build_positions=[np.asarray(a.positions).copy() for a in structures],
        cells=[np.asarray(a.cell, dtype=np.float64).copy()
               for a in structures],
        pbcs=[np.asarray(a.pbc).copy() for a in structures],
        stats=packed_stats(graph, B),
    )
    return graph, host


def build_packed_refresh_spec(host: PackedHostData, graph: PartitionedGraph,
                              r_build: float, dtype=np.float32):
    """Spec for refreshing THIS packed graph's edges on device: per-block
    dense search sized to the pack-time structures (see
    ``neighbors.device.build_packed_spec``). ``r_build`` must be the pack
    cutoff (cutoff + skin)."""
    from ..neighbors.device import build_packed_spec

    return build_packed_spec(
        host.cells, host.pbcs, host.n_atoms, host.node_offsets, r_build,
        graph.n_cap, graph.e_cap, dtype=dtype)


def _device_refresh_packed(static, arrays, graph, positions):
    """Packed-batch rebuild + in-place swap (traceable). ``positions``:
    (1, N_cap, 3) packed input-frame coordinates."""
    from ..neighbors.device import packed_neighbors
    from .graph import refresh_edges

    src, dst, off_cart, n_edges, overflow = packed_neighbors(
        static, arrays, positions[0])
    graph = refresh_edges(graph, src, dst, off_cart, n_edges)
    return graph, n_edges, overflow


_refresh_packed_jitted = None


def device_refresh_packed(static, arrays, graph, positions):
    """Jitted host entry for the packed device refresh — swaps rebuilt
    block-diagonal edge arrays into an existing packed graph without
    re-tracing (same bucket caps => same shapes)."""
    global _refresh_packed_jitted
    if _refresh_packed_jitted is None:
        import jax

        _refresh_packed_jitted = jax.jit(
            _device_refresh_packed, static_argnums=0)
    from ..neighbors.device import _as_device_arrays

    return _refresh_packed_jitted(static, _as_device_arrays(arrays), graph,
                                  positions)


def slot_waste_frac(live: int, slots: int) -> float:
    """THE padding-waste definition: dead padded slots / total slots over
    the compute-bearing arrays. Single source of truth — the serving pack
    stats (:func:`packed_stats`), the training loader's per-step numbers
    (train/data.py) and the analytic predictions (train/packing.py,
    tools/pack_audit.py) all compute waste through this one function, so
    a report can never show two definitions of the same metric."""
    return 1.0 - live / slots if slots else 0.0


def graph_live_slots(graph: PartitionedGraph) -> tuple:
    """(live, slots) census of a packed graph's compute-bearing rows —
    node, edge and (when present) line-graph slots across all partitions.
    ``slot_waste_frac(*graph_live_slots(g))`` is the pack's
    ``padding_waste_frac``."""
    P = graph.num_partitions
    live = int(np.asarray(graph.node_mask).sum()) \
        + int(np.asarray(graph.edge_mask).sum())
    slots = P * (graph.n_cap + graph.e_cap)
    if graph.has_bond_graph:
        slots += P * int(graph.line_src.shape[-1])
        live += int(np.asarray(graph.line_count).sum())
    return live, slots


def packed_stats(graph: PartitionedGraph, n_real_structures: int) -> dict:
    """Telemetry stats for a packed batch (host numpy, before device_put).

    ``padding_waste_frac`` is the fraction of padded (dead) slots across
    the compute-bearing arrays — node, edge and (when present) line rows —
    i.e. the work fraction the bucket quantization spends on masked lanes.
    Works for both the single-shard pack (P=1) and the mesh pack
    (P = batch_parts * spatial_parts; per-partition lists and occupancies
    report the worst partition, matching ``graph_build_stats``).
    """
    P = graph.num_partitions
    nodes = np.asarray(graph.node_mask).sum(axis=1)
    edges = np.asarray(graph.edge_mask).sum(axis=1)
    live, slots = graph_live_slots(graph)
    # total structure slots across batch shards (the legacy pack has one)
    total_slots = graph.batch_parts * graph.batch_size
    stats = {
        "n_atoms": int(graph.n_total_nodes),
        "num_partitions": P,
        "n_cap": graph.n_cap,
        "e_cap": graph.e_cap,
        "b_cap": graph.b_cap,
        "n_nodes_per_part": [int(x) for x in nodes],
        "n_edges_per_part": [int(x) for x in edges],
        "node_occupancy": (float(nodes.max()) / graph.n_cap
                           if graph.n_cap else 0.0),
        "edge_occupancy": (float(edges.max()) / graph.e_cap
                           if graph.e_cap else 0.0),
        "batch_size": n_real_structures,
        "batch_slots": total_slots,
        # slot fill: real structures / padded batch slots — the serving
        # scheduler's primary assembly-quality metric
        "batch_occupancy": (n_real_structures / total_slots
                            if total_slots else 0.0),
        "bucket_key": bucket_key(graph),
        "padding_waste_frac": slot_waste_frac(live, slots),
        "spatial_parts": graph.spatial_size,
        "batch_parts": graph.batch_parts,
        "mesh_shape": [graph.batch_parts, graph.spatial_size],
    }
    if graph.spatial_size > 1:
        send = np.asarray(graph.halo_send_mask).sum(axis=(0, 2))
        stats["halo_send_per_part"] = [int(x) for x in send]
    if graph.has_bond_graph:
        stats["n_lines"] = int(np.asarray(graph.line_count).sum())
        stats.update(line_table_stats(graph))
    return stats
