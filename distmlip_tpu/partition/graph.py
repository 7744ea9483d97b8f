"""PartitionedGraph: the device-side, capacity-padded graph pytree.

All per-partition arrays are stacked along a leading axis of size P and
sharded over the mesh's graph axis by ``shard_map``; inside the shard the
leading axis is 1 (squeezed by the runtime helpers in
``distmlip_tpu.parallel``). Static shapes everywhere; validity is carried by
masks. This replaces the reference's per-GPU python lists of tensors
(reference dist.py:101-126) with a single SPMD pytree.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import numpy as np

from .capacity import CapacityPolicy, line_table_cap
from .plan import PartitionPlan

_default_caps = CapacityPolicy()


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "positions",
        "species",
        "node_mask",
        "owned_mask",
        "edge_src",
        "edge_dst",
        "edge_offset",
        "edge_mask",
        "halo_send_idx",
        "halo_send_mask",
        "halo_recv_idx",
        "lattice",
        "line_src",
        "line_mask",
        "line_count",
        "bond_center",
        "center_in",
        "bond_order",
        "bond_rank",
        "redirect_bits",
        "bond_map_edge",
        "bond_map_bond",
        "bond_map_mask",
        "bond_halo_send_idx",
        "bond_halo_send_mask",
        "bond_halo_recv_idx",
        "n_total_nodes",
        "system",
        "struct_id",
    ],
    meta_fields=["num_partitions", "shifts", "has_bond_graph", "n_cap",
                 "e_cap", "b_cap", "e_split", "batch_size", "spatial_parts"],
)
@dataclass
class PartitionedGraph:
    # --- static metadata ---
    num_partitions: int
    shifts: tuple  # ring shifts used by the halo exchange (e.g. (1, -1))
    has_bond_graph: bool
    n_cap: int
    e_cap: int
    b_cap: int  # bond-node capacity (0 if no bond graph)
    # interior/frontier edge split boundary: edges [0, e_split) have both
    # endpoints locally owned (halo-independent — their messages can be
    # computed while a halo exchange is still in flight); edges
    # [e_split, e_cap) read halo src rows. e_split == e_cap means the
    # layout is unsplit (single partition, or frontier_split=False) and
    # edge_dst is globally nondecreasing; with a split, edge_dst is
    # nondecreasing WITHIN each segment only.
    e_split: int

    # --- per-partition arrays, leading axis P ---
    positions: Any          # (P, N_cap, 3) owned rows valid; halo rows filled in-jit
    species: Any            # (P, N_cap) int32
    node_mask: Any          # (P, N_cap) bool — any valid row (owned + halo)
    owned_mask: Any         # (P, N_cap) bool — owned rows only (pure + to)
    edge_src: Any           # (P, E_cap) int32
    edge_dst: Any           # (P, E_cap) int32
    edge_offset: Any        # (P, E_cap, 3) float
    edge_mask: Any          # (P, E_cap) bool
    # halo exchange tables: one entry per ring shift, stacked as (S, P, H_cap)
    halo_send_idx: Any
    halo_send_mask: Any
    halo_recv_idx: Any      # padded entries point at n_cap (out of bounds -> dropped)
    lattice: Any            # (3, 3) replicated
    n_total_nodes: Any      # () int32 — true number of atoms in the system

    # --- bond graph (present iff has_bond_graph; else zero-size arrays) ---
    # The lines are a slot-major IN-LINE TABLE (:func:`line_table`): slot
    # ``k * b_cap + b`` holds the k-th line INTO bond row ``b``, so a line's
    # destination bond and its centre atom are functions of its position
    # (``LocalGraph.at_line_dst`` / ``sum_to_line_dst``) and only the source
    # is an index array. ``K = line_src.shape[-1] // b_cap`` slabs.
    line_src: Any           # (P, K * b_cap) int32 — bond-node local ids
    line_mask: Any          # (P, K * b_cap) bool — pad slots False
    line_count: Any         # (P, b_cap) int32 — lines into each bond row:
    #                         its slots below the count are live
    bond_center: Any        # (P, b_cap) int32 — atom local id of each bond
    #                         row's source atom, the centre of its in-lines
    # The same lines read by centre atom (:func:`center_table`,
    # ``LocalGraph.in_line_sum``): slot k of row b is the k-th in-bond of
    # its centre, but for the slots a row redirects.
    center_in: Any          # (P, K, N_cap, 2) int32 — k-th in-bond of each
    #                         atom, and the row its redirected bonds read
    bond_order: Any         # (P, b_cap) int32 — bond rows by centre atom
    bond_rank: Any          # (P, b_cap) int32 — its inverse permutation
    redirect_bits: Any      # (P, ceil(K / 32), b_cap) int32 — bit k % 32 of
    #                         word k // 32: slot k of the row is redirected
    bond_map_edge: Any      # (P, M_cap) int32 — local edge id per owned bond node
    bond_map_bond: Any      # (P, M_cap) int32
    bond_map_mask: Any
    bond_halo_send_idx: Any # (S, P, BH_cap)
    bond_halo_send_mask: Any
    bond_halo_recv_idx: Any
    # per-system replicated scalars (UMA charge/spin/dataset conditioning,
    # reference uma/escn_md.py:255-265)
    system: Any = None      # {"charge","spin","dataset"}: () int32 each
    # --- batched multi-structure packing (partition/batch.py) ---
    # batch_size: number of structure SLOTS packed block-diagonally into
    # this graph (0 = unbatched single-structure graph). struct_id maps
    # each node row to its structure slot; padded node rows point at
    # batch_size (one past the last slot) so the per-structure
    # segment_sum readout drops them.
    batch_size: int = 0
    struct_id: Any = None   # (P, N_cap) int32 when batch_size > 0
    # --- 2-D mesh placement (parallel/mesh.py) ---
    # spatial_parts: size of the spatial (halo-ring) sub-axis of the
    # leading partition axis. 0 = legacy 1-D placement (the whole leading
    # axis is spatial). When set, the leading axis factors as
    # (batch_parts, spatial_parts) in row-major order — partition
    # p = b * spatial_parts + s — and shards over the 2-D mesh's
    # ("batch", "spatial") axes jointly. batch_size then counts structure
    # slots PER BATCH SHARD (total slots = batch_parts * batch_size).
    spatial_parts: int = 0

    @property
    def spatial_size(self) -> int:
        """Spatial (ring) extent of the leading partition axis."""
        return self.spatial_parts if self.spatial_parts > 0 \
            else self.num_partitions

    @property
    def batch_parts(self) -> int:
        """Batch-axis extent of the leading partition axis (1 = no batch
        sharding)."""
        return self.num_partitions // self.spatial_size


@dataclass
class HostGraphData:
    """Host companions of a PartitionedGraph needed for reassembly."""

    plan: PartitionPlan
    global_ids: list = field(default_factory=list)
    owned_counts: np.ndarray | None = None
    # shape/occupancy/halo-volume stats captured at build time (host numpy,
    # before device_put) — the telemetry StepRecord's graph fields
    stats: dict | None = None

    def scatter_global(self, global_arr: np.ndarray, n_cap: int, fill=0.0) -> np.ndarray:
        """Split a (N, ...) global array into padded (P, N_cap, ...) locals."""
        P = self.plan.num_partitions
        out = np.full((P, n_cap) + global_arr.shape[1:], fill, dtype=global_arr.dtype)
        for p in range(P):
            g = self.global_ids[p]
            out[p, : len(g)] = global_arr[g]
        return out

    def gather_owned(self, local_arr: np.ndarray, n_total: int) -> np.ndarray:
        """Reassemble a (P, N_cap, ...) owned-node array into (N, ...) global."""
        out = np.zeros((n_total,) + local_arr.shape[2:], dtype=local_arr.dtype)
        oc = self.owned_counts
        for p in range(self.plan.num_partitions):
            g = self.global_ids[p][: oc[p]]
            out[g] = local_arr[p, : oc[p]]
        return out


def _halo_tables(plan: PartitionPlan, section_fn, n_cap, caps, name,
                 send_lists=None, recv_lists=None):
    """Build (S, P, H) send/recv tables.

    Two sources: slab plans expose contiguous to/from layout sections
    (``section_fn``); block plans expose explicit per-(p, q) local-index
    lists (``send_lists``/``recv_lists``, see PartitionPlan) because their
    send sets overlap — a border node goes to up to 7 peers in 3-D. Either
    way the result is one gather->ppermute->scatter round per active ring
    shift; both sides of a pair are ordered by global id so payload slot i
    lands in recv slot i.
    """
    P = plan.num_partitions
    if send_lists is not None:
        def pair(p, kind, q):
            lists = send_lists if kind == "to" else recv_lists
            return np.asarray(lists[p].get(q, np.zeros(0, np.int64)))
    else:
        def pair(p, kind, q):
            s_, e_ = section_fn(p, kind, q)
            return np.arange(s_, e_, dtype=np.int64)

    shift_counts: dict[int, int] = {}
    for p in range(P):
        for q in range(P):
            if q == p:
                continue
            cnt = len(pair(p, "to", q))
            if cnt:
                shift = (q - p) % P
                shift_counts[shift] = max(shift_counts.get(shift, 0), cnt)
    shifts = tuple(sorted(shift_counts))
    h_cap = caps.get(name, max(shift_counts.values(), default=0))
    S = max(len(shifts), 1)
    send_idx = np.zeros((S, P, h_cap), dtype=np.int32)
    send_mask = np.zeros((S, P, h_cap), dtype=bool)
    recv_idx = np.full((S, P, h_cap), n_cap, dtype=np.int32)  # n_cap = drop slot
    for si, s in enumerate(shifts):
        for p in range(P):
            q = (p + s) % P
            to_idx = pair(p, "to", q)
            if len(to_idx):
                send_idx[si, p, : len(to_idx)] = to_idx
                send_mask[si, p, : len(to_idx)] = True
            src_p = (p - s) % P
            fr_idx = pair(p, "from", src_p)
            if len(fr_idx):
                recv_idx[si, p, : len(fr_idx)] = fr_idx
    return shifts, send_idx, send_mask, recv_idx


def expand_shift_tables(tbl, used_shifts, all_shifts, fill):
    """Re-index per-shift halo tables (S, P, H) onto a union shift tuple.

    Rows for shifts the table didn't use are filled with ``fill`` (0 /
    False / the drop slot), so every partition's program sees the same
    static shift set. Shared by ``build_partitioned_graph`` and the mesh
    packer (``partition.batch``), which must equalize shift tuples across
    independently built batch shards.
    """
    if tuple(used_shifts) == tuple(all_shifts) or not all_shifts:
        return tbl
    _, P_, H = tbl.shape
    out = np.full((max(len(all_shifts), 1), P_, H), fill, dtype=tbl.dtype)
    for i, s in enumerate(all_shifts):
        if s in used_shifts:
            out[i] = tbl[list(used_shifts).index(s)]
    return out


def line_slots_needed(line_dst_lists) -> int:
    """The largest number of in-lines of one bond over the given per-
    partition ``line_dst`` lists: the slabs an in-line table needs."""
    return max((int(np.bincount(np.asarray(x, np.int64)).max())
                for x in line_dst_lists if len(x)), default=0)


def line_table(line_src, line_dst, line_center, b_cap: int, slabs: int):
    """One partition's lines as a slot-major in-line table.

    From the line list ``(line_src, line_dst, line_center)`` in any order
    (bond-node local ids, and the centre atom's local id): the lines into
    bond row ``b`` take slots ``0 .. n_b - 1`` of that row in their stable
    dst-sorted order, and slot ``k`` of row ``b`` is entry ``k * b_cap + b``.
    Returns ``(line_src, line_count, bond_center)``: ``(slabs * b_cap,)``
    source ids (a pad slot points at its own row, which is in bounds), the
    ``(b_cap,)`` count ``n_b`` of each row's lines (its slots ``k < n_b``
    are live) and the centre atom of each row's in-lines (a function of the
    destination bond alone: its source atom; 0 for a row with none).
    """
    line_dst = np.asarray(line_dst, np.int64)
    order = np.argsort(line_dst, kind="stable")
    dst = line_dst[order]
    rank = np.arange(len(dst)) - np.searchsorted(dst, dst, side="left")
    if len(dst) and (int(rank.max()) >= slabs or int(dst[-1]) >= b_cap):
        raise ValueError(
            f"in-line table of {slabs} slabs x {b_cap} rows cannot hold "
            f"{int(rank.max()) + 1} lines into one bond / bond row "
            f"{int(dst[-1])}")
    slot = rank * b_cap + dst
    src = np.tile(np.arange(b_cap, dtype=np.int32), slabs)
    src[slot] = np.asarray(line_src)[order]
    count = np.bincount(dst, minlength=b_cap).astype(np.int32)
    center = np.zeros(b_cap, dtype=np.int32)
    center[dst] = np.asarray(line_center)[order]
    assert np.array_equal(center[dst], np.asarray(line_center)[order]), \
        "a bond's in-lines must share their centre atom"
    return src, count, center


def center_table(line_src, line_dst, line_center, b_cap: int, n_cap: int,
                 slabs: int):
    """The same lines read by centre atom (``LocalGraph.in_line_sum``).

    A line ``k -> j -> i`` reads its source bond from the bonds into its
    centre ``j``, a set that depends on ``j`` alone but for the bonds that
    the destination ``j -> i`` skips (``k != i``: the in-bonds from atom
    ``i``, one in a box wider than twice the bond cutoff, several images
    in a smaller one). So slot ``k`` of bond row ``b`` reads the ``k``-th
    in-bond of its centre, except where that one is skipped: such a slot
    below the row's line count ``n_b`` is *redirected* to a position at or
    past ``n_b`` that is not skipped, one for one, in ascending order. Each
    slot ``k < n_b`` then holds exactly one line of the list, and no slot
    more is needed than the list's own largest ``n_b``. The rows a centre's
    bonds skip at slot ``k`` are those of the one atom whose in-bond sits at
    position ``k``, so every bond of the centre redirected there reads the
    same row: one more row a centre and slot.

    From the line list as :func:`line_table` takes it. A centre's in-bonds
    are the distinct sources of its lines, in ascending bond row; a row's
    skipped positions are the ones none of its lines reads. Returns
    ``(center_in, redirect_bits)``: ``(slabs, n_cap, 2)`` bond rows by slot
    and centre, the slot's in-bond and the row the centre's redirected
    bonds read there (row 0 where there is none, in bounds and never read
    live), and the redirected slots of each row as bits, ``(ceil(slabs /
    32), b_cap)`` int32 words: slot ``k`` is bit ``k % 32`` of word
    ``k // 32``. Its shape is the table's own, so every graph of one
    capacity has it, however many slots a row redirects (one where a box
    is wider than twice the bond cutoff, more in tiny periodic boxes:
    :func:`line_table_stats`).
    """
    src = np.asarray(line_src, np.int64)
    dst = np.asarray(line_dst, np.int64)
    cen = np.asarray(line_center, np.int64)
    count = np.bincount(dst, minlength=b_cap)
    if len(count) > b_cap or (len(dst) and int(count.max()) > slabs):
        raise ValueError(f"{slabs} slabs x {b_cap} rows cannot hold the "
                         f"lines into bond row {int(dst.max())}")
    # a source bond enters its lines' centre: its in-bonds are those rows
    center_of = np.full(b_cap, -1, np.int64)
    center_of[src] = cen
    assert np.array_equal(center_of[src], cen), \
        "a bond's out-lines must share their centre atom"
    ins = np.nonzero(center_of >= 0)[0]
    ins = ins[np.argsort(center_of[ins], kind="stable")]
    owner = center_of[ins]
    degree = np.bincount(owner, minlength=n_cap)
    pos = np.zeros(b_cap, np.int64)
    pos[ins] = np.arange(len(ins)) - np.searchsorted(owner, owner)
    table = np.zeros((max(slabs, int(degree.max(initial=0))), n_cap),
                     np.int32)
    table[pos[ins], owner] = ins
    # a row's skipped positions: one (the reverse bond) in a wide box, whose
    # position is what the line positions' sum leaves out of 0 + .. + d - 1
    bond_center = np.zeros(b_cap, np.int64)
    bond_center[dst] = cen
    deg_b = degree[bond_center]
    skipped = np.where(count > 0, deg_b - count, 0)
    psum = np.bincount(dst, weights=pos[src], minlength=b_cap)
    one = np.nonzero(skipped == 1)[0]
    gap = deg_b[one] * (deg_b[one] - 1) // 2 - psum[one].astype(np.int64)
    live = gap < count[one]
    rows, slots, targets = [one[live]], [gap[live]], [count[one[live]]]
    many = np.nonzero(skipped > 1)[0]
    if len(many):
        # several images of one neighbour: mark what each such row reads
        at = np.full(b_cap, -1, np.int64)
        at[many] = np.arange(len(many))
        sel = at[dst] >= 0
        read = np.zeros((len(many), table.shape[0]), bool)
        read[at[dst[sel]], pos[src[sel]]] = True
        p = np.arange(table.shape[0])
        inside = p < deg_b[many][:, None]
        below = p < count[many][:, None]
        r_rows, r_slots = np.nonzero(inside & below & ~read)
        t_rows, t_pos = np.nonzero(inside & ~below & read)
        assert np.array_equal(r_rows, t_rows)
        rows.append(many[r_rows])
        slots.append(r_slots)
        targets.append(t_pos)
    rows, slots, targets = (np.concatenate(x) for x in (rows, slots, targets))
    # one bit a redirected slot; a row's bits in one word are distinct
    # powers of two, so their float64 sum is exact
    words = -(-slabs // 32)
    bits = np.bincount((slots // 32) * b_cap + rows,
                       weights=np.exp2(slots % 32), minlength=words * b_cap)
    redirect_bits = bits.astype(np.uint32).view(np.int32).reshape(words,
                                                                  b_cap)
    # the row a centre's redirected bonds read at a slot: one a centre
    at_slot = np.zeros((slabs, n_cap), np.int32)
    reads = table[targets, bond_center[rows]]
    at_slot[slots, bond_center[rows]] = reads
    assert np.array_equal(at_slot[slots, bond_center[rows]], reads), \
        "a centre's bonds redirected at one slot must read one row"
    return np.stack([table[:slabs], at_slot], axis=-1), redirect_bits


def live_mask(line_count, slabs: int):
    """``(..., slabs * b_cap)`` bool of ``(..., b_cap)`` line counts, slot-major
    as :func:`line_table`: slot ``k`` of row ``b`` holds a line, ``k < n_b``."""
    count = np.asarray(line_count)
    live = np.arange(slabs)[:, None] < count[..., None, :]
    return live.reshape(*count.shape[:-1], slabs * count.shape[-1])


def redirects_per_row(redirect_bits):
    """The redirected slots of each bond row: the bits set in its column of
    ``(..., words, b_cap)`` ``redirect_bits``."""
    bits = np.asarray(redirect_bits).view(np.uint32)
    return np.bitwise_count(bits).sum(axis=-2)


def bond_orders(bond_center) -> dict:
    """``bond_order`` and ``bond_rank`` of ``(P, b_cap)`` bond centres: each
    partition's bond rows by centre atom (stable), and each row's place in
    that order, its inverse."""
    order = np.argsort(bond_center, axis=-1, kind="stable").astype(np.int32)
    rank = np.empty_like(order)
    for p in range(order.shape[0]):
        rank[p, order[p]] = np.arange(order.shape[1], dtype=np.int32)
    return {"bond_order": order, "bond_rank": rank}


def empty_center_tables(P: int, n_cap: int) -> dict:
    """The centre tables of a graph without bonds: zero-size arrays."""
    return dict(center_in=np.zeros((P, 0, n_cap, 2), np.int32),
                redirect_bits=np.zeros((P, 0, 0), np.int32))


def build_partitioned_graph(
    plan: PartitionPlan,
    nl,
    species: np.ndarray,
    lattice: np.ndarray,
    caps: CapacityPolicy | None = None,
    dtype=np.float32,
    system: dict | None = None,
    frontier_split: bool = True,
) -> tuple[PartitionedGraph, HostGraphData]:
    """Pad + stack a PartitionPlan into a PartitionedGraph pytree.

    ``system``: optional per-system scalars (charge, spin, dataset ints) —
    conditioning inputs for UMA-style models; defaults to zeros so the pytree
    structure is stable.

    ``frontier_split``: lay edges out as [interior | frontier] segments
    (each dst-sorted, separately padded) so interior edge compute can
    overlap the in-flight halo ``ppermute`` (see ``PartitionedGraph.e_split``
    and ``LocalGraph.aggregate_edges``). The reorder is exactness-preserving
    — it is a permutation of the same edge set with the same per-segment
    sorted-dst contract. Set False for the historical single-segment layout
    (globally dst-sorted edges).
    """
    caps = caps or _default_caps
    P = plan.num_partitions
    n_cap = caps.get("nodes", max(int(m[-1]) for m in plan.node_markers))
    frontier = [plan.edge_is_frontier(p) for p in range(P)]
    split = frontier_split and any(f.any() for f in frontier)
    if split:
        # separate sticky caps per segment: e_cap must hold the worst-case
        # interior AND frontier counts even when they peak on different
        # partitions, so the boundary (e_split) is a single static index
        # shared by every shard's program
        e_split = caps.get(
            "edges_interior", max(int((~f).sum()) for f in frontier))
        f_cap = caps.get(
            "edges_frontier", max(int(f.sum()) for f in frontier))
        e_cap = e_split + f_cap
    else:
        e_cap = caps.get("edges", max(len(e) for e in plan.edge_ids))
        e_split = e_cap  # unsplit: one globally dst-sorted segment

    positions = np.zeros((P, n_cap, 3), dtype=dtype)
    spec = np.zeros((P, n_cap), dtype=np.int32)
    node_mask = np.zeros((P, n_cap), dtype=bool)
    owned_mask = np.zeros((P, n_cap), dtype=bool)
    edge_src = np.zeros((P, e_cap), dtype=np.int32)
    edge_dst = np.zeros((P, e_cap), dtype=np.int32)
    edge_offset = np.zeros((P, e_cap, 3), dtype=dtype)
    edge_mask = np.zeros((P, e_cap), dtype=bool)

    # positions live in the INPUT (unwrapped) frame — edge offsets are
    # reported relative to it, so MD positions drift out of the box freely
    input_cart = nl.wrapped_cart + nl.shift @ np.asarray(lattice, dtype=np.float64)
    owned_counts = plan.owned_counts
    # per-partition edges sorted by dst within each (interior, frontier)
    # segment so segment reductions see sorted indices (TPU-friendly);
    # bond_map edge indices are remapped to match
    edge_perm_inv = []
    for p in range(P):
        g = plan.global_ids[p]
        nt = len(g)
        positions[p, :nt] = input_cart[g]
        spec[p, :nt] = species[g]
        node_mask[p, :nt] = True
        owned_mask[p, : owned_counts[p]] = True
        ne = len(plan.edge_ids[p])
        perm = np.argsort(plan.dst_local[p], kind="stable")
        if split:
            # stable-partition the dst-sorted order: interior first, then
            # frontier — each segment stays dst-sorted
            perm = perm[np.argsort(frontier[p][perm], kind="stable")]
        n_int = ne - int(frontier[p].sum()) if split else ne
        # padded slot of sorted edge k: interior edges fill [0, n_int),
        # frontier edges fill [e_split, e_split + n_fr)
        slot = np.arange(ne, dtype=np.int64)
        slot[n_int:] += e_split - n_int
        inv = np.empty(ne, dtype=np.int64)
        inv[perm] = slot
        edge_perm_inv.append(inv)
        # (edges in sorted order, start slot in padded array, segment cap end)
        segments = (
            (perm[:n_int], 0, e_split),
            (perm[n_int:], e_split, e_cap),
        )
        for seg, start, cap_end in segments:
            k = len(seg)
            edge_src[p, start:start + k] = plan.src_local[p][seg]
            edge_dst[p, start:start + k] = plan.dst_local[p][seg]
            edge_offset[p, start:start + k] = plan.edge_offsets[p][seg]
            edge_mask[p, start:start + k] = True
            # pad dst with the segment's last real value: keeps each segment
            # nondecreasing for the segment-sum fast path, stays in-bounds
            # for eager gathers; masked messages are zeroed so the extra
            # segment contributions are 0
            edge_dst[p, start + k:cap_end] = (
                plan.dst_local[p][seg[-1]] if k else 0)
        assert np.all(np.diff(edge_dst[p, :e_split]) >= 0), \
            "interior edge_dst must be sorted"
        assert np.all(np.diff(edge_dst[p, e_split:]) >= 0), \
            "frontier edge_dst must be sorted"
        if split:
            assert np.all(plan.src_local[p][perm[:n_int]] < owned_counts[p]), \
                "interior edges must not read halo rows"

    shifts, h_send, h_smask, h_recv = _halo_tables(
        plan, plan.section, n_cap, caps, "halo",
        send_lists=plan.halo_send, recv_lists=plan.halo_recv)

    if plan.has_bond_graph:
        b_cap = caps.get("bonds", max(int(m[-1]) for m in plan.bond_markers))
        m_cap = caps.get("bond_map", max(len(x) for x in plan.bond_mapping_edge))
        # ``lines`` counts table slots: what the largest in-degree takes
        # over the bond rows a partition computes
        slabs = line_table_cap(
            caps, line_slots_needed(plan.line_dst),
            max(len(x) for x in plan.bond_mapping_edge), b_cap
        ) // max(b_cap, 1)
        line_src = np.zeros((P, slabs * b_cap), dtype=np.int32)
        line_count = np.zeros((P, b_cap), dtype=np.int32)
        bond_center = np.zeros((P, b_cap), dtype=np.int32)
        center_in = np.zeros((P, slabs, n_cap, 2), dtype=np.int32)
        redirect_bits = np.zeros((P, -(-slabs // 32), b_cap), dtype=np.int32)
        bm_edge = np.zeros((P, m_cap), dtype=np.int32)
        bm_bond = np.zeros((P, m_cap), dtype=np.int32)
        bm_mask = np.zeros((P, m_cap), dtype=bool)
        for p in range(P):
            lines = (plan.line_src[p], plan.line_dst[p],
                     plan.line_center_local[p])
            line_src[p], line_count[p], bond_center[p] = line_table(
                *lines, b_cap, slabs)
            center_in[p], redirect_bits[p] = center_table(
                *lines, b_cap, n_cap, slabs)
            nm = len(plan.bond_mapping_edge[p])
            bm_edge[p, :nm] = edge_perm_inv[p][plan.bond_mapping_edge[p]]
            bm_bond[p, :nm] = plan.bond_mapping_bond[p]
            bm_mask[p, :nm] = True
        b_shifts, b_send, b_smask, b_recv = _halo_tables(
            plan, plan.bond_section, b_cap, caps, "bond_halo",
            send_lists=plan.bond_halo_send, recv_lists=plan.bond_halo_recv,
        )
        # the node and bond exchanges must ride the same ring shifts
        all_shifts = tuple(sorted(set(shifts) | set(b_shifts)))
        center_tables = dict(center_in=center_in, redirect_bits=redirect_bits)
    else:
        b_cap = 0
        slabs = 0
        line_src = line_count = bond_center = np.zeros((P, 0),
                                                       dtype=np.int32)
        center_tables = empty_center_tables(P, n_cap)
        bm_edge = bm_bond = np.zeros((P, 0), dtype=np.int32)
        bm_mask = np.zeros((P, 0), dtype=bool)
        b_send = np.zeros((1, P, 0), dtype=np.int32)
        b_smask = np.zeros((1, P, 0), dtype=bool)
        b_recv = np.zeros((1, P, 0), dtype=np.int32)
        all_shifts = shifts

    h_send = expand_shift_tables(h_send, shifts, all_shifts, 0)
    h_smask = expand_shift_tables(h_smask, shifts, all_shifts, False)
    h_recv = expand_shift_tables(h_recv, shifts, all_shifts, n_cap)
    if plan.has_bond_graph:
        b_send = expand_shift_tables(b_send, b_shifts, all_shifts, 0)
        b_smask = expand_shift_tables(b_smask, b_shifts, all_shifts, False)
        b_recv = expand_shift_tables(b_recv, b_shifts, all_shifts, b_cap)

    graph = PartitionedGraph(
        num_partitions=P,
        shifts=all_shifts,
        has_bond_graph=plan.has_bond_graph,
        n_cap=n_cap,
        e_cap=e_cap,
        b_cap=b_cap,
        e_split=e_split,
        positions=positions,
        species=spec,
        node_mask=node_mask,
        owned_mask=owned_mask,
        edge_src=edge_src,
        edge_dst=edge_dst,
        edge_offset=edge_offset,
        edge_mask=edge_mask,
        halo_send_idx=h_send,
        halo_send_mask=h_smask,
        halo_recv_idx=h_recv,
        lattice=np.asarray(lattice, dtype=dtype),
        n_total_nodes=np.int32(len(plan.node_part)),
        line_src=line_src,
        line_mask=live_mask(line_count, slabs),
        line_count=line_count,
        bond_center=bond_center,
        **center_tables,
        **bond_orders(bond_center),
        bond_map_edge=bm_edge,
        bond_map_bond=bm_bond,
        bond_map_mask=bm_mask,
        bond_halo_send_idx=b_send,
        bond_halo_send_mask=b_smask,
        bond_halo_recv_idx=b_recv,
        system={
            "charge": np.int32((system or {}).get("charge", 0)),
            "spin": np.int32((system or {}).get("spin", 0)),
            "dataset": np.int32((system or {}).get("dataset", 0)),
        },
    )
    host = HostGraphData(plan=plan, global_ids=plan.global_ids,
                         owned_counts=owned_counts,
                         stats=graph_build_stats(graph))
    return graph, host


def refresh_edges(graph: PartitionedGraph, edge_src, edge_dst, edge_offset,
                  n_edges) -> PartitionedGraph:
    """In-place (shape-preserving) edge swap — traceable inside jit.

    Swaps freshly built edge arrays (from ``neighbors.device``) into an
    existing single-partition or packed ``PartitionedGraph`` without
    changing any static field: same caps => same shapes => the enclosing
    program never re-traces. Re-establishes the padding contract here so
    both device kernels stay contract-free: padded slots are masked, their
    ``dst`` repeats the last real value (nondecreasing, in-bounds), their
    ``src``/``offset`` are zeroed.

    Restrictions (checked at trace time — all static metadata): single
    partition, unsplit edge layout (``e_split == e_cap``), no bond graph
    (the line-graph arrays would go stale; bond-graph models keep the host
    rebuild).
    """
    import jax.numpy as jnp

    if graph.num_partitions != 1:
        raise ValueError(
            f"refresh_edges requires a single-partition graph (got "
            f"P={graph.num_partitions}); multi-partition graphs rebuild on "
            f"the host")
    if graph.e_split != graph.e_cap:
        raise ValueError(
            "refresh_edges requires an unsplit edge layout "
            f"(e_split={graph.e_split} != e_cap={graph.e_cap})")
    if graph.has_bond_graph:
        raise ValueError(
            "refresh_edges cannot rebuild bond/line-graph arrays; "
            "bond-graph models use the host rebuild path")
    import dataclasses

    e_cap = graph.e_cap
    idx = jnp.arange(e_cap, dtype=jnp.int32)
    mask = idx < n_edges
    last = edge_dst[jnp.clip(n_edges - 1, 0, e_cap - 1)]
    dst = jnp.where(mask, edge_dst, last).astype(graph.edge_dst.dtype)
    src = jnp.where(mask, edge_src, 0).astype(graph.edge_src.dtype)
    off = jnp.where(mask[:, None], edge_offset, 0).astype(
        graph.edge_offset.dtype)
    return dataclasses.replace(
        graph,
        edge_src=src[None],
        edge_dst=dst[None],
        edge_offset=off[None],
        edge_mask=mask[None],
    )


def _device_refresh_single(static, arrays, graph, positions):
    """Cell-list rebuild + in-place swap for a single-structure graph.

    ``positions``: (1, N_cap, 3) input-frame coordinates. Returns
    ``(graph', n_edges, overflow)``; on overflow the caller must discard
    ``graph'`` and rebuild on the host with grown caps.
    """
    from ..neighbors.device import cell_list_neighbors

    src, dst, off, n_edges, overflow = cell_list_neighbors(
        static, arrays, positions[0])
    graph = refresh_edges(graph, src, dst,
                          off.astype(positions.dtype), n_edges)
    return graph, n_edges, overflow


_refresh_single_jitted = None


def device_refresh_graph(static, arrays, graph, positions):
    """Jitted host entry for the single-structure device refresh (one
    executable per distinct spec static + graph shape bucket)."""
    global _refresh_single_jitted
    if _refresh_single_jitted is None:
        import jax

        _refresh_single_jitted = jax.jit(
            _device_refresh_single, static_argnums=0)
    from ..neighbors.device import _as_device_arrays

    return _refresh_single_jitted(static, _as_device_arrays(arrays), graph,
                                  positions)


def line_table_stats(graph: PartitionedGraph) -> dict:
    """How far the in-line table engages: ``line_slots`` (its slabs, K),
    ``line_table_fill``, live lines over K x the bond rows computed (1.0
    where every bond has K in-lines; the rest is dense line work on pad
    slots), and ``line_redirects``, the most slots one bond row redirects in
    the centre tables (``m``: 1 where a box is wider than twice the bond
    cutoff, more in a smaller one)."""
    slabs = graph.line_src.shape[-1] // graph.b_cap if graph.b_cap else 0
    rows = int(np.asarray(graph.bond_map_mask).sum())
    live = int(np.asarray(graph.line_count).sum())
    return {"line_slots": slabs,
            "line_table_fill": live / (slabs * rows) if slabs * rows else 0.0,
            "line_redirects": int(redirects_per_row(
                graph.redirect_bits).max(initial=0))}


def graph_build_stats(graph: PartitionedGraph) -> dict:
    """Shape/occupancy/halo-volume stats from a host-side (numpy) graph.

    Called at build time, BEFORE device_put, so reading the masks costs a
    few O(P*cap) numpy sums on arrays already in cache — never a device
    transfer. Keys mirror StepRecord's graph fields.
    """
    nodes = np.asarray(graph.node_mask).sum(axis=1)
    edge_mask = np.asarray(graph.edge_mask)
    edges = edge_mask.sum(axis=1)
    frontier = edge_mask[:, graph.e_split:].sum(axis=1)
    send = np.asarray(graph.halo_send_mask).sum(axis=(0, 2))
    recv = (np.asarray(graph.halo_recv_idx) < graph.n_cap).sum(axis=(0, 2))
    stats = {
        "n_atoms": int(graph.n_total_nodes),
        "num_partitions": graph.num_partitions,
        "n_cap": graph.n_cap,
        "e_cap": graph.e_cap,
        "b_cap": graph.b_cap,
        "n_nodes_per_part": [int(x) for x in nodes],
        "n_edges_per_part": [int(x) for x in edges],
        "node_occupancy": float(nodes.max() / graph.n_cap) if graph.n_cap else 0.0,
        "edge_occupancy": float(edges.max() / graph.e_cap) if graph.e_cap else 0.0,
        # fraction of real edges that must wait on the halo exchange (the
        # non-overlappable tail of each layer); worst partition
        "frontier_edge_frac": float(
            (frontier / np.maximum(edges, 1)).max()) if len(edges) else 0.0,
        "halo_send_per_part": [int(x) for x in send],
        "halo_recv_per_part": [int(x) for x in recv],
        # 2-D mesh placement of the leading partition axis (legacy 1-D
        # graphs report (1, P) — batch axis unused)
        "spatial_parts": graph.spatial_size,
        "batch_parts": graph.batch_parts,
        "mesh_shape": [graph.batch_parts, graph.spatial_size],
    }
    if graph.has_bond_graph:
        bsend = np.asarray(graph.bond_halo_send_mask).sum(axis=(0, 2))
        stats["bond_halo_send_per_part"] = [int(x) for x in bsend]
        # real rows of the bond graph beside n_edges_per_part: the bond
        # nodes a partition computes (those mapped from one of its edges;
        # halo bond rows arrive by exchange) and its live lines
        lines = np.asarray(graph.line_count).sum(axis=1)
        stats["n_bonds_per_part"] = [
            int(x) for x in np.asarray(graph.bond_map_mask).sum(axis=1)]
        stats["n_lines_per_part"] = [int(x) for x in lines]
        # total live line-graph edges (angle terms) — the FLOP model's
        # third graph dimension
        stats["n_lines"] = int(lines.sum())
        stats.update(line_table_stats(graph))
    return stats
