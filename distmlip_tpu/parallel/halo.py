"""Halo exchange and the LocalGraph shard view.

The halo exchange is the TPU-native replacement of the reference's in-place
cross-GPU slice copies (reference dist.py:323-358): inside ``shard_map``,
each partition gathers its "to_q" rows into a fixed-capacity payload, rotates
it around the ring with ``jax.lax.ppermute`` (ICI neighbor traffic for slab
decompositions), and scatters the received payload into its "from" slots.
Padded recv indices point one past the array end, so XLA's
drop-out-of-bounds scatter discards them. ``jax.grad`` transposes the
ppermute automatically, which is exactly the reverse force flow the reference
gets from torch autograd through device copies (reference pes.py:121-124).

One exchange (``_exchange_round``): ONE ``ppermute`` per ring shift per sync
point, no matter how many feature arrays are refreshed together (atom + bond
features ride the same collective), and all shifts' received rows land in
one scatter: fewer, larger collectives expose the latency XLA's
async-collective scheduler can hide behind interior edge compute (see
``LocalGraph.overlapped_edge_sum``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..geometry import COORD_PRECISION
from ..kernels.dispatch import (Gather, fused_edge_aggregate,
                                fused_segment_sum, fused_segment_sum_into,
                                segment_sum_carry, segment_sum_result)
from ..ops.chunk import chunk_layout, chunked, scan_accumulate, take_rows
from ..ops.segment import slab_repeat, slab_sum
from ..telemetry import scope


def _exchange_round(groups, shifts, axis_name):
    """One exchange round: ONE ppermute per ring shift for ALL groups.

    ``groups``: list of ``(feats, send_idx, send_mask, recv_idx)`` with
    per-shift tables shaped (S, H). Every group's masked payload is
    flattened to (S, H*F) and concatenated into one (S, sum H*F) buffer —
    mixed feature widths cost nothing (flat concat, no padding) and mixed
    dtypes are promoted to the widest (bf16 rides fp32 losslessly) and cast
    back on receive. Returns the updated feats list.

    Valid because send rows are owned locals and recv slots are halo
    locals: no scatter feeds a later gather within the round.
    """
    if not shifts or axis_name is None:
        return [g[0] for g in groups]
    n_dev = lax.axis_size(axis_name)
    S = len(shifts)
    dtype = jnp.result_type(*[g[0].dtype for g in groups])
    flats, shapes = [], []
    for feats, send_idx, send_mask, _ in groups:
        payload = feats[send_idx]                      # (S, H, *F)
        m = send_mask.astype(feats.dtype).reshape(
            send_mask.shape + (1,) * (feats.ndim - 1))
        payload = payload * m
        shapes.append(payload.shape)
        flats.append(payload.astype(dtype).reshape(S, -1))
    with scope("halo/coalesce"):
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
    received = []
    for si, shift in enumerate(shifts):
        perm = [(p, (p + shift) % n_dev) for p in range(n_dev)]
        with scope(f"halo/shift{shift}"), scope("ppermute"):
            received.append(lax.ppermute(buf[si], axis_name, perm))
    recv = received[0][None] if S == 1 else jnp.stack(received)  # (S, total)
    out, off = [], 0
    for (feats, _, _, recv_idx), shp in zip(groups, shapes):
        sz = 1
        for d in shp[1:]:
            sz *= int(d)
        seg = recv[:, off:off + sz].reshape(shp).astype(feats.dtype)
        off += sz
        # one scatter across all shifts: from-sections are disjoint per
        # source partition; padded slots point past the array end (dropped)
        rows = seg.reshape((-1,) + shp[2:])
        out.append(feats.at[recv_idx.reshape(-1)].set(rows, mode="drop"))
    return out


@dataclass
class LocalGraph:
    """Per-shard view of a PartitionedGraph (leading P axis squeezed away).

    Passed to model functions inside ``shard_map``; carries the local edge
    lists, masks, halo tables, and the collective axis name. Models call the
    methods below instead of touching collectives directly.

    Edge layout contract: ``edge_dst`` is nondecreasing within each of the
    interior ``[0, e_split)`` and frontier ``[e_split, e_cap)`` segments
    (``indices_are_sorted`` segment sums per segment — use
    ``aggregate_edges``/``overlapped_edge_sum``/``scan_edges``, never a raw
    full-array sorted segment sum when ``has_frontier_split``). Interior
    edges read only owned rows; frontier edges read halo src rows.

    Line layout contract: the lines are a slot-major in-line table over the
    ``b_cap`` bond rows (``partition/graph.line_table``): entry
    ``k * b_cap + b`` of ``line_src`` / ``line_mask`` (and of every per-line
    array a model makes) is the k-th line INTO bond row ``b``, with
    ``line_slots`` slabs. A line's destination bond and its centre atom are
    therefore its position: :meth:`at_line_dst` (a repeat) and
    :meth:`sum_to_line_dst` (a sum over the slabs) are the only code that
    knows the order, each the other's transpose; the centre is
    ``bond_center`` (``(b_cap,)``, one atom a bond row) read through
    ``at_line_dst``. Only ``line_src`` is an index array. Pad slots are
    masked and in bounds; halo and padded bond rows have no live slot.
    The bonds may be short bonds under a cutoff of their own (CHGNet's
    3.0 A of a 6.0 A graph, 11 slabs) or every edge of the graph
    (DimeNet++, ``bond_cutoff == cutoff``: K is then the largest in-degree
    less one, 51 slabs in fcc at 5.5 A): :meth:`in_line_sum` scans such a
    table one slab at a time.
    """

    axis_name: str | None
    shifts: tuple
    n_cap: int
    e_cap: int
    b_cap: int
    species: Any
    node_mask: Any
    owned_mask: Any
    edge_src: Any
    edge_dst: Any       # CONTRACT: nondecreasing within each edge segment
    edge_offset: Any    # (see class docstring) — established by
    edge_mask: Any      # build_partitioned_graph
    halo_send_idx: Any
    halo_send_mask: Any
    halo_recv_idx: Any
    lattice: Any
    # bond graph
    has_bond_graph: bool = False
    line_src: Any = None      # (line_slots * b_cap,) slot-major table
    line_mask: Any = None
    bond_center: Any = None   # (b_cap,)
    bond_map_edge: Any = None
    bond_map_bond: Any = None
    bond_map_mask: Any = None
    bond_halo_send_idx: Any = None
    bond_halo_send_mask: Any = None
    bond_halo_recv_idx: Any = None
    system: Any = None  # replicated per-system scalars (charge/spin/dataset)
    # interior/frontier edge split (PartitionedGraph.e_split); < 0 or
    # == e_cap means unsplit
    e_split: int = -1
    # batched multi-structure packing (PartitionedGraph.batch_size /
    # struct_id); 0 = unbatched. Models never need these — the per-
    # structure readout lives in the batched runtime — but they ride the
    # LocalGraph so the runtime sees them inside the traced function.
    batch_size: int = 0
    struct_id: Any = None
    # Pallas kernel routing for the aggregation helpers below and the
    # models' own dispatch calls: None = env/backend default, False =
    # force the pure-XLA path, "interpret" = interpreter-mode kernels
    # (kernels/dispatch.resolve_kernel_mode)
    kernels: Any = None
    # whether fused-kernel custom VJPs propagate gradients into model
    # parameters (edge-MLP weights, SO(2) stacks). Training programs need
    # True; force/stress programs pass False so the kernel path emits no
    # weight-cotangent work or replicated-input psums (see
    # kernels/dispatch.fused_edge_aggregate)
    kernels_diff_params: bool = True

    @property
    def has_frontier_split(self) -> bool:
        return 0 <= self.e_split < self.e_cap

    @property
    def line_slots(self) -> int:
        """Slabs of the in-line table: the lines a bond row has room for."""
        return self.line_src.shape[0] // self.b_cap if self.b_cap else 0

    def _node_tables(self):
        return (self.halo_send_idx, self.halo_send_mask, self.halo_recv_idx)

    def _bond_tables(self):
        return (self.bond_halo_send_idx, self.bond_halo_send_mask,
                self.bond_halo_recv_idx)

    # ---- collectives ----
    def halo_exchange(self, feats):
        """Refresh halo (from-section) rows of a node feature array."""
        with scope("halo_exchange"):
            return _exchange_round([(feats,) + self._node_tables()],
                                    self.shifts, self.axis_name)[0]

    def bond_halo_exchange(self, feats):
        """Refresh halo rows of a bond-node feature array."""
        if not self.has_bond_graph:
            return feats
        with scope("bond_halo_exchange"):
            return _exchange_round([(feats,) + self._bond_tables()],
                                    self.shifts, self.axis_name)[0]

    def exchange_all(self, node_feats=(), bond_feats=()):
        """Refresh several feature arrays at one sync point.

        Every array rides the SAME ppermute (one collective per ring shift
        total — CHGNet's per-block atom+bond refresh pays 1 instead of 2).
        Returns ``(node_feats_out, bond_feats_out)`` tuples in input order.
        Bond arrays pass through untouched when the graph has no bond
        graph.
        """
        node_feats, bond_feats = tuple(node_feats), tuple(bond_feats)
        groups = [(f,) + self._node_tables() for f in node_feats]
        if self.has_bond_graph:
            groups += [(f,) + self._bond_tables() for f in bond_feats]
        if self.axis_name is None or not self.shifts or not groups:
            return node_feats, bond_feats
        with scope("halo_exchange_all"):
            out = _exchange_round(groups, self.shifts, self.axis_name)
        n = len(node_feats)
        return (tuple(out[:n]),
                tuple(out[n:]) if self.has_bond_graph else bond_feats)

    def psum(self, x):
        if self.axis_name is None:
            return x
        return lax.psum(x, self.axis_name)

    # ---- geometry ----
    def edge_vectors(self, positions, lattice=None):
        """(E_cap, 3) displacement vectors dst - src + offsets @ lattice."""
        lat = self.lattice if lattice is None else lattice
        disp = positions[self.edge_dst] - positions[self.edge_src]
        return disp + jnp.matmul(self.edge_offset.astype(positions.dtype),
                                 lat, precision=COORD_PRECISION)

    # ---- edge aggregation (interior/frontier aware) ----
    def aggregate_edges(self, data, mask=None):
        """Segment-sum per-edge rows onto their dst nodes ((n_cap, ...)).

        Honors the interior/frontier layout: each segment is dst-sorted,
        the concatenation is NOT — so the sorted fast path runs per
        segment. This is the drop-in replacement for the historical
        full-array ``masked_segment_sum(..., indices_are_sorted=True)``.
        Routes through the kernel dispatcher: on the Pallas path the
        masked scatter runs as the dst-tiled fused kernel.
        """
        if not self.has_frontier_split:
            return fused_segment_sum(data, self.edge_dst, self.n_cap, mask,
                                     indices_are_sorted=True,
                                     kernels=self.kernels)
        s = self.e_split
        with scope("edge_aggregate"):  # the slices and the add, too
            out = fused_segment_sum(
                data[:s], self.edge_dst[:s], self.n_cap,
                None if mask is None else mask[:s], indices_are_sorted=True,
                kernels=self.kernels)
            return out + fused_segment_sum(
                data[s:], self.edge_dst[s:], self.n_cap,
                None if mask is None else mask[s:], indices_are_sorted=True,
                kernels=self.kernels)

    def aggregate_edge_messages(self, msg_fn, edge_inputs, mask=None):
        """Fused per-edge compute + dst aggregation ((n_cap, ...)).

        ``msg_fn(*rows) -> (E, ...)`` messages from per-edge inputs;
        ``edge_inputs`` may mix per-edge arrays with
        :class:`distmlip_tpu.kernels.Gather` markers (node-array rows
        gathered at per-edge indices). Honors the interior/frontier
        layout like :meth:`aggregate_edges`. On the Pallas path the
        gather, the message compute and the dst scatter fuse per dst
        tile and the ``(E, width)`` message tensor never materializes;
        the XLA path computes ``msg_fn`` on the full edge arrays and
        segment-sums with the sorted hint (the historical program).
        """
        if not self.has_frontier_split:
            return fused_edge_aggregate(
                msg_fn, edge_inputs, self.edge_dst, self.n_cap, mask,
                indices_are_sorted=True, kernels=self.kernels,
                diff_params=self.kernels_diff_params)
        out = None
        for sl in (slice(0, self.e_split), slice(self.e_split, None)):
            with scope("edge_aggregate"):  # the slices and the add, too
                sliced = [Gather(i.node, i.idx[sl]) if isinstance(i, Gather)
                          else i[sl] for i in edge_inputs]
                part = fused_edge_aggregate(
                    msg_fn, sliced, self.edge_dst[sl], self.n_cap,
                    None if mask is None else mask[sl],
                    indices_are_sorted=True, kernels=self.kernels,
                    diff_params=self.kernels_diff_params)
                out = part if out is None else out + part
        return out

    def edge_chunks(self, chunk: int, *per_edge):
        """Per-edge rows in chunk order, for :meth:`scan_edges`.

        Returns the ``(K, chunk, ...)`` tuple ``(src, dst, mask, *rows)``:
        ``edge_src``, ``edge_dst``, ``edge_mask`` and each ``per_edge``
        array laid out by ``ops/chunk.take_rows`` — static slices of the
        one or two dst-sorted segments, each padded to a chunk multiple
        with copies of its last row, so no chunk straddles the
        interior/frontier boundary and every chunk's dst stays
        nondecreasing. ``mask`` is ``edge_mask`` with the pad rows cut
        out. ``chunk <= 0``: one chunk per segment. Lay rows out ONCE for
        every scan that reads them: the cotangents of all of them pass
        through the layout's transpose (copies) once.
        """
        e_split = self.e_split if self.has_frontier_split else None
        _, row_valid, K, chunk = chunk_layout(
            self.edge_src.shape[0], chunk, e_split)
        take = lambda x: chunked(take_rows(x, chunk, e_split), K, chunk)
        with scope("edge_gather"):
            return (
                take(self.edge_src),
                take(self.edge_dst),
                take(self.edge_mask)
                & chunked(jnp.asarray(row_valid), K, chunk),
                *[take(x) for x in per_edge],
            )

    def scan_edges(self, per_chunk, edge_xs, out_shape, dtype, *, remat):
        """Chunked edge sum ((n_cap, *out_shape)): the messages
        ``per_chunk(src, dst, mask, *rows) -> (chunk, *out_shape)`` of each
        chunk of ``edge_xs`` (:meth:`edge_chunks`), segment-summed onto
        their dst nodes and accumulated over the chunks, so per-edge
        memory is O(chunk). Each chunk's dst is sorted by the layout's
        construction, so the sum keeps the ``indices_are_sorted`` fast
        path and on TPU the dst-tiled Pallas scatter adds it into the
        carried accumulator in place, over the chunk's own dst tiles
        (``kernels/dispatch.fused_segment_sum_into``); elsewhere it is
        ``acc + masked_segment_sum(...)``. ``remat`` (bool or policy name,
        ``ops/chunk.remat_wrap``) checkpoints the chunk body.
        """
        def body(acc, xs):
            srcc, dstc, maskc, *rows = xs
            msg = per_chunk(srcc, dstc, maskc, *rows)
            return fused_segment_sum_into(
                acc, msg, dstc, maskc, kernels=self.kernels), None

        # the scan's own slicing of the chunked rows (and, transposed, the
        # stacking of their cotangents) continues the layout's data path
        with scope("edge_gather"):
            acc0 = segment_sum_carry(self.n_cap, out_shape, dtype,
                                     self.kernels)
            return segment_sum_result(
                scan_accumulate(body, acc0, edge_xs, remat=remat))

    def overlapped_edge_sum(self, msg_fn, v_pre, v_post, edge_data=(),
                            mask=None):
        """Per-edge messages summed to dst with interior/frontier split
        scheduling.

        ``v_post = halo_exchange(v_pre)`` is the freshly exchanged node
        array. Interior edges gather src AND dst from ``v_pre`` (identical
        rows — both endpoints are owned — but data-independent of the
        in-flight ppermute), so XLA's async-collective scheduler can run
        their gathers, GEMMs and segment sum while the exchange is on the
        wire; frontier edges run on ``v_post`` after it lands.

        ``msg_fn(v_src, v_dst, *edge_slices) -> (rows, ...)`` is invoked
        once per segment; ``edge_data`` arrays are sliced alongside.
        """
        with scope("overlapped_edge_sum"):
            if not self.has_frontier_split:
                return fused_edge_aggregate(
                    msg_fn,
                    [Gather(v_post, self.edge_src),
                     Gather(v_post, self.edge_dst), *edge_data],
                    self.edge_dst, self.n_cap, mask,
                    indices_are_sorted=True, kernels=self.kernels,
                    diff_params=self.kernels_diff_params)
            s = self.e_split
            out = None
            for name, sl, v in (("interior", slice(0, s), v_pre),
                                ("frontier", slice(s, None), v_post)):
                with scope(f"edges/{name}"):
                    # dst rows are always owned: read them from v_pre in
                    # BOTH segments so only the frontier src gather waits
                    # on the collective
                    part = fused_edge_aggregate(
                        msg_fn,
                        [Gather(v, self.edge_src[sl]),
                         Gather(v_pre, self.edge_dst[sl]),
                         *[d[sl] for d in edge_data]],
                        self.edge_dst[sl], self.n_cap,
                        None if mask is None else mask[sl],
                        indices_are_sorted=True, kernels=self.kernels,
                        diff_params=self.kernels_diff_params)
                out = part if out is None else out + part
            return out

    # ---- the in-line table's order (see the class docstring) ----
    def at_line_dst(self, bond_rows):
        """Rows of a ``(b_cap, ...)`` bond array at every line's destination
        bond, ``(line_slots * b_cap, ...)``: the array once per slab, no
        gather. Transposes to :meth:`sum_to_line_dst`."""
        return slab_repeat(bond_rows, self.line_slots)

    def sum_to_line_dst(self, line_rows, mask=None):
        """Sum of a per-line array onto the lines' destination bonds,
        ``(b_cap, ...)``: a sum over the slabs, no scatter; ``mask`` zeroes
        rows first. Halo and padded bond rows read zero."""
        return slab_sum(line_rows, self.b_cap, mask)

    def in_line_sum(self, line_fn, src_rows, dst_rows, width: int):
        """Sum over every bond row's in-lines of ``line_fn(src, *dst)``,
        ``(b_cap, width)`` float32: ``src`` the rows of ``src_rows``
        (``(b_cap, F)``) at the lines' source bonds, ``dst`` the
        ``dst_rows`` (each ``(b_cap, ...)``) of their destination bonds,
        which within a slab are the rows themselves. One slab of the table
        a step of a scan with a ``(b_cap, width)`` carry: the only index
        traffic is the gather of ``src_rows`` (its transpose a scatter-add
        of one slab's rows), the sum onto the destinations an add, and no
        array over all slots but ``line_src`` / ``line_mask`` exists. The
        body is checkpointed (``ops/chunk.remat_wrap``), so the backward
        keeps a slab's rows for one slab only. Halo and padded bond rows
        read zero."""
        K, rows = self.line_slots, self.b_cap
        xs = (self.line_src.reshape(K, rows), self.line_mask.reshape(K, rows))

        def body(acc, slab):
            src, live = slab
            out = line_fn(src_rows[src], *dst_rows)
            return acc + jnp.where(live[:, None], out, 0).astype(acc.dtype), \
                None

        acc0 = jnp.zeros((rows, width), jnp.float32)
        return scan_accumulate(body, acc0, xs, remat=True)

    # ---- bond-graph index remaps (reference dist.py:635-702 analogue) ----
    def edge_to_bond(self, edge_feats, bond_feats):
        """Seed owned bond-node rows from their atom-graph edge features.

        ``bond_map_bond`` is ascending by construction (arange of owned
        bonds per structure, block offsets ascending in the packed case)
        and the mask sentinel ``b_cap`` exceeds every real id, so the
        scatter rides the sorted fast path (scatter_hints contract).
        """
        with scope("edge_to_bond"):
            vals = edge_feats[self.bond_map_edge]
            m = self.bond_map_mask
            vals = vals * m.astype(vals.dtype).reshape(
                m.shape + (1,) * (vals.ndim - 1))
            idx = jnp.where(m, self.bond_map_bond, self.b_cap)
            return bond_feats.at[idx].set(vals, mode="drop",
                                          indices_are_sorted=True)

    def bond_to_edge(self, bond_feats, edge_feats):
        """Write owned bond-node features back onto their edges.

        ``bond_map_edge`` is bond-node-ordered, NOT edge-ordered — the
        scatter is legitimately unsorted (audited; sorting would need a
        second, edge-ordered copy of the map pair in the graph layout).
        """
        with scope("bond_to_edge"):
            vals = bond_feats[self.bond_map_bond]
            m = self.bond_map_mask
            vals = vals * m.astype(vals.dtype).reshape(
                m.shape + (1,) * (vals.ndim - 1))
            idx = jnp.where(m, self.bond_map_edge, self.e_cap)
            # contract: allow(scatter_hints)
            return edge_feats.at[idx].set(vals, mode="drop")

    # ---- reductions ----
    def structure_sum(self, per_atom):
        """Per-structure sums of a per-atom quantity on a packed graph.

        Axis-scoped batched readout: one masked ``segment_sum`` onto the
        shard's ``batch_size`` structure slots (owned rows only — halo and
        padded rows carry the ``batch_size`` sentinel and drop), then a
        ``psum`` over the SPATIAL axis so every slab of a spatially
        partitioned structure contributes. The batch axis is never
        touched: batch rows hold disjoint structures, so their readout is
        pure concatenation (shard_map out_specs), not communication.
        Returns ``(batch_size,)`` in ``per_atom``'s dtype.
        """
        if self.struct_id is None or self.batch_size <= 0:
            raise ValueError(
                "structure_sum requires a packed graph (struct_id + "
                "batch_size); build it with pack_structures()")
        with scope("structure_sum"):
            e = jnp.where(self.owned_mask, per_atom.reshape(-1), 0)
            out = jax.ops.segment_sum(
                e, self.struct_id, num_segments=self.batch_size,
                indices_are_sorted=True)
            return self.psum(out)

    def owned_sum(self, per_atom):
        """Sum a per-atom quantity over owned nodes, reduced across the mesh."""
        with scope("owned_sum"):
            m = self.owned_mask.astype(per_atom.dtype)
            local = jnp.sum(
                per_atom * m.reshape(m.shape + (1,) * (per_atom.ndim - 1)))
            return self.psum(local)


def local_graph_from_stacked(
    g, axis_name: str | None, kernels=None,
    kernels_diff_params: bool = True,
) -> tuple[LocalGraph, Any]:
    """Build a LocalGraph from shard-local (1, ...) slices of a PartitionedGraph.

    Returns (local_graph, positions_local) where positions keep their leading
    1-axis squeezed. ``kernels`` is the Pallas-kernel routing flag the
    aggregation helpers dispatch on (None = env/backend default, False =
    pure XLA, "interpret" = the chip-free interpreter kernels);
    ``kernels_diff_params`` is whether kernel custom VJPs propagate into
    model weights (training True, force/stress programs False).
    """
    sq = lambda a: a[0] if a is not None and hasattr(a, "shape") and a.ndim >= 1 else a
    lg = LocalGraph(
        axis_name=axis_name,
        shifts=g.shifts,
        n_cap=g.n_cap,
        e_cap=g.e_cap,
        b_cap=g.b_cap,
        e_split=g.e_split,
        kernels=kernels,
        kernels_diff_params=kernels_diff_params,
        species=sq(g.species),
        node_mask=sq(g.node_mask),
        owned_mask=sq(g.owned_mask),
        edge_src=sq(g.edge_src),
        edge_dst=sq(g.edge_dst),
        edge_offset=sq(g.edge_offset),
        edge_mask=sq(g.edge_mask),
        halo_send_idx=g.halo_send_idx[:, 0],
        halo_send_mask=g.halo_send_mask[:, 0],
        halo_recv_idx=g.halo_recv_idx[:, 0],
        lattice=g.lattice,
        has_bond_graph=g.has_bond_graph,
        line_src=sq(g.line_src),
        line_mask=sq(g.line_mask),
        bond_center=sq(g.bond_center),
        bond_map_edge=sq(g.bond_map_edge),
        bond_map_bond=sq(g.bond_map_bond),
        bond_map_mask=sq(g.bond_map_mask),
        bond_halo_send_idx=g.bond_halo_send_idx[:, 0],
        bond_halo_send_mask=g.bond_halo_send_mask[:, 0],
        bond_halo_recv_idx=g.bond_halo_recv_idx[:, 0],
        system=g.system,
        batch_size=g.batch_size,
        struct_id=sq(g.struct_id),
    )
    return lg, sq(g.positions)
