"""Device-resident neighbor search: on-device cell lists under static caps.

The host FPIS pipeline (``neighbors/native.py`` -> ``partition``) is exact
but synchronous: every Verlet-skin invalidation stops the device, syncs
positions to the host, rebuilds the graph in C/NumPy and re-uploads the
packed arrays. This module removes that last host-bound segment of the MD/
relax hot path (TorchSim's observation, arXiv:2508.06628; same conclusion
for inference kernels in arXiv:2504.16068): the neighbor graph is rebuilt
ENTIRELY on the accelerator, under fixed, sticky capacities, so the rebuild
can live inside a jitted ``lax.while_loop`` and a trajectory never leaves
the chip.

Two kernels share the emission/compaction contract:

- ``cell_list_neighbors`` — single-structure linked-cell search. Atoms are
  binned into a static cell grid (an on-device ``argsort`` + ``searchsorted``
  builds the (ncell, cell_cap) table); candidate pairs come from a static
  stencil of neighboring cells with periodic wrap counts supplying the
  image offsets. The stencil generalizes the classic 27-cell case: when the
  box is smaller than the cutoff the per-axis reach grows past one wrap, so
  multi-image pairs (an atom neighboring its own periodic images) are
  enumerated exactly — parity with ``neighbor_list_numpy`` is pair-set
  EXACT, not approximate (tests/test_device_neighbors.py).
- ``packed_neighbors`` — block-diagonal multi-structure search for graphs
  built by ``partition.pack_structures``. The batched regime is many SMALL
  structures, so each block runs a dense all-pairs x images check (vmapped
  over the batch, trivially sized) and image offsets are baked to Cartesian
  with each structure's own cell, matching the packed layout.

Emission contract (identical to the host builders, so the arrays can be
swapped into an existing ``PartitionedGraph`` without re-tracing):

- edges are enumerated CENTER-major, and the center plays the ``dst`` role
  (owner-computes: messages aggregate onto dst), so the compacted
  ``edge_dst`` is globally nondecreasing — ``indices_are_sorted=True``
  segment sums stay on the fast path;
- compaction is a cumsum counting sort (order-preserving) into the fixed
  ``e_cap`` slots; a count past ``e_cap`` (or a cell past ``cell_cap``)
  raises the OVERFLOW flag instead of silently dropping pairs — callers
  fall back to the host rebuild with grown caps;
- offsets are integer periodic-image vectors relative to the UNWRAPPED
  input frame (``neighbor position = positions[src] + off @ lattice`` seen
  from the dst row), exactly the ``python_ref`` convention.

Capacities are static trace constants: same caps => same shapes => zero
recompiles across rebuilds. ``DISTMLIP_DEVICE_REBUILD=0`` disables every
device-rebuild consumer at once (forcing the host FPIS path).
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

import numpy as np

from .. import geometry
from ..geometry import COORD_PRECISION
from .python_ref import NUMERICAL_TOL, _image_ranges


def device_rebuild_enabled() -> bool:
    """Process-wide kill switch: DISTMLIP_DEVICE_REBUILD=0 forces the host
    FPIS rebuild everywhere (DeviceMD, DistPotential, BatchedPotential)."""
    return os.environ.get("DISTMLIP_DEVICE_REBUILD", "1") != "0"


# ---------------------------------------------------------------------------
# Single-structure cell list
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CellListStatic:
    """Hashable static half of a cell-list spec (jit static argument).

    Every field feeds a traced shape or a trace-time constant; two specs
    with equal statics (and equal-shaped arrays) share one executable.
    """

    grid: tuple          # (g0, g1, g2) cells per axis
    n_stencil: int       # stencil offsets (shape of the arrays' stencil)
    cell_cap: int        # max atoms per cell before overflow
    n_atoms: int         # real atoms (rows [0, n_atoms) of the padded array)
    n_cap: int           # padded node rows
    e_cap: int           # padded edge slots
    pbc: tuple           # (bool, bool, bool)
    r: float             # build cutoff (cutoff + skin)

    @property
    def ncell(self) -> int:
        return int(self.grid[0] * self.grid[1] * self.grid[2])


def estimate_cell_capacity(occupancy: int, floor: int = 4,
                           slack: float = 1.5) -> int:
    """Sticky-style cell capacity from an observed max occupancy: slack
    headroom so atoms migrating between cells mid-trajectory don't
    immediately overflow, floored so near-empty builds keep room."""
    return max(int(math.ceil(occupancy * slack)) + 1, int(floor))


def grow_caps_after_overflow(caps, edges_needed: int, e_cap: int,
                             cell_cap: int, cell_cap_floor: int) -> int:
    """Shared overflow-growth policy for every device-rebuild consumer.

    The kernel reports the TRUE edge need even past ``e_cap``, so an edge
    bust grows the sticky edge bucket directly; otherwise the bust was the
    cell table (whose edge count is undercounted, so the two cases are
    mutually exclusive as observed) and the cell capacity doubles. Returns
    the (possibly grown) cell-cap floor; ``caps`` is grown in place.
    """
    if edges_needed > e_cap:
        caps.get("edges", int(edges_needed))
        return int(cell_cap_floor)
    return max(int(cell_cap_floor), 2 * int(cell_cap))


def build_cell_list_spec(
    lattice,
    pbc,
    r: float,
    n_atoms: int,
    n_cap: int,
    e_cap: int,
    positions=None,
    cell_cap: int | None = None,
    min_cell_cap: int = 4,
    dtype=np.float32,
):
    """Host-side spec construction: grid dims, stencil, capacities.

    Grid: ``g_a = max(1, floor(d_a / r))`` cells along each PERIODIC axis
    (``d_a`` = plane spacing, skew-safe), one cell along non-periodic axes
    (atoms are unbounded there — the distance filter does the work). The
    stencil reach per periodic axis is ``floor(r / w_a) + 1`` cells
    (``w_a = d_a / g_a``): two points whose extended cells differ by D
    along axis a are at least ``(D - 1) * w_a`` apart, so the reach covers
    every pair within ``r`` — including multi-wrap (multi-image) pairs when
    the box is smaller than the cutoff.

    ``cell_cap`` defaults to the observed max occupancy of ``positions``
    (plus slack) — pass the previous spec's grown value after an overflow.
    Returns ``(static, arrays)`` for the jitted kernel; ``arrays`` holds the
    lattice, its inverse, and the stencil as plain numpy (device_put'd on
    first use).
    """
    lattice = np.asarray(lattice, dtype=np.float64)
    pbc_mask = np.asarray(pbc, dtype=bool)
    d = geometry.plane_spacings(lattice)
    grid = np.where(pbc_mask, np.maximum(
        1, np.floor(d / max(r, 1e-6)).astype(np.int64)), 1)
    w = d / grid
    reach = np.where(pbc_mask,
                     np.floor((r + NUMERICAL_TOL) / w).astype(np.int64) + 1,
                     0)
    ax = [np.arange(-k, k + 1) for k in reach]
    stencil = np.stack(
        np.meshgrid(*ax, indexing="ij"), axis=-1).reshape(-1, 3)
    if cell_cap is None:
        occ = 0
        if positions is not None and n_atoms > 0:
            wrapped, _ = geometry.wrap_positions(
                np.asarray(positions, dtype=np.float64)[:n_atoms],
                lattice, pbc_mask)
            frac = geometry.cart_to_frac(wrapped, lattice)
            c = np.clip((frac * grid).astype(np.int64), 0, grid - 1)
            flat = (c[:, 0] * grid[1] + c[:, 1]) * grid[2] + c[:, 2]
            occ = int(np.bincount(flat).max())
        else:
            occ = n_atoms
        cell_cap = estimate_cell_capacity(occ, floor=min_cell_cap)
    static = CellListStatic(
        grid=tuple(int(g) for g in grid),
        n_stencil=int(len(stencil)),
        cell_cap=int(cell_cap),
        n_atoms=int(n_atoms),
        n_cap=int(n_cap),
        e_cap=int(e_cap),
        pbc=tuple(bool(b) for b in pbc_mask),
        r=float(r),
    )
    arrays = {
        "lattice": lattice.astype(dtype),
        "inv_lattice": np.linalg.inv(lattice).astype(dtype),
        "stencil": stencil.astype(np.int32),
    }
    return static, arrays


def _wrap_device(positions, inv_lattice, pbc_mask):
    """(frac, shift, wrapped_frac) with wrapping only on periodic axes —
    the in-jit analogue of ``geometry.wrap_positions``."""
    import jax.numpy as jnp

    frac = jnp.matmul(positions, inv_lattice, precision=COORD_PRECISION)
    shift = jnp.where(pbc_mask, jnp.floor(frac), 0.0)
    return frac, shift.astype(jnp.int32), frac - shift


def _compact_edges(src, dst, off, valid, e_cap: int):
    """Order-preserving cumsum compaction of flat candidate arrays into
    ``e_cap`` slots. Returns (src, dst, off, n_edges, overflow_edges);
    entries past ``e_cap`` are dropped and flagged, never silently lost
    within the count."""
    import jax.numpy as jnp

    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    n_edges = jnp.sum(valid.astype(jnp.int32))
    slot = jnp.where(valid & (pos < e_cap), pos, e_cap)
    src_o = jnp.zeros((e_cap,), jnp.int32).at[slot].set(
        src.astype(jnp.int32), mode="drop")
    dst_o = jnp.zeros((e_cap,), jnp.int32).at[slot].set(
        dst.astype(jnp.int32), mode="drop")
    off_o = jnp.zeros((e_cap, 3), off.dtype).at[slot].set(off, mode="drop")
    return src_o, dst_o, off_o, n_edges, n_edges > e_cap


def cell_list_neighbors(static: CellListStatic, arrays, positions):
    """Traceable single-structure neighbor search (call inside jit/scan/
    while_loop; use :func:`device_neighbor_list` from host code).

    ``positions``: (n_cap, 3) UNWRAPPED input-frame coordinates (padded
    rows ignored). Returns ``(src, dst, off, n_edges, overflow)`` with
    (e_cap,)-shaped edge arrays: ``dst`` is the center atom and is
    nondecreasing over the real prefix; ``off`` is the int32 image offset
    of ``src`` relative to the input frame; ``overflow`` flags a cell or
    edge capacity bust (results must then be discarded by the caller).
    """
    import jax.numpy as jnp

    st = static
    dtype = positions.dtype
    g = jnp.asarray(st.grid, dtype=jnp.int32)
    gf = jnp.asarray(st.grid, dtype=dtype)
    pbc_mask = jnp.asarray(st.pbc)
    lat = jnp.asarray(arrays["lattice"], dtype=dtype)
    inv = jnp.asarray(arrays["inv_lattice"], dtype=dtype)
    stencil = jnp.asarray(arrays["stencil"], dtype=jnp.int32)
    ncell, cap = st.ncell, st.cell_cap
    valid_atom = jnp.arange(st.n_cap) < st.n_atoms

    _, shift, w = _wrap_device(positions, inv, pbc_mask)
    c = jnp.clip(jnp.floor(w * gf).astype(jnp.int32), 0, g - 1)
    flat = (c[:, 0] * g[1] + c[:, 1]) * g[2] + c[:, 2]
    ids = jnp.where(valid_atom, flat, ncell)

    # --- bin via on-device sort: (ncell, cap) table of atom indices ---
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(ncell + 1))
    rank = jnp.arange(st.n_cap, dtype=jnp.int32) - starts[sorted_ids].astype(
        jnp.int32)
    in_cell = sorted_ids < ncell
    overflow_cells = jnp.any(in_cell & (rank >= cap))
    slot = jnp.where(in_cell & (rank < cap),
                     sorted_ids.astype(jnp.int32) * cap + rank,
                     ncell * cap)
    table = jnp.full((ncell * cap,), st.n_cap, jnp.int32).at[slot].set(
        order.astype(jnp.int32), mode="drop").reshape(ncell, cap)

    # --- stencil enumeration: extended cells -> (neighbor cell, wrap) ---
    tc = c[:, None, :] + stencil[None, :, :]              # (n_cap, S, 3)
    wrap = jnp.floor_divide(tc, g)                        # image count
    cin = tc - wrap * g
    ok_st = jnp.all(pbc_mask | (wrap == 0), axis=-1)      # (n_cap, S)
    flat_t = (cin[..., 0] * g[1] + cin[..., 1]) * g[2] + cin[..., 2]
    cand = table[flat_t]                                  # (n_cap, S, cap)
    valid_j = cand < st.n_cap
    jc = jnp.minimum(cand, st.n_cap - 1)

    # --- distance filter against the center's wrapped position ---
    wpos = jnp.matmul(w, lat, precision=COORD_PRECISION)  # (n_cap, 3)
    img_cart = jnp.matmul(wrap.astype(dtype), lat,        # (n_cap, S, 3)
                          precision=COORD_PRECISION)
    diff = wpos[jc] + img_cart[:, :, None, :] - wpos[:, None, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)                    # (n_cap, S, cap)
    r2 = jnp.asarray((st.r + NUMERICAL_TOL) ** 2, dtype=dtype)
    tiny = jnp.asarray(NUMERICAL_TOL ** 2, dtype=dtype)
    valid = (valid_j & ok_st[:, :, None] & valid_atom[:, None, None]
             & (d2 < r2) & (d2 > tiny))

    # --- emit: center = dst (sorted by construction), neighbor = src ---
    # the ref edge (center j, neighbor c at image -wrap) has
    # off = -wrap + shift[src] - shift[dst] in the unwrapped input frame
    off = (-wrap[:, :, None, :] + shift[jc]
           - shift[:, None, None, :]).astype(jnp.int32)   # (n_cap,S,cap,3)
    dst = jnp.broadcast_to(
        jnp.arange(st.n_cap, dtype=jnp.int32)[:, None, None], valid.shape)
    src, dst, off, n_edges, overflow_edges = _compact_edges(
        cand.reshape(-1), dst.reshape(-1), off.reshape(-1, 3),
        valid.reshape(-1), st.e_cap)
    return src, dst, off, n_edges, overflow_cells | overflow_edges


_cell_list_jitted = None


def device_neighbor_list(static: CellListStatic, arrays, positions):
    """Jitted host entry for the single-structure kernel (tests, the
    rebuilds/sec microbench, DistPotential's refresh). One executable per
    distinct ``static`` + positions shape."""
    global _cell_list_jitted
    if _cell_list_jitted is None:
        import jax

        _cell_list_jitted = jax.jit(cell_list_neighbors, static_argnums=0)
    return _cell_list_jitted(static, _as_device_arrays(arrays), positions)


def _as_device_arrays(arrays):
    """Spec arrays as device arrays. jnp.asarray is a no-op for arrays
    already on device, so callers that convert once at spec-install time
    (the hot paths) pay nothing here on subsequent calls."""
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in arrays.items()}


# ---------------------------------------------------------------------------
# Packed (block-diagonal) batch
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PackedStatic:
    """Static half of a packed-batch spec (jit static argument)."""

    n_struct: int        # real structures
    n_max: int           # max atoms over structures
    m_max: int           # max periodic images over structures
    n_cap: int           # packed node rows
    e_cap: int           # packed edge slots
    r: float             # build cutoff (cutoff + skin)


def build_packed_spec(
    cells,
    pbcs,
    n_atoms,
    node_offsets,
    r: float,
    n_cap: int,
    e_cap: int,
    dtype=np.float32,
):
    """Spec for refreshing a block-diagonally packed graph on device.

    Per-structure cells/pbc/image sets are padded to the batch maxima; the
    kernel is a dense all-pairs x images check per block (the packed regime
    is many SMALL structures — TorchSim batching, arXiv:2508.06628), so no
    cell table or cell capacity is involved. Returns ``(static, arrays)``.
    """
    B = len(n_atoms)
    n_max = int(max(int(n) for n in n_atoms))
    imgs_list = []
    for cell, pbc in zip(cells, pbcs):
        n = _image_ranges(np.asarray(cell, dtype=np.float64), pbc, r)
        ax = [np.arange(-k, k + 1) for k in n]
        imgs_list.append(np.stack(
            np.meshgrid(*ax, indexing="ij"), axis=-1).reshape(-1, 3))
    m_max = max(len(m) for m in imgs_list)
    imgs = np.zeros((B, m_max, 3), dtype=np.int32)
    img_mask = np.zeros((B, m_max), dtype=bool)
    for b, m in enumerate(imgs_list):
        imgs[b, : len(m)] = m
        img_mask[b, : len(m)] = True
    gather_idx = np.zeros((B, n_max), dtype=np.int32)
    atom_mask = np.zeros((B, n_max), dtype=bool)
    for b, n in enumerate(n_atoms):
        n = int(n)
        gather_idx[b, :n] = np.arange(n) + int(node_offsets[b])
        atom_mask[b, :n] = True
    cells_np = np.stack([np.asarray(c, dtype=np.float64) for c in cells])
    static = PackedStatic(
        n_struct=B, n_max=n_max, m_max=m_max,
        n_cap=int(n_cap), e_cap=int(e_cap), r=float(r),
    )
    arrays = {
        "gather_idx": gather_idx,
        "atom_mask": atom_mask,
        "cells": cells_np.astype(dtype),
        "inv_cells": np.stack(
            [np.linalg.inv(c) for c in cells_np]).astype(dtype),
        "pbc": np.stack([np.asarray(p, dtype=bool) for p in pbcs]),
        "imgs": imgs,
        "img_mask": img_mask,
    }
    return static, arrays


def packed_neighbors(static: PackedStatic, arrays, positions):
    """Traceable packed-batch neighbor search over a (n_cap, 3) packed
    position array (input frame). Returns ``(src, dst, off_cart, n_edges,
    overflow)``: packed-row edge indices, CARTESIAN offsets (each block
    baked with its own cell, matching ``pack_structures``), nondecreasing
    ``dst`` (blocks are enumerated in packing order, centers within)."""
    import jax.numpy as jnp

    st = static
    dtype = positions.dtype
    gi = jnp.asarray(arrays["gather_idx"])
    am = jnp.asarray(arrays["atom_mask"])
    cells = jnp.asarray(arrays["cells"], dtype=dtype)
    invs = jnp.asarray(arrays["inv_cells"], dtype=dtype)
    pbc = jnp.asarray(arrays["pbc"])
    imgs = jnp.asarray(arrays["imgs"])
    img_mask = jnp.asarray(arrays["img_mask"])

    p = positions[gi]                                     # (B, n_max, 3)
    frac = jnp.einsum("bki,bij->bkj", p, invs, precision=COORD_PRECISION)
    shift = jnp.where(pbc[:, None, :], jnp.floor(frac), 0.0)
    w = frac - shift
    shift = shift.astype(jnp.int32)
    wc = jnp.einsum("bki,bij->bkj", w, cells,             # wrapped cartesian
                    precision=COORD_PRECISION)
    imgc = jnp.einsum("bmi,bij->bmj", imgs.astype(dtype), cells,
                      precision=COORD_PRECISION)

    # diff[b, k(center), j(neighbor), m] = wc[b,j] + imgc[b,m] - wc[b,k]
    diff = (wc[:, None, :, None, :] + imgc[:, None, None, :, :]
            - wc[:, :, None, None, :])
    d2 = jnp.sum(diff * diff, axis=-1)                    # (B, k, j, m)
    r2 = jnp.asarray((st.r + NUMERICAL_TOL) ** 2, dtype=dtype)
    tiny = jnp.asarray(NUMERICAL_TOL ** 2, dtype=dtype)
    valid = (am[:, :, None, None] & am[:, None, :, None]
             & img_mask[:, None, None, :] & (d2 < r2) & (d2 > tiny))

    off_int = (-imgs[:, None, None, :, :]
               + shift[:, None, :, None, :]
               - shift[:, :, None, None, :])              # (B, k, j, m, 3)
    off_cart = jnp.einsum("bkjmi,bin->bkjmn", off_int.astype(dtype), cells,
                          precision=COORD_PRECISION)
    src = jnp.broadcast_to(gi[:, None, :, None], valid.shape)
    dst = jnp.broadcast_to(gi[:, :, None, None], valid.shape)
    return _compact_edges(
        src.reshape(-1), dst.reshape(-1), off_cart.reshape(-1, 3),
        valid.reshape(-1), st.e_cap)


_packed_jitted = None


def device_packed_neighbor_list(static: PackedStatic, arrays, positions):
    """Jitted host entry for the packed kernel."""
    global _packed_jitted
    if _packed_jitted is None:
        import jax

        _packed_jitted = jax.jit(packed_neighbors, static_argnums=0)
    return _packed_jitted(static, _as_device_arrays(arrays), positions)
