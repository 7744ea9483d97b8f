"""Primitives of the chunked edge sum (``LocalGraph.edge_chunks`` /
``scan_edges``, parallel/halo.py).

Models bound per-edge memory by scanning over fixed-size edge chunks
(MACE's density projection, eSCN's rotate/SO(2) pipeline). Per-edge rows
reach chunk order through ``take_rows``: static slices of the one or two
dst-sorted edge segments, each padded to a chunk multiple with copies of
its LAST row, so dst stays nondecreasing for the ``indices_are_sorted``
segment-sum fast path and gathers through padded index rows stay
in-bounds. Padded rows are masked out by ``chunk_layout``'s ``row_valid``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _segments(e_cap: int, chunk: int, e_split: int | None):
    """``([(a, b, pad), ...], chunk)``: the non-empty edge segments, the pad
    rows that bring each to a chunk multiple, and the chunk size clamped to
    the longest segment (``chunk <= 0``: one chunk per segment). No edges:
    no segments."""
    if e_split is not None and 0 <= e_split < e_cap:
        bounds = [(0, e_split), (e_split, e_cap)]
    else:
        bounds = [(0, e_cap)]
    longest = max(b - a for a, b in bounds)
    chunk = longest if chunk <= 0 else min(chunk, longest)
    return [(a, b, -(b - a) % chunk) for a, b in bounds if b > a], chunk


def chunk_layout(e_cap: int, chunk: int, e_split: int | None = None):
    """Chunk layout for edge scans, aligned to the interior/frontier
    boundary.

    Returns ``(row_index, row_valid, K, chunk)``: each scan input is
    ``chunked(take_rows(x, chunk, e_split), K, chunk)`` and ``row_valid``
    is ANDed into the edge mask (``LocalGraph.edge_chunks``). With an active split
    (``0 <= e_split < e_cap``) the two segments are padded to chunk
    multiples INDEPENDENTLY, so no chunk ever straddles the boundary —
    every chunk's dst rows stay nondecreasing and the
    ``indices_are_sorted=True`` scatter fast path survives the split
    layout (a straddling chunk would silently break the hint). Padding
    rows repeat each segment's last row (sorted, in-bounds, masked out by
    ``row_valid``). ``row_index`` names the source row of every chunk-order
    row (``take_rows(x, ...)`` equals ``x[row_index]``); the models do not
    gather through it — it is the layout's specification, for tests and
    for host-side numpy. Cost: at most one extra chunk (plus one chunk of
    pad rows) versus the unaligned layout.
    """
    if e_cap == 0:
        return (np.zeros(0, np.int32), np.zeros(0, bool), 1, 0)
    segments, chunk = _segments(e_cap, chunk, e_split)
    idx, valid = [], []
    for a, b, pad in segments:
        idx += [np.arange(a, b, dtype=np.int32),
                np.full(pad, b - 1, dtype=np.int32)]
        valid += [np.ones(b - a, dtype=bool), np.zeros(pad, dtype=bool)]
    row_index = np.concatenate(idx)
    row_valid = np.concatenate(valid)
    return row_index, row_valid, len(row_index) // chunk, chunk


def take_rows(x, chunk: int, e_split: int | None = None):
    """``x[row_index]`` of ``chunk_layout(len(x), chunk, e_split)``, built
    from static slices: each segment ``x[a:b]`` followed by its last row
    broadcast over the segment's pad rows, in one concatenate (``x`` itself
    when nothing is padded). XLA sees contiguous copies, and the transpose
    is slices, one sum over at most ``chunk - 1`` pad rows per segment and
    an add into the segment's last row — where a gather through
    ``row_index`` transposes to a scatter-add over every row."""
    x = jnp.asarray(x)
    segments, _ = _segments(x.shape[0], chunk, e_split)
    if not any(pad for _, _, pad in segments):
        return x
    parts = []
    for a, b, pad in segments:
        parts.append(x[a:b])
        if pad:
            parts.append(jnp.broadcast_to(x[b - 1:b], (pad,) + x.shape[1:]))
    return jnp.concatenate(parts)


def chunked(x, K: int, chunk: int):
    """(K*chunk, ...) -> (K, chunk, ...) for lax.scan."""
    return x.reshape((K, chunk) + x.shape[1:])


def remat_wrap(body, remat):
    """Apply the requested rematerialization mode to a scan body.

    ``remat`` is False (save everything), True (full checkpoint: recompute
    the whole chunk in the backward — minimal memory, the chunk's forward
    runs again), or the name of a jax checkpoint policy — most usefully
    ``"dots"`` (``dots_with_no_batch_dims_saveable``: keep GEMM outputs
    resident, recompute only the cheap elementwise/gather glue, for a
    bounded activation-memory increase). Scan bodies are all the models
    checkpoint, so a chunk's forward runs twice a step; the benchmark's
    scanning cells run ``remat=True`` and the second run is the
    ``recompute`` pass of the stage tables (PERF.md section 5, ROADMAP S5).
    """
    if remat is False:
        return body
    if remat is True:
        return jax.checkpoint(body)
    policies = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "nothing": jax.checkpoint_policies.nothing_saveable,
    }
    if remat not in policies:
        raise ValueError(f"remat={remat!r}: expected bool or one of "
                         f"{sorted(policies)}")
    return jax.checkpoint(body, policy=policies[remat])


def scan_accumulate(body, acc0, xs, *, remat):
    """Sum ``body`` over chunks: ``body(acc, xs_i) -> (acc', None)``.

    The body is checkpointed whenever ``remat`` (bool or policy name, see
    ``remat_wrap``) — including for K == 1, so a system just under one
    chunk keeps the same bounded backward memory as one just over (the
    single chunk's per-edge intermediates are the largest residuals there).
    """
    b = remat_wrap(body, remat)
    K = jax.tree.leaves(xs)[0].shape[0]
    if K == 1:
        acc, _ = b(acc0, jax.tree.map(lambda x: x[0], xs))
        return acc
    acc, _ = jax.lax.scan(b, acc0, xs)
    return acc
