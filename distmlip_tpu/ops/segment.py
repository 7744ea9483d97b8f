"""Masked segment reductions for padded graphs.

All graph aggregation in the framework goes through these: messages on
padded (invalid) edges are zeroed by the mask, so static-shape padding never
corrupts results. Padding contract (established by
partition/graph.py:build_partitioned_graph): padded ``dst``/``segment_ids``
rows repeat the LAST REAL value — keeping the index arrays nondecreasing for
the ``indices_are_sorted=True`` fast path and in-bounds for eager gathers —
never 0 and never ``num_segments``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HALF_DTYPES = ("bfloat16", "float16")


def _accum_sum(data, segment_ids, num_segments: int,
               indices_are_sorted: bool):
    """The one scatter-accumulation primitive: half-precision inputs
    accumulate in fp32 and round ONCE on the way out (the dtype_discipline
    contract — per-edge bf16 rounding inside a many-edge segment sum loses
    ulps edge by edge), full-precision inputs accumulate as-is."""
    dtype = data.dtype
    if str(dtype) in _HALF_DTYPES:
        out = jax.ops.segment_sum(
            data.astype(jnp.float32), segment_ids,
            num_segments=num_segments,
            indices_are_sorted=indices_are_sorted)
        return out.astype(dtype)
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments,
                               indices_are_sorted=indices_are_sorted)


def masked_segment_sum(data, segment_ids, num_segments: int, mask=None,
                       indices_are_sorted: bool = False):
    """segment_sum with an optional validity mask on the data rows.

    Graph edge/line arrays are emitted dst-sorted by the partition builder,
    so callers aggregating over full edge arrays pass
    ``indices_are_sorted=True`` (TPU scatter fast path). Half-precision
    data accumulates in fp32 (see ``_accum_sum``).
    """
    if mask is not None:
        m = mask.astype(data.dtype)
        data = data * m.reshape(m.shape + (1,) * (data.ndim - m.ndim))
    return _accum_sum(data, segment_ids, num_segments=num_segments,
                      indices_are_sorted=indices_are_sorted)


def masked_segment_mean(data, segment_ids, num_segments: int, mask=None,
                        eps=1e-12, indices_are_sorted: bool = False):
    tot = masked_segment_sum(data, segment_ids, num_segments, mask,
                             indices_are_sorted=indices_are_sorted)
    ones = jnp.ones(data.shape[0], dtype=data.dtype)
    cnt = masked_segment_sum(ones, segment_ids, num_segments, mask,
                             indices_are_sorted=indices_are_sorted)
    return tot / jnp.maximum(cnt, eps).reshape(cnt.shape + (1,) * (tot.ndim - cnt.ndim))


def masked_segment_softmax(logits, segment_ids, num_segments: int, mask=None,
                           indices_are_sorted: bool = False):
    """Numerically stable segment softmax over masked edges.

    ``indices_are_sorted`` plumbs through to the inner ``segment_max`` /
    ``segment_sum`` — dst-sorted edge arrays keep the TPU scatter fast
    path through softmax aggregation too, not just plain sums.
    """
    neg = jnp.finfo(logits.dtype).min
    if mask is not None:
        logits = jnp.where(mask, logits, neg)
    seg_max = jax.ops.segment_max(logits, segment_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=indices_are_sorted)
    logits = logits - seg_max[segment_ids]
    ex = jnp.exp(logits)
    if mask is not None:
        ex = jnp.where(mask, ex, 0.0)
    denom = _accum_sum(ex, segment_ids, num_segments=num_segments,
                       indices_are_sorted=indices_are_sorted)
    return ex / jnp.maximum(denom[segment_ids], 1e-30)


# ---- slot-major tables -----------------------------------------------------
# A table of ``slabs * B`` rows holds slot ``k`` of segment ``b`` at row
# ``k * B + b`` (CHGNet's in-line table, partition/graph.line_table): what a
# sorted list addresses by ``segment_ids`` is here a repeat forward and a sum
# over the slabs backward, with no index array. These functions are the one
# place that knows the order. Each is the other's transpose and says so
# (``custom_vjp``), so a derivative of any order is one of the two forms
# below: a concatenate of the rows and an add of slab slices, both cut at
# multiples of ``B`` (whole tiles where ``B`` is a multiple of 128). XLA's own
# transposes would be pads, and ``reshape(slabs, B, C).sum(0)`` relays the
# rows out with the slab axis on the sublanes (2.0-3.8 ms a sum of 1.1M rows
# on a v5e against 0.3 for the adds: chip runs, PR 37).

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _slab_tile(rows, slabs: int):
    return jnp.concatenate([rows] * slabs, axis=0)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _slab_add(data, slabs: int):
    n = data.shape[0] // slabs
    half = str(data.dtype) in _HALF_DTYPES
    acc = None
    for k in range(slabs):
        part = data[k * n:(k + 1) * n]
        part = part.astype(jnp.float32) if half else part
        acc = part if acc is None else acc + part
    return acc.astype(data.dtype)


_slab_tile.defvjp(lambda rows, slabs: (_slab_tile(rows, slabs), None),
                  lambda slabs, _, g: (_slab_add(g, slabs),))
_slab_add.defvjp(lambda data, slabs: (_slab_add(data, slabs), None),
                 lambda slabs, _, g: (_slab_tile(g, slabs),))


def slab_repeat(rows, slabs: int):
    """``rows`` ``(B, ...)`` at every slot: ``(slabs * B, ...)``, what
    ``rows[segment_ids]`` is on a sorted list. The transpose is
    :func:`slab_sum`: the cotangents of half-precision rows add up in
    float32 and round once (``nn.gather_rows``'s rule)."""
    if slabs == 0:
        return rows[:0]
    if not jnp.issubdtype(rows.dtype, jnp.inexact):
        return jnp.concatenate([rows] * slabs, axis=0)  # masks: no cotangent
    return _slab_tile(rows, slabs)


def slab_sum(data, num_segments: int, mask=None):
    """Sum of the ``(slabs * num_segments, ...)`` rows of a slot-major table
    over its slabs, ``(num_segments, ...)``: ``masked_segment_sum`` of a
    sorted list. Half precision accumulates in float32 and rounds once
    (``_accum_sum``'s rule)."""
    if mask is not None:
        m = mask.astype(data.dtype)
        data = data * m.reshape(m.shape + (1,) * (data.ndim - m.ndim))
    if data.shape[0] == 0:
        return jnp.zeros((num_segments,) + data.shape[1:], data.dtype)
    return _slab_add(data, data.shape[0] // num_segments)


# ---- permutations ----------------------------------------------------------

@jax.custom_vjp
def _permute(rows, order, inverse):
    return rows[order]


def _permute_fwd(rows, order, inverse):
    return _permute(rows, order, inverse), (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    zero = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return _permute(g, inverse, order), zero(order), zero(inverse)


_permute.defvjp(_permute_fwd, _permute_bwd)


def permute_rows(rows, order, inverse):
    """``rows[order]`` for a permutation ``order`` whose inverse is
    ``inverse``: the transpose is the gather ``g[inverse]``, not the
    scatter-add XLA makes of a gather's cotangent (``custom_vjp``, so a
    derivative of any order stays a gather)."""
    return _permute(rows, order, inverse)
