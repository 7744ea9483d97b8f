"""Fused SO(2)/channel-mixing kernel for the equivariant inner loop.

eSCN's SO(2) convolution (models/escn.py) is, per edge, a stack of small
per-|m| GEMMs over the (+m, -m) complex coefficient pairs:

    m = 0:  y0 = f0 @ W0
    m > 0:  y+ = f+ @ Wr - f- @ Wi,   y- = f+ @ Wi + f- @ Wr

with ``f`` the (nl * C)-flattened coefficient block for that |m|. XLA
evaluates each as its own HLO with the per-edge operand round-tripping
HBM between them. The kernel here batches ALL per-(l, m) GEMMs into one
VMEM-resident pallas_call over edge blocks: one load of the (BLK, S, C)
coefficient block, 2 * l_max + 1 MXU matmuls against the VMEM-resident
weight stack, one store. (MACE's per-path channel mixing rides the
generic :func:`distmlip_tpu.kernels.segment.pallas_edge_aggregate`
instead — its contraction is already fused into the density-projection
edge compute.)

Coefficients arrive in the PACKED per-m layout (``packed_m_layout``):
``[m=0 block | m=1 plus | m=1 minus | m=2 plus | ...]`` so every per-m
operand is a static slice — the (cheap, static) permutation from the
e3nn layout is applied by the dispatch layer, not the kernel.

``so2_conv_reference`` is the same math in plain XLA: the fallback path,
the custom-VJP backward, and the parity oracle for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

EDGE_BLK = 256


def packed_m_layout(m_idx: dict) -> tuple:
    """(perm, inv, segments): the packed per-m coefficient order.

    ``m_idx[m] = (plus_indices, minus_indices)`` in the source layout
    (models/escn.py ``self.m_idx``). ``perm`` gathers source -> packed,
    ``inv`` gathers packed -> source, ``segments`` lists
    ``(m, start, nl)`` static slice bounds of each packed block (for
    ``m > 0`` the minus block sits at ``start + nl``).
    """
    order = []
    segments = []
    for m in sorted(m_idx):
        plus, minus = m_idx[m]
        segments.append((m, len(order), len(plus)))
        order.extend(int(i) for i in plus)
        if m > 0:
            order.extend(int(i) for i in minus)
    perm = np.asarray(order, dtype=np.int32)
    inv = np.argsort(perm).astype(np.int32)
    return perm, inv, tuple(segments)


def so2_conv_reference(h_packed, weights, segments, channels: int):
    """Pure-XLA SO(2) convolution on packed-layout coefficients.

    ``weights`` is ``[W0, W1r, W1i, W2r, W2i, ...]`` (one (d, d) matrix
    per m=0 block, a real/imag pair per m > 0, ``d = nl * C``). Returns
    the packed-layout output; identical math to the kernel.
    """
    e = h_packed.shape[0]
    c = channels
    out = []
    wi = 0
    for m, start, nl in segments:
        d = nl * c
        if m == 0:
            f = h_packed[:, start:start + nl, :].reshape(e, d)
            out.append((f @ weights[wi]).reshape(e, nl, c))
            wi += 1
        else:
            fp = h_packed[:, start:start + nl, :].reshape(e, d)
            fm = h_packed[:, start + nl:start + 2 * nl, :].reshape(e, d)
            wr, wim = weights[wi], weights[wi + 1]
            wi += 2
            out.append((fp @ wr - fm @ wim).reshape(e, nl, c))
            out.append((fp @ wim + fm @ wr).reshape(e, nl, c))
    return jnp.concatenate(out, axis=1)


def so2_conv_pallas(h_packed, weights, segments, channels: int, *,
                    edge_blk: int | None = None, interpret: bool = False):
    """One VMEM-resident pallas_call evaluating every per-m GEMM.

    ``h_packed``: (E, S, C) packed-layout coefficients; ``weights`` as in
    :func:`so2_conv_reference` (they ride VMEM whole — SO(2) stacks are
    O(l_max * (l_max * C)^2) bytes, far under the VMEM budget for every
    model config this repo ships).

    The kernel sees the coefficients as ``(E, S * C)`` rows: each per-m
    operand ``(BLK, nl * C)`` is then a static LANE slice (128-aligned
    when ``C`` is a multiple of 128, the published eSCN/UMA width) that
    feeds the MXU as loaded — Mosaic has no in-kernel reshape that folds
    the ``nl`` sublane axis into lanes.
    """
    e, s, c = h_packed.shape
    blk = min(edge_blk or EDGE_BLK, max(8, e))
    e_pad = -(-e // blk) * blk
    h_in = h_packed.reshape(e, s * c)
    if e_pad != e:
        h_in = jnp.pad(h_in, ((0, e_pad - e), (0, 0)))

    kernel = functools.partial(_so2_kernel, segments=segments, channels=c,
                               n_weights=len(weights))
    out = pl.pallas_call(
        kernel,
        grid=(e_pad // blk,),
        in_specs=(
            [pl.BlockSpec((blk, s * c), lambda i: (i, 0))]
            + [pl.BlockSpec(w.shape, lambda i: (0,) * w.ndim)
               for w in weights]
        ),
        out_specs=pl.BlockSpec((blk, s * c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e_pad, s * c), h_packed.dtype),
        interpret=interpret,
    )(h_in, *weights)
    return out[:e].reshape(e, s, c)


def _so2_kernel(h_ref, *refs, segments, channels: int, n_weights: int):
    w_refs = refs[:n_weights]
    out_ref = refs[n_weights]
    c = channels
    # fp32 coefficients ask for a true fp32 contraction, stated so the
    # result does not hang on Mosaic's default; bf16 runs native
    dot = functools.partial(
        jnp.dot, preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if h_ref.dtype == jnp.float32 else None))
    wi = 0
    for m, start, nl in segments:
        d = nl * c
        lo = start * c
        if m == 0:
            y = dot(h_ref[:, lo:lo + d], w_refs[wi][...])
            out_ref[:, lo:lo + d] = y.astype(out_ref.dtype)
            wi += 1
        else:
            fp = h_ref[:, lo:lo + d]
            fm = h_ref[:, lo + d:lo + 2 * d]
            wr = w_refs[wi][...]
            wim = w_refs[wi + 1][...]
            wi += 2
            out_ref[:, lo:lo + d] = (dot(fp, wr) - dot(fm, wim)).astype(
                out_ref.dtype)
            out_ref[:, lo + d:lo + 2 * d] = (dot(fp, wim) + dot(fm, wr)
                                             ).astype(out_ref.dtype)
