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


# ---------------------------------------------------------------------------
# Wigner rotation between lab rows and the edge frame's per-m pieces
# ---------------------------------------------------------------------------
#
# eSCN-MD (models/escn_md.py) rotates lab coefficients into the edge frame
# before its SO(2) convolutions and back after them. A rotation is, per edge
# and degree, a (2l+1) x (2l+1) block times (2l+1) rows of ``c`` channels.
# Here a block ENTRY is a per-edge column of the ``(E, n_cols)`` float32
# array ``cols`` (``D_0 | D_1 | ..`` row-major, ``wigner_cols``), a row is a
# 128-aligned lane slice of a flat operand, and a rotation is a sum of
# column-times-row products on the vector unit:
#
# - a LAB operand is ``(E, S * c)``, coefficient ``(l, p)`` at lanes
#   ``(l * l + p) * c``;
# - the EDGE frame is one PIECE per signed m (``CoeffLayout.signed_ms``),
#   ``(E, nl_m * n_ops * c)``: the l = |m|..lmax coefficients l-major, and
#   within a degree the ``n_ops`` lab operands' channels side by side;
# - to the edge frame: ``piece_m[l, j] = sum_p D_l[p, l + m] * lab_j[l, p]``;
#   to the lab frame:   ``lab_j[l, p] = sum_m D_l[p, l + m] * piece_m[l, j]``
#   over the pieces that are present (|m| <= min(l, mmax)).
#
# The two are each other's transpose in the rows, and the cotangent of the
# columns, ``dD_l[p, l + m] = sum_{j, c} lab_j[l, p, c] * piece_m[l, j, c]``,
# is the third kernel (``wigner_dcols_pallas``).

ROT_BLK = 256   # edges a grid step at 2 bytes an element (VMEM: 16 MiB)
ROT_SUB = 32    # edges a pass of the inner loop (two packed bfloat16 tiles)


def wigner_n_cols(l_max: int) -> int:
    return sum((2 * l + 1) ** 2 for l in range(l_max + 1))


def wigner_col(l: int, p: int, n: int) -> int:
    """Column of block entry ``D_l[p, n]`` in the ``(E, n_cols)`` array."""
    return wigner_n_cols(l - 1) + p * (2 * l + 1) + n


def wigner_cols(blocks):
    """Per-l blocks ``[(E, 2l+1, 2l+1)]`` -> their ``(E, n_cols)`` columns."""
    e = blocks[0].shape[0]
    return jnp.concatenate([d.reshape(e, -1) for d in blocks], axis=1)


def _lab_lo(l: int, p: int, c: int) -> int:
    """First lane of coefficient ``(l, p)`` in a lab operand."""
    return (l * l + p) * c


def _piece_lo(l: int, m: int, j: int, n_ops: int, c: int) -> int:
    """First lane of degree l, operand j in the signed-m piece."""
    return ((l - abs(m)) * n_ops + j) * c


def _ms_of(l: int, m_max: int, ms) -> list:
    return [m for m in ms if abs(m) <= min(l, m_max)]


def _block_cols(Dl, l: int, m_max: int, ms):
    """``(lms, Dl[:, :, those columns])``: the pieces present at degree l,
    ascending (a contiguous run: every |m| <= min(l, mmax), or 0 alone)."""
    lms = sorted(_ms_of(l, m_max, ms))
    assert lms == list(range(lms[0], lms[-1] + 1)), lms
    return lms, Dl[:, :, l + lms[0]:l + lms[-1] + 1]


def wigner_rotate_reference(cols, ins, *, l_max: int, m_max: int, ms,
                            channels: int, n_ops: int, to_edge: bool):
    """The rotation as batched per-l products in plain ``jax.numpy``: the
    path off the TPU and the parity tests' oracle. The blocks are cast to
    the rows' dtype at each use, as the products were written before the
    kernel. ``ins``/result: ``n_ops`` lab operands or the ``ms`` pieces,
    as the kernel takes and gives them."""
    e, c = cols.shape[0], channels
    D = [cols[:, wigner_col(l, 0, 0):wigner_col(l + 1, 0, 0)]
         .reshape(e, 2 * l + 1, 2 * l + 1) for l in range(l_max + 1)]
    if to_edge:
        labs = [x.reshape(e, -1, c) for x in ins]
        out = {m: [] for m in ms}
        for l in range(l_max + 1):
            lms, Dl = _block_cols(D[l], l, m_max, ms)
            parts = [jnp.einsum("epn,epc->enc", Dl.astype(h.dtype),
                                h[:, l * l:(l + 1) ** 2, :]) for h in labs]
            for i, m in enumerate(lms):
                out[m] += [part[:, i, :] for part in parts]
        return tuple(jnp.concatenate(out[m], axis=-1) for m in ms)
    pieces = dict(zip(ms, ins))
    labs = [[] for _ in range(n_ops)]
    for l in range(l_max + 1):
        lms, Dl = _block_cols(D[l], l, m_max, ms)
        for j in range(n_ops):
            lo = lambda m: _piece_lo(l, m, j, n_ops, c)
            rows = jnp.stack([pieces[m][:, lo(m):lo(m) + c] for m in lms],
                             axis=1)
            labs[j].append(jnp.einsum("epn,enc->epc", Dl.astype(rows.dtype),
                                      rows))
    return tuple(jnp.concatenate(x, axis=1).reshape(e, -1) for x in labs)


def _pad_rows(arrays, blk: int):
    e = arrays[0].shape[0]
    e_pad = -(-e // blk) * blk
    if e_pad == e:
        return arrays
    return [jnp.pad(a, ((0, e_pad - e), (0, 0))) for a in arrays]


def _rot_blk(e: int, dtype) -> int:
    blk = ROT_BLK * 2 // max(2, jnp.dtype(dtype).itemsize)
    return min(blk, -(-e // ROT_SUB) * ROT_SUB)


def wigner_rotate_pallas(cols, ins, *, l_max: int, m_max: int, ms,
                         channels: int, n_ops: int, to_edge: bool,
                         interpret: bool = False):
    """One pass over lane-dense rows: ``n_ops`` lab operands -> the ``ms``
    pieces (``to_edge``) or the pieces -> ``n_ops`` lab operands. Blocks
    are float32 columns, rows are read in their dtype, products accumulate
    in float32 and are rounded once into the rows' dtype."""
    e, c = cols.shape[0], channels
    assert c % 128 == 0, c
    s = (l_max + 1) ** 2
    lab_w = s * c
    piece_w = [(l_max + 1 - abs(m)) * n_ops * c for m in ms]
    out_w = piece_w if to_edge else [lab_w] * n_ops
    dtype = ins[0].dtype
    blk = _rot_blk(e, dtype)
    cols_p, *ins_p = _pad_rows([cols, *ins], blk)
    e_pad = cols_p.shape[0]
    row_spec = lambda w: pl.BlockSpec((blk, w), lambda i: (i, 0))
    kernel = functools.partial(
        _wigner_rotate_kernel, l_max=l_max, m_max=m_max, ms=tuple(ms), c=c,
        n_ops=n_ops, to_edge=to_edge, n_in=len(ins))
    out = pl.pallas_call(
        kernel,
        grid=(e_pad // blk,),
        in_specs=[row_spec(cols.shape[1])] + [row_spec(x.shape[1])
                                              for x in ins],
        out_specs=[row_spec(w) for w in out_w],
        out_shape=[jax.ShapeDtypeStruct((e_pad, w), dtype) for w in out_w],
        interpret=interpret,
    )(cols_p, *ins_p)
    return tuple(o[:e] for o in out)


def _wigner_rotate_kernel(cols_ref, *refs, l_max, m_max, ms, c, n_ops,
                          to_edge, n_in):
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    lab_refs, piece_refs = ((in_refs, out_refs) if to_edge
                            else (out_refs, in_refs))
    piece_of = dict(zip(ms, piece_refs))
    f32 = jnp.float32

    def body(i, carry):
        r = pl.ds(pl.multiple_of(i * ROT_SUB, ROT_SUB), ROT_SUB)
        cv = cols_ref[r, :]

        def col(l, p, m):
            k = wigner_col(l, p, l + m)
            return cv[:, k:k + 1]

        for l in range(l_max + 1):
            K = 2 * l + 1
            lms = _ms_of(l, m_max, ms)
            plo = lambda m, j: _piece_lo(l, m, j, n_ops, c)
            if to_edge:
                rows = [[lab_refs[j][r, pl.ds(_lab_lo(l, p, c), c)]
                         .astype(f32) for p in range(K)]
                        for j in range(n_ops)]
                for m in lms:
                    d = [col(l, p, m) for p in range(K)]
                    for j in range(n_ops):
                        acc = d[0] * rows[j][0]
                        for p in range(1, K):
                            acc = acc + d[p] * rows[j][p]
                        piece_of[m][r, pl.ds(plo(m, j), c)] = acc.astype(
                            piece_of[m].dtype)
            else:
                rows = [[piece_of[m][r, pl.ds(plo(m, j), c)].astype(f32)
                         for m in lms] for j in range(n_ops)]
                for p in range(K):
                    d = [col(l, p, m) for m in lms]
                    for j in range(n_ops):
                        acc = d[0] * rows[j][0]
                        for i_m in range(1, len(lms)):
                            acc = acc + d[i_m] * rows[j][i_m]
                        lab_refs[j][r, pl.ds(_lab_lo(l, p, c), c)] = (
                            acc.astype(lab_refs[j].dtype))
        return carry

    jax.lax.fori_loop(0, cols_ref.shape[0] // ROT_SUB, body, None)


def wigner_dcols_pallas(labs, pieces, *, l_max: int, m_max: int, ms,
                        channels: int, interpret: bool = False):
    """Cotangent of the block columns, ``(E, n_cols)`` float32: per kept
    entry the sum of ``lab_j[l, p] * piece_m[l, j]`` over operands and
    channels, products and sums in float32; entries no piece reads are
    zero. The operands' products are added lane by lane first, so an entry
    costs one cross-lane sum whatever ``n_ops`` and ``channels`` are. The
    columns are written in groups of 128 lanes (one to lmax 3, two at
    lmax 4's 165 columns)."""
    e, c = labs[0].shape[0], channels
    assert c % 128 == 0, c
    blk = _rot_blk(e, labs[0].dtype)
    args = _pad_rows([*labs, *pieces], blk)
    e_pad = args[0].shape[0]
    width = -(-wigner_n_cols(l_max) // 128) * 128
    row_spec = lambda w: pl.BlockSpec((blk, w), lambda i: (i, 0))
    kernel = functools.partial(
        _wigner_dcols_kernel, l_max=l_max, m_max=m_max, ms=tuple(ms), c=c,
        n_ops=len(labs))
    out = pl.pallas_call(
        kernel,
        grid=(e_pad // blk,),
        in_specs=[row_spec(x.shape[1]) for x in args],
        out_specs=row_spec(width),
        out_shape=jax.ShapeDtypeStruct((e_pad, width), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:e, :wigner_n_cols(l_max)]


def _wigner_dcols_kernel(*refs, l_max, m_max, ms, c, n_ops):
    lab_refs = refs[:n_ops]
    piece_of = dict(zip(ms, refs[n_ops:n_ops + len(ms)]))
    out_ref = refs[-1]
    f32 = jnp.float32
    # (operand, 128-lane group) of a degree's channels
    lanes = [(j, q * 128) for j in range(n_ops) for q in range(c // 128)]

    groups = out_ref.shape[1] // 128

    def body(i, carry):
        r = pl.ds(pl.multiple_of(i * ROT_SUB, ROT_SUB), ROT_SUB)
        lane = jax.lax.broadcasted_iota(jnp.int32, (ROT_SUB, 128), 1)
        out = [jnp.zeros((ROT_SUB, 128), f32) for _ in range(groups)]
        for l in range(l_max + 1):
            K = 2 * l + 1
            lab = [[lab_refs[j][r, pl.ds(_lab_lo(l, p, c) + q, 128)]
                    .astype(f32) for j, q in lanes] for p in range(K)]
            for m in _ms_of(l, m_max, ms):
                pc = [piece_of[m][r, pl.ds(_piece_lo(l, m, j, n_ops, c) + q,
                                           128)].astype(f32)
                      for j, q in lanes]
                for p in range(K):
                    acc = lab[p][0] * pc[0]
                    for t in range(1, len(lanes)):
                        acc = acc + lab[p][t] * pc[t]
                    g, k = divmod(wigner_col(l, p, l + m), 128)
                    out[g] = jnp.where(lane == k, jnp.sum(
                        acc, axis=-1, keepdims=True), out[g])
        if groups == 1:
            out_ref[r, :] = out[0]
        else:
            for g in range(groups):
                out_ref[r, pl.ds(g * 128, 128)] = out[g]
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // ROT_SUB, body, None)


# ---------------------------------------------------------------------------
# S2 activation: SiLU on the sphere's grid, between two table products
# ---------------------------------------------------------------------------
#
# EquiformerV2 (models/equiformer_v2.py) takes each edge's narrowed
# coefficients to the 324 points of its grid, applies SiLU there and
# integrates back: ``y = F silu(G x)`` per (edge, channel) column. As two
# XLA products the grid values (18 times the coefficients) cross HBM in
# every pass. Here the coefficients come as ``(K, H, E)``: coefficient,
# channel, edge, the order in which XLA lays out the convolution's output
# pieces, so the operand is a view and no copy. A grid step takes a block
# of channels and of edges; the kernel reads a channel's ``(K, L)`` rows
# (a sublane of each coefficient's tile), which are the right operand of
# both products, plain 2-D matrix products, and the grid of a block
# exists only in VMEM. The grid axis is zero-padded to whole tiles
# (``s2_tables``): SiLU(0) = 0, so a padded grid point adds nothing.

# a grid step: 32 channels by 1,024 edges at 2 bytes an element (float32:
# 512; at 1,024 its backward outgrows VMEM). One chunk of eqv2-31m-md-1c,
# (19, 64, 16384) bfloat16, forward plus forward-and-backward on a v5e:
# 4.51 ms, against 4.82 at 8 x 2,048, 4.86 at 16 x 2,048, 4.86 at 64 x 512
# and 8.88 for XLA's products (PERF.md section 6, PR 41)
S2_LANES = 1024
S2_CHANNELS = 32    # a block holds them all where they are no multiple


def s2_tables(to_grid, from_grid, dtype):
    """``(G, Gt, F, Ft)``: ``to_grid`` ``(P, K)`` and ``from_grid``
    ``(K, P)`` with the grid axis zero-padded to whole 16-row tiles, and
    their transposes, as ``dtype`` host arrays."""
    P, K = to_grid.shape
    pp = -(-P // 16) * 16
    G = np.zeros((pp, K), np.float32)
    G[:P] = to_grid
    F = np.zeros((K, pp), np.float32)
    F[:, :P] = from_grid
    return tuple(np.asarray(t, dtype) for t in (G, G.T.copy(), F, F.T.copy()))


def s2_lanes(n: int, dtype) -> int:
    """The edge block of :func:`s2_activation_pallas` over ``n`` edges."""
    lanes = S2_LANES * 2 // max(2, jnp.dtype(dtype).itemsize)
    return min(lanes, -(-n // 128) * 128)


def _s2_dot(a, b):
    # float32 rows ask for a true float32 product, as in _so2_kernel
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=(jax.lax.Precision.HIGHEST
                              if b.dtype == jnp.float32 else None))


def _s2_grid(G, x):
    """The grid values of a channel's rows, float32 sums rounded once to
    the rows' dtype (where the XLA products round them), then widened for
    SiLU."""
    return _s2_dot(G, x).astype(x.dtype).astype(jnp.float32)


# SiLU and its slope through tanh: with h = g / 2 and t = tanh(h), silu(g)
# = h (1 + t) and silu'(g) = (1 + t) / 2 + h (1 - t^2) / 2. One
# transcendental a point and no division: the logistic's form took 6.37 ms
# where this takes 4.82 (the block at 8 x 2,048, PERF.md section 6, PR 41).

def _s2_fwd_kernel(g_ref, f_ref, x_ref, o_ref):
    def channel(j, carry):
        h = 0.5 * _s2_grid(g_ref[...], x_ref[:, j, :])
        s = (h + h * jnp.tanh(h)).astype(o_ref.dtype)
        o_ref[:, j, :] = _s2_dot(f_ref[...], s).astype(o_ref.dtype)
        return carry

    _over_channels(x_ref, channel)


def _s2_bwd_kernel(g_ref, gt_ref, ft_ref, x_ref, dy_ref, dx_ref):
    dtype = dx_ref.dtype

    def channel(j, carry):
        h = 0.5 * _s2_grid(g_ref[...], x_ref[:, j, :])
        t = jnp.tanh(h)
        ds = _s2_dot(ft_ref[...], dy_ref[:, j, :]).astype(dtype).astype(
            jnp.float32)
        dg = ds * (0.5 + 0.5 * t + h * (0.5 - 0.5 * t * t))
        dx_ref[:, j, :] = _s2_dot(gt_ref[...], dg.astype(dtype)).astype(dtype)
        return carry

    _over_channels(x_ref, channel)


def _over_channels(x_ref, channel):
    """``channel(j, None)`` for each channel row of the block, unrolled:
    Mosaic reads a bfloat16 row of a tile only at a static index. One
    traced body, so a step program's trace does not grow with the block."""
    jax.lax.fori_loop(0, x_ref.shape[1], channel, None, unroll=True)


def s2_activation_pallas(x, tables, *, backward_of=None,
                         interpret: bool = False):
    """``F silu(G x)`` over the coefficient axis of ``x`` ``(K, H, E)``
    (``tables`` from :func:`s2_tables`, ``E`` a multiple of
    :func:`s2_lanes`), in one pass over blocks of channels and edges;
    products accumulate in float32 and the grid values, SiLU's and the
    result are rounded to the rows' dtype. With ``backward_of=x0`` it is
    the cotangent of ``x0`` for the output's cotangent ``x`` instead: the
    grid of ``x0`` computed again, ``G^T (silu'(G x0) * F^T x)``."""
    G, Gt, F, Ft = (jnp.asarray(t) for t in tables)
    k, h, e = x.shape
    hb = S2_CHANNELS if h % S2_CHANNELS == 0 else h
    lanes = s2_lanes(e, x.dtype)
    assert e % lanes == 0, (x.shape, lanes)
    block = pl.BlockSpec((k, hb, lanes), lambda c, i: (0, c, i))
    whole = lambda t: pl.BlockSpec(t.shape, lambda c, i: (0, 0))
    if backward_of is None:
        kernel, consts, ins = _s2_fwd_kernel, (G, F), (x,)
    else:
        kernel, consts, ins = _s2_bwd_kernel, (G, Gt, Ft), (backward_of, x)
    return pl.pallas_call(
        kernel,
        grid=(h // hb, e // lanes),
        in_specs=[whole(t) for t in consts] + [block] * len(ins),
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(*consts, *ins)
