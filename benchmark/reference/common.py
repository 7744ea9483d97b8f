"""What the plain references share: the radial functions, the rounding that
stands for a lower precision, sums over blocks of edges or nodes, and the
periodic neighbour search. Nothing here comes from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Operand precisions a reference can be computed in. "float32" is the
# reference proper; the others round every operand of a contraction to that
# type and accumulate in float32, which is what a matrix unit of that type
# does. They serve as controls (see PERF.md, "How correct is decided").
PRECISIONS = ("float32", "bfloat16", "float8_e4m3fn")


def rounder(precision: str):
    """x -> x rounded to ``precision`` and back to float32. The 8-bit type
    is scaled per tensor to its range, as fp8 matrix units are fed: e4m3
    (largest finite value 448) forward, e5m2 (57344) for the cotangent."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)

    def scaled(x, dtype, top):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    @jax.custom_vjp
    def fp8(x):
        return scaled(x, jnp.float8_e4m3fn, 448.0)

    fp8.defvjp(lambda x: (fp8(x), None),
               lambda _, g: (scaled(g, jnp.float8_e5m2, 57344.0),))
    return fp8


def bessel_basis(d, cutoff: float, n_basis: int):
    """sqrt(2/rc) sin(n pi d / rc) / d for n = 1..n_basis."""
    n = jnp.arange(1, n_basis + 1, dtype=d.dtype)
    x = jnp.maximum(d, 1e-8)[..., None]
    return jnp.sqrt(2.0 / cutoff) * jnp.sin(n * jnp.pi * x / cutoff) / x


def polynomial_cutoff(d, cutoff: float, p: int):
    x = jnp.clip(d / cutoff, 0.0, 1.0)
    return (1.0 - (p + 1.0) * (p + 2.0) / 2.0 * x ** p
            + p * (p + 2.0) * x ** (p + 1)
            - p * (p + 1.0) / 2.0 * x ** (p + 2))


def cosine_cutoff(d, cutoff: float):
    return jnp.where(d < cutoff, 0.5 * (jnp.cos(jnp.pi * d / cutoff) + 1.0),
                     0.0)


def silu(x):
    return x * jax.nn.sigmoid(x)


def dense(layer, x, rq):
    y = jnp.matmul(rq(x), rq(layer["w"]))
    return y + layer["b"] if "b" in layer else y


def mlp(layers, x, rq):
    for i, layer in enumerate(layers):
        x = dense(layer, x, rq)
        if i < len(layers) - 1:
            x = silu(x)
    return x


def layernorm(p, x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def blocked(fn, arrays: tuple, block: int | None):
    """``fn`` over the leading rows of ``arrays`` in blocks, one block live
    at a time: returns ``fn``'s rows for all of them. ``fn`` maps arrays of
    ``block`` rows to an array of ``block`` rows, row by row. Each block is
    recomputed in the backward pass, so that the reference fits beside
    nothing else on a chip; ``block=None`` is one plain call."""
    n = arrays[0].shape[0]
    if block is None or n <= block:
        return fn(*arrays)
    k = -(-n // block)
    pad = k * block - n
    padded = tuple(
        jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (k, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), padded)
    return out.reshape((k * block,) + out.shape[2:])[:n]


def blocked_segment_sum(fn, arrays: tuple, dst, n_nodes: int,
                        block: int | None):
    """sum over edges e of ``fn(arrays[e])`` into row ``dst[e]``, in blocks
    of edges (see :func:`blocked`)."""
    n = dst.shape[0]
    if block is None or n <= block:
        return jax.ops.segment_sum(fn(*arrays), dst, num_segments=n_nodes)
    k = -(-n // block)
    pad = k * block - n

    def split(a):
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (k, block) + a.shape[1:])

    live = split(jnp.ones(n, dtype=jnp.float32))

    @jax.checkpoint
    def body(acc, xs):
        keep, d, rows = xs
        msg = fn(*rows)
        msg = msg * keep.reshape((block,) + (1,) * (msg.ndim - 1))
        return acc + jax.ops.segment_sum(msg, d, num_segments=n_nodes), None

    first = jax.eval_shape(fn, *[a[:1] for a in arrays])
    acc0 = jnp.zeros((n_nodes,) + first.shape[1:], dtype=first.dtype)
    acc, _ = jax.lax.scan(body, acc0,
                          (live, split(dst), tuple(split(a) for a in arrays)))
    return acc


def neighbour_pairs(positions: np.ndarray, cell: np.ndarray, cutoff: float):
    """All directed pairs (src, dst) closer than ``cutoff`` in an
    orthorhombic periodic box, with the Cartesian image shift that makes
    ``positions[dst] - positions[src] + shift`` the short vector. A cell
    list in numpy float64; needs every box length over 2 * cutoff, so that
    the minimum image is the only one."""
    cell = np.asarray(cell, dtype=np.float64)
    if np.abs(cell - np.diag(np.diag(cell))).max() > 1e-9:
        raise ValueError("the reference's neighbour search takes an "
                         "orthorhombic box")
    box = np.diag(cell)
    if not np.all(box > 2.0 * cutoff):
        raise ValueError(f"box {box} is not over twice the cutoff {cutoff}")
    pos = np.asarray(positions, dtype=np.float64)
    wrapped = pos - np.floor(pos / box) * box
    nbin = np.maximum((box // cutoff).astype(int), 1)
    width = box / nbin
    bins = np.minimum((wrapped / width).astype(int), nbin - 1)
    flat = (bins[:, 0] * nbin[1] + bins[:, 1]) * nbin[2] + bins[:, 2]
    order = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[order], np.arange(nbin.prod() + 1))
    src_all, dst_all = [], []
    seen = set()
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                step = (np.array([dx, dy, dz]) % nbin)
                key = tuple(np.where(nbin < 3, step, [dx, dy, dz]))
                if key in seen:  # fewer than three bins: images coincide
                    continue
                seen.add(key)
                nb = (bins + [dx, dy, dz]) % nbin
                nflat = (nb[:, 0] * nbin[1] + nb[:, 1]) * nbin[2] + nb[:, 2]
                lo, hi = starts[nflat], starts[nflat + 1]
                count = hi - lo
                src = np.repeat(np.arange(len(pos)), count)
                offs = np.arange(count.sum()) - np.repeat(
                    np.cumsum(count) - count, count)
                dst = order[np.repeat(lo, count) + offs]
                src_all.append(src)
                dst_all.append(dst)
    src = np.concatenate(src_all)
    dst = np.concatenate(dst_all)
    delta = wrapped[dst] - wrapped[src]
    delta -= np.round(delta / box) * box
    dist2 = (delta ** 2).sum(axis=1)
    keep = (dist2 < cutoff * cutoff) & (src != dst)
    src, dst, delta = src[keep], dst[keep], delta[keep]
    shift = delta - (pos[dst] - pos[src])
    by_dst = np.argsort(dst, kind="stable")
    return (src[by_dst].astype(np.int32), dst[by_dst].astype(np.int32),
            shift[by_dst])
