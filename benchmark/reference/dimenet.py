"""Plain reference of DimeNet++ (Gasteiger et al. 2020, arXiv:2011.14115;
DimeNet arXiv:2003.03123) at the sizes of PyG's ``DimeNetPlusPlus``:
float32, ``jax.numpy`` only, no kernels, no partitions, nothing imported
from the program.

The graph holds every directed pair j -> i closer than ``cutoff``; the
hidden state ``m`` lives on those edges, and a triplet k -> j -> i is an
edge k -> j into the centre j and an edge j -> i out of it, with k != i as
periodic images (atom id and image shift)::

    u(x)    1/x - 28 x^5 + 48 x^6 - 21 x^7 on x = d / c < 1, else 0
    e_n     u(x) sin(f_n x), f_n learnable from n pi
    a_ln    u(x_kj) N_ln j_l(z_ln x_kj) Y_l0(theta), flat index 6 l + n,
            cos theta = (r_j - r_i) . (r_k - r_j) / (d_ji d_kj)
    embed   m_ji = silu(W [h_i | h_j | silu(W_R e + b)] + b), h = Emb[Z]
    block   x_ji = silu(W_ji m + b); x_kj = silu(W_kj m + b) * W2 W1 e
            s = silu(W_down x_kj); T_ji = sum_k s_kj * (W_sbf2 W_sbf1 a)
            h = x_ji + silu(W_up T); residual x num_before_skip;
            h = silu(W h + b) + m; residual x num_after_skip
    output  P_i = w silu(W3 silu(W2 silu(W1 W_up sum_j (W_rbf e) m_ji)))

It is the program's mathematics (``models/dimenet.py``) by its own route:
its own roots of j_l (sign changes on a grid, then bisection of the finite
trigonometric sum), j_l as that finite sum (the program uses the upward
recurrence), P_l from its power-series coefficients (the program uses
Bonnet's recurrence), the 42-wide basis formed per triplet and contracted
with ``W_sbf1`` there (the program contracts the radial part per bond), and
its own triplet tables: per atom at most ``reference_max_neighbours`` edges
in and as many out, built inside ``jit`` from static shapes, and their
pairs. An atom with more edges makes every energy NaN: a structure the
tables cannot hold reads as not correct, never as a smaller graph.
Triplets are computed in blocks of centre atoms, each recomputed in the
backward pass.

In a lower ``precision`` the operands of every contraction are rounded
(``chgnet.rounder``: the float8 twin clipped to its range) and so is every
tensor the program keeps in its compute type: the embedding, each layer's
output, the messages before they are summed and the features after each
update (sums accumulate in float32). Geometry, the envelope, the radial and
spherical rows and the energy sums stay float32, as the configuration's
``precision`` says.

``faults`` plants one error in the reference, to see whether the cell's
comparison catches it (``PERF.md`` section 2): ``"chgnet_sign"`` (cos theta
with CHGNet's sign), ``"backtracking"`` (k = i triplets live),
``"n_major"`` (the spherical rows indexed n-major), ``"drop_last_T"`` (the
last block's triplet sum left out).
"""

from __future__ import annotations

from math import factorial

import jax
import jax.numpy as jnp
import numpy as np

from .chgnet import bond_table, rounder
from .common import dense, silu

FAULTS = ("chgnet_sign", "backtracking", "n_major", "drop_last_T")


def _jl_finite64(l: int, z):
    """j_l(z) as the finite sum of sines and cosines (float64 numpy)."""
    return _jl_finite(np, l, z)


def _jl_finite(xp, l: int, z):
    """j_l(z) = [sin(z - l pi/2) P(1/z) + cos(z - l pi/2) Q(1/z)] / z with
    the terminating Hankel sums P and Q (``xp``: numpy or jax.numpy)."""
    s, c = xp.sin(z - l * np.pi / 2), xp.cos(z - l * np.pi / 2)
    inv = 1.0 / z
    p = q = 0.0
    for k in range(l // 2 + 1):
        a = factorial(l + 2 * k) / (4 ** k * factorial(2 * k)
                                    * factorial(l - 2 * k))
        p = p + (-1) ** k * a * inv ** (2 * k)
    for k in range((l + 1) // 2):
        a = factorial(l + 2 * k + 1) / (2 ** (2 * k + 1) * factorial(2 * k + 1)
                                        * factorial(l - 2 * k - 1))
        q = q + (-1) ** k * a * inv ** (2 * k + 1)
    return (s * p + c * q) * inv


def bessel_roots(num_spherical: int, num_radial: int):
    """``(z, norms)``: the first ``num_radial`` positive roots of each j_l
    and sqrt(2) / |j_{l+1}(z_ln)|, float64."""
    grid = np.linspace(0.5, 60.0, 240001)
    z = np.zeros((num_spherical, num_radial))
    for l in range(num_spherical):
        f = _jl_finite64(l, grid)
        cross = np.nonzero(np.sign(f[:-1]) != np.sign(f[1:]))[0][:num_radial]
        for n, i in enumerate(cross):
            lo, hi = grid[i], grid[i + 1]
            for _ in range(100):
                mid = 0.5 * (lo + hi)
                if np.sign(_jl_finite64(l, mid)) == np.sign(
                        _jl_finite64(l, lo)):
                    lo = mid
                else:
                    hi = mid
            z[l, n] = 0.5 * (lo + hi)
    norms = np.sqrt(2.0) / np.abs(np.stack(
        [_jl_finite64(l + 1, z[l]) for l in range(num_spherical)]))
    return z, norms


def legendre_power_coefficients(num_spherical: int) -> np.ndarray:
    """``(L, L)``: row l holds P_l's coefficients of x^0 .. x^(L-1)."""
    out = np.zeros((num_spherical, num_spherical))
    for l in range(num_spherical):
        for k in range(l // 2 + 1):
            out[l, l - 2 * k] = ((-1) ** k * factorial(2 * l - 2 * k)
                                 / (2 ** l * factorial(k) * factorial(l - k)
                                    * factorial(l - 2 * k)))
    return out


class Tables:
    """The roots table, and what the last evaluation found in the graph
    it built (``found``: real edges, live triplets, whether an atom
    overflowed its table), which a count of operations needs and the
    harness does not hand over."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.cfg = cfg
        self.roots, self.norms = bessel_roots(cfg["num_spherical"],
                                              cfg["num_radial"])
        self.legendre = legendre_power_coefficients(cfg["num_spherical"])
        self.found: dict = {}

    def report(self, n_edges, n_triplets, overflow) -> None:
        self.found = {"n_edges": int(n_edges), "n_triplets": int(n_triplets),
                      "overflow": bool(overflow)}


def init_params(cfg: dict, tables: Tables, key):
    """Random weights in PyG's layout: embedding rows uniform in +-sqrt(3),
    every linear Glorot-normal with zero biases (PyG: Glorot-orthogonal,
    and a zero last linear in each output block, which would make the
    energy a constant), frequencies n pi. Traceable."""
    H, O = cfg["hidden_channels"], cfg["out_emb_channels"]
    I, B = cfg["int_emb_size"], cfg["basis_emb_size"]
    R, S = cfg["num_radial"], cfg["num_spherical"]
    keys = iter(jax.random.split(key, 256))

    def linear(d_in, d_out, bias=True):
        std = np.sqrt(2.0 / (d_in + d_out))
        p = {"w": std * jax.random.normal(next(keys), (d_in, d_out),
                                          jnp.float32)}
        if bias:
            p["b"] = jnp.zeros((d_out,), jnp.float32)
        return p

    def residual():
        return {"lin1": linear(H, H), "lin2": linear(H, H)}

    return {
        "rbf_freq": jnp.pi * jnp.arange(1, R + 1, dtype=jnp.float32),
        "embedding": {
            "species": jax.random.uniform(
                next(keys), (cfg["num_species"], H), jnp.float32,
                -np.sqrt(3.0), np.sqrt(3.0)),
            "rbf": linear(R, H), "lin": linear(3 * H, H)},
        "interactions": [{
            "rbf1": linear(R, B, False), "rbf2": linear(B, H, False),
            "sbf1": linear(S * R, B, False), "sbf2": linear(B, I, False),
            "kj": linear(H, H), "ji": linear(H, H),
            "down": linear(H, I, False), "up": linear(I, H, False),
            "before": [residual() for _ in range(cfg["num_before_skip"])],
            "lin": linear(H, H),
            "after": [residual() for _ in range(cfg["num_after_skip"])],
        } for _ in range(cfg["num_blocks"])],
        "outputs": [{
            "rbf": linear(R, H, False), "up": linear(H, O, False),
            "lins": [linear(O, O) for _ in range(cfg["num_output_layers"])],
            "out": linear(O, 1, False),
        } for _ in range(cfg["num_blocks"] + 1)],
    }


def envelope(x, exponent: int):
    p = exponent + 1
    poly = (-(p + 1) * (p + 2) / 2 * x ** (p - 1) + p * (p + 2) * x ** p
            - p * (p + 1) / 2 * x ** (p + 1))
    return jnp.where(x < 1.0, 1.0 / x + poly, 0.0)


def blocks_of(fn, arrays: tuple, block: int | None):
    """``fn`` over blocks of ``block`` leading rows, one block live at a
    time and recomputed in the backward pass."""
    n = arrays[0].shape[0]
    if block is None or n <= block:
        return fn(*arrays)
    k = -(-n // block)
    pad = k * block - n
    padded = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (k, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), padded)
    return out.reshape((k * block,) + out.shape[2:])[:n]


def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  atom_block: int | None = 128, faults: tuple = ()):
    """Per-atom energies (N,). ``edges`` = (src, dst, shift): directed
    pairs with positions[dst] - positions[src] + shift the short vector;
    pairs at or beyond the cutoff are not in the graph."""
    assert set(faults) <= set(FAULTS), faults
    rq = rounder(precision)
    src, dst, shift = edges
    n = positions.shape[0]
    c = float(cfg["cutoff"])
    L, R = cfg["num_spherical"], cfg["num_radial"]
    k_max = int(cfg["reference_max_neighbours"])

    vec = positions[dst] - positions[src] + shift          # j -> i: r_i - r_j
    dt = vec.dtype
    d = jnp.linalg.norm(vec, axis=-1)
    in_graph = d < c
    x = jnp.where(in_graph, d / c, 0.5)
    u = envelope(x, int(cfg["envelope_exponent"])) * in_graph
    rbf = u[:, None] * jnp.sin(params["rbf_freq"] * x[:, None])       # (E, R)
    z = jnp.asarray(tables.roots, dt)
    rad = jnp.stack([_jl_finite(jnp, l, z[l] * x[:, None])
                     for l in range(L)], axis=1)
    rad = u[:, None, None] * jnp.asarray(tables.norms, dt) * rad

    # per centre atom j: the edges k -> j in, the edges j -> i out
    ins, has_in, over_in = bond_table(dst, in_graph, n, k_max)
    outs, has_out, over_out = bond_table(src, in_graph, n, k_max)
    overflow = over_in | over_out

    emb = params["embedding"]
    h = rq(emb["species"])[species]
    r = rq(silu(dense(emb["rbf"], rbf, rq)))
    m = rq(silu(dense(emb["lin"], jnp.concatenate([h[dst], h[src], r],
                                                  axis=-1), rq)))
    # Y_l0 = sqrt((2l+1)/4pi) sum_k c_lk cos^k, an elementwise sum
    coefficients = jnp.asarray(tables.legendre * np.sqrt(
        (2 * np.arange(L) + 1) / (4 * np.pi))[:, None], dt)

    def live_of(ins_b, has_in_b, outs_b, has_out_b):
        """(B, K in, K out): which pairs of the block's tables are
        triplets (k != i as images unless the fault makes them live)."""
        live = has_in_b[:, :, None] & has_out_b[:, None, :]
        if "backtracking" in faults:
            return live
        back = (src[ins_b][:, :, None] == dst[outs_b][:, None, :]) & jnp.all(
            jnp.abs(shift[ins_b][:, :, None] + shift[outs_b][:, None, :])
            < 1e-3, axis=-1)
        return live & ~back

    tables_ = (ins, has_in, outs, has_out)
    n_triplets = blocks_of(
        lambda *t: live_of(*t).sum(axis=(1, 2)), tables_, atom_block).sum()
    jax.debug.callback(tables.report, in_graph.sum(), n_triplets, overflow)

    def triplets(ins_b, has_in_b, outs_b, has_out_b, s, w1, w2):
        """(B, K out, I): the triplet sum T of each edge out of the
        block's centres, over the edges into them."""
        v_kj, v_ji = vec[ins_b][:, :, None], vec[outs_b][:, None, :]
        cos = jnp.sum(v_kj * v_ji, axis=-1) / (
            d[ins_b][:, :, None] * d[outs_b][:, None, :])
        if "chgnet_sign" in faults:
            cos = -cos
        # cos^0 .. cos^(L-1) by products: the slope of cos ** 0 is NaN at
        # cos = 0 (0 x 0^-1), which a table slot or a right angle can hit
        powers = [jnp.ones_like(cos)]
        for _ in range(L - 1):
            powers.append(powers[-1] * cos)
        powers = jnp.stack(powers, axis=-1)[..., None, :]    # (B, K, K, 1, L)
        Y = (powers * coefficients).sum(axis=-1)             # (B, K, K, L)
        a = rad[ins_b][:, :, None, :, :] * Y[..., None]      # (B, K, K, L, R)
        if "n_major" in faults:
            a = jnp.swapaxes(a, -1, -2)
        a = a.reshape(a.shape[:3] + (L * R,))
        live = live_of(ins_b, has_in_b, outs_b, has_out_b)
        sbf = rq(dense(w2, rq(dense(w1, a, rq)), rq))
        msg = rq(s[ins_b][:, :, None, :] * sbf)
        return (msg * live[..., None]).sum(axis=1)

    def output(blk, m):
        msg = rq(rq(dense(blk["rbf"], rbf, rq)) * m) * in_graph[:, None]
        y = rq(jax.ops.segment_sum(msg, dst, num_segments=n))
        y = rq(dense(blk["up"], y, rq))
        for layer in blk["lins"]:
            y = rq(silu(dense(layer, y, rq)))
        return rq(dense(blk["out"], y, rq))[:, 0]

    def residual(p, y):
        return rq(y + rq(silu(dense(p["lin2"], rq(silu(
            dense(p["lin1"], y, rq))), rq))))

    energies = output(params["outputs"][0], m)
    last = cfg["num_blocks"] - 1
    for t, blk in enumerate(params["interactions"]):
        x_ji = rq(silu(dense(blk["ji"], m, rq)))
        x_kj = rq(silu(dense(blk["kj"], m, rq)))
        rbf_kj = rq(dense(blk["rbf2"], rq(dense(blk["rbf1"], rbf, rq)), rq))
        s = rq(silu(dense(blk["down"], rq(x_kj * rbf_kj), rq)))

        def block(ins_b, has_in_b, outs_b, has_out_b, blk=blk, s=s):
            return triplets(ins_b, has_in_b, outs_b, has_out_b, s,
                            blk["sbf1"], blk["sbf2"])

        per_centre = blocks_of(block, tables_, atom_block)  # (N, K, I)
        per_centre = per_centre * has_out[..., None]
        T = rq(jnp.zeros((vec.shape[0], s.shape[-1]), dt)
               .at[outs].add(per_centre))
        if "drop_last_T" in faults and t == last:
            T = jnp.zeros_like(T)
        y = rq(x_ji + rq(silu(dense(blk["up"], T, rq))))
        for p in blk["before"]:
            y = residual(p, y)
        y = rq(rq(silu(dense(blk["lin"], y, rq))) + m)
        for p in blk["after"]:
            y = residual(p, y)
        m = y
        energies = energies + output(params["outputs"][t + 1], m)
    # a product, so that the forces are NaN too
    return energies * jnp.where(overflow, jnp.nan, 1.0)
