"""EquiformerV2: equivariant graph attention over SO(2) convolutions.

The architecture of Liao, Wood, Das and Smidt, "EquiformerV2: Improved
Equivariant Transformer for Scaling to Higher-Degree Representations"
(arXiv:2306.12059), as fairchem's ``equiformer_v2`` builds it, at the OC20
S2EF 31M model's sizes (``EquiformerV2Config``'s defaults). Node state x_i:
``(N, (lmax+1)^2, C)``, coefficients l-major in e3nn's basis
(``ops/so3_e3nn``). Edge j -> i (source j, target i), D_ij the Wigner
blocks into the frame whose polar axis points from i to j, narrowed to
``|m| <= mmax`` there; sigma = SiLU::

    e^u_ij  = [gauss(d_ij) | S_u[Z_j] | T_u[Z_i]]          per use u
    Rad(e)  = W3 s(LN(W2 s(LN(W1 e + b1)) + b2)) + b3
    x_i     = [A[Z_i], 0, ..] + 1/avg_degree sum_j D_ij^T P0(Rad_deg(e_ij))
    block   x += Attn(N1(x));  x += FFN(N2(x))
    Attn    u = D_ij [x_j | x_i];  y = Conv1(u; Rad_b(e_ij))
            y_0 -> [message (lmax+1) x H | a (heads x A) | q (H)]
            z^h = w_h . SLReLU(LN_a(a^h))
            alpha^h = softmax_j(z^h) over every live in-edge of i
            y <- F_e s(G_e y), with its l = 0 coefficient <- s(q)
            v = Conv2(y);  m_i = Proj(sum_j D_ij^T (alpha^h v^h))
    FFN     q' = s(W_s x_0 + b);  h = F_n W_c s(W_b s(W_a G_n Lin1(x)));
            h_0 <- q';  out = Lin2(h)
    E       = sum_i FFN_E(N(x))_i,0 / avg_num_nodes

N is ``layer_norm_sh`` (a LayerNorm on the scalars, the l >= 1 rows over the
root of their mean square per coefficient and channel, a weight per degree),
Conv1/Conv2 are SO(2) convolutions (``escn_md.so2_conv``: per |m| one
linear map, m > 0 as a complex pair), SLReLU(t) = 0.6 t + 0.4 t (2 sigmoid(t)
- 1), and G, F the grids of ``ops/s2_grid`` (the edge's narrowed and scaled
above mmax, the node's whole). No envelope: an edge is live inside the
cutoff and dead outside it (the graph's skin edges are masked). Forces are
-dE/dr; fairchem's direct-force head is not built (MD needs conservative
forces), and :func:`force_head_size` counts what it would hold.

Where the work runs: per block two passes over the edge chunks
(``LocalGraph.edge_chunks``), each building its chunk's Wigner blocks.
Pass A (:meth:`LocalGraph.map_edges`) computes only what the logits need:
the m = 0 piece of the rotation, the m = 0 rows of the radial function and
conv 1's alpha rows; the softmax over every live in-edge of an atom then
runs on the whole ``(K, chunk, heads)`` array, so it is exact however the
chunks and the interior/frontier segments cut an atom's in-edges. Pass B
(:meth:`LocalGraph.scan_edges`) takes alpha as one more chunked row and
sums the messages onto the targets. Every in-edge of an owned atom is on
its partition, so the softmax needs no exchange; the halo rows of x are
refreshed once a block.

Dtypes: geometry, Gaussians, Wigner columns, logits, softmax and the
per-atom energies in float32; features, radial functions, convolutions,
grids, norms and feed-forwards in the configured compute type (a matrix
unit sums bfloat16 products in float32 and rounds once; no float32 copy of
a grid is made).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..geometry import COORD_PRECISION
from ..kernels.dispatch import fused_s2_activation, fused_wigner_rotate
from ..kernels.so3 import wigner_cols
from ..ops.s2_grid import grid_tables
from ..ops.segment import masked_segment_softmax
from ..ops.so3_e3nn import CoeffLayout, wigner_blocks_from_edges
from ..telemetry import scope
from .escn_md import _linear, _linear_init, _rad_apply, _rad_init, so2_conv


# the weights of the logits, kept in float32 under a bfloat16 compute type
FLOAT32_WEIGHTS = ("alpha_norm", "alpha_dot")


@dataclass(frozen=True)
class EquiformerV2Config:
    num_species: int = 90
    sphere_channels: int = 128          # C
    lmax: int = 4
    mmax: int = 2
    num_layers: int = 8
    attn_hidden_channels: int = 64      # H
    num_heads: int = 8
    attn_alpha_channels: int = 64       # A
    attn_value_channels: int = 16       # V
    ffn_hidden_channels: int = 128
    edge_channels: int = 128
    grid_resolution: int = 18
    num_distance_basis: int = 512
    basis_width_scalar: float = 2.0
    cutoff: float = 6.0
    avg_degree: float = 23.395
    avg_num_nodes: float = 77.81317
    edge_chunk: int = 32768
    remat: bool | str = True
    dtype: str = "float32"

    @property
    def sphere_dim(self) -> int:
        return (self.lmax + 1) ** 2


def slrelu(x):
    """fairchem's SmoothLeakyReLU at negative slope 0.2."""
    return 0.6 * x + 0.4 * x * (2.0 * jax.nn.sigmoid(x) - 1.0)


def force_head_size(cfg: EquiformerV2Config) -> int:
    """Parameters of fairchem's direct-force head (an attention block with
    one output channel a degree), which this model does not build."""
    lay = CoeffLayout(cfg.lmax, cfg.mmax)
    return (2 * cfg.num_species * cfg.edge_channels
            + _attention_size(cfg, lay, out_channels=1))


def _so2_size(lay, c_in, c_out, extra):
    size = lay.m_size(0) * c_in * (lay.m_size(0) * c_out + extra)
    size += lay.m_size(0) * c_out + extra                    # m = 0 bias
    for m in range(1, lay.m_max + 1):
        size += 2 * lay.m_size(m) * c_out * lay.m_size(m) * c_in
    return size


def _radial_size(cfg, d_out):
    ce = cfg.edge_channels
    d_in = cfg.num_distance_basis + 2 * ce
    return (d_in + 1) * ce + 2 * ce + (ce + 1) * ce + 2 * ce + (ce + 1) * d_out


def _attention_size(cfg, lay, out_channels):
    """Parameters of an attention block outside its species tables."""
    C, H = cfg.sphere_channels, cfg.attn_hidden_channels
    heads, A = cfg.num_heads, cfg.attn_alpha_channels
    HV = heads * cfg.attn_value_channels
    slots = sum(lay.m_size(m) for m in range(lay.m_max + 1))
    return (_radial_size(cfg, slots * 2 * C)
            + _so2_size(lay, 2 * C, H, heads * A + H) + 2 * A + heads * A
            + _so2_size(lay, H, HV, 0)
            + (cfg.lmax + 1) * out_channels * HV + out_channels)


class EquiformerV2:
    supports_compute_dtype = True

    def __init__(self, config: EquiformerV2Config = EquiformerV2Config()):
        if config.lmax > 6:
            raise NotImplementedError("lmax > 6: extend ops/so3 tables")
        self.cfg = config
        self.lay = CoeffLayout(config.lmax, config.mmax)
        # pass A rotates only the m = 0 piece into the edge frame
        self.lay0 = CoeffLayout(config.lmax, 0)
        to_grid, from_grid = grid_tables(config.lmax,
                                         config.grid_resolution)
        self.node_grid = (to_grid, from_grid)
        # the edge frame's columns, piece by piece (lay.signed_ms, l-major)
        cols = [l * l + l + m for m in self.lay.signed_ms
                for l in range(abs(m), config.lmax + 1)]
        to_grid, from_grid = grid_tables(config.lmax,
                                         config.grid_resolution, config.mmax)
        self.edge_grid = (to_grid[:, cols], from_grid[cols])

    # ---- parameters ------------------------------------------------------
    def init(self, key) -> dict:
        """Normal species tables, torch-style uniform linears ``(out, in)``,
        norms at one and zero. The same tree as the benchmark reference's
        (``benchmark/reference/equiformer.init_params``)."""
        cfg, lay = self.cfg, self.lay
        C, Ce, Z = cfg.sphere_channels, cfg.edge_channels, cfg.num_species
        H, heads = cfg.attn_hidden_channels, cfg.num_heads
        A, HV = cfg.attn_alpha_channels, heads * cfg.attn_value_channels
        F, L = cfg.ffn_hidden_channels, cfg.lmax + 1
        dx = cfg.num_distance_basis + 2 * Ce
        slots = sum(lay.m_size(m) for m in range(lay.m_max + 1))
        ks = iter(jax.random.split(key, 16 + 32 * cfg.num_layers))
        normal = lambda *shape: jax.random.normal(next(ks), shape)

        def uniform(shape, fan_in):
            lim = 1.0 / np.sqrt(fan_in)
            return jax.random.uniform(next(ks), shape, minval=-lim,
                                      maxval=lim)

        def so2(c_in, c_out, extra):
            p = {}
            for m in range(lay.m_max + 1):
                n = lay.m_size(m)
                d_out = n * c_out + extra if m == 0 else 2 * n * c_out
                p[f"m{m}"] = uniform((d_out, n * c_in), n * c_in)
                if m == 0:
                    p["m0_b"] = uniform((d_out,), n * c_in)
            return p

        def norm():
            return {"g": jnp.ones((L, C)), "b": jnp.zeros((C,))}

        def per_degree(c_in, c_out):
            return {"w": uniform((L, c_out, c_in), c_in),
                    "b": uniform((c_out,), c_in)}

        def ffn(c_out):
            return {"scalar": _linear_init(next(ks), C, F),
                    "lin1": per_degree(C, F),
                    "grid": [{"w": uniform((F, F), F)} for _ in range(3)],
                    "lin2": per_degree(F, c_out)}

        params = {
            "sphere_embedding": normal(Z, C),
            "edge_degree": {"source": normal(Z, Ce), "target": normal(Z, Ce),
                            "rad": _rad_init(next(ks), [dx, Ce, Ce, L * C])},
            "blocks": [],
            "norm": norm(),
            "energy": ffn(1),
        }
        for _ in range(cfg.num_layers):
            params["blocks"].append({
                "norm1": norm(),
                "attn": {
                    "source": normal(Z, Ce), "target": normal(Z, Ce),
                    "rad": _rad_init(next(ks), [dx, Ce, Ce, slots * 2 * C]),
                    "conv1": so2(2 * C, H, heads * A + H),
                    "alpha_norm": {"g": jnp.ones((A,)), "b": jnp.zeros((A,))},
                    "alpha_dot": uniform((heads, A), A),
                    "conv2": so2(H, HV, 0),
                    "proj": per_degree(HV, C),
                },
                "norm2": norm(),
                "ffn": ffn(C),
            })
        return params

    # ---- node pieces -----------------------------------------------------
    def _expand(self, w, dtype):
        """(lmax+1, ...) per-degree weights -> (S, ...) per coefficient."""
        reps = np.array([2 * l + 1 for l in range(self.cfg.lmax + 1)])
        return jnp.repeat(w.astype(dtype), reps, axis=0)

    def _norm_sh(self, p, x):
        """``layer_norm_sh`` in float32, the result in x's dtype."""
        dtype, x = x.dtype, x.astype(jnp.float32)
        s0 = x[:, 0]
        mu = jnp.mean(s0, axis=-1, keepdims=True)
        var = jnp.mean((s0 - mu) ** 2, axis=-1, keepdims=True)
        y0 = (s0 - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"][0] + p["b"]
        rest = x[:, 1:]
        ms = jnp.mean(jnp.sum(rest * rest, axis=1), axis=-1) / (
            self.cfg.sphere_dim - 1)
        rest = (rest * jax.lax.rsqrt(ms + 1e-5)[:, None, None]
                * self._expand(p["g"], jnp.float32)[1:])
        return jnp.concatenate([y0[:, None], rest], axis=1).astype(dtype)

    def _per_degree(self, p, x):
        """A linear map per degree, the bias on the scalars."""
        y = jnp.einsum("nsc,sdc->nsd", x, self._expand(p["w"], x.dtype))
        return y.at[:, 0].add(p["b"].astype(x.dtype))

    def _ffn(self, p, x):
        """Gated grid feed-forward: the pointwise MLP on the node grid, the
        scalars replaced by the gate of the input's scalars. Checkpointed:
        the backward runs it again rather than keep its grid values (four
        ``(N, 324, 128)`` arrays a block, 1.6 GB at 4,096 atoms)."""
        return jax.checkpoint(self._grid_ffn)(p, x)

    def _grid_ffn(self, p, x):
        to_grid, from_grid = (jnp.asarray(t, x.dtype) for t in self.node_grid)
        gate = jax.nn.silu(_linear(p["scalar"], x[:, 0]))
        h = self._per_degree(p["lin1"], x)
        g = jnp.einsum("gs,nsh->ngh", to_grid, h)
        g = jax.nn.silu(g @ p["grid"][0]["w"].T)
        g = jax.nn.silu(g @ p["grid"][1]["w"].T)
        g = g @ p["grid"][2]["w"].T
        h = jnp.einsum("sg,ngh->nsh", from_grid, g)
        return self._per_degree(p["lin2"], h.at[:, 0].set(gate))

    # ---- edge pieces -----------------------------------------------------
    def _s2_activation(self, y, gate, kernels=None):
        """SiLU on the edge grid of the message pieces (H lanes a degree),
        then the l = 0 coefficient set to SiLU of the gate scalars."""
        lay, H = self.lay, self.cfg.attn_hidden_channels
        flat = jnp.concatenate([y[m] for m in lay.signed_ms], axis=-1)
        flat = fused_s2_activation(flat.reshape(flat.shape[0], -1, H),
                                   *self.edge_grid, kernels=kernels)
        flat = flat.at[:, 0].set(jax.nn.silu(gate))
        out, o = {}, 0
        for m in lay.signed_ms:
            n = lay.m_size(abs(m))
            out[m] = flat[:, o:o + n].reshape(flat.shape[0], n * H)
            o += n
        return out

    def _logits(self, p, a):
        """(E, heads * A) alpha channels -> (E, heads) float32 logits."""
        cfg = self.cfg
        a = a.astype(jnp.float32).reshape(-1, cfg.num_heads,
                                          cfg.attn_alpha_channels)
        mu = jnp.mean(a, axis=-1, keepdims=True)
        var = jnp.mean((a - mu) ** 2, axis=-1, keepdims=True)
        ln = p["alpha_norm"]
        a = (a - mu) * jax.lax.rsqrt(var + 1e-5) * ln["g"] + ln["b"]
        return jnp.einsum("eha,ha->eh", slrelu(a),
                          p["alpha_dot"].astype(jnp.float32))

    # ---- forward ---------------------------------------------------------
    def energy_fn(self, params, lg, positions):
        cfg, lay = self.cfg, self.lay
        C, S = cfg.sphere_channels, cfg.sphere_dim
        H, heads = cfg.attn_hidden_channels, cfg.num_heads
        A, V = cfg.attn_alpha_channels, cfg.attn_value_channels
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        z = jnp.asarray(lg.species)
        if cfg.dtype == "bfloat16":
            with scope("node_linear"):
                # every weight but the logits' (float32) in the compute type
                params = jax.tree_util.tree_map_with_path(
                    lambda path, w: w if any(
                        getattr(k, "key", None) in FLOAT32_WEIGHTS
                        for k in path) else w.astype(dtype), params)

        with scope("edge_geometry"):
            # pos[src] - pos[dst]: from the target to the source
            vec = -lg.edge_vectors(positions)
            d = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            live = lg.edge_mask & (d < cfg.cutoff)
            # dead edges get a fixed direction (atan2's slope at 0 is NaN)
            safe = jnp.asarray([0.0, 0.0, 1.0], dtype=positions.dtype)
            rhat = jnp.where(live[:, None],
                             vec / jnp.maximum(d, 1e-9)[:, None], safe)
        src, dst, mask, rhat, d, live = lg.edge_chunks(
            cfg.edge_chunk, rhat, d, live)
        with scope("edge_gather"):
            edge_xs = (src, dst, mask & live, rhat, d)
        rotate = partial(fused_wigner_rotate, kernels=lg.kernels)

        def columns(rhatc):
            """The float32 columns of a chunk's Wigner blocks, built in each
            pass (kept for the step, they and a cotangent a pass would cost
            2.7 KB an edge); products at COORD_PRECISION only in a float32
            model."""
            with scope("edge_rotation"):
                return wigner_cols(wigner_blocks_from_edges(
                    cfg.lmax, rhatc, precision=None
                    if dtype == jnp.bfloat16 else COORD_PRECISION))

        with scope("edge_geometry"):
            centers = jnp.linspace(0.0, cfg.cutoff, cfg.num_distance_basis)
        width = (cfg.basis_width_scalar * cfg.cutoff
                 / (cfg.num_distance_basis - 1))

        def radial_of(p, rad, srcc, dstc, dc):
            """A radial function of [gaussians | source | target species];
            the Gaussians a chunk at a time (512 a row: a whole-edge array
            of them and its cotangent would outweigh the chunk's work)."""
            with scope("edge_geometry"):
                gauss = jnp.exp(-0.5 * ((dc[:, None] - centers) / width) ** 2
                                ).astype(dtype)
            with scope("radial_mlp"):
                return _rad_apply(rad, jnp.concatenate(
                    [gauss, p["source"][z[srcc]].astype(dtype),
                     p["target"][z[dstc]].astype(dtype)], axis=-1))

        with scope("node_linear"):
            x = jnp.zeros((positions.shape[0], S, C), dtype=dtype)
            x = x.at[:, 0].set(params["sphere_embedding"][z].astype(dtype))

        def degree_chunk(srcc, dstc, maskc, rhatc, dc):
            p = params["edge_degree"]
            w = radial_of(p, p["rad"], srcc, dstc, dc)
            with scope("edge_rotation"):
                return rotate(columns(rhatc), {0: w}, lay=lay, to_edge=False)

        inv_deg = jnp.asarray(1.0 / cfg.avg_degree, dtype=dtype)
        with scope("embedding"):
            deg = lg.scan_edges(degree_chunk, edge_xs, (S * C,), dtype,
                                remat=cfg.remat)
            with scope("edge_aggregate"):
                deg = deg.reshape(-1, S, C)
            with scope("node_linear"):
                x = x + deg * inv_deg
            x = lg.halo_exchange(x)

        n0 = lay.m_size(0)
        for t, blk in enumerate(params["blocks"]):
            p = blk["attn"]
            with scope(f"layer{t}"):
                with scope("node_tensor"):
                    xn = self._norm_sh(blk["norm1"], x).reshape(-1, S * C)
                with scope("edge_attention"):
                    # conv 1's m = 0 rows: the logits' in pass A, the
                    # message's and the gate's in pass B
                    w0, b0 = p["conv1"]["m0"], p["conv1"]["m0_b"]
                    a0, a1 = n0 * H, n0 * H + heads * A
                    conv1_a = {"w": w0[a0:a1], "b": b0[a0:a1]}
                    conv1_b = {**p["conv1"],
                               "m0": jnp.concatenate([w0[:a0], w0[a1:]]),
                               "m0_b": jnp.concatenate([b0[:a0], b0[a1:]])}
                with scope("radial_mlp"):
                    # the radial scales of the m = 0 piece only
                    last = p["rad"]["lins"][-1]
                    rad_a = {**p["rad"], "lins": p["rad"]["lins"][:-1] + [
                        {"w": last["w"][:n0 * 2 * C],
                         "b": last["b"][:n0 * 2 * C]}]}

                def logit_chunk(srcc, dstc, maskc, rhatc, dc, p=p,
                                rad_a=rad_a, conv1_a=conv1_a):
                    r0 = radial_of(p, rad_a, srcc, dstc, dc)
                    with scope("edge_message"):
                        xs, xd = xn[srcc], xn[dstc]
                    with scope("edge_rotation"):
                        u0 = rotate(columns(rhatc), (xs, xd), lay=self.lay0,
                                    to_edge=True)[0]
                    with scope("edge_attention"):
                        return self._logits(p, _linear(conv1_a, u0 * r0))

                def value_chunk(srcc, dstc, maskc, rhatc, dc, alphac,
                                p=p, conv1_b=conv1_b):
                    rad = radial_of(p, p["rad"], srcc, dstc, dc)
                    with scope("edge_message"):
                        xs, xd = xn[srcc], xn[dstc]
                    with scope("edge_rotation"):
                        cols = columns(rhatc)
                        fr = rotate(cols, (xs, xd), lay=lay, to_edge=True)
                    with scope("edge_message"):
                        y, gate = so2_conv(lay, conv1_b, fr, rad, H)
                    with scope("s2_activation"):
                        y = self._s2_activation(y, gate, lg.kernels)
                    with scope("edge_message"):
                        v, _ = so2_conv(lay, p["conv2"], y, None, heads * V)
                    with scope("edge_attention"):
                        scale = jnp.repeat(alphac.astype(dtype), V, axis=-1)
                        v = {m: (f.reshape(f.shape[0], -1, heads * V)
                                 * scale[:, None]).reshape(f.shape)
                             for m, f in v.items()}
                    with scope("edge_rotation"):
                        return rotate(cols, v, lay=lay, to_edge=False)

                logits = lg.map_edges(logit_chunk, edge_xs, remat=cfg.remat)
                with scope("edge_attention"):
                    K, chunk = logits.shape[:2]
                    alpha = masked_segment_softmax(
                        logits.reshape(K * chunk, heads),
                        edge_xs[1].reshape(-1), lg.n_cap,
                        edge_xs[2].reshape(-1, 1)).reshape(K, chunk, heads)
                msg = lg.scan_edges(value_chunk, edge_xs + (alpha,),
                                    (S * heads * V,), dtype, remat=cfg.remat)
                with scope("edge_aggregate"):
                    msg = msg.reshape(-1, S, heads * V)
                with scope("node_tensor"):
                    x = x + self._per_degree(p["proj"], msg)
                    x = x + self._ffn(blk["ffn"],
                                      self._norm_sh(blk["norm2"], x))
                x = lg.halo_exchange(x)

        with scope("readout"):
            out = self._ffn(params["energy"],
                            self._norm_sh(params["norm"], x))
            return out[:, 0, 0].astype(jnp.float32) / cfg.avg_num_nodes
