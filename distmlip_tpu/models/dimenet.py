"""DimeNet++: directional message passing on the bonds of the graph.

The architecture of Gasteiger (Klicpera), Giri, Margraf and Guennemann,
"Fast and Uncertainty-Aware Directional Message Passing for
Non-Equilibrium Molecules" (arXiv:2011.14115), on DimeNet
(arXiv:2003.03123), at the sizes of ``torch_geometric.nn.DimeNetPlusPlus``
``from_qm9_pretrained``. The hidden state ``m_ji`` lives on every directed
edge j -> i inside the cutoff, and every block passes messages along the
triplets k -> j -> i (k != i)::

    e_n(d)  = u(d/c) sin(f_n d/c)                 radial rows, f_n from n pi
    a_ln    = u(x_kj) N_ln j_l(z_ln x_kj) Y_l0(theta_kji)     spherical rows
    m_ji    = s(W_E [h_i | h_j | s(W_R e(d_ji) + b)] + b),   h = Emb[Z]
    block   A = s(W_ji m_ji + b)
            s_kj = s(W_down (s(W_kj m_kj + b) * W_rbf2 W_rbf1 e(d_kj)))
            T_ji = sum_k s_kj * W_sbf2 W_sbf1 a(d_kj, theta_kji)
            h = R(A + s(W_up T_ji)); h = s(W h + b) + m_ji; m_ji = R(R(h))
    output  P_i = w s(W_3 s(W_2 s(W_1 W_up sum_j (W_rbf e(d_ji)) m_ji)))
    E       = sum_i sum_b P_i^(b)

with s = SiLU, R(y) = y + s(W_2 s(W_1 y + b) + b), u DimeNet's envelope
(``ops/radial.dimenet_envelope``), cos theta_kji = (r_j - r_i) . (r_k -
r_j) / (d_ji d_kj) (the negative of CHGNet's angle at the centre) and the
spherical rows l-major (flat index ``6 l + n``).

Where the work runs: the bonds are the graph's bond nodes with
``bond_cutoff == cutoff``, so every built edge is a bond and the in-line
table (``LocalGraph``) is the triplet set, k != i by atom id as the
partitioner joins it. The triplet sum is :meth:`LocalGraph.in_line_sum`, a
scan over the table's slabs: per slab the 124-wide float32 row of the
source bond ``[s_kj | R_kj | vector | length]``, read as two rows a centre
atom repeated over the centre's bonds (its transpose a sum onto the
centres, sorted); the destination's rows are the slab's own. ``W_sbf1`` is
applied per bond, not per triplet:
``a_ln = rad_ln(d_kj) Y_l(theta)``, so ``W_sbf1 a = sum_l Y_l R_l`` with
``R_l = sum_n rad_ln W_sbf1[l, n]`` (``(b_cap, 7, 8)`` a block), which is
the same sum in another order and leaves no basis over the slots. The sum
onto atoms is one scatter-add of the owned bond rows by their destination
atom. With several partitions the bond halo exchange refreshes the halo
bond rows of ``s`` (64 wide, where ``m`` is 128) once a block, and of the
bond geometry once a step.

Dtypes: linears and features in the configured compute type; geometry,
cos theta, the envelope, the radial and spherical rows, ``R``, the triplet
sum's accumulator and the energy sums in float32.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import radial
from ..ops.segment import masked_segment_sum
from ..telemetry import scope


@dataclass(frozen=True)
class DimeNetPPConfig:
    num_species: int = 95
    hidden_channels: int = 128
    out_emb_channels: int = 256
    int_emb_size: int = 64
    basis_emb_size: int = 8
    num_blocks: int = 4
    num_spherical: int = 7
    num_radial: int = 6
    cutoff: float = 5.0
    envelope_exponent: int = 5
    num_before_skip: int = 1
    num_after_skip: int = 2
    num_output_layers: int = 3
    dtype: str = "float32"

    # the bond graph is every edge inside the cutoff
    @property
    def bond_cutoff(self) -> float:
        return self.cutoff

    use_bond_graph = True


def triplet_cos(geo_kj, geo_ji):
    """cos theta_kji of the triplet k -> j -> i from the ``[vector |
    length]`` rows of its bonds (a bond's vector is r_dst - r_src):
    (r_j - r_i) . (r_k - r_j) / (d_ji d_kj), which is the product of the two
    bond vectors as they are. +1 on a straight chain k, j, i; CHGNet's angle
    at the centre has the other sign. Padded rows (length 0) read 0."""
    return jnp.sum(geo_kj[:, :3] * geo_ji[:, :3], axis=-1) / (
        jnp.maximum(geo_kj[:, 3], 1e-6) * jnp.maximum(geo_ji[:, 3], 1e-6))


def _dense(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def _residual(p, x):
    return x + jax.nn.silu(_dense(p["lin2"], jax.nn.silu(_dense(p["lin1"], x))))


class DimeNetPP:
    supports_compute_dtype = True  # energy_fn honors cfg.dtype="bfloat16"

    def __init__(self, config: DimeNetPPConfig = DimeNetPPConfig()):
        self.cfg = config

    # ---- parameters ----
    def init(self, key) -> dict:
        """PyG's layout and sizes: embedding rows uniform in +-sqrt(3),
        every linear Glorot-normal with zero biases, frequencies n pi. PyG
        zeroes the last linear of each output block; here it is drawn like
        the rest, so that the energy depends on the atoms from the start."""
        c = self.cfg
        H, O, I, B = (c.hidden_channels, c.out_emb_channels, c.int_emb_size,
                      c.basis_emb_size)
        keys = iter(jax.random.split(key, 256))

        def lin(d_in, d_out, bias=True):
            std = np.sqrt(2.0 / (d_in + d_out))
            p = {"w": std * jax.random.normal(next(keys), (d_in, d_out))}
            if bias:
                p["b"] = jnp.zeros((d_out,))
            return p

        res = lambda: {"lin1": lin(H, H), "lin2": lin(H, H)}
        return {
            "rbf_freq": jnp.pi * jnp.arange(1, c.num_radial + 1,
                                            dtype=jnp.float32),
            "embedding": {
                "species": jax.random.uniform(
                    next(keys), (c.num_species, H), minval=-np.sqrt(3.0),
                    maxval=np.sqrt(3.0)),
                "rbf": lin(c.num_radial, H), "lin": lin(3 * H, H)},
            "interactions": [{
                "rbf1": lin(c.num_radial, B, False), "rbf2": lin(B, H, False),
                "sbf1": lin(c.num_spherical * c.num_radial, B, False),
                "sbf2": lin(B, I, False),
                "kj": lin(H, H), "ji": lin(H, H),
                "down": lin(H, I, False), "up": lin(I, H, False),
                "before": [res() for _ in range(c.num_before_skip)],
                "lin": lin(H, H),
                "after": [res() for _ in range(c.num_after_skip)],
            } for _ in range(c.num_blocks)],
            "outputs": [{
                "rbf": lin(c.num_radial, H, False), "up": lin(H, O, False),
                "lins": [lin(O, O) for _ in range(c.num_output_layers)],
                "out": lin(O, 1, False),
            } for _ in range(c.num_blocks + 1)],
        }

    # ---- forward ----
    def energy_fn(self, params, lg, positions):
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        f32 = positions.dtype
        L, N = cfg.num_spherical, cfg.num_radial

        with scope("edge_update"):
            p = jax.tree.map(lambda x: x.astype(dtype),
                             {k: v for k, v in params.items()
                              if k != "rbf_freq"})

        with scope("edge_geometry"):
            # every bond row's [vector | length] (owned rows from their
            # edges, halo rows by the bond halo exchange) and the atoms at
            # its ends (-1 on a halo or padded row)
            vec = lg.edge_vectors(positions)
            d_e = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            geo = lg.edge_to_bond(
                jnp.concatenate([vec, d_e[:, None]], axis=-1)
                * lg.edge_mask[:, None], jnp.zeros((lg.b_cap, 4), f32))
            ends = lg.edge_to_bond(
                jnp.stack([lg.edge_src, lg.edge_dst], axis=-1) + 1,
                jnp.zeros((lg.b_cap, 2), lg.edge_src.dtype)) - 1
            geo = lg.bond_halo_exchange(geo)
            d = geo[:, 3]
            real = d > 0.0
            x = jnp.where(real, d / cfg.cutoff, 1.0)
            env = radial.dimenet_envelope(x, cfg.envelope_exponent)
            rbf = (env[:, None] * jnp.sin(
                params["rbf_freq"].astype(f32) * x[:, None])).astype(dtype)
            owned = ends[:, 0] >= 0
            src_atom = jnp.where(owned, ends[:, 0], 0)
            dst_atom = jnp.where(owned, ends[:, 1], 0)
            # halo and padded rows sum onto no atom (n_cap is dropped)
            onto = jnp.where(owned, ends[:, 1], lg.n_cap)

        with scope("triplet_basis"):
            roots, norms = radial.spherical_bessel_table(L, N)
            jl = radial.spherical_bessel_jl(
                L, jnp.asarray(roots, f32) * x[:, None, None])
            rad = env[:, None, None] * jnp.asarray(norms, f32) * jl  # (b, L, N)

        with scope("edge_update"):
            emb = p["embedding"]
            h = emb["species"][lg.species]
            r = jax.nn.silu(_dense(emb["rbf"], rbf))
            m = jax.nn.silu(_dense(emb["lin"], jnp.concatenate(
                [h[dst_atom], h[src_atom], r], axis=-1)))

        energy = self._output(p["outputs"][0], lg, m, rbf, onto, f32)
        for t, blk in enumerate(p["interactions"]):
            m = self._interaction(blk, lg, m, rbf, rad, geo, dtype, f32)
            out = self._output(p["outputs"][t + 1], lg, m, rbf, onto, f32)
            with scope("readout"):
                energy = energy + out
        return energy

    def _interaction(self, blk, lg, m, rbf, rad, geo, dtype, f32):
        """One interaction block on the bond rows ``m`` (``(b_cap, H)``)."""
        cfg = self.cfg
        L, B = cfg.num_spherical, cfg.basis_emb_size
        with scope("edge_update"):
            a = jax.nn.silu(_dense(blk["ji"], m))
            kj = jax.nn.silu(_dense(blk["kj"], m))
            kj = kj * _dense(blk["rbf2"], _dense(blk["rbf1"], rbf))
            s = jax.nn.silu(_dense(blk["down"], kj))         # (b_cap, I)
        # the source bond of a line may be a halo row: its s from its owner
        s = lg.bond_halo_exchange(s)

        with scope("triplet_message"):
            # W_sbf1 on the radial part, per bond: R_l = sum_n rad_ln W[l, n]
            # operands in the compute type, the sum in float32 (a float32
            # product of the rounded operands: no bf16 x bf16 -> f32 dot
            # on the CPU)
            w1 = blk["sbf1"]["w"].reshape(L, cfg.num_radial, B)
            R = jnp.einsum("bln,lnk->blk", rad.astype(dtype).astype(f32),
                           w1.astype(f32))
            table = jnp.concatenate(
                [s.astype(jnp.float32), R.reshape(-1, L * B), geo], axis=-1)
            I = s.shape[-1]

            def line(src, geo_dst):
                with scope("triplet_basis"):
                    Y = radial.legendre_rows(
                        triplet_cos(src[:, -4:], geo_dst), L)  # (b_cap, L)
                sbf = jnp.einsum("bl,blk->bk", Y,
                                 src[:, I:I + L * B].reshape(-1, L, B))
                sbf = _dense(blk["sbf2"], sbf.astype(dtype))
                return src[:, :I].astype(dtype) * sbf

            T = lg.in_line_sum(line, table, (geo,), I).astype(dtype)

        with scope("edge_update"):
            h = a + jax.nn.silu(_dense(blk["up"], T))
            for res in blk["before"]:
                h = _residual(res, h)
            h = jax.nn.silu(_dense(blk["lin"], h)) + m
            for res in blk["after"]:
                h = _residual(res, h)
            return h

    def _output(self, blk, lg, m, rbf, onto, f32):
        """One output block: per-atom energies ``(n_cap,)`` in float32."""
        with scope("edge_aggregate"):
            msg = _dense(blk["rbf"], rbf) * m
            y = masked_segment_sum(msg, onto, lg.n_cap)
        with scope("readout"):
            y = _dense(blk["up"], y)
            for layer in blk["lins"]:
                y = jax.nn.silu(_dense(layer, y))
            return _dense(blk["out"], y)[:, 0].astype(f32)
