"""ESCNMD — UMA/fairchem-parameterized eSCN backbone (weight-ingestible).

Where ``models/escn.py`` implements the eSCN *capabilities* in this repo's
own parameterization, this model reconstructs the fairchem ``eSCNMDBackbone``
surface tensor-for-tensor so pretrained UMA-family checkpoints can be
converted (MAPPINGS["escn"], models/convert.py) — the same discipline the
CHGNet/TensorNet rewrites applied to matgl. The reconstruction is pinned by
the reference wrapper's visible usage (reference
implementations/uma/escn_md.py):

- per-edge Wigner matrices via the Jd-table pipeline ``X(a) J X(b) J``
  in e3nn's y-polar basis (escn_md.py:74-130) — ops/so3_e3nn, tables
  derived from scratch and validated against the shipped Jd.pt;
- m-major coefficient packing for the SO(2) convolutions with (cos, sin)
  pairs mixed by (W_r, W_i) blocks (the to_m mapping, escn_md.py:117-129);
- mmax narrowing of edge-frame coefficients (escn_md.py:111-114);
- node features (N, (lmax+1)^2, C) with scalars initialized from the
  species embedding plus the per-system csd (charge/spin/dataset)
  embedding (escn_md.py:319-330);
- edge scalars = cat(gaussian distance expansion, source species emb,
  target species emb) feeding both the edge-degree embedding and the
  SO(2) radial scaling (escn_md.py:221-247);
- MOLE: SO(2) weights as per-system convex expert mixtures, coefficients
  replicated/psum-consistent across partitions (escn_md.py:343-357). The
  expert axis is collapsed ONCE a step, before the first edge scan
  (``_merge_experts``): a system is served by one merged model.

Internals fairchem does NOT expose through the wrapper (block wiring,
norm/activation/FFN details, RadialFunction shape) are RECONSTRUCTIONS from
the public equiformer_v2/eSCN lineage, documented inline; every such choice
is mirrored exactly by the float64 torch oracle in
tests/test_convert_escn.py, which is the converter's golden contract, and
listed under ``assumed`` in ``benchmark/configs/uma-s-1.json``, the
configuration the benchmark runs: the feed-forward is the SPECTRAL form (a
linear map per degree, gate activation, a linear map per degree) where
``uma-s-1`` is believed to set ``ff_type: grid``; ``RadialFunction`` is
Linear, LayerNorm, SiLU, Linear at ``edge_channels``; no neighbour cap
(``max_neighbors``) is applied. ``ESCNMDConfig``'s defaults are the tests'
sizes, not a published model's; the published widths live in that file.
Layout is channels-LAST (C in the TPU lane axis) per the round-3 finding.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..geometry import COORD_PRECISION
from ..kernels.dispatch import fused_wigner_rotate
from ..kernels.so3 import wigner_cols
from ..ops import radial
from ..ops.nn import cast_params_subtrees
from ..ops.so3_e3nn import CoeffLayout, wigner_blocks_from_edges
from ..telemetry import scope


@dataclass(frozen=True)
class ESCNMDConfig:
    max_num_elements: int = 100
    sphere_channels: int = 64       # C
    lmax: int = 2
    mmax: int = 2
    num_layers: int = 2
    hidden_channels: int = 64       # SO(2) conv hidden width
    edge_channels: int = 32         # species embeddings + rad_func hidden
    num_distance_basis: int = 64    # gaussian smearing resolution
    # fairchem's GaussianSmearing(start, stop, num, basis_width_scalar) uses
    # sigma = basis_width_scalar * offset spacing; the eSCN/equiformer_v2/UMA
    # lineage constructs it with basis_width_scalar=2.0. The scalar is a
    # module attr, NOT a checkpoint tensor, so conversion cannot recover it —
    # it must match here by construction (PARITY.md calibration point).
    basis_width_scalar: float = 2.0
    cutoff: float = 5.0
    avg_degree: float = 14.0        # edge-degree + message rescale factor
    num_experts: int = 1            # > 1: MOLE mixtures on SO(2) weights
    # csd conditioning (UMA charge/spin/dataset, escn_md.py:255-265)
    num_charges: int = 25
    charge_min: int = -12
    num_spins: int = 10
    num_datasets: int = 4
    use_envelope: bool = True       # smooth cutoff on messages + edge-degree
    edge_chunk: int = 32768         # lax.scan edge chunking (0 = off)
    remat: bool | str = True    # bool or checkpoint-policy name (ops/chunk)
    dtype: str = "float32"

    @property
    def sphere_dim(self) -> int:
        return (self.lmax + 1) ** 2


def _rand(key, shape, scale):
    return scale * jax.random.normal(key, shape)


def _linear_init(key, d_in, d_out, bias=True):
    k1, k2 = jax.random.split(key)
    lim = 1.0 / np.sqrt(d_in)
    p = {"w": jax.random.uniform(k1, (d_out, d_in), minval=-lim, maxval=lim)}
    if bias:
        p["b"] = jax.random.uniform(k2, (d_out,), minval=-lim, maxval=lim)
    return p


def _linear(p, x, precision=None):
    y = jnp.matmul(x, p["w"].T, precision=precision)
    if "b" in p:
        y = y + p["b"]
    return y


def _rad_init(key, dims):
    """RadialFunction (equiformer_v2 lineage): Linear -> LayerNorm -> SiLU
    per intermediate stage, bare Linear last. dims = [in, hidden, out]."""
    ks = jax.random.split(key, len(dims))
    p = {"lins": [], "lns": []}
    for i in range(len(dims) - 1):
        p["lins"].append(_linear_init(ks[i], dims[i], dims[i + 1]))
        if i < len(dims) - 2:
            p["lns"].append({"g": jnp.ones((dims[i + 1],)),
                             "b": jnp.zeros((dims[i + 1],))})
    return p


def _rad_apply(p, x):
    n = len(p["lins"])
    for i in range(n):
        x = _linear(p["lins"][i], x)
        if i < n - 1:
            ln = p["lns"][i]
            mu = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            x = (x - mu) * jax.lax.rsqrt(var + 1e-5) * ln["g"] + ln["b"]
            x = jax.nn.silu(x)
    return x


class ESCNMD:
    supports_compute_dtype = True

    def __init__(self, config: ESCNMDConfig = ESCNMDConfig()):
        if config.lmax > 6:
            raise NotImplementedError("lmax > 6: extend ops/so3 tables")
        self.cfg = config
        self.lay = CoeffLayout(config.lmax, config.mmax)
        # rad_func per-coefficient scaling vector length (input channels
        # per coefficient x paired coefficients per |m|), m = 0..mmax
        self._rad_splits = [
            self.lay.m_size(m) for m in range(self.lay.m_max + 1)
        ]

    # ---- parameters (shapes mirror the fairchem state dict 1:1) ----
    def init(self, key) -> dict:
        cfg = self.cfg
        C, H, Ce = cfg.sphere_channels, cfg.hidden_channels, cfg.edge_channels
        Dx = cfg.num_distance_basis + 2 * Ce
        K = cfg.num_experts
        lay = self.lay
        ks = iter(jax.random.split(key, 32 + cfg.num_layers * 16))

        def so2_weights(c_in, c_out, extra_m0, internal):
            p = {}
            m0_in = lay.m_size(0) * c_in
            m0_out = lay.m_size(0) * c_out + extra_m0
            shape0 = (K, m0_out, m0_in) if K > 1 else (m0_out, m0_in)
            lim = 1.0 / np.sqrt(m0_in)
            p["m0"] = jax.random.uniform(next(ks), shape0, minval=-lim,
                                         maxval=lim)
            p["m0_b"] = jnp.zeros((m0_out,))
            for m in range(1, lay.m_max + 1):
                nl = lay.m_size(m)
                shape = ((K, 2 * nl * c_out, nl * c_in) if K > 1
                         else (2 * nl * c_out, nl * c_in))
                lim = 1.0 / np.sqrt(nl * c_in)
                p[f"m{m}"] = jax.random.uniform(next(ks), shape, minval=-lim,
                                                maxval=lim)
            if not internal:
                p["rad"] = _rad_init(
                    next(ks), [Dx, Ce, sum(self._rad_splits) * c_in])
            return p

        params = {
            "sphere_embedding": {"w": _rand(next(ks), (cfg.max_num_elements, C), 1.0)},
            "source_embedding": {"w": _rand(next(ks), (cfg.max_num_elements, Ce), 1.0)},
            "target_embedding": {"w": _rand(next(ks), (cfg.max_num_elements, Ce), 1.0)},
            "csd": {
                "charge": {"w": _rand(next(ks), (cfg.num_charges, C), 1.0)},
                "spin": {"w": _rand(next(ks), (cfg.num_spins, C), 1.0)},
                "dataset": {"w": _rand(next(ks), (cfg.num_datasets, C), 1.0)},
                "mix": _linear_init(next(ks), 3 * C, C),
            },
            "edge_deg_rad": _rad_init(next(ks), [Dx, Ce, (cfg.lmax + 1) * C]),
            "blocks": [],
            "norm": {"w": jnp.ones((cfg.lmax + 1, C))},
            "energy_head": {
                "lin1": _linear_init(next(ks), C, C),
                "lin2": _linear_init(next(ks), C, 1),
            },
            "species_ref": {"w": jnp.zeros((cfg.max_num_elements,))},
        }
        if K > 1:
            params["mole_gate"] = {
                "lin1": _linear_init(next(ks), 2 * C, C),
                "lin2": _linear_init(next(ks), C, K),
            }
        for _ in range(cfg.num_layers):
            params["blocks"].append({
                "norm1": {"w": jnp.ones((cfg.lmax + 1, C))},
                "so2_1": so2_weights(2 * C, H, cfg.lmax * H, internal=False),
                "so2_2": so2_weights(H, C, 0, internal=True),
                "ff_norm": {"w": jnp.ones((cfg.lmax + 1, C))},
                "ff": {
                    "lin1": {"w": _rand(next(ks), (cfg.lmax + 1, H, C),
                                        1.0 / np.sqrt(C)),
                             "b": jnp.zeros((H,))},
                    "gate": _linear_init(next(ks), C, cfg.lmax * H),
                    "lin2": {"w": _rand(next(ks), (cfg.lmax + 1, C, H),
                                        1.0 / np.sqrt(H)),
                             "b": jnp.zeros((C,))},
                },
            })
        return params

    # ---- building blocks -------------------------------------------------
    def _rms_norm_sh(self, w, x):
        """Degree-balanced RMS norm with per-(l, channel) affine weight
        (rms_norm_sh: each coefficient weighted 1/(2l+1)/(lmax+1) so every
        degree contributes equally to the norm; no centering, no bias)."""
        cfg = self.cfg
        bal = np.zeros((cfg.sphere_dim,), dtype=np.float64)
        o = 0
        for l in range(cfg.lmax + 1):
            bal[o:o + 2 * l + 1] = 1.0 / ((2 * l + 1) * (cfg.lmax + 1))
            o += 2 * l + 1
        bal_j = jnp.asarray(bal, dtype=x.dtype)
        ms = jnp.mean(jnp.sum(x * x * bal_j[:, None], axis=-2), axis=-1)
        x = x * jax.lax.rsqrt(ms + 1e-12)[..., None, None]
        w_full = jnp.repeat(w.astype(x.dtype),
                            np.array([2 * l + 1 for l in range(cfg.lmax + 1)]),
                            axis=0)
        return x * w_full

    # Between the two rotations (``kernels/dispatch.fused_wigner_rotate``)
    # the edge-frame coefficients travel as PIECES: a dict ``{m: (E_c, nl_m *
    # c)}`` over the signed m of ``lay.signed_ms`` (0, +1, -1, ..), each the
    # l = |m|..lmax coefficients of that m side by side on the lane axis,
    # l-major, c channels a degree (``lay.piece_rows``; into the edge frame
    # the sender's channels, then the receiver's). That is the operand the
    # SO(2) weights multiply, and lab rows are flat ``(E_c, S * c)`` too, so
    # every step of a chunk is a matrix product, an elementwise product, a
    # static lane slice or the rotation's one pass: no (E_c, S, c) array, no
    # index list, no scatter.

    def _so2_conv(self, p, fr, rad_scale, c_out):
        """SO(2) convolution on pieces ``fr``; returns ``(pieces, extra)``
        with ``c_out`` lanes a degree and ``extra`` the m = 0 map's outputs
        beyond its ``nl_0 * c_out`` (the gate scalars; none: ``(E_c, 0)``).

        Per |m| one linear map of the piece; m > 0 uses the (W_r, W_i)
        complex pair structure y+ = W_r f+ - W_i f-, y- = W_r f- + W_i f+
        (the fairchem SO2_m_Convolution packing: fc output = [real | imag]
        halves). ``p["m<k>"]`` are plain ``(out, in)`` matrices: the expert
        axis is collapsed once a step (``_merge_experts``). ``rad_scale``:
        optional per-lane input scaling from the radial function, the pieces'
        lanes in the order m = 0, 1, .. and the same for +m and -m."""
        lay = self.lay
        if rad_scale is not None:
            offs = np.cumsum(
                [0] + [fr[m].shape[1] for m in range(lay.m_max + 1)])
            fr = {m: f * rad_scale[:, offs[abs(m)]:offs[abs(m) + 1]]
                  for m, f in fr.items()}
        d0 = lay.m_size(0) * c_out
        out0 = fr[0] @ p["m0"].T + p["m0_b"].astype(fr[0].dtype)
        out = {0: out0[:, :d0]}
        for m in range(1, lay.m_max + 1):
            d_out = lay.m_size(m) * c_out
            Wr, Wi = p[f"m{m}"][:d_out], p[f"m{m}"][d_out:]
            out[m] = fr[m] @ Wr.T - fr[-m] @ Wi.T
            out[-m] = fr[-m] @ Wr.T + fr[m] @ Wi.T
        return out, out0[:, d0:]

    def _gate_act(self, x, gates):
        """Gate activation on pieces ``x`` (H lanes a degree): scalars (the
        first H lanes of the m = 0 piece) -> silu, every l > 0 coefficient
        times sigmoid of its degree's gate scalars. ``gates`` ``(E_c, lmax *
        H)`` runs l-major from l = 1, so a piece's l = max(|m|, 1)..lmax
        lanes meet the gates' lanes from ``(max(|m|, 1) - 1) * H`` on."""
        H = self.cfg.hidden_channels
        g = jax.nn.sigmoid(gates)
        y = {m: x[m] * g[:, (abs(m) - 1) * H:] for m in x if m}
        y[0] = jnp.concatenate(
            [jax.nn.silu(x[0][:, :H]), x[0][:, H:] * g], axis=-1)
        return y

    def _ffn(self, p, x):
        """Feed-forward: per-l SO3 linear -> gate activation -> SO3 linear
        (gate-type FFN; scalars get the l=0 bias)."""
        cfg, lay = self.cfg, self.lay
        gates = _linear(p["gate"], x[:, 0, :])  # from input scalars
        h = jnp.einsum("nsc,shc->nsh", x, self._expand_lweights(p["lin1"]["w"], x.dtype))
        h = h.at[:, 0, :].add(p["lin1"]["b"].astype(x.dtype))
        # the gate activation of ``_gate_act`` on (N, S, H) node blocks
        g = jax.nn.sigmoid(gates.reshape(-1, cfg.lmax, cfg.hidden_channels))
        g = jnp.repeat(g, np.arange(1, cfg.lmax + 1) * 2 + 1, axis=1)
        h = jnp.concatenate(
            [jax.nn.silu(h[:, :1, :]), h[:, 1:, :] * g], axis=1)
        y = jnp.einsum("nsh,sch->nsc", h, self._expand_lweights(p["lin2"]["w"], x.dtype))
        y = y.at[:, 0, :].add(p["lin2"]["b"].astype(x.dtype))
        return y

    def _expand_lweights(self, w, dtype):
        """(lmax+1, a, b) per-degree weights -> (S, a, b) per-coefficient."""
        reps = np.array([2 * l + 1 for l in range(self.cfg.lmax + 1)])
        return jnp.repeat(w.astype(dtype), reps, axis=0)

    def _merge_experts(self, params, mole):
        """The expert axis of every SO(2) weight collapsed by the system's
        MOLE coefficients, in float32: the blocks again, each ``m<k>`` a
        plain ``(out, in)`` matrix. Once a step; the edge scans see one
        merged model, never the experts."""
        merged = []
        for blk in params["blocks"]:
            blk = dict(blk)
            for conv in ("so2_1", "so2_2"):
                blk[conv] = {
                    # a weighted sum on the vector unit, exact in float32
                    # (a float32 matmul is one bfloat16 pass on a TPU)
                    k: (jnp.sum(mole[:, None, None] * w, axis=0)
                        if k[0] == "m" and k[1:].isdigit() else w)
                    for k, w in blk[conv].items()}
            merged.append(blk)
        return {**params, "blocks": merged}

    # ---- forward ---------------------------------------------------------
    def energy_fn(self, params, lg, positions):
        """Which precision runs where (``cfg.dtype = "bfloat16"``): edge
        geometry, Wigner blocks (float32 columns into the rotation kernel,
        which multiplies and sums in float32 and rounds its result once; off
        the kernel path they are cast to bfloat16 at every use), the MOLE
        gate with its softmax and the expert collapse, the energy head and
        ``species_ref`` stay float32; radial functions, SO(2) convolutions,
        gate activation, norms and feed-forward run in the compute dtype.
        Every line sits in a stage scope (telemetry/stages.py)."""
        cfg = self.cfg
        C, H, S = cfg.sphere_channels, cfg.hidden_channels, cfg.sphere_dim
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        z = jnp.asarray(lg.species)

        with scope("node_linear"):
            # csd (charge/spin/dataset) system embedding, in the weights'
            # own precision: it feeds the gate as well as the node scalars
            sys_state = lg.system or {}
            qi = jnp.clip(
                jnp.asarray(sys_state.get("charge", 0)) - cfg.charge_min,
                0, cfg.num_charges - 1)
            si = jnp.clip(jnp.asarray(sys_state.get("spin", 0)), 0,
                          cfg.num_spins - 1)
            di = jnp.clip(jnp.asarray(sys_state.get("dataset", 0)), 0,
                          cfg.num_datasets - 1)
            csd = _linear(params["csd"]["mix"], jnp.concatenate([
                params["csd"]["charge"]["w"][qi],
                params["csd"]["spin"]["w"][si],
                params["csd"]["dataset"]["w"][di],
            ], axis=-1), precision="highest")  # (C,)

        # MOLE: psum-consistent composition + csd gate, then ONE merged set
        # of SO(2) weights for the step, before the first edge scan
        if cfg.num_experts > 1:
            if lg.struct_id is not None and lg.batch_size > 0:
                # the composition pool below spans the WHOLE graph — on a
                # packed batch that would silently mix structures' gates.
                # The base models/escn.py ESCN implements per-structure
                # gating; this UMA-MD variant does not (yet).
                raise NotImplementedError(
                    "ESCNMD's MOLE gate pools composition per system; "
                    "batched (packed) graphs would mix structures. Use "
                    "models.escn.ESCN for batched inference, or "
                    "num_experts=1.")
            with scope("expert_mix"):
                zemb_g = params["sphere_embedding"]["w"][z]
                owned = lg.owned_mask.astype(zemb_g.dtype)[:, None]
                comp = lg.psum(jnp.sum(zemb_g * owned, axis=0))
                count = lg.psum(jnp.sum(owned))
                gate_in = jnp.concatenate(
                    [comp / jnp.maximum(count, 1.0), csd])
                g = jax.nn.silu(_linear(params["mole_gate"]["lin1"], gate_in,
                                        precision="highest"))
                mole = jax.nn.softmax(_linear(params["mole_gate"]["lin2"], g,
                                              precision="highest"))
                params = self._merge_experts(params, mole)

        if cfg.dtype == "bfloat16":
            with scope("node_linear"):
                # after the collapse: the experts are read once, in float32
                params = cast_params_subtrees(
                    params, dtype,
                    keep_fp32=("species_ref", "energy_head", "mole_gate"))
        with scope("edge_geometry"):
            # fairchem's edge vector points src -> ... pos[src] - pos[dst]
            # (reference compute.py:169-173); lg.edge_vectors is dst - src
            vec = -lg.edge_vectors(positions)
            d = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            # masked (padding) edges get a fixed safe direction: their rhat
            # is (0,0,0), and atan2's gradient at the origin is NaN — which
            # would poison the whole force array through the 0-weighted
            # messages
            safe = jnp.asarray([0.0, 0.0, 1.0], dtype=positions.dtype)
            rhat = jnp.where(lg.edge_mask[:, None],
                             vec / jnp.maximum(d, 1e-9)[:, None], safe)
            env = (
                radial.polynomial_cutoff(d, cfg.cutoff) * lg.edge_mask
                if cfg.use_envelope else lg.edge_mask.astype(positions.dtype)
            ).astype(dtype)
            # gaussian smearing over [0, cutoff]; sigma = basis_width_scalar
            # x center spacing (fairchem GaussianSmearing convention)
            centers = jnp.linspace(0.0, cfg.cutoff, cfg.num_distance_basis)
            width = (cfg.basis_width_scalar * cfg.cutoff
                     / (cfg.num_distance_basis - 1))
            gauss = jnp.exp(-0.5 * ((d[:, None] - centers) / width) ** 2
                            ).astype(dtype)

        with scope("node_linear"):
            csd = csd.astype(dtype)
            zemb = params["sphere_embedding"]["w"][z].astype(dtype)
            h = jnp.zeros((positions.shape[0], S, C), dtype=dtype)
            h = h.at[:, 0, :].set(zemb + csd[None, :])

        # per-edge rows in chunk order, laid out once for the five scans
        edge_xs = lg.edge_chunks(cfg.edge_chunk, rhat, gauss, env)

        # per-l lab-from-edge blocks; ops/so3_e3nn builds them at >= fp32
        # with pole-safe angles: COORD_PRECISION products only in a float32
        # model (bfloat16 rows lose those digits anyway)
        wigner_blocks = partial(
            wigner_blocks_from_edges, cfg.lmax,
            precision=None if dtype == jnp.bfloat16 else COORD_PRECISION)

        def edge_scan(per_chunk):
            """Chunked edge sum ``(n_cap, S, C)`` of ``per_chunk(srcc, dstc,
            maskc, cols, cols_env, gaussc) -> (E_c, S * C)`` rows; the blocks
            are rebuilt per chunk, as the rotation's float32 columns
            ``cols`` and, for the way back to the lab frame, ``cols_env``:
            the columns times the envelope, in which a rotation is linear."""
            def with_blocks(srcc, dstc, maskc, rhatc, gaussc, envc):
                with scope("edge_rotation"):
                    cols = wigner_cols(wigner_blocks(rhatc))
                    cols_env = cols * envc.astype(cols.dtype)[:, None]
                return per_chunk(srcc, dstc, maskc, cols, cols_env, gaussc)

            rows = lg.scan_edges(with_blocks, edge_xs, (S * C,), dtype,
                                 remat=cfg.remat)
            with scope("edge_aggregate"):
                return rows.reshape(-1, S, C)

        rotate = partial(fused_wigner_rotate, lay=self.lay,
                         kernels=lg.kernels)

        def radial_of(p, srcc, dstc, gaussc):
            """Radial function of [gaussians | source | target species]."""
            with scope("radial_mlp"):
                return _rad_apply(p, jnp.concatenate([
                    gaussc,
                    params["source_embedding"]["w"][z[srcc]].astype(dtype),
                    params["target_embedding"]["w"][z[dstc]].astype(dtype),
                ], axis=-1))

        # --- edge-degree embedding (escn_md.py:221-247): radial weights
        # placed in the edge frame's m=0 slots, rotated to the lab frame,
        # degree-summed onto the receiver, / avg_degree
        def deg_chunk(srcc, dstc, maskc, cols, cols_env, gaussc):
            w = radial_of(params["edge_deg_rad"], srcc, dstc, gaussc)
            with scope("edge_rotation"):
                # w is the m = 0 piece as the radial function leaves it
                return rotate(cols_env, {0: w}, to_edge=False)

        inv_deg = jnp.asarray(1.0 / cfg.avg_degree, dtype=dtype)
        with scope("embedding"):
            deg = edge_scan(deg_chunk)
            with scope("node_linear"):
                h = h + deg * inv_deg
            h = lg.halo_exchange(h)

        for t, blk in enumerate(params["blocks"]):

            def so2_chunk(srcc, dstc, maskc, cols, cols_env, gaussc,
                          blk=blk):
                # per-coefficient scales
                rad = radial_of(blk["so2_1"]["rad"], srcc, dstc, gaussc)
                with scope("edge_message"):
                    xn_src = hn_rows[srcc]
                    xn_dst = hn_rows[dstc]
                with scope("edge_rotation"):
                    fr = rotate(cols, (xn_src, xn_dst), to_edge=True)
                with scope("edge_message"):
                    y, gates = self._so2_conv(blk["so2_1"], fr, rad, H)
                    y = self._gate_act(y, gates)
                    y, _ = self._so2_conv(blk["so2_2"], y, None, C)
                with scope("edge_rotation"):
                    return rotate(cols_env, y, to_edge=False)

            with scope(f"layer{t}"):
                # message path reads the NORMALIZED features (with the
                # system embedding re-injected into the scalars); residual
                # keeps h
                with scope("node_tensor"):
                    hn = self._rms_norm_sh(blk["norm1"]["w"], h)
                    hn = hn.at[:, 0, :].add(csd[None, :])
                    # flat rows: the edge side holds no 9-long tile axis
                    hn_rows = hn.reshape(-1, S * C)
                msg = edge_scan(so2_chunk)
                with scope("node_tensor"):
                    h = h + msg * inv_deg
                    # FFN with pre-norm and residual
                    h = h + self._ffn(
                        blk["ff"], self._rms_norm_sh(blk["ff_norm"]["w"], h))
                h = lg.halo_exchange(h)

        with scope("readout"):
            h = self._rms_norm_sh(params["norm"]["w"], h)
            s = h[:, 0, :]
            e = _linear(params["energy_head"]["lin2"],
                        jax.nn.silu(_linear(params["energy_head"]["lin1"],
                                            s.astype(positions.dtype))))[:, 0]
            return e + params["species_ref"]["w"][z].astype(positions.dtype)
