"""MACE: higher-order equivariant message passing (ACE product basis).

TPU-native implementation of the MACE architecture (Batatia et al. 2022) —
the reference's flagship distributed family (reference
implementations/mace/models.py:45-220: per-partition embeddings ->
interaction -> product -> readout loop with an atom_transfer after every
interaction). Built entirely on this repo's SO(3) module (real spherical
harmonics + real coupling tensors, ops/so3.py) instead of e3nn.

Feature layout: equivariant node features are a dict {l: (N, 2l+1, C)} —
channels LAST so the C=128 axis lands in the TPU lane dimension. TPU
arrays tile their trailing two axes to (sublane, lane)=(8|16, 128); with
channels last the small spherical axes (3..16) pad only the sublane axis
(<=2x) instead of the lane axis (8..32x), which round-3 profiling showed
was inflating every hot tensor's HBM traffic by an order of magnitude.
Message construction (density projection):
    A_i^{l3} = (1/avg_n) sum_j sum_{l1,l2} R^{l1l2l3}(r_ij) *
               CG[(l1,l2,l3)] (h_j^{l1}, Y^{l2}(r_ij))
followed by a species-weighted symmetric contraction in MACE's exact
U-matrix parameterization (orthonormal symmetric coupling basis per
(l_out, correlation) — ops/so3.py:symmetric_coupling_basis) and linear
updates with species-dependent residual connections (upstream's skip_tp).
Per-layer invariant readouts accumulate into the site energy, matching
MACE's scale/shift + E0s structure.

TPU mapping: the density projection folds every (l_h, l_Y, l_out) CG path
into one dense block matrix so each edge chunk is a single MXU GEMM
(_projection_tables); the symmetric contraction runs Horner-style over
node chunks; segment sums ride the sorted-dst fast path.

Distributed contract: one halo exchange of the packed node features after
each interaction (same cadence as the reference's atom_transfer,
models.py:165).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import radial
from ..ops.nn import linear, linear_init, linear_init_vp, mlp, mlp_init, mlp_init_vp
from ..ops.so3 import (
    real_clebsch_gordan,
    spherical_harmonics,
    symmetric_coupling_basis,
)
from ..telemetry import scope


@dataclass(frozen=True)
class MACEConfig:
    num_species: int = 95
    channels: int = 64
    l_max: int = 3            # spherical-harmonic order on edges
    a_lmax: int = 2           # irreps kept in the density A / product basis
    hidden_lmax: int = 1      # irreps of hidden node features (0..L)
    correlation: int = 3      # body order - 1 (ACE correlation)
    num_interactions: int = 2
    scalar_last: bool = True  # upstream MACE keeps only scalar (l=0) hidden
                              # features out of the final interaction/product
    num_bessel: int = 8
    radial_mlp: int = 64
    radial_layers: int = 3    # hidden layers in the radial MLP (upstream MACE
                              # uses [64, 64, 64], no biases)
    radial_scale: float = 16.0  # INIT-time gain folded into the radial
                                # MLP's output layer: keeps the density
                                # projection A healthy at init (the cutoff
                                # envelope shrinks near-cutoff edges) so
                                # correlation-2/3 products carry weight.
                                # Not applied at runtime — converted
                                # upstream weights are used verbatim.
    cutoff: float = 5.0
    cutoff_p: int = 6         # polynomial-envelope power (upstream MACE
                              # checkpoints commonly use 5)
    avg_num_neighbors: float = 14.0
    num_heads: int = 1        # multi-head readouts (upstream MACE heads:
    head: int = 0             # per-head E0s/scale/shift/readout columns);
                              # ``head`` selects the column evaluated
    zbl: bool = False         # ZBL screened pair repulsion under the
                              # learned potential (ref mace/models.py:121-128)
    atomic_numbers: tuple | None = None  # species index -> Z (for ZBL);
                                         # default: index + 1
    remat: bool | str = True  # rematerialize the scans' chunk bodies (and
                              # nothing else) in the backward pass: True
                              # (full), False, or a policy name ("dots":
                              # keep GEMM outputs — ops/chunk.remat_wrap)
    edge_chunk: int = 32768  # process edges in chunks of this size inside a
                             # lax.scan: bounds the per-edge path-tensor and
                             # radial-weight memory regardless of system size
                             # (0 disables chunking)
    node_chunk: int = 4096   # same for the per-node symmetric contraction
                             # (the Horner intermediates are (n, d, S, S, C))
    dtype: str = "float32"


def _triangle(l1, l2, l3):
    return abs(l1 - l2) <= l3 <= l1 + l2


def _message_paths(h_ls, l_max, out_ls):
    """(l_h, l_Y, l_out) combos for the density projection.

    Parity-filtered (l_h + l_Y + l_out even): node features and spherical
    harmonics carry SH parity, and upstream MACE's conv_tp keeps only the
    parity-consistent instructions, so odd-sum paths do not exist there —
    matching the path set (and radial-MLP output width) exactly is required
    for weight parity. Order matches upstream's instruction sort: by output
    irrep first (stable within an l_out by enumeration order)."""
    paths = [
        (lh, ly, lo)
        for lh in h_ls
        for ly in range(l_max + 1)
        for lo in out_ls
        if _triangle(lh, ly, lo) and (lh + ly + lo) % 2 == 0
    ]
    return sorted(paths, key=lambda p: p[2])


def _projection_tables(h_ls, l_max, paths):
    """Density projection tables: fold ALL (l_h, l_Y, l_out) CG couplings
    into one dense block matrix.

        W[(l_h m) * S_Y + (l_Y n), q(path, p)] = CG^{l_h l_Y l_out}[m, n, p]

    Per edge chunk the contraction is factored through the channel-free
    intermediate T[e, m, q] = sum_n Y[e, n] W[(m, n), q] (tiny), then
    M[e, q, c] = sum_m T[e, m, q] h_src[e, m, c] — S_h fused multiply-adds
    per output element, with no (E, S_h*S_Y, C) outer product materialized
    (replaces the per-path ``ecm,en,mnp->ecp`` einsums of round 1 and the
    outer-product GEMM of round 2).

    Returns dict with: W (K, Q) float64, q_path (Q,) path index per column,
    h_off {l: row-block offset}, S_h, S_Y, and lo_cols {l_out: (P_l, 2l+1)}
    column groups for the per-path output mixing.
    """
    S_Y = (l_max + 1) ** 2
    h_off = {}
    off = 0
    for l in h_ls:
        h_off[l] = off
        off += 2 * l + 1
    S_h = off
    y_off = {l: l * l for l in range(l_max + 1)}

    Q = sum(2 * lo + 1 for (_, _, lo) in paths)
    W = np.zeros((S_h * S_Y, Q))
    q_path = np.zeros(Q, dtype=np.int32)
    cols_by_lo: dict[int, list] = {}
    q = 0
    for pi, (lh, ly, lo) in enumerate(paths):
        cg = real_clebsch_gordan(lh, ly, lo)  # (2lh+1, 2ly+1, 2lo+1)
        mi = h_off[lh] + np.arange(2 * lh + 1)
        ni = y_off[ly] + np.arange(2 * ly + 1)
        rows = (mi[:, None] * S_Y + ni[None, :]).reshape(-1)
        W[np.ix_(rows, np.arange(q, q + 2 * lo + 1))] = cg.reshape(-1, 2 * lo + 1)
        q_path[q : q + 2 * lo + 1] = pi
        cols_by_lo.setdefault(lo, []).append(np.arange(q, q + 2 * lo + 1))
        q += 2 * lo + 1
    lo_cols = {lo: np.stack(cols) for lo, cols in cols_by_lo.items()}
    return {
        "W": W, "q_path": q_path, "h_off": h_off, "S_h": S_h, "S_Y": S_Y,
        "lo_cols": lo_cols,
    }


class MACE:
    supports_compute_dtype = True  # energy_fn honors cfg.dtype="bfloat16"

    def __init__(self, config: MACEConfig = MACEConfig()):
        self.cfg = config
        c = config
        if not 0 <= c.head < c.num_heads:
            raise ValueError(
                f"head={c.head} out of range for num_heads={c.num_heads}"
            )
        self.h_ls0 = [0]
        self.h_ls = list(range(c.hidden_lmax + 1))
        self.a_ls = list(range(c.a_lmax + 1))
        # per-interaction input/output irrep sets: embeddings are scalar, the
        # final layer emits scalars only when scalar_last (upstream MACE's
        # "select only scalars for last layer")
        self.h_ls_in: list[list[int]] = []
        self.h_ls_out: list[list[int]] = []
        prev = self.h_ls0
        for t in range(c.num_interactions):
            self.h_ls_in.append(prev)
            out = (
                [0]
                if (c.scalar_last and t == c.num_interactions - 1)
                else self.h_ls
            )
            self.h_ls_out.append(out)
            prev = out
        self.msg_paths = [
            _message_paths(self.h_ls_in[t], c.l_max, self.a_ls)
            for t in range(c.num_interactions)
        ]
        self.proj = [
            _projection_tables(self.h_ls_in[t], c.l_max, self.msg_paths[t])
            for t in range(c.num_interactions)
        ]
        # ACE product basis: orthonormal symmetric U tensors per
        # (l_out, correlation), shared across interactions (the A irreps are
        # the same every layer) — MACE's U-matrix symmetric contraction
        self.prod_U = {
            l: {
                nu: symmetric_coupling_basis(tuple(self.a_ls), l, nu)
                for nu in range(1, c.correlation + 1)
            }
            for l in self.h_ls
        }

    # ---- parameters ----
    def init(self, key) -> dict:
        cfg = self.cfg
        C = cfg.channels
        n_keys = 8 + cfg.num_interactions * 32
        ks = iter(jax.random.split(key, n_keys))
        params = {
            "species_emb": {"w": jax.random.normal(next(ks), (cfg.num_species, C))},
            "species_ref": {"w": jnp.zeros((cfg.num_heads, cfg.num_species))},
            "scale": jnp.ones((cfg.num_heads,)),
            "shift": jnp.zeros((cfg.num_heads,)),
            "interactions": [],
        }
        if cfg.zbl:
            params["zbl"] = {
                "a_exp": jnp.float32(0.300),
                "a_prefactor": jnp.float32(0.4543),
            }
        for t in range(cfg.num_interactions):
            n_paths = len(self.msg_paths[t])
            in_ls, out_ls = self.h_ls_in[t], self.h_ls_out[t]
            inter = {
                # per-l channel mixing of the sender features
                "lin_up": {
                    str(l): linear_init_vp(next(ks), C, C) for l in in_ls
                },
                # radial_scale is folded into the OUTPUT layer at init only;
                # the forward pass applies the MLP verbatim (conversion
                # overwrites these weights with upstream values unscaled)
                "radial": (lambda r: r[:-1] + [
                    {"w": r[-1]["w"] * cfg.radial_scale}
                ])(mlp_init_vp(
                    next(ks),
                    [cfg.num_bessel]
                    + [cfg.radial_mlp] * cfg.radial_layers
                    + [n_paths * C],
                )),
                # per-path output mixing (upstream MACE's post-conv_tp
                # e3nn Linear: one C x C block per (path, l_out) pair)
                "lin_A": {
                    str(l): jax.random.normal(
                        next(ks), (self.proj[t]["lo_cols"][l].shape[0], C, C)
                    )
                    / np.sqrt(self.proj[t]["lo_cols"][l].shape[0] * C)
                    for l in self.a_ls
                },
                # species-dependent U-basis product weights (MACE's
                # symmetric-contraction weights: (num_elements, n_paths, C)
                # per output irrep and correlation order)
                "product": {
                    str(l): {
                        f"w{nu}": jax.random.normal(
                            next(ks),
                            (cfg.num_species, U.shape[-1], C),
                        )
                        / np.sqrt(U.shape[-1])
                        for nu, U in self.prod_U[l].items()
                        if U is not None
                    }
                    for l in out_ls
                },
                "lin_msg": {
                    str(l): linear_init_vp(next(ks), C, C) for l in out_ls
                },
                # species-dependent residual (upstream's skip_tp:
                # FullyConnectedTensorProduct(h, species one-hot) — one C x C
                # block per species per (l common to input and output)
                "lin_res": {
                    str(l): jax.random.normal(
                        next(ks), (cfg.num_species, C, C)
                    )
                    / np.sqrt(C)
                    for l in out_ls
                    if l in in_ls
                },
                # bias-free like upstream's Linear/NonLinearReadoutBlock
                "readout": (
                    mlp_init(next(ks), [C, 16, cfg.num_heads], bias=False)
                    if t == cfg.num_interactions - 1
                    else [linear_init(next(ks), C, cfg.num_heads, bias=False)]
                ),
            }
            params["interactions"].append(inter)
        return params

    # ---- packing helpers for the halo exchange ----
    def _pack(self, h):
        return jnp.concatenate(
            [h[l].reshape(h[l].shape[0], -1) for l in sorted(h)], axis=-1
        )

    def _unpack(self, flat, ls, C):
        out = {}
        o = 0
        for l in ls:
            d = C * (2 * l + 1)
            out[l] = flat[:, o : o + d].reshape(-1, 2 * l + 1, C)
            o += d
        return out

    # ---- forward ----
    def energy_fn(self, params, lg, positions):
        cfg = self.cfg
        C = cfg.channels
        # geometry stays in the positions dtype; features/messages run in the
        # configured compute dtype (cfg.dtype="bfloat16" puts every GEMM on
        # the MXU's native precision); per-atom energy terms accumulate in
        # the positions dtype below
        dtype = (
            jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        )
        acc_dtype = positions.dtype

        # every stage below sits in its telemetry scope (telemetry/stages.py)
        with scope("edge_geometry"):
            vec = lg.edge_vectors(positions)
            d = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            rhat = vec / jnp.maximum(d, 1e-9)[:, None]
            env = (
                radial.polynomial_cutoff(d, cfg.cutoff, p=cfg.cutoff_p)
                * lg.edge_mask
            ).astype(dtype)
            # envelope multiplies the bessel features BEFORE the radial MLP
            # (upstream's RadialEmbeddingBlock); the bias-free MLP maps
            # 0 -> 0, so messages still vanish smoothly at the cutoff
            bessel = (
                radial.spherical_bessel_basis(d, cfg.cutoff, cfg.num_bessel)
                * env[:, None]
            ).astype(dtype)
            Y = {l: spherical_harmonics(l, rhat)
                 for l in range(cfg.l_max + 1)}

        z = lg.species
        with scope("node_linear"):
            h = {0: params["species_emb"]["w"][z][:, None, :].astype(dtype)}
        with scope("halo"):
            h = self._unpack(lg.halo_exchange(self._pack(h)), [0], C)

        head = cfg.head
        # site/readout energies accumulate in the positions dtype: bf16 has
        # too few mantissa bits for per-atom energy sums
        with scope("readout"):
            e_site = params["species_ref"]["w"][head][z].astype(acc_dtype)
        # ZBL joins the *interaction* energies: upstream ScaleShiftMACE puts
        # pair_node_energy into node_es_list and scale-shifts the sum
        # (reference mace/models.py:131,174-175), so it must sit inside
        # scale*(...)+shift, not alongside the unscaled E0 reference
        acc = jnp.zeros(positions.shape[0], dtype=acc_dtype)
        if cfg.zbl:
            with scope("pair_repulsion"):
                acc = acc + self._zbl_site(params, lg, d, acc_dtype)

        # per-edge rows in chunk order, laid out ONCE for both interactions
        # (nothing here is an interaction's own): the cotangents of both
        # pass through the layout's transpose once (LocalGraph.edge_chunks)
        with scope("edge_gather"):
            Y_full = jnp.concatenate(
                [Y[l] for l in range(cfg.l_max + 1)], axis=-1
            ).astype(dtype)                               # (E, S_Y)
        edge_xs = lg.edge_chunks(cfg.edge_chunk, Y_full, bessel)

        for t, inter in enumerate(params["interactions"]):
            body = partial(self._interaction, lg=lg, edge_xs=edge_xs,
                           z=z, t=t)
            with scope(f"interaction{t}"):
                h = body(inter, h)
            with scope("halo"):
                h = self._unpack(lg.halo_exchange(self._pack(h)),
                                 self.h_ls_out[t], C)

            # invariant readout (head column selected)
            with scope("readout"):
                scalars = h[0][:, 0, :]
                if t == cfg.num_interactions - 1:
                    r_out = mlp(inter["readout"], scalars)[:, head]
                else:
                    r_out = linear(inter["readout"][0], scalars)[:, head]
                acc = acc + r_out.astype(acc_dtype)

        with scope("readout"):
            scale = params["scale"][head].astype(acc_dtype)
            shift = params["shift"][head].astype(acc_dtype)
            return e_site + scale * acc + shift

    def _zbl_site(self, params, lg, d, dtype):
        """Per-atom ZBL pair repulsion (half per directed edge), added under
        the learned potential exactly as the reference aggregates its
        per-partition pair energies (mace/models.py:121-128)."""
        from .pair import zbl_edge_energy

        cfg = self.cfg
        if cfg.atomic_numbers is not None:
            # cfg.atomic_numbers is a host config value, not a device array
            # contract: allow(DML001)
            z_of = jnp.asarray(np.asarray(cfg.atomic_numbers, dtype=np.int32))
        else:
            z_of = jnp.arange(1, cfg.num_species + 1, dtype=jnp.int32)
        z_num = z_of[lg.species]
        e_edge = zbl_edge_energy(
            z_num[lg.edge_src], z_num[lg.edge_dst], d.astype(dtype),
            a_exp=params["zbl"]["a_exp"], a_prefactor=params["zbl"]["a_prefactor"],
            p=cfg.cutoff_p,
        )
        e_edge = jnp.where(lg.edge_mask, e_edge, 0.0)
        # aggregate_edges: per-segment sorted sums under the
        # interior/frontier edge layout
        return 0.5 * lg.aggregate_edges(e_edge[:, None])[:, 0]

    def _interaction(self, inter, h, *, lg, edge_xs, z, t):
        """One MACE interaction: density projection + symmetric contraction +
        linear update. ``cfg.remat`` checkpoints the chunk bodies of its edge
        and node scans (the per-edge per-path tensors live there), not the
        interaction. ``edge_xs`` is energy_fn's chunk-ordered
        ``(src, dst, mask, Y, bessel)``, each ``(K, chunk, ...)``."""
        cfg = self.cfg
        C = cfg.channels
        chunk = edge_xs[0].shape[1]
        dtype = edge_xs[4].dtype
        # run the whole interaction in the compute dtype: cast the parameter
        # subtree so mixed-precision promotion can't silently upcast the
        # GEMMs back to fp32 (O(param bytes) per step — negligible next to
        # the per-edge activations; a no-op when params are already cast)
        inter = jax.tree.map(
            lambda x: x.astype(dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            inter,
        )
        n_nodes = h[0].shape[0]
        h_ls = self.h_ls_in[t]
        out_ls = self.h_ls_out[t]
        paths = self.msg_paths[t]
        proj = self.proj[t]
        Wp = jnp.asarray(proj["W"], dtype=dtype)          # (S_h*S_Y, Q)
        q_path = jnp.asarray(proj["q_path"])              # (Q,)
        nQ = proj["W"].shape[1]

        # sender features, channel-mixed per l, packed (N, S_h, C)
        with scope("node_linear"):
            hu = jnp.concatenate(
                [
                    jnp.einsum("nmc,cd->nmd", h[l],
                               inter["lin_up"][str(l)]["w"])
                    for l in h_ls
                ],
                axis=1,
            )

        # density projection A, accumulated over edge chunks (memory-bounded):
        # per chunk, outer(h_src, Y) -> one GEMM over every CG path -> radial
        # weight -> ONE sorted segment sum carrying all Q path components
        # (LocalGraph.scan_edges).
        Wp3 = Wp.reshape(proj["S_h"], proj["S_Y"], nQ)

        def chunk_message(srcc, dstc, maskc, Yc, besc):
            with scope("radial_mlp"):
                Rc = mlp(inter["radial"], besc).reshape(chunk, len(paths), C)
            # factor the CG contraction: T[e,m,q] = sum_n Y[e,n] W[(m,n),q]
            # is channel-free and tiny (E_c, S_h, Q); contracting it with
            # h_src over m (<= S_h) then costs S_h fused multiply-adds per
            # (q, c) — no (E_c, S_h*S_Y, C) outer product ever materializes
            # (the outer was ~0.5 GB/chunk and 16x the FLOPs)
            with scope("edge_message"):
                T = jnp.einsum("en,mnq->emq", Yc, Wp3)
                M = jnp.einsum("emq,emc->eqc", T, hu[srcc])  # (E_c, Q, C)
                return M * Rc[:, q_path, :]                  # per-path radial

        A_all = lg.scan_edges(chunk_message, edge_xs, (nQ, C), dtype,
                              remat=cfg.remat)
        # per-path output mixing on nodes (upstream's post-conv_tp linear):
        # A[l] = sum_paths A_all[:, :, cols(path)] @ W_path — (P_l*C) GEMMs
        with scope("node_linear"):
            inv_avg = jnp.asarray(1.0 / cfg.avg_num_neighbors, dtype=dtype)
            A = {
                l: jnp.einsum(
                    "npmc,pcd->nmd",
                    A_all[:, proj["lo_cols"][l]] * inv_avg,
                    inter["lin_A"][str(l)].astype(dtype),
                )
                for l in self.a_ls
            }

        # ---- symmetric contraction (ACE product basis, U-matrix form) ----
        # node-chunked: the Horner intermediates are (n, d, S, S, C)
        with scope("node_tensor"):
            # (N, S_A, C)
            A_flat = jnp.concatenate([A[l] for l in self.a_ls], axis=1)
            h_in_ls = [l for l in h_ls if l in h]
            h_flat = jnp.concatenate([h[l] for l in h_in_ls], axis=1)
            nchunk = cfg.node_chunk if cfg.node_chunk > 0 else n_nodes
            nchunk = min(nchunk, n_nodes)
            Kn = -(-n_nodes // nchunk)
            padn = Kn * nchunk - n_nodes

            def padn_c(x):
                if padn == 0:
                    return x
                widths = [(0, padn)] + [(0, 0)] * (x.ndim - 1)
                return jnp.pad(x, widths)

            A_ch = padn_c(A_flat).reshape(Kn, nchunk, -1, C)
            z_ch = padn_c(z).reshape(Kn, nchunk)
            h_ch = padn_c(h_flat).reshape(Kn, nchunk, -1, C)

            def node_body(_, xs):
                Ac, zc, hc = xs
                outs = []
                for l in out_ls:
                    B = self._sym_contract(
                        inter["product"][str(l)], self.prod_U[l], Ac, zc, dtype
                    )
                    with scope("node_linear"):
                        m = jnp.einsum("nmc,cd->nmd", B,
                                       inter["lin_msg"][str(l)]["w"])
                        if l in h_in_ls and str(l) in inter["lin_res"]:
                            off = sum(2 * ll + 1 for ll in h_in_ls if ll < l)
                            hl = hc[:, off : off + 2 * l + 1, :]
                            Wr = inter["lin_res"][str(l)][zc].astype(
                                dtype)                          # (n, C, C)
                            m = m + jnp.einsum("nmc,ncd->nmd", hl, Wr)
                    outs.append(m)
                return None, jnp.concatenate(outs, axis=1)

            from ..ops.chunk import remat_wrap

            body = remat_wrap(node_body, cfg.remat)
            if Kn == 1:
                # single-chunk path keeps the remat mode too (the edge
                # scan's contract: a system just under one node chunk must
                # have the same backward memory bound as one just over)
                _, out_flat = body(None, (A_ch[0], z_ch[0], h_ch[0]))
            else:
                _, out_flat = jax.lax.scan(body, None, (A_ch, z_ch, h_ch))
                out_flat = out_flat.reshape(Kn * nchunk, -1, C)[:n_nodes]

            h_new = {}
            o = 0
            for l in out_ls:
                d = 2 * l + 1
                h_new[l] = out_flat[:, o : o + d, :]
                o += d
        return h_new

    def _sym_contract(self, wts, Us, Ac, zc, dtype):
        """B(A)[n, d, c] = sum_nu W_nu[z_n] . U_nu . A^(x nu) — evaluated
        highest correlation first in Horner form (mace's contraction order:
        each step adds the next-lower U.W block, then contracts one A index).
        Ac: (n, S_A, C); returns (n, 2l+1, C). Channels stay in the trailing
        (lane) axis through every intermediate."""
        numax = max(nu for nu, U in Us.items() if U is not None)
        letters = "uvwxy"
        # U stored (S,)*nu + (d, k) -> transpose to (d, S..., k)
        U_t = {
            nu: jnp.asarray(np.moveaxis(U, -2, 0), dtype=dtype)
            for nu, U in Us.items()
            if U is not None
        }
        w = {nu: wts[f"w{nu}"][zc].astype(dtype) for nu in U_t}  # (n, k, C)

        s_in = letters[: numax - 1]
        # G[n,k,q,c] = w[n,k,c] A[n,q,c]: fold the path and last tensor index
        # into one MXU contraction of U against G
        G = jnp.einsum("nkc,nqc->nkqc", w[numax], Ac)
        t = jnp.einsum(f"d{s_in}qk,nkqc->nd{s_in}c", U_t[numax], G)
        for nu in range(numax - 1, 0, -1):
            s_cur = letters[:nu]
            if nu in U_t:
                t = t + jnp.einsum(
                    f"d{s_cur}k,nkc->nd{s_cur}c", U_t[nu], w[nu]
                )
            t = jnp.einsum(
                f"nd{s_cur}c,n{s_cur[-1]}c->nd{s_cur[:-1]}c", t, Ac
            )
        return t
