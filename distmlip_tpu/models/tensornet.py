"""TensorNet: O(3)-equivariant message passing on rank-2 tensor features.

TPU-native implementation of TensorNet (Simeon & De Fabritiis 2023) in
**matgl's exact parameterization** (torchmd-net port), so pretrained matgl
checkpoints convert weight-for-weight (``convert.MAPPINGS["tensornet"]``).
The reference distributes matgl's TensorNet via ``from_existing`` __dict__
copy (reference implementations/matgl/models/tensornet.py:204-214); its
module inventory is pinned by enable_distributed_mode (:179-197) and the
readout math by dist_forward (:131-159): tensor_embedding -> interaction
layers (atom_transfer after each) -> decompose/tensor_norm invariants ->
out_norm LayerNorm -> linear -> final_layer.gated MLP -> sum.

Per-node state X_i in R^{3 x 3 x C}, channels LAST: TPU arrays tile their
trailing two axes to (sublane, lane=128), so keeping C in the lane axis
(instead of a 3-wide matrix axis padded to 128) cuts the physical footprint
of every tensor-valued intermediate ~40x. The scalar-gate unflatten keeps
torchmd-net's (C, 3) order so matgl weights convert unchanged. Distributed
contract: edges live with their dst
owner, so every in-edge of an owned node is local; after the embedding and
each interaction layer the updated tensors of border nodes are refreshed on
neighbors via ``lg.halo_exchange`` (same cadence as the reference's
``atom_transfer``, tensornet.py:121-128).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels.dispatch import Gather
from ..ops import radial
from ..ops.nn import (cast_params_subtrees, embedding, gather_rows,
                      layernorm, layernorm_init, linear, linear_init, mlp,
                      mlp_init)
from ..telemetry import scope


@dataclass(frozen=True)
class TensorNetConfig:
    num_species: int = 95
    units: int = 64           # hidden_channels
    num_rbf: int = 32
    num_layers: int = 2
    cutoff: float = 5.0
    final_hidden: tuple | None = None  # final_layer.gated dims, default (units, units)
    dtype: str = "float32"

    @property
    def _final_hidden(self):
        return self.final_hidden if self.final_hidden is not None else (self.units, self.units)


def decompose(X):
    """Split (..., 3, 3, C) into (trace-part I, antisymmetric A,
    sym-traceless S); the matrix lives in axes (-3, -2)."""
    with scope("node_tensor"):
        trace = (X[..., 0, 0, :] + X[..., 1, 1, :] + X[..., 2, 2, :])[
            ..., None, None, :
        ]
        eye = jnp.eye(3, dtype=X.dtype)[:, :, None]
        I = trace / 3.0 * eye
        Xt = jnp.swapaxes(X, -3, -2)
        A = 0.5 * (X - Xt)
        S = 0.5 * (X + Xt) - I
        return I, A, S


def tensor_norm(X):
    """Per-channel squared Frobenius norm: (..., 3, 3, C) -> (..., C)."""
    with scope("node_tensor"):
        return jnp.sum(X * X, axis=(-3, -2))


def _vector_to_skew(v):
    """(..., 3) -> (..., 3, 3) antisymmetric [v]_x (torchmd-net
    vector_to_skewtensor convention)."""
    zero = jnp.zeros_like(v[..., 0])
    rows = [
        jnp.stack([zero, -v[..., 2], v[..., 1]], axis=-1),
        jnp.stack([v[..., 2], zero, -v[..., 0]], axis=-1),
        jnp.stack([-v[..., 1], v[..., 0], zero], axis=-1),
    ]
    return jnp.stack(rows, axis=-2)


def _mix(lin, comp):
    """torchmd-net channel mix: Linear over the channel axis of a
    (..., 3, 3, C) component (torch permutes around nn.Linear; here the
    channel axis is already last, so it is one lane-resident GEMM)."""
    with scope("node_linear"):
        return jnp.einsum("...ijc,cd->...ijd", comp, lin["w"])


class TensorNet:
    def __init__(self, config: TensorNetConfig = TensorNetConfig()):
        self.cfg = config

    # ---- parameters ----
    def init(self, key) -> dict:
        cfg = self.cfg
        ks = iter(jax.random.split(key, 24 + 10 * cfg.num_layers))
        C, R = cfg.units, cfg.num_rbf
        params = {
            # tensor_embedding.*
            "species_emb": {"w": jax.random.normal(next(ks), (cfg.num_species, C))},
            "emb2": linear_init(next(ks), 2 * C, C),
            "dist_proj": [linear_init(next(ks), R, C) for _ in range(3)],
            "emb_lin_scalar": [linear_init(next(ks), C, 2 * C),
                               linear_init(next(ks), 2 * C, 3 * C)],
            "emb_lin_tensor": [linear_init(next(ks), C, C, bias=False)
                               for _ in range(3)],
            "init_norm": layernorm_init(C),
            "layers": [],
            # readout (reference dist_forward :131-151)
            "out_norm": layernorm_init(3 * C),
            "linear": linear_init(next(ks), 3 * C, C),
            "final": mlp_init(next(ks), [C] + list(cfg._final_hidden) + [1]),
            "species_ref": {"w": jnp.zeros((cfg.num_species, 1))},
            "data_std": jnp.ones(()),
        }
        for _ in range(cfg.num_layers):
            params["layers"].append({
                "lin_scalar": [linear_init(next(ks), R, C),
                               linear_init(next(ks), C, 2 * C),
                               linear_init(next(ks), 2 * C, 3 * C)],
                "lin_tensor": [linear_init(next(ks), C, C, bias=False)
                               for _ in range(6)],
            })
        return params

    supports_compute_dtype = True  # energy_fn honors cfg.dtype="bfloat16"

    # ---- forward ----
    def energy_fn(self, params, lg, positions):
        cfg = self.cfg
        C = cfg.units
        # features/GEMMs in the compute dtype; geometry + readout stack in
        # the positions dtype (same policy as MACE/eSCN/CHGNet)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        fp = params
        if cfg.dtype == "bfloat16":
            params = cast_params_subtrees(
                params, dtype,
                keep_fp32=("species_ref", "out_norm", "linear", "final",
                           "data_std"))

        # every stage below sits in its telemetry scope (telemetry/stages.py)
        with scope("edge_geometry"):
            vec = lg.edge_vectors(positions)
            d = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            rhat = (vec / jnp.maximum(d, 1e-9)[:, None]).astype(dtype)
            env = (radial.cosine_cutoff(d, cfg.cutoff)
                   * lg.edge_mask).astype(dtype)
            rbf = radial.spherical_bessel_basis(
                d, cfg.cutoff, cfg.num_rbf).astype(dtype)

            # --- tensor embedding (torchmd-net TensorEmbedding) ---
            eye = jnp.eye(3, dtype=dtype)[:, :, None]            # (3, 3, 1)
            A_e = _vector_to_skew(rhat)[..., None]               # (E, 3, 3, 1)
            S_e = (rhat[:, :, None] * rhat[:, None, :])[..., None] - eye / 3.0

        with scope("node_linear"):
            z = embedding(params["species_emb"], lg.species)     # (N, C)
        # gather_rows: on the bf16 path the backward accumulates per-node
        # feature grads from every referencing edge in fp32, not bf16
        with scope("edge_message"):
            Zij = linear(params["emb2"],
                         jnp.concatenate([gather_rows(z, lg.edge_src),
                                          gather_rows(z, lg.edge_dst)],
                                         axis=-1))
        with scope("radial_mlp"):
            W1 = linear(params["dist_proj"][0], rbf) * env[:, None]  # (E, C)
            W2 = linear(params["dist_proj"][1], rbf) * env[:, None]
            W3 = linear(params["dist_proj"][2], rbf) * env[:, None]

        # the (E, 3, 3, C) edge tensor is 9C wide vs the ~4C of its inputs
        # — built INSIDE the fused dst-tile kernel on the Pallas path, so
        # it never materializes in HBM (kernels/dispatch); the XLA path
        # builds it whole and segment-sums with the sorted hint, exactly
        # the historical program
        def embed_msg(zij, w1, w2, w3, ae, se):
            return zij[:, None, None, :] * (
                w1[:, None, None, :] * eye
                + w2[:, None, None, :] * ae
                + w3[:, None, None, :] * se
            )

        X = lg.aggregate_edge_messages(
            embed_msg, (Zij, W1, W2, W3, A_e, S_e), mask=lg.edge_mask)

        with scope("node_linear"):
            norm = layernorm(params["init_norm"], tensor_norm(X))
            for lin in params["emb_lin_scalar"]:
                norm = jax.nn.silu(linear(lin, norm))
            # torchmd-net's (C, 3) unflatten order
            norm = norm.reshape(-1, C, 3)
        with scope("node_tensor"):
            I, A, S = decompose(X)
            I = _mix(params["emb_lin_tensor"][0], I)
            A = _mix(params["emb_lin_tensor"][1], A)
            S = _mix(params["emb_lin_tensor"][2], S)
            X = (I * norm[:, None, None, :, 0] + A * norm[:, None, None, :, 1]
                 + S * norm[:, None, None, :, 2])
        X = lg.halo_exchange(X)

        # --- interaction layers ---
        for t, lp in enumerate(params["layers"]):
            with scope(f"interaction{t}"):
                X = self._interaction(lp, lg, X, rbf, env)
            X = lg.halo_exchange(X)

        # --- invariant readout (reference dist_forward :131-151) ---
        with scope("readout"):
            I, A, S = decompose(X)
            inv = jnp.concatenate(
                [tensor_norm(I), tensor_norm(A), tensor_norm(S)], axis=-1
            ).astype(positions.dtype)
            x = linear(fp["linear"], layernorm(fp["out_norm"], inv))
            e_atom = mlp(fp["final"], x)[:, 0]
            e_ref = fp["species_ref"]["w"][lg.species, 0]
            return fp["data_std"] * e_atom + e_ref

    def _interaction(self, lp, lg, X, rbf, env):
        """torchmd-net TensorNetInteraction (O(3) group): radial edge gates,
        per-channel normalization X/(||X||+1), channel mixes, neighbor
        message M, B = YM + MY, normalized remix, X + dX + dX^2."""
        C = self.cfg.units
        with scope("radial_mlp"):
            f = rbf
            for lin in lp["lin_scalar"]:
                f = jax.nn.silu(linear(lin, f))
            # torchmd-net (C, 3) order
            f = (f * env[:, None]).reshape(-1, C, 3)

        with scope("node_tensor"):
            X = X / (tensor_norm(X) + 1.0)[..., None, None, :]
            I, A, S = decompose(X)
            I = _mix(lp["lin_tensor"][0], I)
            A = _mix(lp["lin_tensor"][1], A)
            S = _mix(lp["lin_tensor"][2], S)
            Y = I + A + S

        # 27C of gathered src components fold into a 9C message inside the
        # fused kernel (in-kernel src gather on the Pallas path)
        def int_msg(f_e, i_s, a_s, s_s):
            return (f_e[:, None, None, :, 0] * i_s
                    + f_e[:, None, None, :, 1] * a_s
                    + f_e[:, None, None, :, 2] * s_s)

        M = lg.aggregate_edge_messages(
            int_msg,
            (f, Gather(I, lg.edge_src), Gather(A, lg.edge_src),
             Gather(S, lg.edge_src)),
            mask=lg.edge_mask)

        # batched 3x3 matmuls over (node, channel); the matrix axes are
        # (-3, -2), channels ride the lane axis untouched
        matmul = lambda P, Q: jnp.einsum("nijc,njkc->nikc", P, Q)
        with scope("node_tensor"):
            B = matmul(Y, M) + matmul(M, Y)
            I, A, S = decompose(B)
            np1 = (tensor_norm(B) + 1.0)[..., None, None, :]
            I = _mix(lp["lin_tensor"][3], I / np1)
            A = _mix(lp["lin_tensor"][4], A / np1)
            S = _mix(lp["lin_tensor"][5], S / np1)
            dX = I + A + S
            return X + dX + matmul(dX, dX)
