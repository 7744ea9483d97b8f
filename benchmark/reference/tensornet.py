"""Plain reference of TensorNet (Simeon & De Fabritiis 2023) in matgl's
parameterisation (``TensorNet-MatPES-PBE-v2025.1-PES``): float32,
``jax.numpy`` only, no kernels, no padding, no partitions.

Node state X_i in R^{3 x 3 x C}. With I, A, S the isotropic, antisymmetric
and symmetric-traceless parts:

    embedding:  X_i = sum_{j -> i} Z_ij (w1 1 + w2 [r]_x + w3 (r r^T - 1/3))
                then per-part channel mixes scaled by an MLP of |X|^2
    layer:      Y = mix(X / (|X|^2 + 1));  M_i = sum_j f_ij . (I, A, S)_j
                B = Y M + M Y;  dX = mix(B / (|B|^2 + 1));  X += dX + dX dX
    readout:    E_i = MLP(linear(layernorm(|I|^2, |A|^2, |S|^2)))

Forces are -dE/dpositions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import (bessel_basis, blocked_segment_sum, cosine_cutoff, dense,
                     layernorm, mlp, rounder, silu)


class Tables:
    """TensorNet needs no coupling tables; the class keeps the two
    references' interfaces alike."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.cfg = cfg


def init_params(cfg: dict, tables: Tables, key):
    """Random weights in the program's tree layout (torch-style uniform
    initialisation, as the program's own initialiser). Traceable."""
    c, r, species = cfg["units"], cfg["num_rbf"], cfg["num_species"]
    keys = iter(jax.random.split(key, 64 + 32 * cfg["num_layers"]))

    def linear(d_in, d_out, bias=True):
        lim = 1.0 / np.sqrt(d_in)
        p = {"w": jax.random.uniform(next(keys), (d_in, d_out), jnp.float32,
                                     -lim, lim)}
        if bias:
            p["b"] = jax.random.uniform(next(keys), (d_out,), jnp.float32,
                                        -lim, lim)
        return p

    norm = lambda dim: {"g": jnp.ones((dim,), jnp.float32),
                        "b": jnp.zeros((dim,), jnp.float32)}
    params = {
        "species_emb": {"w": jax.random.normal(next(keys), (species, c),
                                               jnp.float32)},
        "emb2": linear(2 * c, c),
        "dist_proj": [linear(r, c) for _ in range(3)],
        "emb_lin_scalar": [linear(c, 2 * c), linear(2 * c, 3 * c)],
        "emb_lin_tensor": [linear(c, c, bias=False) for _ in range(3)],
        "init_norm": norm(c),
        "layers": [],
        "out_norm": norm(3 * c),
        "linear": linear(3 * c, c),
        "final": [linear(c, c), linear(c, c), linear(c, 1)],
        "species_ref": {"w": jnp.zeros((species, 1), jnp.float32)},
        "data_std": jnp.ones((), jnp.float32),
    }
    for _ in range(cfg["num_layers"]):
        params["layers"].append({
            "lin_scalar": [linear(r, c), linear(c, 2 * c),
                           linear(2 * c, 3 * c)],
            "lin_tensor": [linear(c, c, bias=False) for _ in range(6)],
        })
    return params


def _parts(x):
    """(..., 3, 3, C) -> isotropic, antisymmetric, symmetric-traceless."""
    trace = x[..., 0, 0, :] + x[..., 1, 1, :] + x[..., 2, 2, :]
    iso = trace[..., None, None, :] / 3.0 * jnp.eye(3, dtype=x.dtype)[:, :, None]
    xt = jnp.swapaxes(x, -3, -2)
    return iso, 0.5 * (x - xt), 0.5 * (x + xt) - iso


def _norm2(x):
    return jnp.sum(x * x, axis=(-3, -2))


def _skew(v):
    zero = jnp.zeros_like(v[..., 0])
    return jnp.stack([
        jnp.stack([zero, -v[..., 2], v[..., 1]], axis=-1),
        jnp.stack([v[..., 2], zero, -v[..., 0]], axis=-1),
        jnp.stack([-v[..., 1], v[..., 0], zero], axis=-1)], axis=-2)


def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  edge_block: int | None = 65536,
                  node_block: int | None = None):
    """Per-atom energies (N,). ``edges`` = (src, dst, shift): directed
    pairs with positions[dst] - positions[src] + shift the short vector."""
    rq = rounder(precision)
    src, dst, shift = edges
    n, c = positions.shape[0], cfg["units"]
    vec = positions[dst] - positions[src] + shift
    d = jnp.linalg.norm(vec, axis=-1)
    rhat = vec / d[:, None]
    env = cosine_cutoff(d, cfg["cutoff"])
    rbf = bessel_basis(d, cfg["cutoff"], cfg["num_rbf"])
    eye = jnp.eye(3, dtype=jnp.float32)[:, :, None]
    mix = lambda lin, x: jnp.einsum("...ijc,cd->...ijd", rq(x), rq(lin["w"]))
    matmul = lambda p, q: jnp.einsum("nijc,njkc->nikc", rq(p), rq(q))

    zemb = params["species_emb"]["w"][species]

    def embed(src_b, dst_b, rbf_b, env_b, rhat_b):
        zij = dense(params["emb2"],
                    jnp.concatenate([zemb[src_b], zemb[dst_b]], axis=-1), rq)
        w = [dense(p, rbf_b, rq) * env_b[:, None]
             for p in params["dist_proj"]]
        a_e = _skew(rhat_b)[..., None]
        s_e = (rhat_b[:, :, None] * rhat_b[:, None, :])[..., None] - eye / 3.0
        return rq(zij)[:, None, None, :] * (
            rq(w[0])[:, None, None, :] * eye
            + rq(w[1])[:, None, None, :] * rq(a_e)
            + rq(w[2])[:, None, None, :] * rq(s_e))

    x = blocked_segment_sum(embed, (src, dst, rbf, env, rhat), dst, n,
                            edge_block)
    scal = layernorm(params["init_norm"], _norm2(x))
    for lin in params["emb_lin_scalar"]:
        scal = silu(dense(lin, scal, rq))
    scal = scal.reshape(-1, c, 3)
    iso, anti, sym = _parts(x)
    x = (mix(params["emb_lin_tensor"][0], iso) * scal[:, None, None, :, 0]
         + mix(params["emb_lin_tensor"][1], anti) * scal[:, None, None, :, 1]
         + mix(params["emb_lin_tensor"][2], sym) * scal[:, None, None, :, 2])

    for layer in params["layers"]:
        x = x / (_norm2(x) + 1.0)[..., None, None, :]
        iso, anti, sym = _parts(x)
        iso = mix(layer["lin_tensor"][0], iso)
        anti = mix(layer["lin_tensor"][1], anti)
        sym = mix(layer["lin_tensor"][2], sym)
        y = iso + anti + sym

        def message(src_b, rbf_b, env_b, layer=layer, parts=(iso, anti, sym)):
            f = rbf_b
            for lin in layer["lin_scalar"]:
                f = silu(dense(lin, f, rq))
            f = rq((f * env_b[:, None]).reshape(-1, c, 3))
            return sum(f[:, None, None, :, k] * rq(parts[k][src_b])
                       for k in range(3))

        m = blocked_segment_sum(message, (src, rbf, env), dst, n, edge_block)
        b = matmul(y, m) + matmul(m, y)
        iso, anti, sym = _parts(b)
        scale = (_norm2(b) + 1.0)[..., None, None, :]
        dx = (mix(layer["lin_tensor"][3], iso / scale)
              + mix(layer["lin_tensor"][4], anti / scale)
              + mix(layer["lin_tensor"][5], sym / scale))
        x = x + dx + matmul(dx, dx)

    iso, anti, sym = _parts(x)
    inv = jnp.concatenate([_norm2(iso), _norm2(anti), _norm2(sym)], axis=-1)
    # the readout stack stays float32 in the program at every compute type
    same = rounder("float32")
    out = dense(params["linear"], layernorm(params["out_norm"], inv), same)
    e_atom = mlp(params["final"], out, same)[:, 0]
    return (params["data_std"] * e_atom
            + params["species_ref"]["w"][species, 0])
