"""Minimal neural-net building blocks on plain parameter pytrees.

Models in this framework are pure functions over nested-dict parameter
pytrees (no flax dependency on the hot path): transparent for sharding,
trivial to convert into from torch state dicts, and friendly to
``jax.grad``/``optax``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def linear_init(key, d_in: int, d_out: int, bias: bool = True, scale: str = "torch"):
    """Torch-style default init: W, b ~ U(-1/sqrt(d_in), 1/sqrt(d_in)).

    Non-zero bias init matters: scale-producing MLPs fed with small inputs
    must still emit O(1) outputs at init, or deep feature pipelines collapse.
    """
    wkey, bkey = jax.random.split(key)
    if scale == "glorot":
        lim = np.sqrt(6.0 / (d_in + d_out))
        w = jax.random.uniform(wkey, (d_in, d_out), minval=-lim, maxval=lim)
    else:
        lim = 1.0 / np.sqrt(d_in)
        w = jax.random.uniform(wkey, (d_in, d_out), minval=-lim, maxval=lim)
    p = {"w": w}
    if bias:
        lim = 1.0 / np.sqrt(d_in)
        p["b"] = jax.random.uniform(bkey, (d_out,), minval=-lim, maxval=lim)
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def linear_init_vp(key, d_in: int, d_out: int):
    """Variance-preserving linear init (e3nn convention): W ~ N(0, 1/d_in)."""
    return {"w": jax.random.normal(key, (d_in, d_out)) / np.sqrt(d_in)}


def cast_params_subtrees(params: dict, dtype, keep_fp32: tuple = ()):
    """Cast floating leaves of a param dict to ``dtype``, leaving the named
    top-level subtrees untouched (precision-critical pieces like species
    reference energies and readout heads). Shared by the model zoo's
    bfloat16 compute switch."""
    def cast(tree):
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )

    return {k: (v if k in keep_fp32 else cast(v)) for k, v in params.items()}


def silu_2mom_gain() -> float:
    """e3nn's normalize2mom(silu) constant: 1 / sqrt(E[silu(x)^2]), x~N(0,1),
    by Gauss-Hermite quadrature. Single source of truth shared by the
    variance-preserving init below and the torch-weight conversion folding
    (models/convert.py)."""
    global _SILU_GAIN
    if _SILU_GAIN is None:
        x, w = np.polynomial.hermite_e.hermegauss(201)
        silu = x / (1.0 + np.exp(-x))
        _SILU_GAIN = float(1.0 / np.sqrt(np.sum(w * silu**2) / np.sum(w)))
    return _SILU_GAIN


_SILU_GAIN = None


def mlp_init_vp(key, dims: list[int], act_gain: float | None = None):
    """Bias-free variance-preserving MLP init (e3nn FullyConnectedNet
    convention): W ~ N(0, g^2/d_in), with g compensating silu's second
    moment (silu_2mom_gain) on layers fed by an activation, so deep
    bias-free stacks keep O(1) outputs."""
    if act_gain is None:
        act_gain = silu_2mom_gain()
    keys = jax.random.split(key, len(dims) - 1)
    out = []
    for i, (k, a, b) in enumerate(zip(keys, dims[:-1], dims[1:])):
        g = act_gain if i > 0 else 1.0
        out.append({"w": jax.random.normal(k, (a, b)) * (g / np.sqrt(a))})
    return out


def mlp_init(key, dims: list[int], bias: bool = True):
    keys = jax.random.split(key, len(dims) - 1)
    return [linear_init(k, a, b, bias=bias) for k, a, b in zip(keys, dims[:-1], dims[1:])]


def mlp(p, x, act=jax.nn.silu, final_act=None):
    for i, layer in enumerate(p):
        x = linear(layer, x)
        if i < len(p) - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


def gated_mlp_init(key, d_in: int, dims: list[int]):
    """CHGNet-style gated MLP: core MLP * sigmoid(gate MLP)."""
    k1, k2 = jax.random.split(key)
    return {
        "core": mlp_init(k1, [d_in] + dims),
        "gate": mlp_init(k2, [d_in] + dims),
    }


def gated_mlp(p, x, act=jax.nn.silu):
    core = mlp(p["core"], x, act=act, final_act=act)
    gate = mlp(p["gate"], x, act=act, final_act=jax.nn.sigmoid)
    return core * gate


def layernorm_init(dim: int):
    return {"g": jnp.ones((dim,)), "b": jnp.zeros((dim,))}


def layernorm(p, x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def gather_rows(table, idx):
    """Row gather whose GRADIENT accumulates in fp32.

    A plain ``table[idx]`` on a half-precision table transposes to a
    half-precision scatter-add — per-row grad contributions from every
    referencing edge/atom round at bf16 as they accumulate (and violate
    the dtype_discipline contract: accumulate fp32, store half). Routing
    the gather through an fp32 view moves the scatter-add to fp32 — the
    cotangent upcasts PER CONTRIBUTION before accumulation and rounds to
    the storage dtype once — while the forward still hands consumers the
    original compute dtype (the upcast fuses into the gather; rows, not
    the table, pay the convert).
    """
    if table.dtype in (jnp.bfloat16, jnp.float16):
        return table.astype(jnp.float32)[idx].astype(table.dtype)
    return table[idx]


def embedding(p, idx):
    return gather_rows(p["w"], idx)
