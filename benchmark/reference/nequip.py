"""Plain reference of NequIP (Batzner et al., Nat. Commun. 2022,
arXiv:2101.03164) at the sizes of SevenNet-0 (Park et al., JCTC 2024,
arXiv:2402.03789; github.com/MDIL-SNU/SevenNet,
``pretrained_potentials/SevenNet_0__11July2024``): float32, ``jax.numpy``
only, one ``einsum`` per coupling path, no fused table, no chunk layout, no
padding, no partitions. It imports nothing of the program.

With ``mul_l`` the channels of degree l (128 / 64 / 32), all irreps even
(so parity restricts no path), S species slots, and per convolution t the
irreps ``in_t`` of h and ``out_t`` of the next h (the last keeps scalars
only), ``g_t`` gate scalars (one per channel of out_t's l > 0)::

    d_e = |r_dst - r_src + shift|,  u_e = r_e / d_e,  Y_l(u_e), |Y_l|^2 = 2l+1
    b_e = sqrt(2 / r_c) sin(f_n d_e) / d_e * env(d_e)      f_n = n pi / r_c, trainable
    env = 1 (d < r_on); (r_c^2 - d^2)^2 (r_c^2 + 2 d^2 - 3 r_on^2) / (r_c^2 - r_on^2)^3; 0 (d >= r_c)
    h^0 = onehot(z) W_emb / sqrt(S)
    s   = Lin_sc(h)        in_t -> (mul_0 + g_t)x0e + out_t's l > 0
    x   = Lin_1(h)         in_t -> in_t
    R_e = MLP(b_e)         8 -> 64 -> 64 -> sum over paths of mul_{l_in}, no bias, silu*
    m_e[p] = sqrt(2 l_out + 1) R_e[p] * CG^p(x[src_e]_{l_in}, Y_{l_Y}(u_e))
    a_i = sum_{e -> i} m_e / sqrt(avg_num_neighbors)
    y   = Lin_2(a) + s     paths into one degree share one fan-in
    h'_0 = silu*(y_0[:mul_0]);  h'_l = y_l * silu*(gates_l)
    E_i = (h^T W_r1 / sqrt(mul_0)) W_r2 / sqrt(mul_0 / 2) * scale + shift[z_i]

Every ``Lin`` is e3nn's ``x W / sqrt(fan_in)`` per degree without bias,
``silu*`` is silu times e3nn's second-moment gain, ``CG^p`` the real
coupling with sum(C^2) = 2 l_out + 1 (``so3.clebsch_gordan``). Forces are
-dE/dpositions.

Departures from the published model, all for want of the checkpoint and its
``pre_train.yaml`` (the configuration lists them under ``assumed``): the
XPLOR envelope with r_on = 4.5 multiplying the Bessel rows before the MLP;
Bessel frequencies at n pi / r_c with the prefactor sqrt(2 / r_c); the
division by sqrt(avg_num_neighbors); silu on scalars and gates alike; the
factor sqrt(2 l_out + 1) a path; ``scale`` 1 and ``shift`` 0. The sizes
are checked by a count: 842,440 weights at 89 species.

Membership is explicit (``d < cutoff``): the harness pads the edge list
with ghost edges of exactly the cutoff, where the envelope is 0 and the
bias-free MLP gives 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import so3
from .chgnet import rounder  # common.rounder with the 8-bit clip (PERF.md 2)
from .common import blocked_segment_sum, silu
from .mace import SILU_GAIN


class Tables:
    """The irreps and coupling paths a configuration implies."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.cfg = cfg
        hidden = tuple(cfg["channels"])
        n = cfg["num_convolutions"]
        self.mul_out = [hidden] * (n - 1) + [hidden[:1]]
        self.mul_in = [hidden[:1]] + self.mul_out[:-1]
        self.gates = [sum(m[1:]) for m in self.mul_out]
        self.paths = [
            [(li, ly, lo) for li in range(len(self.mul_in[t]))
             for ly in range(cfg["l_max"] + 1)
             for lo in range(len(self.mul_out[t]))
             if abs(li - ly) <= lo <= li + ly] for t in range(n)]
        self.cg = {p: so3.clebsch_gordan(*p)
                   for paths in self.paths for p in paths}

    def wide(self, t: int) -> dict:
        """Channels of each degree of ``y``: the scalars carry the gates."""
        out = self.mul_out[t]
        return {0: out[0] + self.gates[t],
                **{l: m for l, m in enumerate(out) if l}}

    def fan_in(self, t: int, l_out: int) -> int:
        return sum(self.mul_in[t][li] for li, _, lo in self.paths[t]
                   if lo == l_out)


def name(path) -> str:
    return "_".join(map(str, path))


def init_params(cfg: dict, tables: Tables, key):
    """Random weights, N(0, 1) as e3nn draws them and as the program's own
    initialiser does: one matrix per path and layer for the radial MLP's
    last layer and for ``Lin_2``. Traceable: jit it to make every leaf in
    one call on the device. ``scale`` and ``shift`` are the release's fit
    to its training set and are not among the 842,440."""
    n = cfg["num_convolutions"]
    keys = iter(jax.random.split(key, 8 + 48 * n))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)
    hidden = list(cfg["radial_hidden"])
    layers = []
    for t in range(n):
        mul_in, wide = tables.mul_in[t], tables.wide(t)
        dims = [cfg["num_bessel"]] + hidden
        layers.append({
            "lin_sc": {str(l): normal(mul_in[l], wide[l])
                       for l in wide if l < len(mul_in)},
            "lin_1": {str(l): normal(m, m) for l, m in enumerate(mul_in)},
            "radial": [normal(a, b) for a, b in zip(dims[:-1], dims[1:])],
            "radial_out": {name(p): normal(hidden[-1], mul_in[p[0]])
                           for p in tables.paths[t]},
            "lin_2": {name(p): normal(mul_in[p[0]], wide[p[2]])
                      for p in tables.paths[t]},
        })
    c = tables.mul_out[-1][0]
    return {
        # a one-hot's components have second moment 1 / S, which e3nn's
        # 1 / sqrt(fan_in) takes for 1: the gain keeps h^0 at unit variance
        # at random weights (a checkpoint's embedding carries it itself)
        "embedding": normal(cfg["num_species"], tables.mul_in[0][0])
        * np.sqrt(cfg["num_species"]),
        "frequencies": jnp.arange(1, cfg["num_bessel"] + 1,
                                  dtype=jnp.float32) * (np.pi / cfg["cutoff"]),
        "layers": layers,
        "readout": [normal(c, c // 2), normal(c // 2, 1)],
        "scale": jnp.ones((), jnp.float32),
        "shift": jnp.zeros((cfg["num_species"],), jnp.float32),
    }


def count_weights(params) -> int:
    """Trained weights: every leaf but ``scale`` and ``shift``."""
    return sum(int(np.prod(x.shape)) for k, v in params.items()
               if k not in ("scale", "shift") for x in jax.tree.leaves(v))


def xplor(d, cutoff: float, cutoff_on: float):
    rc2, on2 = cutoff ** 2, cutoff_on ** 2
    switch = ((rc2 - d ** 2) ** 2 * (rc2 + 2.0 * d ** 2 - 3.0 * on2)
              / (rc2 - on2) ** 3)
    return jnp.where(d < cutoff_on, 1.0, jnp.where(d < cutoff, switch, 0.0))


def act(x):
    """e3nn's normalize2mom(silu)."""
    return SILU_GAIN * silu(x)


def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  edge_block: int | None = 16384, faults: tuple = ()):
    """Per-atom energies (N,). ``edges`` = (src, dst, shift): directed
    pairs with positions[dst] - positions[src] + shift the short vector.
    ``faults`` (tests only) leaves a piece of the mathematics out:
    ``"gates"`` (gate scalars unactivated), ``"odd_path"`` (the cross
    product path (1, 1, 1) dropped), ``"self_connection"``."""
    # rq rounds the operands of contractions AND, as the configuration's
    # ``precision`` states for the program, every tensor it keeps in its
    # compute type: features, radial weights, messages before the sum, the
    # sums, the gate's pieces (an identity in float32)
    rq = rounder(precision)
    lin = lambda x, w: rq(jnp.einsum("nmc,cd->nmd", rq(x), rq(w)) / np.sqrt(
        w.shape[0]))
    src, dst, shift = edges
    n = positions.shape[0]
    vec = positions[dst] - positions[src] + shift
    d = jnp.linalg.norm(vec, axis=-1)
    u = vec / d[:, None]
    inside = d < cfg["cutoff"]
    bessel = (np.sqrt(2.0 / cfg["cutoff"])
              * jnp.sin(params["frequencies"] * d[:, None]) / d[:, None])
    radial_in = jnp.where(
        inside, xplor(d, cfg["cutoff"], cfg["cutoff_on"]), 0.0)[:, None] \
        * bessel
    ys = [so3.spherical_harmonics(l, u, jnp)
          for l in range(cfg["l_max"] + 1)]

    z = species
    h = {0: rq(params["embedding"][z][:, None, :]
               / np.sqrt(cfg["num_species"]))}
    for t, layer in enumerate(params["layers"]):
        paths = [p for p in tables.paths[t]
                 if not ("odd_path" in faults and p == (1, 1, 1))]
        s = {int(l): lin(h[int(l)], w) for l, w in layer["lin_sc"].items()}
        x = {int(l): lin(h[int(l)], w) for l, w in layer["lin_1"].items()}

        def messages(src_b, radial_b, *y_b, x=x, layer=layer, paths=paths):
            hidden = radial_b
            for w in layer["radial"]:
                hidden = rq(act(rq(jnp.matmul(rq(hidden), rq(w))
                                   / np.sqrt(w.shape[0]))))
            out = []
            for p in paths:
                li, ly, lo = p
                w = layer["radial_out"][name(p)]
                weight = rq(jnp.matmul(rq(hidden), rq(w))
                            / np.sqrt(w.shape[0]))
                cg = jnp.asarray(tables.cg[p], jnp.float32)
                with_y = jnp.einsum("abp,eb->eap", rq(cg), rq(y_b[ly]))
                coupled = rq(jnp.einsum("eap,eac->epc", rq(with_y),
                                        rq(x[li][src_b])))
                piece = rq(np.sqrt(2 * lo + 1) * coupled
                           * weight[:, None, :])
                out.append(piece.reshape(piece.shape[0], -1))
            return jnp.concatenate(out, axis=1)

        summed = rq(blocked_segment_sum(
            messages, (src, radial_in, *ys), dst, n, edge_block)
            / np.sqrt(cfg["avg_num_neighbors"]))
        y, o = {}, 0
        for p in paths:
            li, _, lo = p
            mul = tables.mul_in[t][li]
            piece = summed[:, o:o + (2 * lo + 1) * mul].reshape(
                n, 2 * lo + 1, mul)
            o += (2 * lo + 1) * mul
            w = layer["lin_2"][name(p)]
            term = rq(jnp.einsum("nmc,cd->nmd", rq(piece), rq(w)) / np.sqrt(
                tables.fan_in(t, lo)))
            y[lo] = rq(y[lo] + term) if lo in y else term
        if "self_connection" not in faults:
            y = {l: rq(y[l] + s[l]) if l in s else y[l] for l in y}
        scalars = tables.mul_out[t][0]
        gates = y[0][:, 0, scalars:]
        if tables.gates[t] and "gates" not in faults:
            gates = rq(act(gates))
        h, o = {0: rq(act(y[0][:, :, :scalars]))}, 0
        for l in sorted(y):
            if l:
                mul = tables.mul_out[t][l]
                h[l] = rq(y[l] * gates[:, None, o:o + mul])
                o += mul
    w1, w2 = params["readout"]
    e = rq(jnp.matmul(rq(jnp.matmul(rq(h[0][:, 0, :]), rq(w1))
                         / np.sqrt(w1.shape[0])), rq(w2))
           / np.sqrt(w2.shape[0]))
    return e[:, 0] * params["scale"] + params["shift"][z]
