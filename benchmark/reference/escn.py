"""Plain reference of eSCN-MD, the backbone of UMA (Wood et al.,
arXiv:2506.23971; ``uma-s-1``): float32, ``jax.numpy`` only, every expert
mixed as written, no chunk layout, no padding contract, no kernel.

Node state x_i: one vector per degree l <= lmax and channel, (N, (lmax+1)^2,
C), scalars from the species embedding plus the system embedding ``csd``
(charge, spin, dataset). With x~ = norm(x) plus ``csd`` on its scalars,
D_ij the per-degree Wigner blocks that take lab coefficients into the frame
whose polar axis is the edge (pos_j - pos_i pointing at the sender j, i the
receiver), narrowed to |m| <= mmax, r_ij the radial function of
[Gaussians | source-species | target-species embedding], and
W = sum_k softmax(gate)_k W_k the 32 experts merged by a per-system gate:

    embedding:  x_i += 1/avg_degree sum_j env(d_ij) D_ij^T [r_ij in the m = 0 slots]
    layer:      x_i += 1/avg_degree sum_j env(d_ij) D_ij^T SO2_2(W)(
                    Gate(SO2_1(W)(r_ij * [D_ij x~_j | D_ij x~_i])))
                x_i += FFN(norm(x_i))
    readout:    E_i = MLP(scalars of norm(x_i)) + species_ref

An SO(2) convolution maps, for each |m|, the coefficients (l >= |m|) x
channels through one linear map; for m > 0 the (cos m phi, sin m phi) pair
(f+, f-) goes through a complex pair of maps: y+ = Wr f+ - Wi f-,
y- = Wr f- + Wi f+. The first convolution also gives the gates of the
activation between the two.

**The Wigner blocks take a route of their own.** The lab basis is this
package's real harmonics (``so3.py``, z polar). An edge frame is a
right-handed triad (a, b, u) with u the edge's unit vector and a any unit
vector across it; the edge-frame basis is the same harmonics in the triad's
coordinates, so D^1 is the triad itself (rows a, b, u) and D^2 couples
D^1 x D^1 through the (1, 1, 2) table. The angle of ``a`` about u is free:
the convolutions commute with it, so the energy does not depend on it
(``free_angle`` turns it, for the test of that). Only lmax <= 2 is built.

Forces are -dE/dpositions. The sum over edges runs in blocks, each
recomputed in the backward pass: at 8,192 atoms x 54 neighbours
``[D x~_j | D x~_i]`` alone is 4 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import so3
from .common import (blocked_segment_sum, layernorm, polynomial_cutoff,
                     rounder, silu)

# where the harmonic of order m sits in a degree's block of ``so3.py``:
# (cos m phi, sin m phi) for m > 0, the one zonal function for m = 0
_PLUS = {0: {0: 0}, 1: {0: 2, 1: 0}, 2: {0: 2, 1: 3, 2: 4}}   # [l][m]
_MINUS = {1: {1: 1}, 2: {1: 1, 2: 0}}


class Tables:
    """Where each (l, m) sits in the stacked coefficients, and the coupling
    table behind D^2."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.lmax, self.mmax = int(cfg["lmax"]), int(cfg["mmax"])
        if not 0 <= self.mmax <= self.lmax <= 2:
            raise ValueError("the reference builds Wigner blocks to l = 2")
        self.cg112 = so3.clebsch_gordan(1, 1, 2)
        # per m: the stack rows of (l, +m) and (l, -m), l = m..lmax
        self.plus = {m: np.array([l * l + _PLUS[l][m]
                                  for l in range(m, self.lmax + 1)])
                     for m in range(self.mmax + 1)}
        self.minus = {m: np.array([l * l + _MINUS[l][m]
                                   for l in range(m, self.lmax + 1)])
                      for m in range(1, self.mmax + 1)}
        self.degree_of_row = np.repeat(np.arange(self.lmax + 1),
                                       2 * np.arange(self.lmax + 1) + 1)

    def n_l(self, m: int) -> int:
        return self.lmax + 1 - m


def init_params(cfg: dict, tables: Tables, key):
    """Random weights in the program's tree layout, which is fairchem's
    state dict tensor for tensor (normal embeddings, torch-style uniform
    linears ``(out, in)``, as the program's own initialiser draws them). An
    SO(2) convolution holds per m one matrix ``(experts, out, in)``, inputs
    (l, channel) l-major; outputs likewise, for m = 0 followed by the
    gates, for m > 0 the real rows then the imaginary ones. Traceable."""
    c, h = cfg["sphere_channels"], cfg["hidden_channels"]
    ce, k, lmax = cfg["edge_channels"], cfg["num_experts"], tables.lmax
    z = cfg["max_num_elements"]
    dx = cfg["num_distance_basis"] + 2 * ce
    slots = sum(tables.n_l(m) for m in range(tables.mmax + 1))
    keys = iter(jax.random.split(key, 64 + 32 * cfg["num_layers"]))

    def uniform(shape, fan_in):
        lim = 1.0 / np.sqrt(fan_in)
        return jax.random.uniform(next(keys), shape, jnp.float32, -lim, lim)

    def linear(d_in, d_out):
        return {"w": uniform((d_out, d_in), d_in),
                "b": uniform((d_out,), d_in)}

    normal = lambda *shape: {"w": jax.random.normal(next(keys), shape,
                                                    jnp.float32)}
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
    experts = (k,) if k > 1 else ()

    def radial(d_out):
        return {"lins": [linear(dx, ce), linear(ce, d_out)],
                "lns": [{"g": ones(ce), "b": zeros(ce)}]}

    def so2(c_in, c_out, n_gates):
        p = {}
        for m in range(tables.mmax + 1):
            n = tables.n_l(m)
            d_out = n * c_out + n_gates if m == 0 else 2 * n * c_out
            p[f"m{m}"] = uniform(experts + (d_out, n * c_in), n * c_in)
            if m == 0:
                p["m0_b"] = zeros(d_out)
        return p

    params = {
        "sphere_embedding": normal(z, c),
        "source_embedding": normal(z, ce),
        "target_embedding": normal(z, ce),
        "csd": {"charge": normal(cfg["num_charges"], c),
                "spin": normal(cfg["num_spins"], c),
                "dataset": normal(cfg["num_datasets"], c),
                "mix": linear(3 * c, c)},
        "edge_deg_rad": radial((lmax + 1) * c),
        "blocks": [],
        "norm": {"w": ones(lmax + 1, c)},
        "energy_head": {"lin1": linear(c, c), "lin2": linear(c, 1)},
        "species_ref": {"w": zeros(z)},
    }
    if k > 1:
        params["mole_gate"] = {"lin1": linear(2 * c, c),
                               "lin2": linear(c, k)}
    for _ in range(cfg["num_layers"]):
        params["blocks"].append({
            "norm1": {"w": ones(lmax + 1, c)},
            "so2_1": {**so2(2 * c, h, lmax * h),
                      "rad": radial(slots * 2 * c)},
            "so2_2": so2(h, c, 0),
            "ff_norm": {"w": ones(lmax + 1, c)},
            "ff": {"lin1": {"w": normal(lmax + 1, h, c)["w"] / np.sqrt(c),
                            "b": zeros(h)},
                   "gate": linear(c, lmax * h),
                   "lin2": {"w": normal(lmax + 1, c, h)["w"] / np.sqrt(h),
                            "b": zeros(c)}},
        })
    return params


# ---- geometry ------------------------------------------------------------

def edge_frames(u, free_angle=None):
    """(E, 3, 3): rows a, b, u of a right-handed triad per unit vector u.
    ``a`` is the coordinate axis u is least along, made orthogonal to u,
    then turned about u by ``free_angle``."""
    axis = jnp.eye(3, dtype=u.dtype)[jnp.argmin(jnp.abs(u), axis=-1)]
    a = axis - jnp.sum(axis * u, axis=-1, keepdims=True) * u
    a = a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    b = jnp.cross(u, a)
    if free_angle is not None:
        co, si = jnp.cos(free_angle)[:, None], jnp.sin(free_angle)[:, None]
        a, b = co * a + si * b, co * b - si * a
    return jnp.stack([a, b, u], axis=-2)


def wigner_blocks(tables: Tables, frames):
    """[D^0, .., D^lmax], D^l (E, 2l+1, 2l+1) with
    Y_l(frame r) = D^l Y_l(r): edge-frame coefficients are D^l times the
    lab's, and D^l's transpose takes them back."""
    hi = jax.lax.Precision.HIGHEST
    blocks = [jnp.ones(frames.shape[:-2] + (1, 1), frames.dtype), frames]
    if tables.lmax == 2:
        cg = jnp.asarray(tables.cg112, frames.dtype)
        blocks.append(jnp.einsum("ijk,eia,ejb,abc->ekc", cg, frames, frames,
                                 cg, precision=hi))
    return blocks[:tables.lmax + 1]


def _rotate(blocks, x, rq, back: bool = False):
    """Every degree's coefficients through its block ((E, S, C) -> same)."""
    out, o = [], 0
    for l, d in enumerate(blocks):
        rows = x[:, o:o + 2 * l + 1, :]
        out.append(jnp.einsum("eqp,eqc->epc" if back else "epq,eqc->epc",
                              rq(d), rq(rows)))
        o += 2 * l + 1
    return jnp.concatenate(out, axis=1)


# ---- pieces --------------------------------------------------------------

def norm_sh(tables: Tables, w, x):
    """x over its root mean square (each degree weighing the same, no
    centring), times a weight per degree and channel."""
    deg = tables.degree_of_row
    balance = jnp.asarray(1.0 / ((2 * deg + 1) * (tables.lmax + 1)),
                          x.dtype)
    ms = jnp.mean(jnp.sum(x * x * balance[:, None], axis=-2), axis=-1)
    return x / jnp.sqrt(ms + 1e-12)[:, None, None] * w[deg]


def gate_activation(tables: Tables, x, gates):
    """Scalars through silu, every higher degree scaled by the sigmoid of
    its gate ((rows, lmax, H), one per degree and channel). The scalars
    are row 0 of the stack in both frames."""
    deg = tables.degree_of_row
    scale = jnp.concatenate(
        [jnp.ones_like(gates[:, :1]), jax.nn.sigmoid(gates)], axis=1)[:, deg]
    return jnp.concatenate([silu(x[:, :1]), x[:, 1:]], axis=1) * scale


def linear(p, x, rq):
    return jnp.matmul(rq(x), rq(p["w"]).T) + p["b"]


def radial_function(p, x, rq):
    """Linear, LayerNorm, SiLU, Linear."""
    hidden = silu(layernorm(p["lns"][0], linear(p["lins"][0], x, rq)))
    return linear(p["lins"][1], hidden, rq)


def so2_convolution(tables: Tables, w, x, rq, c_out: int, scale=None):
    """``x`` (E, S, c_in) in the edge frame -> ((E, S, c_out), gates or
    None); ``w`` holds the merged matrices ``(out, in)``; ``scale``
    (E, slots, c_in) multiplies the input per (l, |m|) slot, m-major."""
    c_in = x.shape[-1]
    y = jnp.zeros(x.shape[:2] + (c_out,), x.dtype)
    gates, slot = None, 0
    # (out rows, in) -> (l_out, c_out, l_in, c_in), and the map it is
    mix = lambda f, q, n: jnp.einsum(
        "elc,odlc->eod", rq(f), rq(q.reshape(n, c_out, n, c_in)))
    for m in range(tables.mmax + 1):
        n = tables.n_l(m)
        s = 1.0 if scale is None else scale[:, slot:slot + n]
        slot += n
        fp = x[:, tables.plus[m]] * s
        if m == 0:
            main, rest = w["m0"][:n * c_out], w["m0"][n * c_out:]
            bias = w["m0_b"]
            y = y.at[:, tables.plus[0]].set(
                mix(fp, main, n) + bias[:n * c_out].reshape(n, c_out))
            if rest.shape[0]:
                gates = jnp.einsum(
                    "elc,glc->eg", rq(fp),
                    rq(rest.reshape(-1, n, c_in))) + bias[n * c_out:]
            continue
        fm = x[:, tables.minus[m]] * s
        re, im = w[f"m{m}"][:n * c_out], w[f"m{m}"][n * c_out:]
        y = y.at[:, tables.plus[m]].set(mix(fp, re, n) - mix(fm, im, n))
        y = y.at[:, tables.minus[m]].set(mix(fm, re, n) + mix(fp, im, n))
    return y, gates


def feed_forward(tables: Tables, p, x, rq):
    """A linear map per degree, the gate activation (gates from the input's
    scalars), a linear map per degree; biases on the scalars."""
    deg = tables.degree_of_row
    gates = linear(p["gate"], x[:, 0], rq)
    h = jnp.einsum("nsc,shc->nsh", rq(x), rq(p["lin1"]["w"][deg]))
    h = h.at[:, 0].add(p["lin1"]["b"])
    h = gate_activation(tables, h, gates.reshape(len(x), tables.lmax, -1))
    y = jnp.einsum("nsh,sch->nsc", rq(h), rq(p["lin2"]["w"][deg]))
    return y.at[:, 0].add(p["lin2"]["b"])


def expert_coefficients(params, species, csd):
    """softmax of the gate over [mean species embedding of the system |
    system embedding]: one set of coefficients per system, float32."""
    same = rounder("float32")
    composition = jnp.mean(params["sphere_embedding"]["w"][species], axis=0)
    hidden = silu(linear(params["mole_gate"]["lin1"],
                         jnp.concatenate([composition, csd]), same))
    return jax.nn.softmax(linear(params["mole_gate"]["lin2"], hidden, same))


def merge_experts(w, coefficients):
    """sum_k coefficients_k W_k for every matrix with an expert axis (all
    of them, or none in a model of one expert)."""
    if coefficients is None:
        return w
    hi = jax.lax.Precision.HIGHEST
    return {name: (jnp.einsum("k,kab->ab", coefficients, a, precision=hi)
                   if a.ndim == 3 else a)
            for name, a in w.items() if name != "rad"}


def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  edge_block: int | None = 16384, free_angle=None):
    """Per-atom energies (N,). ``edges`` = (src, dst, shift): directed
    pairs with positions[dst] - positions[src] + shift the short vector;
    messages go from src to dst. Float32 everywhere for ``"float32"``; a
    lower precision rounds the operands of the contractions in rotations,
    radial functions, convolutions and feed-forward, and leaves geometry,
    blocks' construction, gate, expert merge and energy head as they are
    (what the configuration's ``precision`` states of the program)."""
    rq, same = rounder(precision), rounder("float32")
    src, dst, shift = edges
    n, c, h = (positions.shape[0], cfg["sphere_channels"],
               cfg["hidden_channels"])
    lmax, rows = tables.lmax, (tables.lmax + 1) ** 2
    system = cfg.get("system", {})

    # the vector from the receiver to the sender, as fairchem has it
    vec = positions[src] - positions[dst] - shift
    d = jnp.linalg.norm(vec, axis=-1)
    env = polynomial_cutoff(d, cfg["cutoff"], 6)
    centres = jnp.linspace(0.0, cfg["cutoff"], cfg["num_distance_basis"])
    width = (cfg["basis_width_scalar"] * cfg["cutoff"]
             / (cfg["num_distance_basis"] - 1))
    gauss = jnp.exp(-0.5 * ((d[:, None] - centres) / width) ** 2)
    unit = vec / d[:, None]
    angle = jnp.zeros_like(d) if free_angle is None else free_angle

    csd = linear(params["csd"]["mix"], jnp.concatenate([
        params["csd"]["charge"]["w"][system.get("charge", 0)
                                     - cfg["charge_min"]],
        params["csd"]["spin"]["w"][system.get("spin", 0)],
        params["csd"]["dataset"]["w"][system.get("dataset", 0)]]), same)
    coefficients = (expert_coefficients(params, species, csd)
                    if cfg["num_experts"] > 1 else None)

    def edge_scalars(src_b, dst_b, gauss_b):
        return jnp.concatenate(
            [gauss_b, params["source_embedding"]["w"][species[src_b]],
             params["target_embedding"]["w"][species[dst_b]]], axis=-1)

    def blocks_of(unit_b, angle_b):
        return wigner_blocks(tables, edge_frames(unit_b, angle_b))

    def degree_message(src_b, dst_b, gauss_b, env_b, unit_b, angle_b):
        w = radial_function(params["edge_deg_rad"],
                            edge_scalars(src_b, dst_b, gauss_b), rq)
        y = jnp.zeros((len(src_b), rows, c), w.dtype)
        y = y.at[:, tables.plus[0]].set(w.reshape(-1, lmax + 1, c))
        return _rotate(blocks_of(unit_b, angle_b), y, rq,
                       back=True) * env_b[:, None, None]

    x = jnp.zeros((n, rows, c), jnp.float32)
    x = x.at[:, 0].set(params["sphere_embedding"]["w"][species] + csd)
    arrays = (src, dst, gauss, env, unit, angle)
    x = x + blocked_segment_sum(degree_message, arrays, dst, n,
                                edge_block) / cfg["avg_degree"]

    for layer in params["blocks"]:
        w1 = merge_experts(layer["so2_1"], coefficients)
        w2 = merge_experts(layer["so2_2"], coefficients)
        xn = norm_sh(tables, layer["norm1"]["w"], x)
        xn = xn.at[:, 0].add(csd)

        def message(src_b, dst_b, gauss_b, env_b, unit_b, angle_b,
                    layer=layer, w1=w1, w2=w2, xn=xn):
            blocks = blocks_of(unit_b, angle_b)
            scale = radial_function(layer["so2_1"]["rad"],
                                    edge_scalars(src_b, dst_b, gauss_b), rq)
            both = jnp.concatenate([_rotate(blocks, xn[src_b], rq),
                                    _rotate(blocks, xn[dst_b], rq)], axis=-1)
            y, gates = so2_convolution(
                tables, w1, both, rq, h,
                scale.reshape(len(src_b), -1, 2 * c))
            y = gate_activation(tables, y,
                                gates.reshape(len(src_b), lmax, -1))
            y, _ = so2_convolution(tables, w2, y, rq, c)
            return _rotate(blocks, y, rq, back=True) * env_b[:, None, None]

        x = x + blocked_segment_sum(message, arrays, dst, n,
                                    edge_block) / cfg["avg_degree"]
        x = x + feed_forward(tables, layer["ff"],
                             norm_sh(tables, layer["ff_norm"]["w"], x), rq)

    scalars = norm_sh(tables, params["norm"]["w"], x)[:, 0]
    hidden = silu(linear(params["energy_head"]["lin1"], scalars, same))
    return (linear(params["energy_head"]["lin2"], hidden, same)[:, 0]
            + params["species_ref"]["w"][species])
