"""NequIP: gated equivariant convolutions with unequal channels per degree.

The architecture of Batzner et al. (Nat. Commun. 2022, arXiv:2101.03164) in
the form SevenNet-0 ships it (Park et al., JCTC 2024, arXiv:2402.03789):
hidden features ``128x0e + 64x1e + 32x2e`` (a channel count per degree),
all irreps even, so every triangle-allowed ``(l_in, l_Y, l_out)`` couples —
the odd-sum paths ``(1,1,1)``, ``(1,2,2)``, ``(2,1,2)``, ``(2,2,1)`` among
them, which MACE's parity filter drops — and a gate on the ``l > 0``
features. Per convolution t, with h the node features::

    s   = Lin_sc(h)                     in -> (scalars + gates)x0e + out's l > 0
    x   = Lin_1(h)                      in -> in
    R_e = MLP(bessel(d_e) * env(d_e))   8 -> 64 -> 64 -> sum over paths of mul_in
    m_e[p] = sqrt(2 l_out + 1) R_e[p] * CG^p(x[src_e], Y(u_e))
    a_i = sum_{e -> i} m_e / sqrt(avg_num_neighbors)
    y   = Lin_2(a) + s                  paths into one degree share a fan-in
    h'_0 = silu*(y_0[:scalars]);  h'_l = y_l * silu*(gates_l)

Every ``Lin`` is e3nn's: per degree ``x W / sqrt(fan_in)``, no bias, weights
N(0, 1); ``silu*`` is silu times e3nn's second-moment gain. The constants
are applied where the weights are cast, so a converted checkpoint is used
verbatim. Site energies are two linears on the last layer's scalars.

Feature layout: a node is one flat row ``[l=0 | l=1 as (m, c) | l=2 as
(m, c)]``, 480 numbers at the published widths; the halo exchanges that
row. A message is one flat row too, a block per input degree, a piece
``(2 l_out + 1) x mul_in`` per path inside it; each block is padded with
zero columns to whole lane tiles (128 columns: every slice the chunk body
cuts is then a whole number of tiles, which the Pallas edge sum's block
copies need; 3,136 -> 3,200 columns in a middle layer) and ``Lin_2`` never
reads the padding.

TPU mapping: nothing on the edge side has a tile axis of 3 or 5, or 64 or
32 channels in the lanes. Per chunk the coupling is
``sum_a (Y W_a) * (x_a[src] Tile)``: ``Y W_a`` one matrix product of the
nine harmonics with the coupling table of input component ``a`` laid out
over the block's columns, ``x_a[src] Tile`` the 64 (32) channels of that
component repeated over the block by a one-hot product (a 128-wide degree
is repeated by a lane-aligned ``tile``), and the radial MLP's last layer
has its columns laid out over the message row the same way. The sum onto
nodes is ``LocalGraph.scan_edges``.

Distributed contract: one halo exchange of the flat features after each
convolution that feeds another (the embedding needs none: halo rows know
their species; the last convolution's scalars feed owned atoms only).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import radial
from ..ops.nn import silu_2mom_gain
from ..ops.so3 import real_clebsch_gordan, spherical_harmonics
from ..telemetry import scope

_SEVENNET_0 = ((128, 64, 32),) * 4 + ((128,),)
_LANES = 128  # a TPU lane tile: message blocks are padded to whole tiles


@dataclass(frozen=True)
class NequIPConfig:
    num_species: int = 95
    # channels per degree (l = 0, 1, ...) leaving each convolution; the
    # embedding has the first entry's scalars
    irreps: tuple = _SEVENNET_0
    l_max: int = 2            # spherical-harmonic order on edges
    num_bessel: int = 8
    radial_hidden: tuple = (64, 64)
    cutoff: float = 5.0
    cutoff_on: float = 4.5    # the XPLOR envelope is 1 below this
    avg_num_neighbors: float = 42.0
    remat: bool | str = True  # as MACEConfig.remat: the scan's chunk body
    edge_chunk: int = 32768   # as MACEConfig.edge_chunk
    dtype: str = "float32"


def _layer_tables(mul_in, mul_out, l_max, avg_num_neighbors):
    """Paths and message layout of one convolution.

    ``blocks``: per input degree ``(li, mul, x_off, width, tile)`` with
    ``x_off`` the degree's offset in a node row, ``width`` the block's
    columns after padding and ``tile`` the one-hot ``(mul, width)`` matrix
    that repeats a component's channels over the block (None where
    ``mul`` is whole lane tiles and a lane-aligned ``jnp.tile`` does it);
    ``y_table`` ``(S_Y, sum over blocks and a of width)``: the coupling of
    input component ``a`` with every harmonic, times ``sqrt(2 l_out + 1)
    / sqrt(avg_num_neighbors)``, repeated over the channels; ``r_cols``:
    the radial MLP's output column
    of each message column; ``pieces``: ``{path: (start, l_out, mul_in)}``
    in the message row; ``fan_in``: ``{l_out: channels of all its paths}``.
    """
    in_ls = [l for l, m in enumerate(mul_in) if m]
    out_ls = [l for l, m in enumerate(mul_out) if m]
    paths = [(li, ly, lo) for li in in_ls for ly in range(l_max + 1)
             for lo in out_ls if abs(li - ly) <= lo <= li + ly]
    s_y = (l_max + 1) ** 2
    blocks, tables, r_cols, pieces, fan_in = [], [], [], {}, {}
    x_off = start = r_off = 0
    for li in in_ls:
        mul = mul_in[li]
        mine = [p for p in paths if p[0] == li]
        n_cols = sum(2 * lo + 1 for _, _, lo in mine) * mul
        width = -(-n_cols // _LANES) * _LANES
        table = np.zeros((2 * li + 1, s_y, width))
        cols = np.zeros(width, np.int32)
        o = 0
        for path in mine:
            _, ly, lo = path
            cg = real_clebsch_gordan(li, ly, lo) * np.sqrt(
                (2 * lo + 1) / avg_num_neighbors)
            pieces[path] = (start + o, lo, mul)
            fan_in[lo] = fan_in.get(lo, 0) + mul
            for m in range(2 * lo + 1):
                table[:, ly * ly:(ly + 1) ** 2, o:o + mul] = \
                    cg[:, :, m, None]
                cols[o:o + mul] = r_off + np.arange(mul)
                o += mul
            r_off += mul
        tile = None
        if mul % _LANES:
            at = np.arange(width)
            tile = (at % mul == np.arange(mul)[:, None]) & (at < n_cols)
        blocks.append((li, mul, x_off, width, tile))
        tables += list(table)
        r_cols.append(cols)
        x_off += (2 * li + 1) * mul
        start += width
    return {"paths": paths, "blocks": blocks,
            "y_table": np.concatenate(tables, axis=1),
            "r_cols": np.concatenate(r_cols), "n_radial": r_off,
            "pieces": pieces, "fan_in": fan_in, "width": start}


def _split(flat, muls):
    """A flat node row as ``{l: (N, 2l+1, mul)}``."""
    out, o = {}, 0
    for l, mul in enumerate(muls):
        if mul:
            d = (2 * l + 1) * mul
            out[l] = flat[:, o:o + d].reshape(-1, 2 * l + 1, mul)
            o += d
    return out


def _flat(parts):
    return jnp.concatenate(
        [parts[l].reshape(parts[l].shape[0], -1) for l in sorted(parts)],
        axis=-1)


class NequIP:
    supports_compute_dtype = True  # energy_fn honors cfg.dtype="bfloat16"

    def __init__(self, config: NequIPConfig = NequIPConfig()):
        self.cfg = c = config
        self.mul_out = [tuple(m) for m in c.irreps]
        self.mul_in = [(c.irreps[0][0],)] + self.mul_out[:-1]
        # gate scalars of a convolution: one per channel of its l > 0 output
        self.n_gates = [sum(m[1:]) for m in self.mul_out]
        self.tables = [
            _layer_tables(self.mul_in[t], self.mul_out[t], c.l_max,
                          c.avg_num_neighbors)
            for t in range(len(c.irreps))]

    @staticmethod
    def path_key(path) -> str:
        return "_".join(map(str, path))

    # ---- parameters ----
    def init(self, key) -> dict:
        """Every weight N(0, 1) as e3nn draws them (the forward pass
        divides by sqrt(fan_in)), the embedding's times sqrt(S); Bessel
        frequencies n pi / r_c. ``rescale``
        (scale 1, per-species shift 0) is the release's fit to its training
        set, not part of the 842,440 trained weights."""
        cfg = self.cfg
        keys = iter(jax.random.split(key, 8 + 32 * len(cfg.irreps)))
        normal = lambda *shape: jax.random.normal(next(keys), shape)
        layers = []
        for t, tb in enumerate(self.tables):
            mul_in, mul_out = self.mul_in[t], self.mul_out[t]
            wide = {0: mul_out[0] + self.n_gates[t],
                    **{l: m for l, m in enumerate(mul_out) if l and m}}
            dims = [cfg.num_bessel, *cfg.radial_hidden, tb["n_radial"]]
            layers.append({
                "lin_sc": {str(l): normal(mul_in[l], wide[l])
                           for l in wide if l < len(mul_in) and mul_in[l]},
                "lin_1": {str(l): normal(m, m)
                          for l, m in enumerate(mul_in) if m},
                "radial": [{"w": normal(a, b)}
                           for a, b in zip(dims[:-1], dims[1:])],
                "lin_2": {self.path_key(p): normal(p_mul, wide[lo])
                          for p, (_, lo, p_mul) in tb["pieces"].items()},
            })
        scalars = self.mul_out[-1][0]
        return {
            # INIT-time gain, as MACEConfig.radial_scale: a one-hot's
            # components have second moment 1 / S where e3nn's
            # 1 / sqrt(fan_in) assumes 1, so unit-variance rows would start
            # every feature at 1 / sqrt(S) and the gated l > 0 channels at
            # less each layer. Not applied at run time.
            "embedding": {"w": normal(cfg.num_species, cfg.irreps[0][0])
                          * math.sqrt(cfg.num_species)},
            "bessel": {"frequencies": jnp.arange(
                1, cfg.num_bessel + 1, dtype=jnp.float32) * (
                    math.pi / cfg.cutoff)},
            "layers": layers,
            "readout": [{"w": normal(scalars, scalars // 2)},
                        {"w": normal(scalars // 2, 1)}],
            "rescale": {"scale": jnp.ones(()),
                        "shift": jnp.zeros((cfg.num_species,))},
        }

    # ---- forward ----
    def energy_fn(self, params, lg, positions):
        cfg = self.cfg
        # geometry, harmonics, envelope and Bessel rows in the positions
        # dtype; features and matrix products in the configured compute
        # dtype; site energies accumulate in the positions dtype
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else positions.dtype
        acc_dtype = positions.dtype

        # every stage below sits in its telemetry scope (telemetry/stages.py)
        with scope("edge_geometry"):
            vec = lg.edge_vectors(positions)
            d = jnp.linalg.norm(
                jnp.where(lg.edge_mask[:, None], vec, 1.0), axis=-1)
            safe = jnp.maximum(d, 1e-9)
            rhat = vec / safe[:, None]
            env = radial.xplor_cutoff(d, cfg.cutoff, cfg.cutoff_on) \
                * lg.edge_mask
            # the envelope multiplies the Bessel rows BEFORE the bias-free
            # MLP, which maps 0 to 0: messages vanish smoothly at the cutoff
            freq = params["bessel"]["frequencies"].astype(d.dtype)
            bessel = (math.sqrt(2.0 / cfg.cutoff) * jnp.sin(freq * d[:, None])
                      / safe[:, None] * env[:, None]).astype(dtype)
            Y = jnp.concatenate(
                [spherical_harmonics(l, rhat) for l in range(cfg.l_max + 1)],
                axis=-1).astype(dtype)                        # (E, S_Y)
        # per-edge rows in chunk order, laid out once for all convolutions
        edge_xs = lg.edge_chunks(cfg.edge_chunk, Y, bessel)

        z = lg.species
        with scope("node_linear"):
            # onehot(z) W / sqrt(S): halo rows carry their species, so the
            # embedding needs no exchange
            h = (params["embedding"]["w"]
                 * (1.0 / math.sqrt(cfg.num_species)))[z].astype(dtype)

        last = len(params["layers"]) - 1
        for t, layer in enumerate(params["layers"]):
            body = partial(self._convolution, lg=lg, edge_xs=edge_xs, t=t)
            # as MACE's interaction{t}, no stage: every equation inside
            # sits under a stage of its own
            with scope(f"convolution{t}"):
                h = body(layer, h)
            if t < last:
                with scope("halo"):
                    h = lg.halo_exchange(h)

        with scope("readout"):
            w1, w2 = (p["w"] for p in params["readout"])
            e = (h @ (w1 * (1.0 / math.sqrt(w1.shape[0]))).astype(dtype)) @ (
                w2 * (1.0 / math.sqrt(w2.shape[0]))).astype(dtype)
            scale = params["rescale"]["scale"].astype(acc_dtype)
            shift = params["rescale"]["shift"].astype(acc_dtype)
            return e[:, 0].astype(acc_dtype) * scale + shift[z]

    def _convolution(self, layer, h, *, lg, edge_xs, t):
        """One gated convolution on the flat node rows ``h``; returns the
        next layer's. ``cfg.remat`` checkpoints the chunk body of its edge
        scan, not the convolution. ``edge_xs`` is energy_fn's chunk-ordered
        ``(src, dst, mask, Y, bessel)``."""
        cfg, tb = self.cfg, self.tables[t]
        dtype = h.dtype
        mul_in, mul_out = self.mul_in[t], self.mul_out[t]
        gain = silu_2mom_gain()
        act = lambda v: gain * jax.nn.silu(v)
        # e3nn's x W / sqrt(fan_in), the constant folded into the cast
        # (math, not numpy: a numpy scalar is float64 under x64)
        lin = lambda w, fan_in: (w * (1.0 / math.sqrt(fan_in))).astype(dtype)

        with scope("node_linear"):
            parts = _split(h, mul_in)
            mix = lambda name, l: jnp.einsum(
                "nmc,cd->nmd", parts[l],
                lin(layer[name][str(l)], mul_in[l]))
            s = {int(l): mix("lin_sc", int(l)) for l in layer["lin_sc"]}
            x = _flat({l: mix("lin_1", l) for l in parts})
            # the radial MLP's weights: silu* feeds layers two and three,
            # so its gain goes into their rows; the last layer's columns
            # laid out over the message row (a path's radial weight
            # multiplies every m of its piece)
            ws = [p["w"] for p in layer["radial"]]
            ws = [lin(w * (1.0 if i == 0 else gain), w.shape[0])
                  for i, w in enumerate(ws)]
            ws[-1] = ws[-1][:, tb["r_cols"]]
            y_table = jnp.asarray(tb["y_table"], dtype=dtype)

        def chunk_message(srcc, dstc, maskc, Yc, besc):
            with scope("radial_mlp"):
                R = besc
                for i, w in enumerate(ws):
                    R = R @ w
                    if i < len(ws) - 1:
                        R = jax.nn.silu(R)
            with scope("edge_message"):
                xs = x[srcc]                              # (E_c, in width)
                T = Yc @ y_table
                out, o = [], 0
                for li, mul, x_off, width, tile in tb["blocks"]:
                    block = None
                    for a in range(2 * li + 1):
                        xa = xs[:, x_off + a * mul:x_off + (a + 1) * mul]
                        rep = (jnp.tile(xa, (1, width // mul)) if tile is None
                               else xa @ jnp.asarray(tile, dtype=dtype))
                        term = T[:, o:o + width] * rep
                        block = term if block is None else block + term
                        o += width
                    out.append(block)
                return jnp.concatenate(out, axis=-1) * R

        a = lg.scan_edges(chunk_message, edge_xs, (tb["width"],), dtype,
                          remat=cfg.remat)

        with scope("node_linear"):
            y = {}
            for path, (start, lo, mul) in tb["pieces"].items():
                piece = a[:, start:start + (2 * lo + 1) * mul].reshape(
                    -1, 2 * lo + 1, mul)
                term = jnp.einsum(
                    "nmc,cd->nmd", piece,
                    lin(layer["lin_2"][self.path_key(path)],
                        tb["fan_in"][lo]))
                y[lo] = term if lo not in y else y[lo] + term
        with scope("node_gate"):
            y = {l: y[l] + s[l] if l in s else y[l] for l in y}
            scalars = mul_out[0]
            gates = act(y[0][:, 0, scalars:])
            new, o = {0: act(y[0][:, :, :scalars])}, 0
            for l in sorted(y):
                if l:
                    new[l] = y[l] * gates[:, None, o:o + mul_out[l]]
                    o += mul_out[l]
            return _flat(new)
