"""Plain reference of MACE (Batatia et al. 2022; MACE-MP-0, arXiv:2401.00096)
in the parameterisation the program serves: float32, ``jax.numpy`` only, no
kernels, no padding, no partitions, edges by a cell list of its own.

Per interaction t, with h the node features {l: (N, 2l+1, C)}:

    hu_l    = h_l W_up,l
    R_e     = MLP(bessel(d_e) * envelope(d_e))              (paths * C)
    A_p,i   = 1/avg * sum_{e -> i} R_e,p * CG_p(hu[src e], Y(r_e))   per path p
    A_l     = sum_{p -> l} A_p W_A,l,p
    B_l     = sum_nu  W_nu[z] . U_nu . A^(x nu)             (Horner over nu)
    h'_l    = B_l W_msg,l + h_l W_res,l[z]
    E_i    += readout_t(h'_0)

and E = sum_i e0[z_i] + scale * E_i + shift. Forces are -dE/dpositions.
The departure from the published model: the U tensors come from
``so3.symmetric_basis`` (an orthonormal basis of the same space as e3nn's),
as the program's do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import so3
from .common import (bessel_basis, blocked, blocked_segment_sum, dense, mlp,
                     polynomial_cutoff, rounder)

# e3nn's normalize2mom(silu): 1 / sqrt(E[silu(x)^2]), x ~ N(0, 1)
_x, _w = np.polynomial.hermite_e.hermegauss(201)
SILU_GAIN = float(1.0 / np.sqrt(
    np.sum(_w * (_x / (1.0 + np.exp(-_x))) ** 2) / np.sum(_w)))


class Tables:
    """The irreps, coupling paths and U tensors a configuration implies."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.cfg = cfg
        hidden = list(range(cfg["hidden_lmax"] + 1))
        self.a_ls = list(range(cfg["a_lmax"] + 1))
        self.h_in, self.h_out, self.paths = [], [], []
        prev = [0]
        for t in range(cfg["num_interactions"]):
            last = t == cfg["num_interactions"] - 1
            out = [0] if last else hidden  # scalars only leave the last one
            paths = [(lh, ly, lo) for lh in prev
                     for ly in range(cfg["l_max"] + 1) for lo in self.a_ls
                     if abs(lh - ly) <= lo <= lh + ly
                     and (lh + ly + lo) % 2 == 0]
            self.h_in.append(prev)
            self.h_out.append(out)
            self.paths.append(sorted(paths, key=lambda p: p[2]))
            prev = out
        self.u = {l: {nu: so3.symmetric_basis(tuple(self.a_ls), l, nu,
                                              cache_dir)
                      for nu in range(1, cfg["correlation"] + 1)}
                  for l in hidden}

    def paths_to(self, t: int, l_out: int) -> list:
        return [i for i, p in enumerate(self.paths[t]) if p[2] == l_out]


def init_params(cfg: dict, tables: Tables, key):
    """Random weights in the program's tree layout, variances as the
    program's own initialiser has them. Traceable: jit it to make every
    leaf in one call on the device."""
    c, heads, species = cfg["channels"], 1, cfg["num_species"]
    keys = iter(jax.random.split(key, 8 + 64 * cfg["num_interactions"]))
    normal = lambda shape, scale=1.0: jax.random.normal(
        next(keys), shape, dtype=jnp.float32) * scale
    uniform = lambda shape, lim: jax.random.uniform(
        next(keys), shape, dtype=jnp.float32, minval=-lim, maxval=lim)
    params = {
        "species_emb": {"w": normal((species, c))},
        "species_ref": {"w": jnp.zeros((heads, species), jnp.float32)},
        "scale": jnp.ones((heads,), jnp.float32),
        "shift": jnp.zeros((heads,), jnp.float32),
        "interactions": [],
    }
    for t in range(cfg["num_interactions"]):
        last = t == cfg["num_interactions"] - 1
        dims = ([cfg["num_bessel"]]
                + [cfg["radial_mlp"]] * cfg["radial_layers"]
                + [len(tables.paths[t]) * c])
        radial = [{"w": normal((a, b), (SILU_GAIN if i else 1.0) / np.sqrt(a))}
                  for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
        # the gain that keeps the density projection O(1) at random weights
        radial[-1] = {"w": radial[-1]["w"] * cfg["radial_scale"]}
        readout = ([{"w": uniform((c, 16), 1 / np.sqrt(c))},
                    {"w": uniform((16, heads), 0.25)}] if last
                   else [{"w": uniform((c, heads), 1 / np.sqrt(c))}])
        params["interactions"].append({
            "lin_up": {str(l): {"w": normal((c, c), 1 / np.sqrt(c))}
                       for l in tables.h_in[t]},
            "radial": radial,
            "lin_A": {str(l): normal(
                (len(tables.paths_to(t, l)), c, c),
                1 / np.sqrt(len(tables.paths_to(t, l)) * c))
                for l in tables.a_ls},
            "product": {str(l): {
                f"w{nu}": normal((species, u.shape[-1], c),
                                 1 / np.sqrt(u.shape[-1]))
                for nu, u in tables.u[l].items() if u is not None}
                for l in tables.h_out[t]},
            "lin_msg": {str(l): {"w": normal((c, c), 1 / np.sqrt(c))}
                        for l in tables.h_out[t]},
            "lin_res": {str(l): normal((species, c, c), 1 / np.sqrt(c))
                        for l in tables.h_out[t] if l in tables.h_in[t]},
            "readout": readout,
        })
    return params


def _product(weights: dict, us: dict, a, z, rq):
    """B[n, d, c] = sum_nu W_nu[z_n] . U_nu . A^(x nu), highest order first."""
    orders = sorted((nu for nu, u in us.items() if u is not None),
                    reverse=True)
    letters = "uvwxy"
    t = None
    for nu in range(orders[0], 0, -1):
        s = letters[:nu]
        if nu in orders:
            u = jnp.asarray(us[nu], dtype=jnp.float32)   # (S,)*nu + (d, k)
            w = weights[f"w{nu}"][z]                      # (n, k, C)
            if t is None:
                g = jnp.einsum("nkc,nqc->nkqc", rq(w), rq(a))
                t = jnp.einsum(f"{s[:-1]}qdk,nkqc->nd{s[:-1]}c", rq(u), rq(g))
                continue
            t = t + jnp.einsum(f"{s}dk,nkc->nd{s}c", rq(u), rq(w))
        t = jnp.einsum(f"nd{s}c,n{s[-1]}c->nd{s[:-1]}c", rq(t), rq(a))
    return t


def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  edge_block: int | None = 16384,
                  node_block: int | None = 512):
    """Per-atom energies (N,). ``edges`` = (src, dst, shift): directed
    pairs with positions[dst] - positions[src] + shift the short vector."""
    rq = rounder(precision)
    src, dst, shift = edges
    n, c = positions.shape[0], cfg["channels"]
    vec = positions[dst] - positions[src] + shift
    d = jnp.linalg.norm(vec, axis=-1)
    rhat = vec / d[:, None]
    radial_in = (bessel_basis(d, cfg["cutoff"], cfg["num_bessel"])
                 * polynomial_cutoff(d, cfg["cutoff"], cfg["cutoff_p"])[:, None])
    ys = [so3.spherical_harmonics(l, rhat, jnp)
          for l in range(cfg["l_max"] + 1)]

    z = species
    h = {0: params["species_emb"]["w"][z][:, None, :]}
    energy = jnp.zeros(n, jnp.float32)
    for t, inter in enumerate(params["interactions"]):
        paths = tables.paths[t]
        hu = {l: jnp.einsum("nmc,cd->nmd", rq(h[l]),
                            rq(inter["lin_up"][str(l)]["w"]))
              for l in tables.h_in[t]}

        def messages(src_b, bes_b, *y_b, hu=hu, inter=inter, paths=paths):
            radial = mlp(inter["radial"], bes_b, rq).reshape(
                -1, len(paths), c)
            out = []
            for i, (lh, ly, lo) in enumerate(paths):
                cg = jnp.asarray(so3.clebsch_gordan(lh, ly, lo), jnp.float32)
                with_y = jnp.einsum("abp,eb->eap", rq(cg), rq(y_b[ly]))
                coupled = jnp.einsum("eap,eac->epc", rq(with_y),
                                     rq(hu[lh][src_b]))
                out.append(coupled * radial[:, i, None, :])
            return jnp.concatenate(out, axis=1)        # (E_b, Q, C)

        a_paths = blocked_segment_sum(messages, (src, radial_in, *ys), dst, n,
                                      edge_block) / cfg["avg_num_neighbors"]
        offs = np.concatenate([[0], np.cumsum([2 * p[2] + 1 for p in paths])])
        a = jnp.concatenate([
            sum(jnp.einsum("nmc,cd->nmd",
                           rq(a_paths[:, offs[i]:offs[i + 1], :]),
                           rq(inter["lin_A"][str(l)][k]))
                for k, i in enumerate(tables.paths_to(t, l)))
            for l in tables.a_ls], axis=1)             # (N, S_A, C)

        def update(a_b, z_b, *h_b, inter=inter, t=t):
            out = []
            for l in tables.h_out[t]:
                b = _product(inter["product"][str(l)], tables.u[l], a_b, z_b,
                             rq)
                m = jnp.einsum("nmc,cd->nmd", rq(b),
                               rq(inter["lin_msg"][str(l)]["w"]))
                if str(l) in inter["lin_res"]:
                    m = m + jnp.einsum(
                        "nmc,ncd->nmd", rq(h_b[tables.h_in[t].index(l)]),
                        rq(inter["lin_res"][str(l)][z_b]))
                out.append(m)
            return jnp.concatenate(out, axis=1)

        flat = blocked(update, (a, z, *[h[l] for l in tables.h_in[t]]),
                       node_block)
        h, o = {}, 0
        for l in tables.h_out[t]:
            h[l] = flat[:, o:o + 2 * l + 1, :]
            o += 2 * l + 1
        scalars = h[0][:, 0, :]
        if t == cfg["num_interactions"] - 1:
            energy = energy + mlp(inter["readout"], scalars, rq)[:, 0]
        else:
            energy = energy + dense(inter["readout"][0], scalars, rq)[:, 0]
    return (params["species_ref"]["w"][0][z]
            + params["scale"][0] * energy + params["shift"][0])
