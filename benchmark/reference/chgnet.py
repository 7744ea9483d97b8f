"""Plain reference of CHGNet (Deng et al. 2023) in matgl's parameterisation
(``CHGNet-MPtrj-*``): float32, ``jax.numpy`` only, no kernels, no padding
of the program's kind, no partitions, nothing imported from the program.

Two graphs. The atom graph holds every directed pair closer than
``cutoff``. The bond graph's nodes are the atom-graph edges no longer than
``bond_cutoff`` (bonds); its edges (lines) are the ordered pairs of bonds
a = (s -> i), b = (i -> k) that meet at a centre atom i, k != s, each with
the angle at i between i -> s and i -> k.

    bases       R(d) = P(B(d)) B(d), B_n = sqrt(2/rc) sin(f_n d / rc) / d,
                f_n learnable from n pi, and matgl's quirk: the polynomial
                cutoff P takes the expansion VALUES, not the distance
                Fourier(theta) = [cos f_0 t, sin f_1 t, cos f_1 t, ...] / pi
    features    v_i = embedding(species), e_ij = linear(R(d)),
                a_line = linear(Fourier(theta))
    block t     atom conv   v_i += W sum_j G([v_j | v_i | e_ij]) (W_ab R(d))
                bond conv   e_b  += (W sum_a G([e_a | e_b | a_ab | v_i]))
                                    (W_3 R_3(d_b))            (bonds only)
                angle       a_ab += G([e_a | e_b | a_ab | v_i])
                with G = core x sigmoid gate, two MLPs on the same input
    readout     sitewise linear (magnetic moments) BEFORE the last atom
                conv, which no bond conv follows; E_i = data_std
                MLP(v_i) + species_ref

Departures from matgl, each as the program (``models/chgnet.py``) has it:
graph membership is explicit (``d < cutoff``, ``d <= bond_cutoff``): the
harness hands over padded edges, some of length exactly the cutoff, and
the embedded edge feature has a bias; cos theta is clipped at 1 - 1e-6
(matgl: 1e-7); the angle update after the last bond conv, which nothing
reads, is not computed; the atom graph has no edge update
(``bond_update_hidden`` None); the shared weights are ``"both"``, so
``bond_bond`` weights exist and, without an edge update, multiply nothing.

In a lower ``precision`` the operands of every contraction are rounded
(``common.rounder``) and so is every tensor the program keeps in its compute
type between them: embeddings, radial weights, each layer's activations,
messages before they are summed, and the atom, bond and angle features after
each update (sums accumulate in float32 there too). Geometry, bases, theta
and the readout stay float32, as the configuration's ``precision`` says.

The line graph is built here, inside ``jit``, from static shapes: per atom
a table of at most K bonds in and K bonds out (K =
``cfg["reference_max_bonds"]``) and their K x K pairs, those with k = s
masked out. An atom with more than K bonds makes every energy NaN: a
structure the tables cannot hold reads as not correct, never as a smaller
graph. Lines are computed in blocks of centre atoms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .common import blocked, blocked_segment_sum, dense, mlp, silu

COS_CLIP = 1e-6


def rounder(precision: str):
    """``common.rounder``; for the 8-bit type the same rounding with the
    scaled tensor held to the type's range before it is converted. On the
    chip ``x / scale`` can pass the largest finite value by a rounding of
    the division; e5m2 has an infinity, and the backward pass at this
    cell's size then came out all NaN (my chip run, PR 32: 24,576 of
    24,576 force components; none with the clip, none on the CPU either
    way). The clip moves no value that is in range."""
    if precision != "float8_e4m3fn":
        return common.rounder(precision)

    def scaled(x, dtype, top):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (jnp.clip(x / scale, -top, top).astype(dtype)
                .astype(jnp.float32) * scale)

    @jax.custom_vjp
    def fp8(x):
        return scaled(x, jnp.float8_e4m3fn, 448.0)

    fp8.defvjp(lambda x: (fp8(x), None),
               lambda _, g: (scaled(g, jnp.float8_e5m2, 57344.0),))
    return fp8


class Tables:
    """No coupling tables; keeps what the last evaluation found in the
    graph it built (``found``: real edges, bonds, lines, and whether an
    atom overflowed its K slots), which is what a count of operations
    needs and the harness does not hand over."""

    def __init__(self, cfg: dict, cache_dir: str | None = None):
        self.cfg = cfg
        self.found: dict = {}

    def report(self, n_edges, n_bonds, n_lines, overflow) -> None:
        self.found = {"n_edges": int(n_edges), "n_bonds": int(n_bonds),
                      "n_lines": int(n_lines), "overflow": bool(overflow)}


def hidden_sizes(cfg: dict) -> dict:
    """Hidden sizes of the gated MLPs and the readout: matgl's defaults,
    which are the program's (the configuration's ``assumed`` lists them)."""
    c = cfg["units"]
    return {"atom": [c], "bond": [c], "angle": [], "final": [c, c]}


def init_params(cfg: dict, tables: Tables, key):
    """Random weights in this reference's own layout (normal embeddings,
    torch-style uniform linears, frequencies at their initial values);
    ``families/chgnet.program_params`` maps it onto the program's tree.
    Traceable."""
    c, r, species = cfg["units"], cfg["num_rbf"], cfg["num_species"]
    n_angle = 2 * cfg["num_angle"] + 1
    hidden = hidden_sizes(cfg)
    keys = iter(jax.random.split(key, 64 + 32 * cfg["num_blocks"]))

    def linear(d_in, d_out, bias=True):
        lim = 1.0 / np.sqrt(d_in)
        p = {"w": jax.random.uniform(next(keys), (d_in, d_out), jnp.float32,
                                     -lim, lim)}
        if bias:
            p["b"] = jax.random.uniform(next(keys), (d_out,), jnp.float32,
                                        -lim, lim)
        return p

    def stack(sizes):
        return [linear(a, b) for a, b in zip(sizes[:-1], sizes[1:])]

    def gated(d_in, sizes):
        return {"core": stack([d_in] + sizes + [c]),
                "gate": stack([d_in] + sizes + [c])}

    n = jnp.arange(1, r + 1, dtype=jnp.float32)
    shared = cfg.get("shared_bond_weights", "both")
    if shared != "both":
        raise ValueError("the reference takes shared_bond_weights 'both', "
                         f"not {shared!r}")
    return {
        "frequencies": {
            "bond": jnp.pi * n, "three_body": jnp.pi * n,
            "angle": jnp.arange(0, cfg["num_angle"] + 1, dtype=jnp.float32)},
        "atom_embedding": jax.random.normal(next(keys), (species, c),
                                            jnp.float32),
        "bond_embedding": linear(r, c),
        "angle_embedding": linear(n_angle, c),
        "radial_weights": {"atom_bond": linear(r, c, bias=False),
                           "bond_bond": linear(r, c, bias=False),
                           "three_body": linear(r, c, bias=False)},
        "atom_conv": [{**gated(3 * c, hidden["atom"]),
                       "out": linear(c, c, bias=False)}
                      for _ in range(cfg["num_blocks"])],
        "bond_conv": [{**gated(4 * c, hidden["bond"]),
                       "out": linear(c, c, bias=False),
                       "angle": gated(4 * c, hidden["angle"])}
                      for _ in range(cfg["num_blocks"] - 1)],
        "sitewise": linear(c, 1),
        "final": stack([c] + hidden["final"] + [1]),
        "species_ref": jnp.zeros((species,), jnp.float32),
        "data_std": jnp.ones((), jnp.float32),
    }


# ---- bases -----------------------------------------------------------------

def expansion(d, frequencies, cutoff: float, exponent: int = 5):
    """matgl's learnable Bessel basis with its polynomial cutoff taken of
    the expansion values (hard zero where a value exceeds the cutoff)."""
    x = d[..., None]
    basis = jnp.sqrt(2.0 / cutoff) * jnp.sin(frequencies * x / cutoff) / x
    ratio = basis / cutoff
    p = float(exponent)
    poly = (1.0 - (p + 1.0) * (p + 2.0) / 2.0 * ratio ** exponent
            + p * (p + 2.0) * ratio ** (exponent + 1)
            - p * (p + 1.0) / 2.0 * ratio ** (exponent + 2))
    return jnp.where(basis <= cutoff, poly, 0.0) * basis


def fourier(theta, frequencies):
    """[cos f_0 t, sin f_1 t, cos f_1 t, sin f_2 t, ...] / pi."""
    arg = theta[..., None] * frequencies
    pairs = jnp.stack([jnp.sin(arg[..., 1:]), jnp.cos(arg[..., 1:])],
                      axis=-1).reshape(theta.shape + (-1,))
    return jnp.concatenate([jnp.cos(arg[..., :1]), pairs], axis=-1) / jnp.pi


def angle(vec_in, d_in, vec_out, d_out):
    """The angle at the centre atom i between i -> s and i -> k, from the
    bond s -> i (``vec_in``) and the bond i -> k (``vec_out``)."""
    cos = -jnp.sum(vec_in * vec_out, axis=-1) / (d_in * d_out)
    return jnp.arccos(jnp.clip(cos, -1.0 + COS_CLIP, 1.0 - COS_CLIP))


def gated(p, x, rq):
    """core(x) x gate(x): silu after every core layer, silu between the
    gate's layers and a sigmoid after its last. Every layer's output is a
    tensor the program keeps in its compute type: rounded."""
    core = gate = x
    for layer in p["core"]:
        core = rq(silu(dense(layer, core, rq)))
    for i, layer in enumerate(p["gate"]):
        last = i == len(p["gate"]) - 1
        gate = dense(layer, gate, rq)
        gate = rq(jax.nn.sigmoid(gate) if last else silu(gate))
    return core * gate


# ---- the line graph --------------------------------------------------------

def bond_table(atom_of_bond, is_bond, n_atoms: int, k: int):
    """Per atom the ids of at most ``k`` bonds filed under it:
    ``(table (N, k), filled (N, k), overflow)``. Integer work only."""
    n_edges = atom_of_bond.shape[0]
    key = jnp.where(is_bond, atom_of_bond, n_atoms)   # the rest sort last
    order = jnp.argsort(key, stable=True)
    key = key[order]
    slot = jnp.arange(n_edges) - jnp.searchsorted(key, key, side="left")
    real = key < n_atoms
    keep = real & (slot < k)
    row = jnp.where(keep, key, n_atoms)               # row N is dropped
    col = jnp.where(keep, slot, 0)
    table = jnp.zeros((n_atoms + 1, k), order.dtype).at[row, col].set(order)
    filled = jnp.zeros((n_atoms + 1, k), bool).at[row, col].set(keep)
    return table[:n_atoms], filled[:n_atoms], jnp.any(real & (slot >= k))


def line_graph(src, dst, is_bond, n_atoms: int, k: int):
    """For every centre atom its bonds in (s -> i) and out (i -> k) and
    which of the K x K pairs are lines."""
    bonds_in, has_in, over_in = bond_table(dst, is_bond, n_atoms, k)
    bonds_out, has_out, over_out = bond_table(src, is_bond, n_atoms, k)
    is_line = (has_in[:, :, None] & has_out[:, None, :]
               & (src[bonds_in][:, :, None] != dst[bonds_out][:, None, :]))
    return bonds_in, bonds_out, has_out, is_line, over_in | over_out


# ---- the model -------------------------------------------------------------

def site_energies(params, cfg: dict, tables: Tables, species, positions,
                  edges, precision: str = "float32",
                  edge_block: int | None = 65536,
                  atom_block: int | None = 512, with_sites: bool = False):
    """Per-atom energies (N,); with ``with_sites`` also the sitewise
    readout (N,), the magnetic moments' magnitude. ``edges`` = (src, dst,
    shift): directed pairs with positions[dst] - positions[src] + shift the
    short vector; pairs at or beyond the cutoff are not in the graph."""
    rq = rounder(precision)
    same = rounder("float32")
    src, dst, shift = edges
    n = positions.shape[0]
    cutoff, bond_cutoff = float(cfg["cutoff"]), float(cfg["bond_cutoff"])
    exponent = int(cfg.get("cutoff_exponent", 5))
    k = int(cfg["reference_max_bonds"])
    freq = params["frequencies"]

    vec = positions[dst] - positions[src] + shift
    d = jnp.linalg.norm(vec, axis=-1)
    in_graph = d < cutoff
    is_bond = in_graph & (d <= bond_cutoff)
    keep = in_graph[:, None].astype(vec.dtype)
    bond = is_bond[:, None].astype(vec.dtype)
    radial = expansion(d, freq["bond"], cutoff, exponent) * keep
    radial3 = expansion(d, freq["three_body"], bond_cutoff, exponent) * bond
    weights = params["radial_weights"]
    w_atom = rq(dense(weights["atom_bond"], radial, rq))     # (E, C)
    w_three = rq(dense(weights["three_body"], radial3, rq))  # (E, C)

    bonds_in, bonds_out, has_out, is_line, overflow = line_graph(
        src, dst, is_bond, n, k)
    jax.debug.callback(tables.report, in_graph.sum(), is_bond.sum(),
                       is_line.sum(), overflow)
    live = is_line[..., None].astype(vec.dtype)            # (N, K, K, 1)

    v = params["atom_embedding"][species]                  # (N, C)
    e = rq(dense(params["bond_embedding"], radial, rq))    # (E, C)

    def embed_angles(b_in, b_out):
        theta = angle(vec[b_in][:, :, None], d[b_in][:, :, None],
                      vec[b_out][:, None, :], d[b_out][:, None, :])
        return rq(dense(params["angle_embedding"],
                        fourier(theta, freq["angle"]), rq))

    a = blocked(embed_angles, (bonds_in, bonds_out), atom_block)

    def atom_conv(layer, v, e):
        def message(src_b, dst_b, e_b, w_b, keep_b):
            m = gated(layer, jnp.concatenate([v[src_b], v[dst_b], e_b],
                                             axis=-1), rq)
            return rq(m * w_b) * keep_b

        total = blocked_segment_sum(message, (src, dst, e, w_atom, keep),
                                    dst, n, edge_block)
        return rq(v + dense(layer["out"], total, rq))

    def line_rows(v_b, b_in, b_out, a_b, e):
        """(B, K, K, 4C): [bond in | bond out | angle | centre atom]."""
        shape = a_b.shape
        return jnp.concatenate([
            jnp.broadcast_to(e[b_in][:, :, None, :], shape),
            jnp.broadcast_to(e[b_out][:, None, :, :], shape), a_b,
            jnp.broadcast_to(v_b[:, None, None, :], shape)], axis=-1)

    for t in range(cfg["num_blocks"] - 1):
        v = atom_conv(params["atom_conv"][t], v, e)
        layer = params["bond_conv"][t]

        def onto_bonds(v_b, b_in, b_out, a_b, live_b, layer=layer, e=e):
            m = gated(layer, line_rows(v_b, b_in, b_out, a_b, e), rq)
            return (rq(m) * live_b).sum(axis=1)            # over bonds in

        total = blocked(onto_bonds, (v, bonds_in, bonds_out, a, live),
                        atom_block)                        # (N, K, C)
        update = dense(layer["out"], total, rq)
        update = update * has_out[..., None].astype(update.dtype)
        e = rq(e + jnp.zeros_like(e).at[bonds_out].add(update) * w_three)
        if t + 2 < cfg["num_blocks"]:
            def new_angles(v_b, b_in, b_out, a_b, live_b, layer=layer, e=e):
                m = gated(layer["angle"],
                          line_rows(v_b, b_in, b_out, a_b, e), rq)
                return rq(a_b + m * live_b)

            a = blocked(new_angles, (v, bonds_in, bonds_out, a, live),
                        atom_block)

    # the readout stacks stay float32 in the program at every compute type
    sites = (jnp.abs(dense(params["sitewise"], v, same)[:, 0])
             if with_sites else None)
    v = atom_conv(params["atom_conv"][-1], v, e)
    e_atom = mlp(params["final"], v, same)[:, 0]
    energies = params["data_std"] * e_atom + params["species_ref"][species]
    # a product, so that the forces are NaN too
    energies = energies * jnp.where(overflow, jnp.nan, 1.0)
    return (energies, sites) if with_sites else energies
