"""MACE: how the harness builds the program's model from a configuration
file, hands it the benchmark's weights, and counts a step's operations."""

from __future__ import annotations

import numpy as np

from ..reference import mace as reference  # noqa: F401  (found by name)


def build_model(kwargs: dict):
    from distmlip_tpu.models import MACE, MACEConfig

    return MACE(MACEConfig(**kwargs))


def program_params(params: dict, tables, model) -> dict:
    """The benchmark's weights in the program's tree. The product weights
    are coefficients over a basis of symmetric couplings, and the program's
    basis and the reference's are two orthonormal bases of one space (each
    a null space taken by an SVD, so neither is reproducible bit for bit):
    w_program = (U_program^T U_reference) w_reference is the same function.
    A program basis that spans another space gives a mixing that is not
    orthogonal, and the forces then disagree."""
    import jax.numpy as jnp

    out = dict(params)
    out["interactions"] = []
    for inter in params["interactions"]:
        product = {}
        for l, weights in inter["product"].items():
            product[l] = {}
            for name, w in weights.items():
                nu = int(name[1:])
                u_prog = np.asarray(model.prod_U[int(l)][nu])
                u_ref = tables.u[int(l)][nu]
                k = u_ref.shape[-1]
                if u_prog.shape != u_ref.shape:
                    raise ValueError(
                        f"program's U basis for l={l}, nu={nu} has shape "
                        f"{u_prog.shape}, the reference's {u_ref.shape}")
                mixing = u_prog.reshape(-1, k).T @ u_ref.reshape(-1, k)
                product[l][name] = jnp.einsum(
                    "jk,skc->sjc", jnp.asarray(mixing, jnp.float32), w)
        out["interactions"].append({**inter, "product": product})
    return out


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: one cutoff per interaction."""
    return cfg["num_interactions"] * cfg["cutoff"]


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms and ``n_edges`` real
    directed edges inside the cutoff: the contractions of the plain
    reference's forward pass, and for each the cotangent contractions that
    forces need, one per operand that depends on the positions (weights
    get no gradient). No padded rows, no skin edges, nothing recomputed,
    no elementwise work."""
    c = cfg["channels"]
    s_a = sum(2 * l + 1 for l in tables.a_ls)
    edge = node = 0.0
    for t in range(cfg["num_interactions"]):
        moved = int(t > 0)  # the first interaction's h is the embedding
        paths = tables.paths[t]
        s_in = sum(2 * l + 1 for l in tables.h_in[t])
        node += s_in * c * c * (1 + moved)                       # lin_up
        dims = ([cfg["num_bessel"]]
                + [cfg["radial_mlp"]] * cfg["radial_layers"]
                + [len(paths) * c])
        edge += 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        for lh, ly, lo in paths:
            a, b, p = 2 * lh + 1, 2 * ly + 1, 2 * lo + 1
            edge += 2 * a * b * p              # CG with Y
            edge += (2 + moved) * a * p * c    # ... with hu[src]
        node += 2 * sum(2 * lo + 1 for _, _, lo in paths) * c * c  # lin_A
        for l in tables.h_out[t]:
            d = 2 * l + 1
            orders = {nu: u.shape[-1] for nu, u in tables.u[l].items()
                      if u is not None}
            top = max(orders)
            node += 2 * d * s_a ** top * orders[top] * c     # U . (w A)
            node += sum(d * s_a ** nu * k * c                # U . w below it
                        for nu, k in orders.items() if nu < top)
            node += 3 * sum(d * s_a ** nu * c for nu in range(1, top))
            node += 2 * d * c * c                            # lin_msg
            if l in tables.h_in[t]:
                node += (1 + moved) * d * c * c              # lin_res
        last = t == cfg["num_interactions"] - 1
        node += 2 * (c * 16 + 16 if last else c)             # readout
    return 2.0 * (n_edges * edge + n_atoms * node)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """Operations and bytes one step needs of each kernel the model calls:
    ``segment_sum`` once per interaction over the rows the graph holds
    (the edges inside cutoff + skin: the call is given all of them), at
    two bytes an element in and out, four an id."""
    c = cfg["channels"]
    flops = bytes_ = 0.0
    for t in range(cfg["num_interactions"]):
        q = sum(2 * lo + 1 for _, _, lo in tables.paths[t])
        flops += n_edges_built * q * c
        bytes_ += 2.0 * q * c * (n_edges_built + n_atoms) + 4.0 * n_edges_built
    return {"segment_sum": {"flops": flops, "bytes": bytes_}}
