"""NequIP (SevenNet-0): how the harness builds the program's model from a
configuration file, hands it the benchmark's weights, and counts a step's
operations."""

from __future__ import annotations

from ..reference import nequip as reference  # noqa: F401  (found by name)


def build_model(kwargs: dict):
    """The file gives the hidden channels per degree and the number of
    convolutions; the last convolution keeps the scalars only."""
    from distmlip_tpu.models import NequIP, NequIPConfig

    kw = dict(kwargs)
    hidden = tuple(kw.pop("channels"))
    n = kw.pop("num_convolutions")
    return NequIP(NequIPConfig(
        irreps=(hidden,) * (n - 1) + (hidden[:1],),
        radial_hidden=tuple(kw.pop("radial_hidden")), **kw))


def program_params(params: dict, tables, model) -> dict:
    """The reference keeps one matrix per path for the radial MLP's last
    layer; the program one matrix whose columns run over its paths in its
    own order."""
    import jax.numpy as jnp

    layers = []
    for t, layer in enumerate(params["layers"]):
        last = jnp.concatenate(
            [layer["radial_out"][model.path_key(p)]
             for p in model.tables[t]["paths"]], axis=1)
        layers.append({
            "lin_sc": layer["lin_sc"], "lin_1": layer["lin_1"],
            "radial": [{"w": w} for w in (*layer["radial"], last)],
            "lin_2": layer["lin_2"]})
    return {"embedding": {"w": params["embedding"]},
            "bessel": {"frequencies": params["frequencies"]},
            "layers": layers,
            "readout": [{"w": w} for w in params["readout"]],
            "rescale": {"scale": params["scale"], "shift": params["shift"]}}


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: one cutoff per convolution."""
    return cfg["num_convolutions"] * cfg["cutoff"]


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms and ``n_edges`` real
    directed edges inside the cutoff: the contractions of the plain
    reference's forward pass, and for each the cotangent contractions that
    forces need, one per operand that depends on the positions (weights
    get no gradient). No padded rows, no skin edges, nothing recomputed,
    no elementwise work."""
    hidden = list(cfg["radial_hidden"])
    edge = node = 0.0
    for t, paths in enumerate(tables.paths):
        moved = int(t > 0)  # the first convolution's h is the embedding
        mul_in, wide = tables.mul_in[t], tables.wide(t)
        for l, m in enumerate(mul_in):
            d = 2 * l + 1
            node += (1 + moved) * d * m * m                    # Lin_1
            if l in wide:
                node += (1 + moved) * d * m * wide[l]          # Lin_sc
        dims = [cfg["num_bessel"]] + hidden
        edge += 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        for li, ly, lo in paths:
            a, b, p = 2 * li + 1, 2 * ly + 1, 2 * lo + 1
            turns = int(ly > 0)  # Y_0 is a constant: no cotangent
            edge += 2 * hidden[-1] * mul_in[li]    # the path's radial weight
            edge += (1 + turns) * a * b * p        # CG with Y
            edge += (1 + turns + moved) * a * p * mul_in[li]  # with x[src]
            node += 2 * p * mul_in[li] * wide[lo]  # Lin_2
    c = tables.mul_out[-1][0]
    node += 2 * (c * (c // 2) + c // 2)                        # readout
    return 2.0 * (n_edges * edge + n_atoms * node)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """Operations and bytes one step needs of each kernel the model calls:
    ``segment_sum`` once per convolution over the rows the graph holds
    (the edges inside cutoff + skin: the call is given all of them) at the
    message's real width (1,152 / 3,136 x 3 / 224 at the published sizes),
    two bytes an element in and out, four an id."""
    flops = bytes_ = 0.0
    for t, paths in enumerate(tables.paths):
        width = sum((2 * lo + 1) * tables.mul_in[t][li] for li, _, lo in paths)
        flops += n_edges_built * width
        bytes_ += 2.0 * width * (n_edges_built + n_atoms) + 4.0 * n_edges_built
    return {"segment_sum": {"flops": flops, "bytes": bytes_}}
