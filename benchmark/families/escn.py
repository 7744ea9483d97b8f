"""eSCN-MD (the backbone of UMA): how the harness builds the program's model
from a configuration file, hands it the benchmark's weights, and counts a
step's operations."""

from __future__ import annotations

from ..reference import escn as reference  # noqa: F401  (found by name)


def build_model(kwargs: dict):
    """``system`` (charge, spin, dataset row) is the reference's to read:
    the program takes them from ``atoms.info``, which the md driver leaves
    empty, that is 0, 0 and row 0."""
    from distmlip_tpu.models import ESCNMD, ESCNMDConfig

    system = kwargs.get("system", {})
    if any(system.values()):
        raise ValueError("the md driver's atoms carry no charge, spin or "
                         f"dataset: the program would run 0, 0, 0, not "
                         f"{system}")
    return ESCNMD(ESCNMDConfig(
        **{k: v for k, v in kwargs.items() if k != "system"}))


def program_params(params: dict, tables, model) -> dict:
    """The reference draws its weights in the program's tree layout."""
    return params


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: the edge-degree embedding and one
    cutoff per layer."""
    return (cfg["num_layers"] + 1) * cfg["cutoff"]


def _so2_sizes(tables, c_in: int, c_out: int, n_gates: int):
    """Multiply-adds per edge of one SO(2) convolution, and its weights."""
    per_edge = weights = 0
    for m in range(tables.mmax + 1):
        n = tables.n_l(m)
        if m == 0:
            size = n * c_in * (n * c_out + n_gates)
            per_edge += size
        else:
            size = 2 * n * c_in * n * c_out    # real and imaginary map
            per_edge += 2 * size               # each meets f+ and f-
        weights += size
    return per_edge, weights


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms and ``n_edges`` real
    directed edges inside the cutoff: the contractions of the plain
    reference's forward pass, and for each the cotangent contractions that
    forces need, one per operand that depends on the positions (weights
    get no gradient). The expert merge once a step (it depends on no
    position). No padded rows, no skin edges, nothing recomputed, no
    elementwise work."""
    c, h = cfg["sphere_channels"], cfg["hidden_channels"]
    ce, lmax = cfg["edge_channels"], tables.lmax
    dx = cfg["num_distance_basis"] + 2 * ce
    rows = (lmax + 1) ** 2
    slots = sum(tables.n_l(m) for m in range(tables.mmax + 1))
    block_size = sum((2 * l + 1) ** 2 for l in range(lmax + 1))
    # D^2 = C (D^1 x D^1) C through the (1, 1, 2) table, as two products
    # (9 x 3 x 5, then 9 x 5 x 5); both frames move: forward + 2 cotangents
    wigner = 3 * (9 * 3 * 5 + 9 * 5 * 5) if lmax == 2 else 0
    rotate = lambda width: block_size * width   # one stack through blocks
    # edge-degree embedding: radial function (its input moves with the
    # distance: 2 x), rotation out (blocks and rows both move: 3 x)
    edge = 2 * (dx * ce + ce * (lmax + 1) * c) + 3 * rotate(c) + wigner
    node = mix = 0.0
    for t in range(cfg["num_layers"]):
        conv1, w1 = _so2_sizes(tables, 2 * c, h, lmax * h)
        conv2, w2 = _so2_sizes(tables, h, c, 0)
        mix += cfg["num_experts"] * (w1 + w2)
        edge += 2 * (dx * ce + ce * slots * 2 * c)       # radial function
        edge += 3 * 2 * rotate(c)                        # sender, receiver in
        edge += 2 * (conv1 + conv2)                      # weights fixed: 2 x
        edge += 3 * rotate(c) + wigner                   # message out
        node += 2 * (c * lmax * h + rows * c * h + rows * h * c)  # FFN
    node += 2 * (c * c + c)                              # energy head
    return 2.0 * (n_edges * edge + n_atoms * node + mix)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """Operations and bytes one step needs of each kernel the model calls:
    ``segment_sum`` once per edge scan (the edge-degree embedding and each
    layer) over the rows the graph holds (the edges inside cutoff + skin:
    the call is given all of them), at two bytes an element in and out,
    four an id."""
    width = (tables.lmax + 1) ** 2 * cfg["sphere_channels"]
    scans = cfg["num_layers"] + 1
    return {"segment_sum": {
        "flops": float(scans * n_edges_built * width),
        "bytes": scans * (2.0 * width * (n_edges_built + n_atoms)
                          + 4.0 * n_edges_built)}}
