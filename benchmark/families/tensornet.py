"""TensorNet: how the harness builds the program's model from a
configuration file, hands it the benchmark's weights, and counts a step's
operations."""

from __future__ import annotations

from ..reference import tensornet as reference  # noqa: F401  (found by name)


def build_model(kwargs: dict):
    from distmlip_tpu.models import TensorNet, TensorNetConfig

    return TensorNet(TensorNetConfig(**kwargs))


def program_params(params: dict, tables, model) -> dict:
    return params


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: the embedding and one cutoff per
    layer."""
    return (cfg["num_layers"] + 1) * cfg["cutoff"]


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms and ``n_edges`` real
    directed edges inside the cutoff: the contractions of the plain
    reference's forward pass, and for each the cotangent contractions that
    forces need, one per operand that depends on the positions (weights
    get no gradient). No padded rows, no skin edges, nothing recomputed,
    no elementwise work: the messages themselves are elementwise."""
    c, r = cfg["units"], cfg["num_rbf"]
    edge = 2 * c * c                                 # Zij: species only
    edge += 2 * 3 * r * c                            # three distance maps
    node = 2 * (c * 2 * c + 2 * c * 3 * c)           # norm MLP
    node += 2 * 3 * 9 * c * c                        # three channel mixes
    for _ in range(cfg["num_layers"]):
        edge += 2 * (r * c + c * 2 * c + 2 * c * 3 * c)   # radial gates
        node += 2 * 6 * 9 * c * c                         # six channel mixes
        node += 3 * 3 * 27 * c                            # Y M, M Y, dX dX
    node += 2 * (3 * c * c + 2 * c * c + c)          # readout
    return 2.0 * (n_edges * edge + n_atoms * node)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """TensorNet's messages run as XLA on the chip (Mosaic refuses
    ``edge_aggregate``), so no kernel has a roofline to report yet."""
    return {}
