"""CHGNet: how the harness builds the program's model from a configuration
file, hands it the benchmark's weights, and counts a step's operations."""

from __future__ import annotations

from ..reference import chgnet as reference  # noqa: F401  (found by name)

# keys of a configuration's ``model`` that only the reference reads
REFERENCE_ONLY = ("reference_max_bonds",)


def build_model(kwargs: dict):
    from distmlip_tpu.models import CHGNet, CHGNetConfig

    return CHGNet(CHGNetConfig(**{k: v for k, v in kwargs.items()
                                  if k not in REFERENCE_ONLY}))


def program_params(params: dict, tables, model) -> dict:
    """The reference's tree (``reference.init_params``) in the layout of
    ``models/chgnet.py``: the same arrays under the program's names, a
    gated MLP's two stacks side by side, each convolution's bias-free
    output map beside them."""
    gated = lambda p: {"core": p["core"], "gate": p["gate"]}
    weights = params["radial_weights"]
    return {
        "freq_bond": params["frequencies"]["bond"],
        "freq_three": params["frequencies"]["three_body"],
        "freq_angle": params["frequencies"]["angle"],
        "atom_emb": {"w": params["atom_embedding"]},
        "bond_emb": [params["bond_embedding"]],
        "angle_emb": [params["angle_embedding"]],
        "atom_bond_w": weights["atom_bond"],
        "bond_bond_w": weights["bond_bond"],
        "three_bond_w": weights["three_body"],
        "atom_blocks": [{"node_update": gated(layer),
                         "node_out": layer["out"]}
                        for layer in params["atom_conv"]],
        "bond_blocks": [{"node_update": gated(layer),
                         "node_out": layer["out"],
                         "angle_update": gated(layer["angle"])}
                        for layer in params["bond_conv"]],
        "sitewise": params["sitewise"],
        "final": params["final"],
        "species_ref": {"w": params["species_ref"][:, None]},
        "data_std": params["data_std"],
    }


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: one cutoff per atom convolution,
    and a bond convolution between two of them reaches one bond further
    (the bond k -> j that a message j -> i carries was updated from the
    bonds into k)."""
    return (cfg["num_blocks"] * cfg["cutoff"]
            + (cfg["num_blocks"] - 1) * cfg["bond_cutoff"])


def _stack(sizes) -> int:
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms, ``n_edges`` real directed
    edges inside the cutoff, and the real bonds and real lines of the
    structure: the contractions of the plain reference's forward pass, and
    for each the cotangent contraction that forces need (every operand
    that is not a weight depends on the positions: 2 x). No padded rows,
    no skin edges, no empty slots of the reference's tables, nothing
    recomputed, no elementwise work.

    The harness hands a family atoms and edges only. Bonds and lines are
    what the reference found in the graph it built for the comparison
    (``tables.found``, reported from inside its ``jit``): counted from the
    positions of the run, not assumed per atom."""
    import jax

    jax.effects_barrier()       # the report is a host callback
    found = getattr(tables, "found", None)
    if not found:
        raise RuntimeError(
            "the reference has not run: its Tables hold no bond or line "
            "count for step_flops")
    scale = n_edges / max(found["n_edges"], 1)   # a sampled region: 1 else
    n_bonds, n_lines = found["n_bonds"] * scale, found["n_lines"] * scale
    c, r = cfg["units"], cfg["num_rbf"]
    hidden = reference.hidden_sizes(cfg)
    blocks = cfg["num_blocks"]
    edge = r * c                                     # bond embedding
    edge += 2 * r * c                                # atom-bond, three-body
    edge += blocks * 2 * _stack([3 * c] + hidden["atom"] + [c])
    node = blocks * c * c                            # conv output maps
    node += _stack([c] + hidden["final"] + [1])      # readout
    line = (2 * cfg["num_angle"] + 1) * c            # angle embedding
    line += (blocks - 1) * 2 * _stack([4 * c] + hidden["bond"] + [c])
    line += max(blocks - 2, 0) * 2 * _stack([4 * c] + hidden["angle"] + [c])
    bond = (blocks - 1) * c * c                      # conv output maps
    return 2.0 * 2.0 * (n_edges * edge + n_atoms * node + n_lines * line
                        + n_bonds * bond)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """No Pallas kernel runs in a CHGNet step on the chip: both message
    passes go through ``edge_aggregate``, which Mosaic refuses at 64-wide
    rows (``kernels/dispatch.TPU_DEFAULT_MODE``), so they are XLA and no
    kernel has a roofline to report."""
    return {}
