"""DimeNet++: how the harness builds the program's model from a
configuration file, hands it the benchmark's weights, and counts a step's
operations."""

from __future__ import annotations

from ..reference import dimenet as reference  # noqa: F401  (found by name)

# keys of a configuration's ``model`` that only the reference reads
REFERENCE_ONLY = ("reference_max_neighbours",)


def build_model(kwargs: dict):
    from distmlip_tpu.models import DimeNetPP, DimeNetPPConfig

    return DimeNetPP(DimeNetPPConfig(**{k: v for k, v in kwargs.items()
                                        if k not in REFERENCE_ONLY}))


def program_params(params: dict, tables, model) -> dict:
    """The reference's tree is PyG's layout, which the program keeps."""
    return params


def receptive_radius(cfg: dict) -> float:
    """How far an atom's energy reaches: the embedding reads one bond, and
    each interaction block one bond further (m_ji reads m_kj)."""
    return (cfg["num_blocks"] + 1) * cfg["cutoff"]


def step_flops(cfg: dict, tables, n_atoms: int, n_edges: int) -> float:
    """Operations (2 per multiply-add) that one energy-and-forces
    evaluation needs over ``n_atoms`` real atoms, ``n_edges`` real directed
    edges inside the cutoff and the live triplets k -> j -> i among them:
    the contractions of the plain reference's forward pass, and for each
    the cotangent contraction that forces need (2 x). No padded rows, no
    skin edges or triplets, no empty table slots, nothing recomputed, no
    elementwise work. The triplets are what the reference found in the
    graph it built for the comparison (``tables.found``, reported from
    inside its ``jit``), not a number assumed per atom."""
    import jax

    jax.effects_barrier()       # the report is a host callback
    found = getattr(tables, "found", None)
    if not found:
        raise RuntimeError(
            "the reference has not run: its Tables hold no triplet count "
            "for step_flops")
    scale = n_edges / max(found["n_edges"], 1)   # a sampled region: 1 else
    n_triplets = found["n_triplets"] * scale
    H, O = cfg["hidden_channels"], cfg["out_emb_channels"]
    I, B = cfg["int_emb_size"], cfg["basis_emb_size"]
    R, S = cfg["num_radial"], cfg["num_spherical"]
    blocks = cfg["num_blocks"]
    residuals = cfg["num_before_skip"] + cfg["num_after_skip"]
    edge = R * H + 3 * H * H                          # embedding block
    edge += blocks * (2 * H * H + R * B + B * H + H * I + I * H
                      + (2 * residuals + 1) * H * H)  # interaction blocks
    edge += (blocks + 1) * R * H                      # output blocks' W_rbf
    triplet = blocks * (S * R * B + B * I)            # W_sbf1, W_sbf2
    node = (blocks + 1) * (H * O + cfg["num_output_layers"] * O * O + O)
    return 2.0 * 2.0 * (n_edges * edge + n_triplets * triplet
                        + n_atoms * node)


def kernel_work(cfg: dict, tables, n_atoms: int, n_edges_built: int) -> dict:
    """No Pallas kernel runs in a DimeNet++ step: the triplet sum is a scan
    of XLA gathers and adds, the sum onto atoms an XLA scatter-add."""
    return {}
