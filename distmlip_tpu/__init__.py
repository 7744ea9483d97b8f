"""DistMLIP-TPU: a TPU-native graph-parallel framework for machine-learning
interatomic potentials (MLIPs).

A ground-up JAX/XLA re-design of the capabilities of DistMLIP
(reference: /root/reference, survey: SURVEY.md): periodic neighbor-graph
construction on the host (C++/OpenMP), spatial graph partitioning with halo
regions, and graph-parallel GNN inference/training over a
``jax.sharding.Mesh`` with halo exchange as XLA collectives
(``shard_map`` + ``ppermute``) instead of cross-GPU tensor copies.

Dtype policy (reference: DistMLIP/__init__.py:9-33): a process-global default
float/int width used by graph construction and models. On TPU the compute
dtype additionally supports bfloat16 for the matmul-heavy paths.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()  # the phase `distmlip/import` starts here

import numpy as np  # noqa: E402

__version__ = "0.1.0"

# ---------------------------------------------------------------------------
# Global dtype registry.
#
# float_np/int_np: host-side (numpy) graph arrays.
# float_jax: device-side feature/parameter dtype.
# Neighbor search always runs in float64 on the host regardless of this
# setting (matches the reference's C layer, fpis.c).
# ---------------------------------------------------------------------------
float_np = np.float32
int_np = np.int32
_compute_dtype = "float32"  # "float32" | "bfloat16"


def set_default_dtype(type_: str = "float", size: int = 32) -> None:
    """Set the process-global default dtypes.

    Mirrors the reference API (DistMLIP/__init__.py:15-33) but without a
    torch dependency: sets numpy dtypes used for graph arrays.
    """
    global float_np, int_np
    if type_ != "float":
        raise ValueError(f"Unsupported type {type_!r}; only 'float'.")
    if size == 32:
        float_np, int_np = np.float32, np.int32
    elif size == 64:
        float_np, int_np = np.float64, np.int64
    else:
        raise ValueError(f"Unsupported float size {size}; use 32 or 64.")


def set_compute_dtype(name: str) -> None:
    """Set the on-device compute dtype ("float32" or "bfloat16")."""
    global _compute_dtype
    if name not in ("float32", "bfloat16"):
        raise ValueError(name)
    _compute_dtype = name


def compute_dtype():
    import jax.numpy as jnp

    return jnp.bfloat16 if _compute_dtype == "bfloat16" else jnp.float32


from . import geometry  # noqa: E402,F401
from . import telemetry  # noqa: E402,F401

telemetry.log_phase("distmlip/import", _T_IMPORT, time.perf_counter())
