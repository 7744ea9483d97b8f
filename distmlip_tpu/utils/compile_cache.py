"""Persistent XLA compile cache, placed from outside or at a fixed path.

A MACE step at published width compiles for minutes and every fresh
machine starts with no compiled code, so each entry point that compiles on
the chip (``chip_smoke.py``, ``benchmark/run.py``, ``tools/load_test.py``) calls
:func:`enable_compile_cache` before its first jit.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets no path in code. Otherwise the cache lives in
    ``<checkout>/.jax_cache`` — a FIXED path (git-ignored): the directory
    is part of the cache key, so one derived from a temp name, a pid or
    the time would never hit.
    """
    import jax

    from ..telemetry.trace import listen_to_jax

    # an entry point's own jits (weights, tables) compile or load before
    # the first potential exists: the phase log hears them from here on
    listen_to_jax()
    # An executable loaded from the cache keeps the metadata of the code
    # that compiled it, and jax leaves metadata out of the cache's key. The
    # stage tables of a tracing session (telemetry/trace.py) read each
    # instruction's scope stack from that metadata: names that older code
    # wrote would be read as this code's. With metadata in the key, a
    # change of scopes (or of a traced line's number) compiles anew.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
