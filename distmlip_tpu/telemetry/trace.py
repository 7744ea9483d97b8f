"""Trace annotations for the host hot path and jitted programs.

Two kinds of annotation, matching how JAX profiling works:

- ``annotate(name)`` — HOST-side ``jax.profiler.TraceAnnotation`` for the
  phases that run in Python (neighbor build, partition/pad, device_put).
  Gated on a module flag: disabled (the default) it returns a shared
  null context manager — no jax import, no object construction beyond one
  tuple lookup — so instrumented call sites add no measurable overhead.
- ``scope(name)`` — ``jax.named_scope`` for code inside ``jit``. This only
  attaches metadata to the traced HLO (op names in xprof timelines); it
  costs nothing at runtime by construction, so it is always on.

``device_trace(logdir)`` captures an xprof trace AND enables host
annotations for its duration, so one context manager produces the fully
named timeline the paper-style per-phase analysis needs.

A tracing SESSION is what ``set_tracing(True)`` ... ``set_tracing(False)``
brackets. While one is open the potentials note each executable they
dispatch (``note_dispatch``: a dict store, nothing else). When it closes,
each noted executable's compiled text is read into a stage table
(``stages.stage_table``): which instruction belongs to which stage of the
model and to which pass. ``stage_tables()`` returns the tables of the last
closed session: plain data that outlives the potential.
"""

from __future__ import annotations

import contextlib

_tracing = False
_noted: dict = {}         # (id(fn), argument shapes) -> (fn, args), session
_stage_tables: list = []  # of the last closed session


def set_tracing(on: bool) -> None:
    """Globally enable/disable host-side TraceAnnotations; switching them
    off closes the session and builds its stage tables."""
    global _tracing
    was, _tracing = _tracing, bool(on)
    if _tracing and not was:
        _noted.clear()
    elif was and not _tracing:
        _close_session()


def tracing_enabled() -> bool:
    return _tracing


def note_dispatch(fn, *args) -> None:
    """Remember that ``fn(*args)`` was dispatched in this session (the
    jitted callable and its newest arguments per shape signature, so that
    the session's close finds the very executable in jit's caches). A
    callable without ``lower`` (an AOT dispatcher) is noted and skipped at
    the close. Does nothing outside a session."""
    if not _tracing:
        return
    import jax

    key = (id(fn), tuple((getattr(x, "shape", ()), str(getattr(x, "dtype", "")))
                         for x in jax.tree.leaves(args)))
    _noted[key] = (fn, args)


def _close_session() -> None:
    """Stage tables of what the session dispatched. ``lower`` on the noted
    arguments finds the traced program, its lowering and the loaded
    executable in jit's in-memory caches, so nothing compiles here; the
    text is read and dropped. An executable whose text cannot be had keeps
    an empty table with the ``error``: its device time then reads as
    unattributed."""
    import time

    from .stages import stage_table

    _stage_tables.clear()
    for fn, args in list(_noted.values()):
        lower = getattr(fn, "lower", None)
        if lower is None:
            continue
        t0 = time.perf_counter()
        table = {"executable": getattr(fn, "__name__", type(fn).__name__),
                 "instructions": []}
        try:
            table["instructions"] = stage_table(
                lower(*args).compile().as_text())
        except Exception as e:  # noqa: BLE001 - a trace must not fail a run
            table["error"] = repr(e)
        if table["instructions"] and not any(
                row["stage"] for row in table["instructions"]):
            # every potential scopes at least its geometry and its readout
            table["error"] = (
                "no declared scope in the compiled text: an executable "
                "from a compile cache that code without them wrote (see "
                "utils/compile_cache.enable_compile_cache)")
        table["build_s"] = time.perf_counter() - t0
        _stage_tables.append(table)
    _noted.clear()


def stage_tables() -> list:
    """``[{"executable", "instructions": [{"head", "stage", "pass",
    "stages"?, "inherited"?}, ...], "build_s", "error"?}, ...]`` of the
    last closed session; empty while tracing was never on."""
    return _stage_tables


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullContext()


def annotate(name: str):
    """Host-side trace annotation; a shared no-op object when disabled."""
    if not _tracing:
        return _NULL
    import jax

    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """Named scope for jitted code (trace-time metadata only)."""
    import jax

    return jax.named_scope(name)


@contextlib.contextmanager
def device_trace(logdir: str):
    """jax.profiler trace context (view with tensorboard/xprof); host
    annotations are enabled for the duration so the timeline names every
    phase the runtime instruments."""
    import jax

    was = _tracing
    set_tracing(True)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        set_tracing(was)
