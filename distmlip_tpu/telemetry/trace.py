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

A PHASE is a span of work that happens once per process, per graph build
or per compile, never once per step: the package's import, the runtime
build, a graph build's three parts, the four parts of a call that built a
graph or compiled, and jax's own stages of a compile (``listen_to_jax``).
``phase(name)`` / ``log_phase(name, t0, t1)`` record it on
``time.perf_counter()`` whether or not a session is open; inside one
``phase`` is also the ``TraceAnnotation`` of that name, so a rebuild in a
traced window sits on the device trace's clock. ``phases()`` is plain
data, like ``stage_tables()``. ``annotate`` stays the per-step primitive.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

_tracing = False
_noted: dict = {}         # (id(fn), argument shapes) -> (fn, args), session
_stage_tables: list = []  # of the last closed session
# (name, t0, t1, thread id) on perf_counter, oldest first; a set-up logs
# some dozens, a compile four, a graph build three
_phases: deque = deque(maxlen=4096)
_phase_lock = threading.Lock()  # phases are rare: never on a steady step


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
    "stages"?, "inherited"?, "pass_inherited"?}, ...], "build_s",
    "error"?}, ...]`` of the
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


class phase:
    """Context manager: logs ``(name, t0, t1, thread id)`` when it closes,
    tracing on or off, and inside a session holds the ``TraceAnnotation``
    of the same name open meanwhile."""

    __slots__ = ("name", "t0", "annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.annotation = annotate(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        log_phase(self.name, self.t0, time.perf_counter())
        return self.annotation.__exit__(*exc)


def log_phase(name: str, t0: float, t1: float) -> None:
    """A phase whose kind is known only afterwards (a call's four parts
    once it is seen to have compiled; a jax event that reports its
    duration as it ends)."""
    with _phase_lock:
        _phases.append((name, t0, t1, threading.get_ident()))


def phases() -> list:
    """``[(name, t0, t1, thread id), ...]`` of this process, oldest first,
    ``t0`` / ``t1`` on ``time.perf_counter()``."""
    with _phase_lock:
        return list(_phases)


def phase_totals() -> dict:
    """``{name: [summed seconds, count]}`` of the log: the one line an
    operator's tool prints after its set-up. A sum, not a union: a nested
    phase counts in its own name and in its caller's."""
    out: dict = {}
    for name, t0, t1, _ in phases():
        row = out.setdefault(name, [0.0, 0])
        row[0] += t1 - t0
        row[1] += 1
    return {name: [round(s, 6), n] for name, (s, n) in out.items()}


def reset_phases() -> None:
    """Tests."""
    with _phase_lock:
        _phases.clear()


# jax's duration events -> phase names. The backend event spans
# ``compile_or_get_cached``: the compile, or the load from the persistent
# cache, in which case a ``jax/cache_retrieval`` lies inside it.
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax/cache_retrieval",
}
_JAX_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
_COMPILE = frozenset(_JAX_PHASES.values())
_jax_counts = dict.fromkeys(_JAX_COUNTS.values(), 0)
_listening = False


def _on_jax_duration(event: str, seconds: float, **_) -> None:
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    now = time.perf_counter()
    t0, me = now - seconds, threading.get_ident()
    with _phase_lock:
        # every jit a program calls reports its own trace as it ends,
        # hundreds to a model, each inside its caller's trace or inside the
        # lowering that traces it again: the log keeps the outermost
        while _phases and _phases[-1][0] == "jax/trace" \
                and _phases[-1][3] == me and _phases[-1][1] >= t0:
            _phases.pop()
        _phases.append((name, t0, now, me))


def _on_jax_event(event: str, **_) -> None:
    name = _JAX_COUNTS.get(event)
    if name is not None:
        _jax_counts[name] += 1


def listen_to_jax() -> None:
    """Turn jax's own timing of a compile's stages into phases ending
    now, and count the persistent cache's hits and misses. The events fire
    at compiles only. Registered once a process: by an entry point's
    ``enable_compile_cache()`` before its first jit, else by the first
    potential that builds its runtime (never at import)."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    jax.monitoring.register_event_listener(_on_jax_event)


def jax_cache_counts() -> dict:
    """``{"cache_hits", "cache_misses"}`` of jax's persistent compile
    cache since :func:`listen_to_jax`."""
    return dict(_jax_counts)


def compile_in(t0: float, t1: float) -> tuple[float, bool]:
    """What jax did on this thread to build executables inside the call
    ``[t0, t1]``: ``(seconds, from_cache)``. The seconds are the union of
    the ``jax/trace``, ``jax/lower`` and ``jax/backend_compile`` phases (a
    nested jit's trace lies inside its caller's), so they hold neither the
    first run nor the wait; ``from_cache`` says every backend phase held a
    retrieval from the persistent cache (and there was one)."""
    me = threading.get_ident()
    inside = [(a, b, name) for name, a, b, tid in phases()
              if tid == me and a >= t0 and b <= t1 and name in _COMPILE]
    backend = sum(name == "jax/backend_compile" for _, _, name in inside)
    loads = sum(name == "jax/cache_retrieval" for _, _, name in inside)
    seconds, end = 0.0, t0
    for a, b, name in sorted(inside):
        if name != "jax/cache_retrieval" and b > end:
            seconds += b - max(a, end)
            end = b
    return seconds, backend > 0 and loads >= backend


def log_first_call(t_start: float, t_prepared: float, t_dispatched: float,
                   t_waited: float, t_done: float) -> None:
    """The four parts of a potential's call that built a graph or an
    executable, from the five stamps the call took anyway."""
    log_phase("distmlip/first_call.prepare", t_start, t_prepared)
    log_phase("distmlip/first_call.dispatch", t_prepared, t_dispatched)
    log_phase("distmlip/first_call.wait", t_dispatched, t_waited)
    log_phase("distmlip/first_call.results_to_host", t_waited, t_done)


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
