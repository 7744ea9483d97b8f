"""The phase log of ``telemetry/trace.py``: what happens once (an import, a
graph build, a compile) is on one always-on list that shares the
profiler's clock, and jax's own timing of a compile's stages is on it."""

from __future__ import annotations

import threading
import time

import pytest

from distmlip_tpu.telemetry import trace

pytestmark = pytest.mark.tier1


@pytest.fixture(autouse=True)
def empty_log():
    trace.reset_phases()
    yield
    trace.set_tracing(False)
    trace.reset_phases()


def test_import_is_the_first_phase_and_costs_no_jax_import():
    """A child of its own: this process imported the package long ago."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import time; t = time.perf_counter()\n"
         "import sys, distmlip_tpu\n"
         "from distmlip_tpu.telemetry import phases\n"
         "(name, t0, t1, _), = phases()\n"
         "assert name == 'distmlip/import' and t <= t0 < t1, (name, t0, t1)\n"
         "assert t1 <= time.perf_counter()\n"
         "assert 'jax' not in sys.modules\n"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_a_phase_is_logged_with_tracing_off():
    assert not trace.tracing_enabled()
    t0 = time.perf_counter()
    with trace.phase("distmlip/some_build"):
        time.sleep(0.01)
    t1 = time.perf_counter()
    (name, a, b, tid), = trace.phases()
    assert name == "distmlip/some_build" and tid == threading.get_ident()
    assert t0 <= a and b <= t1 and b - a >= 0.01
    # the per-step primitive keeps its contract
    assert trace.annotate("distmlip/prepare") is trace.annotate("x")


def test_inside_a_session_a_phase_is_also_the_annotation(monkeypatch):
    import jax

    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    trace.set_tracing(True)
    with trace.phase("distmlip/neighbor_build"):
        assert opened == [("enter", "distmlip/neighbor_build")]
    assert opened[-1] == ("exit", "distmlip/neighbor_build")
    assert [p[0] for p in trace.phases()] == ["distmlip/neighbor_build"]
    trace.set_tracing(False)
    with trace.phase("distmlip/partition"):
        pass
    assert len(opened) == 2 and len(trace.phases()) == 2


def test_a_phase_that_raises_is_logged_and_does_not_swallow():
    with pytest.raises(KeyError):
        with trace.phase("distmlip/partition"):
            raise KeyError("x")
    assert [p[0] for p in trace.phases()] == ["distmlip/partition"]


def test_log_phase_after_the_fact_and_oldest_first():
    trace.log_phase("b", 2.0, 3.0)
    trace.log_phase("a", 0.5, 1.0)
    assert [(n, a, b) for n, a, b, _ in trace.phases()] == [
        ("b", 2.0, 3.0), ("a", 0.5, 1.0)]
    got = trace.phases()
    got.clear()                      # a copy: plain data
    assert len(trace.phases()) == 2
    trace.log_first_call(1.0, 2.0, 3.0, 4.0, 5.0)
    assert [(n, a, b) for n, a, b, _ in trace.phases()[2:]] == [
        ("distmlip/first_call.prepare", 1.0, 2.0),
        ("distmlip/first_call.dispatch", 2.0, 3.0),
        ("distmlip/first_call.wait", 3.0, 4.0),
        ("distmlip/first_call.results_to_host", 4.0, 5.0)]


def test_the_log_is_bounded():
    bound = trace._phases.maxlen
    assert bound and bound >= 1024
    for i in range(bound + 10):
        trace.log_phase("p", float(i), float(i) + 0.5)
    got = trace.phases()
    assert len(got) == bound and got[0][1] == 10.0  # the oldest went


def test_appends_from_two_threads_keep_their_thread_ids():
    import sys

    n, ids = 2000, {}
    ready = threading.Barrier(2)

    def work(tag):
        ids[tag] = threading.get_ident()
        ready.wait(timeout=10)
        for i in range(n):
            if i % 2:
                trace.log_phase(tag, float(i), float(i) + 1.0)
            else:
                with trace.phase(tag):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    got = trace.phases()
    assert len(got) == min(2 * n, trace._phases.maxlen)
    assert {(name, tid) for name, _, _, tid in got} == set(ids.items())


def test_a_fresh_jit_compile_yields_jax_phases_inside_the_call():
    import jax
    import jax.numpy as jnp

    trace.listen_to_jax()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):               # a nested jit: its trace lies in this one's
        return inner(x).sum() + jnp.cos(x).sum()

    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    trace.reset_phases()
    t0 = time.perf_counter()
    jax.block_until_ready(outer(x))
    t1 = time.perf_counter()
    got = trace.phases()
    names = [p[0] for p in got]
    assert {"jax/trace", "jax/lower", "jax/backend_compile"} <= set(names)
    assert names.count("jax/backend_compile") == 1
    # the nested traces were folded into the outermost
    assert names.count("jax/trace") == 1
    slack = 2e-3                    # jax times itself on time.time()
    for name, a, b, tid in got:
        assert t0 - slack <= a <= b <= t1 + slack, (name, a - t0, b - t0)
        assert tid == threading.get_ident()
    seconds, from_cache = trace.compile_in(t0 - slack, t1 + slack)
    assert 0.0 < seconds <= t1 - t0 + 2 * slack
    assert isinstance(from_cache, bool)
    # a second call compiles nothing and logs nothing
    n = len(trace.phases())
    jax.block_until_ready(outer(x))
    assert len(trace.phases()) == n
    assert trace.compile_in(t1 + slack, time.perf_counter()) == (0.0, False)


def test_a_call_served_by_the_persistent_cache_reads_cache():
    """``compile_in`` on a hand-made log: one backend phase with a
    retrieval inside it is a load; two backend phases and one retrieval
    are not; another thread's phases are not this call's."""
    me = threading.get_ident()
    log = [("jax/trace", 10.0, 11.0, me), ("jax/trace", 10.2, 10.4, me),
           ("jax/lower", 11.0, 11.5, me),
           ("jax/cache_retrieval", 11.6, 11.9, me),
           ("jax/backend_compile", 11.5, 12.0, me),
           ("jax/backend_compile", 10.0, 12.0, me + 1),
           ("distmlip/first_call.wait", 12.0, 13.0, me)]
    trace._phases.extend(log)
    seconds, from_cache = trace.compile_in(9.0, 14.0)
    assert seconds == pytest.approx(2.0) and from_cache is True
    trace._phases.append(("jax/backend_compile", 12.0, 12.5, me))
    seconds, from_cache = trace.compile_in(9.0, 14.0)
    assert seconds == pytest.approx(2.5) and from_cache is False
    assert trace.compile_in(20.0, 30.0) == (0.0, False)


def test_registering_twice_adds_one_listener():
    from jax._src import monitoring

    trace.listen_to_jax()
    durations = len(monitoring.get_event_duration_listeners())
    events = len(monitoring.get_event_listeners())
    trace.listen_to_jax()
    trace.listen_to_jax()
    assert len(monitoring.get_event_duration_listeners()) == durations
    assert len(monitoring.get_event_listeners()) == events
    assert monitoring.get_event_duration_listeners().count(
        trace._on_jax_duration) == 1
    assert monitoring.get_event_listeners().count(trace._on_jax_event) == 1
    assert set(trace.jax_cache_counts()) == {"cache_hits", "cache_misses"}


def test_the_module_imports_no_jax_at_module_level():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(trace))
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = {a.name.split(".")[0] for n in top if isinstance(n, ast.Import)
             for a in n.names} | {(n.module or "").split(".")[0]
                                  for n in top if isinstance(n, ast.ImportFrom)}
    assert "jax" not in names, names
