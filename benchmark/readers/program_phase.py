"""Where set-up goes, from the program's own log of what it does once
(``distmlip_tpu.telemetry.trace.phases()``: ``(name, t0, t1, thread)`` on
``time.perf_counter()``: the package's import, the runtime and graph
builds, the four parts of a call that compiled or built a graph, and jax's
own timing of each trace, lowering and backend compile or cache load).

Set-up is the interval from the process's start (``T_START`` of the
``__main__`` module where it is a float, as ``benchmark/run.py`` sets it
on its first line; else the log's earliest stamp) to the start of the
first ``bench/md_step`` of ``run["spans"]``. Phases are clipped to it.
The metric's file picks the ``phases`` by name, of them with ``within``
only those whose middle lies inside a phase of one of those names (jax's
stages of the potential's own program are the ones inside its first
call's dispatch; an entry point's own jits, its weights and tables, are
traced and lowered too), and how they are given, ``as``: ``s``, the length of the UNION of their intervals (a nested jit's
trace lies inside its caller's and counts once); ``count``, their number;
``uncovered_share``, 100 x (1 - union of ALL phases / the interval): what
no phase names, the runtime's start, the benchmark's own tables and
weights, the warm-up steps. ``log`` prints the whole split once, on
standard error, with the longest stretches that no phase covers, each
between the phases that end before it and start after it. Nothing to read (an empty log, a program from before the
log, a run without a timed step; and, as for every reader of a time, a
run without a device plane: the CPU of the tests sets up another
program): ``None``.
"""

import json
import sys
from collections import defaultdict


def phases():
    try:
        from distmlip_tpu.telemetry import trace
    except ImportError:
        return None
    return getattr(trace, "phases", lambda: None)()


def union_s(intervals) -> float:
    """Seconds covered by at least one of ``[(t0, t1), ...]``."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def gaps(start: float, end: float, inside, longest: int = 5) -> list:
    """``[[seconds, after, before], ...]``: the longest uncovered
    stretches of the interval, named by the phase that ended last before
    each and the one that starts next (``start`` / ``first_step`` at the
    interval's ends)."""
    out, covered, last = [], start, "start"
    for name, t0, t1 in sorted(inside, key=lambda p: p[1]):
        if t0 > covered:
            out.append([round(t0 - covered, 6), last, name])
        if t1 > covered:
            covered, last = t1, name
    if end > covered:
        out.append([round(end - covered, 6), last, "first_step"])
    return sorted(out, reverse=True)[:longest]


def set_up(run: dict, log):
    """``(start, end, [(name, t0, t1), ...])``: the set-up interval and the
    phases inside it, clipped; None without a timed step."""
    end = next((t0 for name, t0, _ in run["spans"]
                if name == "bench/md_step"), None)
    start = getattr(sys.modules.get("__main__"), "T_START", None)
    if not isinstance(start, float):
        start = min(t0 for _, t0, _, _ in log)
    if end is None or end <= start:
        return None
    return start, end, [(name, max(t0, start), min(t1, end))
                        for name, t0, t1, _ in log
                        if t1 > start and t0 < end]


def read(run: dict, params: dict):
    trace = run["trace"]
    if trace is None or not trace.device_planes():
        return None
    log = phases()
    if not log:
        return None
    found = set_up(run, log)
    if found is None:
        return None
    start, end, inside = found
    if params.get("log"):
        by_name = defaultdict(list)
        for name, t0, t1 in inside:
            by_name[name].append((t0, t1))
        print("[bench] setup_phases " + json.dumps({
            "interval_s": round(end - start, 6),
            "covered_s": round(union_s((a, b) for _, a, b in inside), 6),
            "longest_uncovered": gaps(start, end, inside),
            "s_and_count_by_phase": {
                name: [round(union_s(spans), 6), len(spans)]
                for name, spans in sorted(by_name.items())}}),
            file=sys.stderr, flush=True)
    if params["as"] == "uncovered_share":
        return 100.0 * (1.0 - union_s((a, b) for _, a, b in inside)
                        / (end - start))
    picked = [(a, b) for name, a, b in inside if name in params["phases"]]
    if "within" in params:
        hosts = [(a, b) for name, a, b in inside if name in params["within"]]
        picked = [(a, b) for a, b in picked
                  if any(lo <= 0.5 * (a + b) <= hi for lo, hi in hosts)]
    if params["as"] == "count":
        return len(picked)
    return union_s(picked)
