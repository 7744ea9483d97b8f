"""Device time by the stage of the model it belongs to: self time of the
busiest device's operations (a ``while`` does not count its body twice),
joined by operation name with the stage tables the program built when the
traced window's tracing session closed
(``distmlip_tpu.telemetry.trace.stage_tables()``: per compiled instruction
its stage and its pass, read from the compiled step's own metadata).

The metric's file picks what is summed: ``stages`` (names of
``telemetry/stages.STAGES``, or ``unattributed``: in no table, in two
tables under different stages, or under no declared scope) or ``passes``
(``forward`` / ``backward`` / ``recompute``); and how it is given: ``as``
``ms_per_step`` or ``share`` (in % of the device's whole operation self
time). ``log`` prints the whole breakdown once, on standard error. Nothing
to read (no device plane, no table, a program from before the tables):
``None``.
"""

import importlib
import json
import sys
from collections import defaultdict


def stage_tables():
    try:
        from distmlip_tpu.telemetry import trace
    except ImportError:
        return None
    return getattr(trace, "stage_tables", lambda: None)()


def labels(tables, short_name) -> dict:
    """{device event name: (stage, pass)}; where two tables disagree about
    a name, what they disagree on is None."""
    known = {}
    for table in tables:
        for row in table["instructions"]:
            key = short_name(row["head"])
            label = (row["stage"], row["pass"])
            known[key] = (tuple(a if a == b else None
                                for a, b in zip(known[key], label))
                          if key in known else label)
    return known


def by_label(own: dict, known: dict) -> dict:
    """{(stage or None, pass or None): ns} of a plane's self times."""
    out = defaultdict(int)
    for name, ns in own.items():
        out[known.get(name, (None, None))] += ns
    return out


def read(run: dict, params: dict):
    trace = run["trace"]
    if trace is None or not trace.device_planes() or not run["traced_steps"]:
        return None
    tables = stage_tables()
    if not tables:
        return None
    short_name = importlib.import_module(
        run["cell"].package + ".harness.trace").short_name
    known = labels(tables, short_name)
    own = trace.self_times(trace.busiest_plane())
    split = by_label(own, known)
    total = sum(split.values())
    if not total:
        return None
    steps = run["traced_steps"]
    if params.get("log"):
        per = lambda i: {str(k): round(sum(
            ns for key, ns in split.items() if key[i] == k) / 1e6 / steps, 6)
            for k in sorted({key[i] for key in split}, key=str)}
        print("[bench] stage_time " + json.dumps({
            "ms_per_step_by_stage": per(0), "ms_per_step_by_pass": per(1),
            "op_self_ms_per_step": round(total / 1e6 / steps, 6),
            "longest_unattributed_ms_per_step": [
                [name, round(ns / 1e6 / steps, 6)] for name, ns in sorted(
                    own.items(), key=lambda kv: -kv[1])
                if known.get(name, (None,))[0] is None][:8],
            "table_rows": sum(len(t["instructions"]) for t in tables),
            "table_errors": [t["error"] for t in tables if "error" in t],
            "table_build_s": sum(t.get("build_s", 0.0) for t in tables)}),
            file=sys.stderr, flush=True)
    if "stages" in params:
        picked = sum(ns for (stage, _), ns in split.items()
                     if (stage or "unattributed") in params["stages"])
    else:
        picked = sum(ns for (_, pass_), ns in split.items()
                     if pass_ in params["passes"])
    if params["as"] == "share":
        return 100.0 * picked / total
    return picked / 1e6 / steps
