#!/usr/bin/env python
"""Perf-regression baseline gate over bench/telemetry rounds.

    python tools/perf_gate.py --input bench_result.json
        [--baseline PERF_BASELINE.json] [--json]
    python tools/perf_gate.py --jsonl run.jsonl
    python tools/perf_gate.py --check-schema
    python tools/perf_gate.py --input r.json --write-baseline PERF_BASELINE.json

Diffs one round's metrics against a baseline file. None is committed: the
one this tool was seeded with held CPU dry-run figures under device metric
names and was deleted in PR 21; the benchmark PR (ROADMAP S1, S9) writes
the next ``PERF_BASELINE.json`` from a chip run. Until then ``--baseline``
names the file to compare against.

- **baseline schema** — ``{"schema": 1, "metrics": {name: {"value": v,
  "tolerance_frac": f, "direction": "higher_is_better" |
  "lower_is_better"}}, "allow_regressions": [name...], "source": ...}``.
  Per-metric tolerance bands absorb run-to-run noise (CPU-dryrun
  timings get wide bands; structural counts like collectives/step get
  zero). ``allow_regressions`` is the EXPLICIT allow-list for
  intentional regressions: a listed metric still prints its delta but
  does not gate — remove the entry (and re-baseline) once the
  regression is either reverted or accepted into a new baseline.
- **inputs** — ``--input``: a bench ``--json`` capture (the LAST
  parseable JSON object line of the file, so a raw stdout teed from
  bench.py works as-is); numeric top-level fields become metrics.
  ``--jsonl``: a telemetry round; metrics derive from the aggregated
  report (compile counters, per-phase p50s, mfu, hbm ratio).
- **hbm drift watch** — ``hbm_est_over_measured`` (bench) /
  ``hbm_estimator_ratio`` (telemetry) is evaluated whenever the input
  carries it — the producers only emit it when MEASURED device stats
  existed, so the one-sided > 4.0 planner-drift check now runs on any
  measured round (ROADMAP "drift watch").
- **--check-schema** — self-test: validates the baseline file when one
  exists (an explicitly named one must) AND pushes a synthetic
  regression + identity round through the comparator, asserting they
  classify as exit-3 / exit-0 respectively. Chained into
  ``contract_check --lint`` so a malformed baseline edit fails CI at
  lint time, not at the next bench round.
- **--write-baseline OUT** — seed/refresh a baseline from the current
  input (``--tolerance`` sets the default band; direction inferred from
  the metric name, throughput/quality up, time/count down).

Exit codes: 0 within bands, 2 usage or schema error, 3 unexplained
regression (outside its band and not allow-listed).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA_VERSION = 1
DIRECTIONS = ("higher_is_better", "lower_is_better")
DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")

# name fragments implying "bigger is better" when seeding a baseline
_HIGHER = ("atoms_per_sec", "per_sec", "mfu", "occupancy", "hit_rate",
           "coverage", "headroom", "value", "edge_balance")


def validate_baseline(doc) -> list:
    """Schema findings for a parsed baseline document (empty = valid)."""
    errs = []
    if not isinstance(doc, dict):
        return ["baseline is not a JSON object"]
    if doc.get("schema") != SCHEMA_VERSION:
        errs.append(f"schema must be {SCHEMA_VERSION}, "
                    f"got {doc.get('schema')!r}")
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        errs.append("metrics must be a non-empty object")
        metrics = {}
    for name, m in metrics.items():
        if not isinstance(m, dict):
            errs.append(f"metrics[{name!r}] is not an object")
            continue
        if not isinstance(m.get("value"), (int, float)) \
                or isinstance(m.get("value"), bool):
            errs.append(f"metrics[{name!r}].value must be a number")
        tol = m.get("tolerance_frac")
        if not isinstance(tol, (int, float)) or isinstance(tol, bool) \
                or tol < 0:
            errs.append(f"metrics[{name!r}].tolerance_frac must be a "
                        f"number >= 0")
        if m.get("direction") not in DIRECTIONS:
            errs.append(f"metrics[{name!r}].direction must be one of "
                        f"{list(DIRECTIONS)}")
    allow = doc.get("allow_regressions", [])
    if not isinstance(allow, list) \
            or any(not isinstance(a, str) for a in allow):
        errs.append("allow_regressions must be a list of metric names")
    else:
        for a in allow:
            if metrics and a not in metrics:
                errs.append(f"allow_regressions entry {a!r} names no "
                            f"baseline metric")
    return errs


def metrics_from_result(path) -> dict:
    """Numeric metrics from a bench ``--json`` capture: the last
    parseable JSON object line (bench stdout also carries ``#`` noise
    lines on stderr and, on failure, tracebacks — tolerate anything)."""
    doc = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not (line.startswith("{") and line.endswith("}")):
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
    if not isinstance(doc, dict):
        raise ValueError(f"no JSON object line in {path}")
    out = {}
    for k, v in doc.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[k] = float(v)
    return out


def metrics_from_jsonl(path) -> dict:
    """Derived metrics from a telemetry JSONL round (the aggregated
    report's counters: compile split, per-phase p50s, mfu, hbm ratio)."""
    from distmlip_tpu.telemetry.report import aggregate, read_jsonl

    rep = aggregate(read_jsonl(path))
    c = rep.counters
    out = {"n_records": float(rep.n_records)}
    for key in ("compiles", "compiles_fresh", "compiles_aot",
                "compile_time_s", "mean_mfu", "hbm_estimator_ratio",
                "mean_structures_per_sec", "mean_kernel_coverage",
                "collective_count", "rebuilds_total"):
        if key in c:
            out[key] = float(c[key])
    for phase, stats in rep.phases.items():
        out[f"phase_{phase}_p50"] = float(stats.get("p50", 0.0))
    if c.get("serving"):
        out["serve_latency_p99_s"] = float(c["serving"]["latency_p99_s"])
    if c.get("training"):
        out["train_examples_per_sec"] = float(
            c["training"]["mean_examples_per_sec"])
    return out


def compare(baseline: dict, current: dict) -> list:
    """[(name, status, detail)] per baseline metric; status in
    {ok, improved, regression, allowed_regression, missing}."""
    allow = set(baseline.get("allow_regressions", []))
    findings = []
    for name, m in sorted(baseline["metrics"].items()):
        if name not in current:
            findings.append((name, "missing",
                             "metric absent from the current round"))
            continue
        base, cur = float(m["value"]), float(current[name])
        tol = float(m["tolerance_frac"])
        higher = m["direction"] == "higher_is_better"
        band = abs(base) * tol
        delta = cur - base
        worse = (delta < -band) if higher else (delta > band)
        better = (delta > band) if higher else (delta < -band)
        rel = (delta / base) if base else float(delta != 0.0)
        detail = (f"current {cur:g} vs baseline {base:g} "
                  f"({rel:+.1%}, band ±{tol:.0%})")
        if worse:
            status = ("allowed_regression" if name in allow
                      else "regression")
        elif better:
            status = "improved"
        else:
            status = "ok"
        findings.append((name, status, detail))
    return findings


def hbm_drift_findings(current: dict) -> list:
    """The un-parked estimator drift watch: one-sided > 4x, evaluated
    whenever the input carries a measured est/measured ratio at all."""
    out = []
    for key in ("hbm_est_over_measured", "hbm_estimator_ratio"):
        if key not in current:
            continue
        ratio = float(current[key])
        if ratio > 4.0:
            out.append((key, "regression",
                        f"static HBM plan estimates {ratio:.2f}x the "
                        f"measured peak (> 4x, one-sided) — retune "
                        f"analysis/memory.py before trusting its "
                        f"admission gates"))
        else:
            out.append((key, "ok", f"est/measured {ratio:.2f}x <= 4x"))
    return out


def write_baseline(current: dict, path: str, tolerance: float,
                   source: str) -> dict:
    metrics = {}
    for name, v in sorted(current.items()):
        higher = any(h in name for h in _HIGHER)
        # exact-count metrics (collectives, compiles, records) get a zero
        # band — they are structural, not noisy
        structural = (float(v) == int(v)
                      and any(s in name for s in (
                          "collectives", "collective_count", "compiles",
                          "n_records", "rebuilds")))
        metrics[name] = {
            "value": v,
            "tolerance_frac": 0.0 if structural else tolerance,
            "direction": ("higher_is_better" if higher
                          else "lower_is_better"),
        }
    doc = {"schema": SCHEMA_VERSION, "metrics": metrics,
           "allow_regressions": [], "source": source}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def self_test(baseline_path) -> list:
    """--check-schema: baseline-file validation + comparator probes. The
    default path may be absent (no baseline is committed yet); a path the
    caller named must exist."""
    errs = []
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            return [f"cannot parse {baseline_path}: {e}"]
        errs.extend(f"{baseline_path}: {e}"
                    for e in validate_baseline(doc))
    elif baseline_path != DEFAULT_BASELINE:
        errs.append(f"{baseline_path} does not exist")
    # comparator probes: a synthetic regression must classify as one, an
    # identity round must not, the allow-list must downgrade
    probe = {"schema": SCHEMA_VERSION, "allow_regressions": ["b"],
             "metrics": {
                 "a": {"value": 100.0, "tolerance_frac": 0.1,
                       "direction": "higher_is_better"},
                 "b": {"value": 1.0, "tolerance_frac": 0.0,
                       "direction": "lower_is_better"}}}
    if validate_baseline(probe):
        errs.append("validator rejects a known-good document")
    ident = {s for _, s, _ in compare(probe, {"a": 100.0, "b": 1.0})}
    if ident != {"ok"}:
        errs.append(f"identity round classified {sorted(ident)}, "
                    f"expected all ok")
    by = {n: s for n, s, _ in compare(probe, {"a": 50.0, "b": 2.0})}
    if by.get("a") != "regression":
        errs.append("synthetic -50% on a higher_is_better metric did "
                    "not classify as regression")
    if by.get("b") != "allowed_regression":
        errs.append("allow-listed regression did not downgrade")
    if not any(s == "regression"
               for _, s, _ in hbm_drift_findings(
                   {"hbm_est_over_measured": 5.0})):
        errs.append("hbm drift watch did not flag a 5x ratio")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--input", default=None,
                    help="bench --json capture (last JSON object line)")
    ap.add_argument("--jsonl", default=None,
                    help="telemetry JSONL round")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--check-schema", action="store_true",
                    help="validate the baseline file + comparator "
                         "self-test, no gating")
    ap.add_argument("--write-baseline", default=None, metavar="OUT",
                    help="seed/refresh a baseline from the current input")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="default tolerance band when writing "
                         "(structural counts get 0)")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 0 if e.code in (0, None) else 2

    if args.check_schema:
        errs = self_test(args.baseline)
        for e in errs:
            print(f"schema error: {e}", file=sys.stderr)
        if not errs:
            print("perf_gate schema ok: " + (
                args.baseline if os.path.exists(args.baseline)
                else "comparator only, no baseline committed"))
        return 0 if not errs else 2

    if bool(args.input) == bool(args.jsonl):
        print("usage error: exactly one of --input / --jsonl required",
              file=sys.stderr)
        return 2
    try:
        current = (metrics_from_result(args.input) if args.input
                   else metrics_from_jsonl(args.jsonl))
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        src = os.path.basename(args.input or args.jsonl)
        doc = write_baseline(current, args.write_baseline,
                             args.tolerance, source=src)
        print(f"wrote {args.write_baseline}: "
              f"{len(doc['metrics'])} metric(s) from {src}")
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"error: cannot read baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 2
    errs = validate_baseline(baseline)
    if errs:
        for e in errs:
            print(f"schema error: {e}", file=sys.stderr)
        return 2

    findings = compare(baseline, current)
    findings.extend(hbm_drift_findings(current))
    n_reg = sum(s == "regression" for _, s, _ in findings)
    if args.json:
        print(json.dumps({
            "baseline": args.baseline,
            "findings": [{"metric": n, "status": s, "detail": d}
                         for n, s, d in findings],
            "regressions": n_reg,
        }, indent=2, sort_keys=True))
    else:
        for name, status, detail in findings:
            mark = {"ok": " ", "improved": "+", "missing": "?",
                    "allowed_regression": "!",
                    "regression": "X"}[status]
            print(f" [{mark}] {name:<32} {status:<19} {detail}")
        print(f"perf gate: {len(findings)} metric(s), "
              f"{n_reg} unexplained regression(s)")
    return 3 if n_reg else 0


if __name__ == "__main__":
    raise SystemExit(main())
