#!/usr/bin/env python
"""Roofline report over the contract-check program family.

    python tools/roofline.py [--models chgnet,tensornet,mace,escn]
        [--programs SUBSTR] [--json] [--times times.json]
        [--jsonl run.jsonl] [--mfu-floor F]

Traces the SAME programs ``tools/contract_check.py`` gates (every model
at 1x1 / 2x1 / 2x2, the packed batch, the ensembles, the DeviceMD chunk,
the train steps and tier family) and places each on the roofline:

- **flops**  — :func:`obs.roofline.jaxpr_flop_estimate` over the traced
  jaxpr (dot_general-exact, padding included: the cost the device pays);
- **bytes**  — minimum HBM traffic from the static memory planner
  (:func:`analysis.memory.analyze_memory`, arg + const + out bytes);
- **intensity** = flops / bytes;
- **achieved / mfu** — only when a measured step time exists for the
  program: ``--times times.json`` maps program-name substrings to
  seconds, ``--jsonl run.jsonl`` pulls warm-step device medians from a
  telemetry round by bucket/kind. Peak FLOP/s comes from
  :func:`utils.flops.device_peaks` (one table keyed by ``device_kind``;
  no entry on CPU -> mfu renders n/a). No chip is needed for the
  flops/bytes/intensity columns — CPU CI exercises the full report path
  (the cost-model fallback of the acceptance gate).

``--mfu-floor F`` exits 3 when any program WITH a computable MFU (a
measured time and a known peak) sits below ``F`` — the pinned-floor
regression gate; programs without measurements never trip it.

Exit codes: 0 clean, 2 usage error, 3 MFU-floor regression.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_flag = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()


def trace_programs(models, want_substr=None):
    """The contract-check program family, traced (no chip, no compile)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import contract_check as cc

    want = (cc._want_all if not want_substr
            else (lambda n: want_substr in n))
    programs = []
    for name in models:
        cc._trace_model_programs(name, programs, want)
    if want("packed_batch[tensornet][B=4]"):
        cc._trace_packed_batch(programs)
    cc._trace_ensemble(programs, want)
    if want("device_md[pair][1x1]"):
        cc._trace_device_md(programs)
    cc._trace_train_step(programs, want)
    cc._trace_train_step_tiers(programs, want)
    return programs


def _times_from_jsonl(path):
    """{bucket-or-kind name: median warm-step device seconds} from a
    telemetry JSONL round (same grouping rows_from_records uses)."""
    from distmlip_tpu.telemetry.report import read_jsonl

    groups = {}
    for r in read_jsonl(path):
        if getattr(r, "compiled", False):
            continue  # compile steps skew a median meant for warm steps
        t = (r.timings or {}).get("device_s", 0.0)
        if t <= 0:
            continue
        for key in (r.bucket_key, r.kind):
            if key:
                groups.setdefault(key, []).append(float(t))
    out = {}
    for key, ts in groups.items():
        ts.sort()
        out[key] = ts[len(ts) // 2]
    return out


def _lookup_time(name, times):
    """Longest-substring match of a program name against the times map —
    `train_step` must not shadow `train_step[tensornet][2x1]`."""
    best, best_len = 0.0, -1
    for key, t in times.items():
        if key in name and len(key) > best_len:
            best, best_len = float(t), len(key)
    return best if best_len >= 0 else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="roofline", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--models", default="chgnet,tensornet,mace,escn")
    ap.add_argument("--programs", default=None,
                    help="only programs whose name contains SUBSTR")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--times", default=None,
                    help="JSON file: {program-substring: seconds}")
    ap.add_argument("--jsonl", default=None,
                    help="telemetry JSONL: warm-step device medians by "
                         "bucket/kind")
    ap.add_argument("--mfu-floor", type=float, default=None,
                    help="exit 3 when a measured program's MFU falls "
                         "below this fraction")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 0 if e.code in (0, None) else 2

    times = {}
    try:
        if args.jsonl:
            times.update(_times_from_jsonl(args.jsonl))
        if args.times:
            with open(args.times) as f:
                times.update(json.load(f))
    except (OSError, json.JSONDecodeError, AttributeError) as e:
        print(f"usage error: cannot read times: {e}", file=sys.stderr)
        return 2
    models = tuple(m.strip() for m in args.models.split(",") if m.strip())

    from distmlip_tpu.analysis.memory import analyze_memory
    from distmlip_tpu.obs.roofline import (RooflineRow, bytes_touched,
                                           format_roofline_table,
                                           jaxpr_flop_estimate)
    from distmlip_tpu.utils.flops import device_peaks

    peak, peak_bw = device_peaks() or (0.0, 0.0)
    programs = trace_programs(models, args.programs)
    rows = []
    for prog in programs:
        n_dev = 2 if ("2x1" in prog.name or "2x2" in prog.name) else 1
        if "2x2" in prog.name:
            n_dev = 4
        t = _lookup_time(prog.name, times)
        rows.append(RooflineRow(
            program=prog.name,
            flops=jaxpr_flop_estimate(prog.jaxpr),
            bytes=float(bytes_touched(analyze_memory(prog.jaxpr))),
            time_s=t, peak_flops=peak, peak_bytes_per_s=peak_bw,
            n_devices=n_dev,
            source="measured" if t > 0 else "cost_model"))

    below = [r for r in rows
             if args.mfu_floor is not None and r.time_s > 0
             and r.peak_flops > 0 and r.mfu < args.mfu_floor]
    if args.json:
        print(json.dumps({
            "rows": [r.as_dict() for r in rows],
            "peak_flops_per_device": peak,
            "mfu_floor": args.mfu_floor,
            "below_floor": [r.program for r in below],
        }, indent=2, sort_keys=True))
    else:
        print(format_roofline_table(
            rows, title=f"roofline: {len(rows)} program(s), "
            f"peak/device="
            + (f"{peak:.3g} FLOP/s" if peak else "n/a (no TPU)")))
        if below:
            print()
            for r in below:
                print(f"MFU REGRESSION: {r.program} mfu={r.mfu:.4f} "
                      f"< floor {args.mfu_floor}")
    return 3 if below else 0


if __name__ == "__main__":
    raise SystemExit(main())
