#!/usr/bin/env python
"""Render the per-phase report for a telemetry JSONL run.

    python tools/telemetry_report.py run.jsonl [--json report.json]
        [--trace-dir traces/] [--stall-factor 5]
        [--occupancy-floor 0.35] [--imbalance-factor 2]

Reads StepRecord JSONL (produced by distmlip_tpu.telemetry.JsonlSink: any
DistPotential/DeviceMD run with a JsonlSink attached), prints the per-phase total/mean/p50/p90/p99/max table
and run counters, and flags anomalies: wedge-style stalls, padding-occupancy
collapse, and halo-volume imbalance. ``--trace-dir`` additionally loads
exported Perfetto trace JSON (distmlip_tpu.obs / load_test --trace-out)
and renders per-request critical-path percentiles (queue/pack/compile/
device) next to the per-phase table, flagging ``queue_dominant`` when the
median queue wait exceeds the median device time. Exit codes: 0 clean, 4
anomalies flagged, 2 usage, 1 unreadable input.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distmlip_tpu.telemetry.report import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
