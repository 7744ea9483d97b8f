"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process; needs a TPU with at least the cell's chips and never falls
back to another backend. The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run), then ``compared``: each number that decided
``correct`` beside its limit. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT  # the package, not this directory

from benchmark.harness import device, spec  # noqa: E402
from benchmark.harness.trace import top  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def per_layer_metrics(cell, run: dict) -> dict:
    """Each of the cell's per-layer metrics through its reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell.per_layer:
        read, params = spec.load_reader(cell, metric)
        value = read(run, params)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def device_block(devices, outcome: dict, trace) -> tuple[dict, dict | None]:
    block = device.identity(devices)
    block["memory_peak_bytes"] = outcome["memory_peak_bytes"]
    if trace is None or not trace.device_planes():
        return block, None  # no device plane: the CPU of the tests
    t0, t1 = trace.window()
    planes = trace.device_planes()
    block["busy_s"] = sum(trace.busy_ns(p) for p in planes) / len(planes) / 1e9
    block["window_s"] = (t1 - t0) / 1e9
    busiest = trace.busiest_plane()
    return block, {"device_ops": top(trace.self_times(busiest)),
                   "idle_gaps": top(trace.idle_gaps(busiest))}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = spec.load_cell(args.workload, ROOT)
    try:
        devices, cache_dir = device.open_chips(cell.chips)
    except device.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    log(f"cell {cell.name} seed {args.seed} devices "
        f"{device.identity(devices)} compile cache {cache_dir}")

    driver = spec.load_module(cell, "drivers", cell.traffic["driver"])
    outcome = driver.execute(
        cell, args.seed, args.seconds, bool(args.trace), devices, T_START,
        workdir=os.path.join(ROOT, ".bench_cache"))
    log(f"notes {json.dumps(outcome['notes'], default=str)}")

    if args.trace:
        metrics = per_layer_metrics(cell, outcome["run"])
    else:
        metrics = {m["name"]: {"value": float(outcome["values"][m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    block, breakdown = device_block(devices, outcome,
                                    outcome["run"]["trace"])
    result = {"correct": bool(outcome["correct"]),
              "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"]),
              "metrics": metrics, "device": block}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = outcome["compared"]
    for c in outcome["compared"]:
        print(f"compared {c['name']} = {c['value']:.6g} (limit "
              f"{c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
