"""Two sets of runs of one cell, as the bounds are set from (not part of a
run; the parent never touches jax, each run is a process of its own).

    python3 benchmark/measure.py --workload <cell> --seeds 11,12,13,14,15,16 [--traced 3]

Runs the benchmark's command once per seed, twice over (the same seeds in
both sets), then ``--traced`` runs with ``--trace 1``. Prints every result
line, and per end-to-end metric each set's median and spread: the distance
between the first and third quartile (``statistics.quantiles(n=4)``) over
the median. Result lines are appended to ``chiprun_out/sets_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(command, cell, seed, seconds, trace, log):
    t0 = time.perf_counter()
    done = subprocess.run(
        command + ["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    if done.returncode != 0:
        print(done.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"run of {cell} seed {seed} exited "
                         f"{done.returncode}")
    result = json.loads(done.stdout.strip().splitlines()[-1])
    record = {"cell": cell, "seed": seed, "trace": trace, "wall_s": wall,
              **result}
    print(json.dumps(record), flush=True)
    log.write(json.dumps(record) + "\n")
    log.flush()
    if not result["correct"]:
        print(done.stderr[-2000:], file=sys.stderr)
    return result


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--traced", type=int, default=0)
    parser.add_argument("--sets", type=int, default=2)
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"sets_{args.workload}.jsonl"),
              "a") as log:
        sets = [[one_run(bench["command"], args.workload, seed,
                         bench["run_seconds"], 0, log) for seed in seeds]
                for _ in range(args.sets)]
        for seed in seeds[:args.traced]:
            one_run(bench["command"], args.workload, seed,
                    bench["run_seconds"], 1, log)
    for metric in sets[0][0]["metrics"] if sets else []:
        for i, runs in enumerate(sets):
            values = [r["metrics"][metric]["value"] for r in runs]
            print(f"{args.workload} {metric} set {i}: median "
                  f"{statistics.median(values):.6g} spread "
                  f"{spread(values):.5f} values "
                  f"{[round(v, 6) for v in values]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
