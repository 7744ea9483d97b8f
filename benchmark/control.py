"""Readings for the limits that decide ``correct`` (not part of a run).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 4 --controls 3

For each seed, in one process: set-up, a short window at the cell's own
size, then the comparison a run makes (the lower reading), and for the
first ``--controls`` seeds the control: the plain reference computed in the
precision below the configuration's, put in the program's place and
compared as the program is (the upper reading). One JSON line per seed on
standard output, and appended to ``chiprun_out/control_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

from benchmark.harness import compare, device, spec  # noqa: E402

# the precision below the one a configuration computes in
BELOW = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=4.0)
    parser.add_argument("--controls", type=int, default=3)
    args = parser.parse_args(argv)

    cell = spec.load_cell(args.workload, ROOT)
    devices, _ = device.open_chips(cell.chips)
    driver = spec.load_module(cell, "drivers", cell.traffic["driver"])
    below = BELOW[cell.config["potential"]["compute_dtype"]]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        state = driver.set_up(cell, seed, devices, tables_dir=os.path.join(
            ROOT, ".bench_cache", "tables"))
        window = driver.run_window(state, args.seconds)
        driver.release_program(state)
        verdict = driver.check(state, window, seed)
        line = {"cell": cell.name, "seed": seed, "steps": window.steps,
                "program": verdict["numbers"], "correct": verdict["correct"]}
        if i < args.controls:
            reference = verdict["reference"]
            if reference["region"] is None:
                forces = driver.reference_forces(
                    state, window.positions, (below,))[below][1]
            else:
                moved, numbers, open_cell, core = reference["region"]
                forces = driver.reference_forces(
                    state, moved, (below,), numbers=numbers,
                    cell=open_cell)[below][1][core]
            rounding = compare.relative(reference["rounding_forces"],
                                        reference["forces"])
            error = compare.relative(forces, reference["forces"])
            line["control"] = {"precision": below, "force_rel_err": error,
                               "force_err_vs_rounding": error / rounding}
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, f"control_{cell.name}.jsonl"),
                  "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
