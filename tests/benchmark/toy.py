"""A toy benchmark tree for the CPU tests: the committed data files copied
into a temporary root, with a cell, a configuration, a mix and a per-layer
metric ADDED as new files and entries, the way a later PR adds them."""

from __future__ import annotations

import json
import os
import shutil

from benchmark.harness import spec

TOY_MODELS = {
    "mace": {"num_species": 95, "channels": 8, "l_max": 2, "a_lmax": 2,
             "hidden_lmax": 1, "correlation": 2, "num_interactions": 2,
             "num_bessel": 4, "radial_mlp": 8, "radial_layers": 3,
             "radial_scale": 16.0, "cutoff": 5.0, "cutoff_p": 6,
             "avg_num_neighbors": 42.0},
    "tensornet": {"num_species": 95, "units": 8, "num_rbf": 4,
                  "num_layers": 2, "cutoff": 5.0},
}


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


# float32 program against the float32 reference
TIGHT = {"force_rel_err": 1e-3, "kick_rel_err": 1e-3}
# bfloat16 program: the limits' shape of the committed cells
SERVED = {"force_err_vs_rounding": 8.0, "kick_rel_err": 0.3}


def make_root(tmp: str, family: str = "mace", reps=(3, 3, 3), chips: int = 1,
              compute_dtype: str = "float32", limits: dict = TIGHT,
              check_region: dict | None = None) -> str:
    """Returns the toy root; its cell is ``toy-md``."""
    bench = spec.load_benchmark()
    for sub in ("configs", "traffic", "metrics", "readers", "limits"):
        shutil.copytree(os.path.join(spec.ROOT, "benchmark", sub),
                        os.path.join(tmp, "benchmark", sub))
    _write(os.path.join(tmp, "benchmark/configs/toy.json"), {
        "name": "toy", "source": "test", "family": family,
        "model": TOY_MODELS[family],
        "potential": {"compute_dtype": compute_dtype},
        "reduced": {}, "assumed": {}})
    extra = {} if check_region is None else {"check_region": check_region}
    _write(os.path.join(tmp, "benchmark/traffic/md-toy.json"), {
        "driver": "md", **extra,
        "structure": {"kind": "perturbed_fcc", "reps": list(reps), "a": 3.9,
                      "sigma": 0.04, "number": 14},
        "temperature_k": 300.0, "ensemble": "nve", "timestep_fs": 0.05,
        "skin": 0.5, "warmup_steps": 1, "trace_steps": 2, "caps": {}})
    _write(os.path.join(tmp, "benchmark/limits/toy-md.json"), {
        "cell": "toy-md",
        "limits": {k: {"limit": v} for k, v in limits.items()}})
    _write(os.path.join(tmp, "benchmark/metrics/toy.steps.json"),
           {"reader": "toy_steps"})
    with open(os.path.join(tmp, "benchmark/readers/toy_steps.py"), "w") as f:
        f.write("def read(run, params):\n    return run['steps']\n")
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-md", "config": "toy",
                               "traffic": "md-toy", "chips": chips,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "toy.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Drivers (calculators/md.py)",
        "moves": "atom_steps_per_s_per_chip", "workloads": ["toy-md"]})
    for metric in bench["per_layer"]:
        if "workloads" in metric and metric["name"] != "toy.steps":
            metric["workloads"] = metric["workloads"] + ["toy-md"]
    _write(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp
