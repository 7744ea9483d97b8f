"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is an entry of ``workloads``: it names a configuration (whose entry
under ``configs`` names its file) and a traffic mix, which is
``traffic/<name>.json`` under one of ``paths``. A per-layer metric is
``metrics/<name>.json`` there, and names its reader, ``readers/<name>.py``.
The limits that decide a cell's ``correct`` are ``limits/<cell>.json``.
A mix names its driver kind and a configuration its family: modules
``drivers/<kind>.py`` and ``families/<family>.py`` of the package the
benchmark's first path holds. Adding any of these is adding files and
entries; nothing here lists them.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration's file
    traffic_name: str
    traffic: dict         # the mix's file
    end_to_end: list      # the metric entries this cell reports
    per_layer: list
    limits: dict          # limits/<cell>.json: what decides `correct`
    package: str          # import name of the benchmark's code package
    paths: list           # the benchmark's directories, relative to root
    root: str


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_file(root: str, paths: list, *relative: str) -> str:
    for base in paths:
        candidate = os.path.join(root, base, *relative)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        f"{os.path.join(*relative)} is under none of {paths} in {root}")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _read_json(find_file(
        root, bench["paths"], "traffic", entry["traffic"] + ".json"))
    reported = {m["name"]: m for m in bench["end_to_end"]
                if _reported_in(m, name)}
    per_layer = [m for m in bench["per_layer"]
                 if _reported_in(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic,
                end_to_end=list(reported.values()), per_layer=per_layer,
                limits=_read_json(find_file(
                    root, bench["paths"], "limits", name + ".json"))["limits"],
                package=bench["paths"][0].replace("/", "."),
                paths=list(bench["paths"]), root=root)


def load_module(cell: Cell, kind: str, name: str):
    """``<package>.<kind>.<name>``, e.g. the driver ``md`` or the family
    ``mace``."""
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} has characters outside a name")
    return importlib.import_module(f"{cell.package}.{kind}.{name}")


def load_reader(cell: Cell, metric: dict):
    """(read, params) of a per-layer metric: ``params`` is the metric's
    data file, ``read(run, params)`` the function of the reader module that
    the file names. Readers are loaded by path, so one added beside the
    others is found without an import line anywhere."""
    params = _read_json(find_file(
        cell.root, cell.paths, "metrics", metric["name"] + ".json"))
    reader = params["reader"]
    if not NAME.match(reader):
        raise ValueError(f"reader name {reader!r}")
    path = find_file(cell.root, cell.paths, "readers", reader + ".py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_reader_{reader.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read, params
