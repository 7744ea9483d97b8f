"""The command itself: it refuses any backend but a TPU; with the look for
a chip skipped it drives a whole run at toy size on the CPU, prints the
contract's last line, and reads ``correct`` false when the timed path is
broken underneath."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

import toy
from benchmark import run as cli
from benchmark.harness import device, spec

CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]


def test_command_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = spec.load_benchmark()
    done = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs a TPU" in done.stderr


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """``run.main`` with the look for a chip skipped: toy cell, the CPU's
    devices, no persistent compile cache."""
    def drive(family="mace", chips=1, reps=(3, 3, 3), trace=0, seed=5):
        root = toy.make_root(str(tmp_path / f"root-{seed}-{trace}"), family,
                             reps=reps, chips=chips,
                             compute_dtype="bfloat16", limits=toy.SERVED)
        monkeypatch.setattr(cli, "ROOT", root)
        monkeypatch.setattr(device, "require_chips",
                            lambda n: jax.devices()[:n])
        monkeypatch.setattr(
            "distmlip_tpu.utils.compile_cache.enable_compile_cache",
            lambda: "off")
        monkeypatch.setenv("DISTMLIP_KERNELS", "interpret")
        return cli.main(["--workload", "toy-md", "--seed", str(seed),
                         "--seconds", "0.2", "--trace", str(trace)])

    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield drive
    for key, value in keep.items():
        jax.config.update(key, value)


def last_line(capsys) -> dict:
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_last_line_has_the_contract_keys(on_cpu, capsys):
    assert on_cpu(trace=0, seed=2 ** 31 + 9) == 0
    result, err = last_line(capsys)
    assert list(result) == CONTRACT + ["compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {
        "atom_steps_per_s_per_chip", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == 1
    # each number compared beside its limit: last on stderr, last in the line
    names = [c["name"] for c in result["compared"]]
    assert names == ["force_err_vs_rounding", "kick_rel_err"]
    tail = err.strip().splitlines()[-len(names):]
    assert all(line.startswith(f"compared {n} = ")
               for line, n in zip(tail, names))


def test_traced_run_reports_the_per_layer_metrics(on_cpu, capsys):
    assert on_cpu(trace=1) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is True
    # on the CPU the trace holds no device plane: what reads the device
    # trace finds nothing and is left out; counts and host spans are there
    assert set(result["metrics"]) == {
        "driver.host_ms_per_step.md", "potential.compiles_in_window.md",
        "potential.rebuilds_in_window.md", "device.hbm_peak_gb.md",
        "toy.steps"}
    assert result["metrics"]["potential.compiles_in_window.md"]["value"] == 0
    assert result["metrics"]["potential.rebuilds_in_window.md"]["value"] == 0


def unmoved_state(monkeypatch):
    """A step that returns its state unchanged."""
    from distmlip_tpu.calculators import MolecularDynamics

    monkeypatch.setattr(MolecularDynamics, "_velocity_verlet",
                        lambda self: None)


def altered_answer(monkeypatch):
    """Forces altered where they are produced."""
    from distmlip_tpu.calculators import DistPotential

    sound = DistPotential.calculate

    def calculate(self, atoms):
        out = sound(self, atoms)
        out["forces"] = out["forces"] * 1.25
        return out

    monkeypatch.setattr(DistPotential, "calculate", calculate)


def no_exchange(monkeypatch):
    """The exchange between chips left out: halo rows keep stale
    features."""
    from distmlip_tpu.parallel.halo import LocalGraph

    monkeypatch.setattr(LocalGraph, "halo_exchange",
                        lambda self, feats: feats)


@pytest.mark.parametrize("fault, chips, reps", [
    (unmoved_state, 1, (3, 3, 3)), (altered_answer, 1, (3, 3, 3)),
    (no_exchange, 4, (12, 3, 3))], ids=lambda x: getattr(x, "__name__", None))
def test_a_broken_timed_path_reads_not_correct(on_cpu, capsys, monkeypatch,
                                               fault, chips, reps):
    fault(monkeypatch)
    assert on_cpu("tensornet", chips=chips, reps=reps) == 0
    result, _ = last_line(capsys)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["compared"])
