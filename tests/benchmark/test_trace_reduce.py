"""The reduction from a trace to numbers, on the small trace recorded
beside this file (``data/small_trace.json``: two device planes, nested
operations, collectives, host spans), whose answers are worked out by
hand in the comments."""

from __future__ import annotations

import os

import pytest

from benchmark.harness import spec
from benchmark.harness.trace import Trace, top

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    return Trace.from_json(os.path.join(HERE, "data", "small_trace.json"))


def test_planes_and_window(trace):
    assert trace.device_planes() == ["/device:TPU:0", "/device:TPU:1"]
    assert trace.window() == (1000, 11000)


def test_busy_union_counts_overlap_once(trace):
    # TPU:0: [1000,3000] u [2000,2500] (nested) u [4000,9000] u [8000,9500]
    #        u [10000,11000] = 2000 + 5500 + 1000
    assert trace.busy_ns("/device:TPU:0") == 8500
    # TPU:1: [1000,2000] u [5000,6000]
    assert trace.busy_ns("/device:TPU:1") == 2000
    assert trace.busiest_plane() == "/device:TPU:0"


def test_self_times_take_nested_operations_out(trace):
    own = trace.self_times("/device:TPU:0")
    assert own["while.1"] == 1500           # 2000 less the fusion inside
    assert own["fusion.7"] == 500
    assert own["closed_call.3_tpu_custom_call"] == 5000
    assert top(own, 2)[0] == ["closed_call.3_tpu_custom_call", 5e-6]


def test_sums_by_name_and_collectives(trace):
    plane = "/device:TPU:0"
    assert trace.sum_matching(plane, "tpu_custom_call") == (1, 5000)
    assert trace.sum_matching(plane, "collective-permute") == (2, 2500)
    assert trace.sum_matching(plane, "no-such-op") == (0, 0)


def test_idle_gaps_are_named_by_the_host_span(trace):
    gaps = trace.idle_gaps("/device:TPU:0", floor_ns=200)
    # gaps: [3000,4000] under distmlip/positions_upload (inside
    # bench/calculate inside bench/md_step: the innermost wins), and
    # [9500,10000] after bench/calculate has returned
    assert gaps == {"distmlip/positions_upload": 1000, "bench/md_step": 500}
    pooled = trace.idle_gaps("/device:TPU:0", floor_ns=800)
    assert pooled == {"distmlip/positions_upload": 1000,
                      "between_device_ops_under_0_us": 500}
    assert trace.idle_gaps("/device:TPU:1", floor_ns=200) == {
        "distmlip/positions_upload": 3000, "bench/calculate": 5000}


def run_for(trace, **extra):
    base = {"trace": trace, "traced_steps": 2, "steps": 4, "chips": 1,
            "window_s": 2.0, "memory_peak_bytes": 3_000_000_000,
            "flops_per_step": 1e12, "counters": {"compiles_in_window": 0},
            "peaks": {"flops_per_s": 200e12, "hbm_bytes_per_s": 800e9},
            "kernel_work": {"segment_sum": {"flops": 1e3, "bytes": 800.0}},
            "spans": [("bench/md_step", 0.0, 1.0), ("bench/md_step", 1.0, 2.0),
                      ("bench/calculate", 0.1, 0.9),
                      ("bench/calculate", 1.1, 1.9)]}
    base.update(extra)
    return base


def reader(name):
    cell = spec.load_cell(spec.load_benchmark()["workloads"][0]["name"])
    metric = next(m for m in spec.load_benchmark()["per_layer"]
                  if m["name"] == name)
    return spec.load_reader(cell, metric)


@pytest.mark.parametrize("name, expected", [
    ("device.idle_share.md", 15.0),                 # 1 - 8500 / 10000
    ("runtime.collective_ms_per_step.md", 2500 / 1e6 / 2),
    # least time max(1e3 / 200e12, 800 / 800e9) = 1 ns; 5000 ns over 2 steps
    ("kernel.segment_sum_roofline.md", 100.0 * 1e-9 / 2.5e-6),
    ("model.mfu.md", 100.0 * 1e12 * 4 / 2.0 / 200e12),
    ("driver.host_ms_per_step.md", 1e3 * (2.0 - 1.6) / 4),
    ("potential.compiles_in_window.md", 0),
    ("device.hbm_peak_gb.md", 3.0),
])
def test_readers(trace, name, expected):
    read, params = reader(name)
    assert read(run_for(trace), params) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "device.idle_share.md", "runtime.collective_ms_per_step.md",
    "kernel.segment_sum_roofline.md"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    read, params = reader(name)
    assert read(run_for(None), params) is None
    assert read(run_for(Trace([("/host:CPU", "t", "bench/md_step", 0, 5)])),
                params) is None
