"""The trace reduction of ``bench/trace.py``: synthetic intervals, and a
short trace recorded on a TPU v5e kept under ``bench/fixtures``."""

import os

import pytest

import _bench_tiny as tb
from bench import trace

FIXTURE = os.path.join(tb.REPO, "bench", "fixtures", "v5e_decode")


def test_union_merges_overlaps_and_touching():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_gaps_and_clip():
    busy = trace.union(trace.clip([(-1, 1), (2, 3), (5, 9)], 0, 6))
    assert busy == [(0, 1), (2, 3), (5, 6)]
    assert trace.gaps(busy, 0, 6) == [(1, 2), (3, 5)]
    assert trace.gaps([], 0, 1) == [(0, 1)]


def test_gap_takes_the_innermost_span():
    spans = [(0, 10, "bench.tick"), (4, 6, "bench.record")]
    assert trace.label((4.5, 5.5), spans) == "bench.record"
    assert trace.label((1, 2), spans) == "bench.tick"
    assert trace.label((11, 12), spans) == "none"


def test_module_names_drop_the_launch_id():
    assert trace.module_name("jit_step(123)") == "jit_step"
    assert trace.module_name("jit_prefill_chunk") == "jit_prefill_chunk"


def test_reduce_synthetic():
    raw = {"host": [(0.0, 10.0, "bench.window"), (0.0, 4.0, "bench.tick")],
           "devices": {"/device:TPU:0": {
               "ops": [(1.0, 2.0, "a"), (1.5, 3.0, "b"), (8.0, 12.0, "a")],
               "modules": [(1.0, 3.0, "jit_step(7)")]}}}
    red = trace.reduce(raw)
    dev = red["devices"][0]
    assert red["window_s"] == 10.0 and red["busy_s"] == 4.0
    assert dev["ops_s"] == {"a": 1.0, "b": 1.5}     # (8, 12) ends outside
    assert dev["modules_s"] == {"jit_step": [2.0]}
    assert dev["idle_gaps"] == [("none", 5.0), ("bench.tick", 1.0)]


@pytest.fixture(scope="module")
def recorded():
    if not os.path.isdir(FIXTURE):
        pytest.skip("no recorded trace")
    return trace.reduce(trace.load(FIXTURE))


def test_recorded_trace_busy_and_gaps_fill_the_window(recorded):
    dev = recorded["devices"][0]
    idle = sum(s for _, s in dev["idle_gaps"])
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    assert abs(recorded["busy_s"] + idle - recorded["window_s"]) < 1e-6


def test_recorded_trace_names_the_kernel_and_the_step(recorded):
    dev = recorded["devices"][0]
    assert any("flash_decode_paged" in k for k in dev["ops_s"])
    assert len(dev["modules_s"]["jit_step"]) >= 2
    # Ops overlap at most where nested; their sum covers the busy time.
    assert sum(dev["ops_s"].values()) >= recorded["busy_s"] * 0.999
    assert trace.breakdown(recorded)["device_ops"]
