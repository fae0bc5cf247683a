"""The trace reduction of ``bench/trace.py``: synthetic intervals, and a
short trace recorded on a TPU v5e kept under ``bench/fixtures``."""

import os

import numpy as np
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
    assert trace.labels([(1, 2), (4.5, 5.5), (11, 12)], spans) == \
        ["bench.tick", "bench.record", "none"]


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


def test_reduce_keeps_only_the_cells_chips():
    """Planes of devices outside the cell are left out; busy time is the
    mean over the cell's planes, ops and launches are kept per plane."""
    dev = lambda lo, hi: {"ops": [(lo, hi, "a")],  # noqa: E731
                          "modules": [(lo, hi, "jit_step(1)")]}
    raw = {"host": [(0.0, 10.0, "bench.window")],
           "devices": {"/device:TPU:2": dev(1.0, 5.0),
                       "/device:TPU:0": dev(1.0, 3.0),
                       "/device:TPU:10": dev(0.0, 10.0)}}
    red = trace.reduce(raw, {0, 2})
    assert [d["name"] for d in red["devices"]] == ["/device:TPU:0",
                                                   "/device:TPU:2"]
    assert red["busy_s"] == 3.0
    assert trace.reduce(raw)["busy_s"] == 16.0 / 3
    with pytest.raises(ValueError):
        trace.reduce(raw, {5})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_labels_in_one_sweep_match_the_plain_rule(seed):
    """``labels`` names each gap as the plain rule does: the shortest
    span (then the least name) with start <= middle <= end."""
    rng = np.random.default_rng(seed)
    spans = []
    for _ in range(300):
        a = float(rng.integers(0, 400)) / 4
        spans.append((a, a + float(rng.integers(0, 40)) / 4,
                      f"bench.s{rng.integers(0, 5)}"))
    busy = trace.union([(float(a), float(a) + 0.25) for a in
                        rng.integers(0, 500, 200) / 4])
    gs = trace.gaps(busy, 0.0, 130.0)

    def plain(g):
        mid = 0.5 * (g[0] + g[1])
        inside = [(b - a, n) for a, b, n in spans if a <= mid <= b]
        return min(inside)[1] if inside else "none"

    assert len(gs) > 50
    assert trace.labels(gs, spans) == [plain(g) for g in gs]
