"""The program's reading of a profiler trace (``repro.serve.profile``)
against the benchmark's (``bench/trace.py``), on the trace recorded on a
TPU v5e under ``bench/fixtures``: the same busy time and idle gaps in
the window, and no engine span in a trace taken before the engine had
any."""

import os

import pytest

import _bench_tiny as tb
from bench import trace
from repro.serve import profile

FIXTURE = os.path.join(tb.REPO, "bench", "fixtures", "v5e_decode")


@pytest.fixture(scope="module")
def both():
    if not os.path.isdir(FIXTURE):
        pytest.skip("no recorded trace")
    raw = trace.load(FIXTURE)
    return raw, trace.reduce(raw), profile.load(FIXTURE)


def test_fixture_busy_time_and_gaps_agree(both):
    raw, red, prof = both
    (lo, hi), = [(a, b) for a, b, n in raw["host"] if n == trace.WINDOW]
    busy = sum(b - a for a, b in trace.clip(prof["busy"], lo, hi))
    assert busy == pytest.approx(red["busy_s"], rel=1e-9)
    gaps = profile.idle_gaps(prof["busy"], prof["spans"], lo, hi)
    assert [d for _, d in gaps] == pytest.approx(
        [d for _, d in red["devices"][0]["idle_gaps"]], rel=1e-9)
    assert prof["spans"] == [] and {n for n, _ in gaps} == {"none"}
