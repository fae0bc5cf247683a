"""Scheduler: 99th percentile of the gaps between consecutive tokens of
one stream, over gaps that end in the window (ms)."""
from bench import readers


def read(run):
    gaps = [1e3 * (b - a) for r in run.requests
            for a, b in zip(r.times, r.times[1:]) if run.inside(b)]
    return readers.pct(gaps, 99)
