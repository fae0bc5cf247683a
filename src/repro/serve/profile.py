"""The engine's spans read back from a profiler trace.

Under ``jax.profiler.start_trace`` every ``serve.telemetry`` span lands
in the trace's host plane as an event named ``serve.<name>``, its
metadata as stats, on the same clock as the device's operations. This
module reads those events, and the busy intervals of the first device,
from the ``.xplane.pb`` a session wrote (``launch/serve.py
--profile-dir``), and reduces them to what the host did per tick and
what the device waited on:

  * ``tick_host_s`` — each ``serve.tick`` less the time its fetch spans
    (``serve.decode.fetch``, ``serve.spec_verify.fetch``,
    ``serve.prefill_fetch``: the waits for the device's results) cover:
    the host's own work per tick;
  * ``queue_ms`` — the queue wait each first admission carries on its
    ``serve.admit.request`` span (a re-admission carries ``readmit``
    and none);
  * ``idle_gaps`` — the device's idle gaps, each named by the innermost
    ``serve.*`` span the host was in at its middle (``none`` outside
    every engine span).
"""

from __future__ import annotations

import glob
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
Span = Tuple[float, float, str, Dict[str, Any]]   # start_s, end_s, name, meta

PREFIX = "serve."
# Loop and call ops span the ops they run; only leaves count as busy.
CONTAINERS = ("while", "conditional", "call")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(hi, out[-1][1]))
        else:
            out.append((lo, hi))
    return out


def _op_name(text: str) -> str:
    """``%copy.107 = bf16[...] copy(...)`` -> ``copy.107``."""
    return text[1:].split(" = ", 1)[0] if text.startswith("%") else text


def load(run_dir: str) -> dict:
    """``spans``: the ``serve.*`` host events of the newest ``.xplane.pb``
    under ``run_dir``, sorted by start; ``busy``: the merged intervals in
    which an op ran on the first TPU device (empty where the trace has
    no device plane, as on the CPU). Times are seconds."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{run_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {run_dir}")
    spans: List[Span] = []
    devices: Dict[int, List[Interval]] = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(int(plane.name.split(":")[-1]), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events
                               if not _op_name(e.name).startswith(CONTAINERS))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name,
                              dict(list(e.stats))) for e in line.events
                             if e.name.startswith(PREFIX))
    busy = union(devices[min(devices)]) if devices else []
    return {"spans": sorted(spans, key=lambda s: (s[0], -s[1])),
            "busy": busy}


def tick_host_s(spans: Sequence[Span]) -> List[float]:
    """Per ``serve.tick`` span: its length less the union of the fetch
    spans inside it."""
    fetches = [(a, b) for a, b, n, _ in spans if n.endswith("fetch")]
    out = []
    for a, b, name, _ in spans:
        if name == PREFIX + "tick":
            inside = union([(max(x, a), min(y, b)) for x, y in fetches
                            if y > a and x < b])
            out.append((b - a) - sum(y - x for x, y in inside))
    return out


def queue_ms(spans: Sequence[Span]) -> Dict[int, float]:
    """rid -> the queue wait its first admission carries."""
    return {m["rid"]: m["queue_ms"] for _, _, n, m in spans
            if n == PREFIX + "admit.request" and "queue_ms" in m}


def idle_gaps(busy: Sequence[Interval], spans: Sequence[Span],
              lo: float, hi: float) -> List[Tuple[str, float]]:
    """(innermost span at the gap's middle, seconds) for every part of
    [lo, hi] that ``busy`` leaves uncovered, longest first."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    gaps = [g for g in gaps if g[1] > g[0]]
    # A sweep over the midpoints: the engine's spans nest on one thread,
    # so the innermost open span is the one that opened last.
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, open_, j = [], [], 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while j < len(order) and order[j][0] <= mid:
            open_.append(order[j])
            j += 1
        open_ = [s for s in open_ if s[1] >= mid]
        out.append((open_[-1][2] if open_ else "none", b - a))
    return sorted(out, key=lambda x: -x[1])


def summary(prof: dict, lo: Optional[float] = None,
            hi: Optional[float] = None) -> dict:
    """The reductions over [lo, hi] (default: from the first tick's start
    to the last tick's end). ``idle_s`` (idle seconds by phase) and
    ``longest_idle`` (the ten longest gaps) are there only where the
    trace has a device plane."""
    spans = prof["spans"]
    ticks = [s for s in spans if s[2] == PREFIX + "tick"]
    if lo is None:
        if not ticks:
            raise ValueError("the trace holds no serve.tick span")
        lo, hi = ticks[0][0], max(s[1] for s in ticks)
    inside = [s for s in spans if lo <= s[0] and s[1] <= hi]
    host = tick_host_s(inside)
    out = {"window_s": hi - lo, "ticks": len(host),
           "tick_host_ms": 1e3 * float(np.mean(host)) if host else None,
           "queue_ms": queue_ms(inside)}
    if prof["busy"]:
        gaps = idle_gaps(prof["busy"], spans, lo, hi)
        idle: Dict[str, float] = {}
        for name, d in gaps:
            idle[name] = idle.get(name, 0.0) + d
        out["idle_s"] = idle
        out["longest_idle"] = gaps[:10]
    return out
