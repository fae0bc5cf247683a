"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

What it reads, per device plane (``/device:TPU:<n>``): the ``XLA Ops``
line, one event per operation run on the device (named by the HLO
instruction; loop and call ops, which span the ops they run, are left
out), and the ``XLA Modules`` line, one event per executable launch.
From the host planes it takes the benchmark's own
``jax.profiler.TraceAnnotation`` spans, whose names start with
``bench.``; ``bench.window`` marks the traced window.

What it gives: the busy union of the device's operations inside the
window, the idle gaps between them (each labelled by the innermost
benchmark span the host was in at the gap's middle), the device time of
each operation name and each executable's launches.
"""

from __future__ import annotations

import glob
import heapq
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

Interval = Tuple[float, float]
WINDOW = "bench.window"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def labels(gs: Sequence[Interval],
           spans: Sequence[Tuple[float, float, str]]) -> List[str]:
    """For each of the gaps ``gs`` (in time order, as ``gaps`` gives
    them), the name of the shortest host span around its middle, or
    "none". One sweep over the spans by start, keeping those still open:
    a window holds millions of gaps and thousands of spans."""
    order = sorted(spans)
    open_: List[Tuple[float, float, str]] = []    # heap by end
    out, i = [], 0
    for g in gs:
        mid = 0.5 * (g[0] + g[1])
        while i < len(order) and order[i][0] <= mid:
            a, b, n = order[i]
            heapq.heappush(open_, (b, b - a, n))
            i += 1
        while open_ and open_[0][0] < mid:
            heapq.heappop(open_)
        out.append(min((d, n) for _, d, n in open_)[1] if open_ else "none")
    return out


CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%copy.107 = bf16[...] copy(...)`` -> ``copy.107``."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text


def module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(run_dir: str) -> dict:
    """Raw events (seconds) of the newest ``.xplane.pb`` under a
    ``jax.profiler`` output directory."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{run_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {run_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"].extend(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                        for e in line.events)
                elif line.name == "XLA Ops":
                    # A loop or call op spans the ops it runs: keep leaves.
                    for e in line.events:
                        name = op_name(e.name)
                        if not name.startswith(CONTAINERS):
                            dev["ops"].append(
                                (e.start_ns * 1e-9, e.end_ns * 1e-9, name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def plane_id(name: str) -> int:
    """``/device:TPU:3`` -> 3, the JAX device id."""
    return int(name.split(":")[-1])


def reduce(raw: dict, keep: Optional[Set[int]] = None) -> dict:
    """Busy and idle time, op and executable times, inside the window.

    Only the planes of the devices with ids in ``keep`` count, where it
    is given: those of the cell's chips. Times of ops and executables are
    per device plane; ``busy_s`` is the mean over the planes, and idle
    gaps are those of the first plane."""
    wins = [(a, b) for a, b, n in raw["host"] if n == WINDOW]
    names = sorted((n for n in raw["devices"]
                    if keep is None or plane_id(n) in keep), key=plane_id)
    if not wins or not names:
        raise ValueError("trace holds no window span or no device plane")
    lo, hi = wins[0]
    spans = [s for s in raw["host"] if s[2] != WINDOW]
    per_dev = []
    for name in names:
        dev = raw["devices"][name]
        ops = [(a, b, n) for a, b, n in dev["ops"] if lo <= a and b <= hi]
        busy = union(clip([(a, b) for a, b, _ in dev["ops"]], lo, hi))
        idle = gaps(busy, lo, hi)
        op_s: Dict[str, float] = {}
        for a, b, n in ops:
            op_s[n] = op_s.get(n, 0.0) + (b - a)
        mods: Dict[str, List[float]] = {}
        for a, b, n in dev["modules"]:
            if lo <= a and b <= hi:
                mods.setdefault(module_name(n), []).append(b - a)
        per_dev.append({
            "name": name,
            "busy_s": sum(b - a for a, b in busy),
            "ops_s": op_s,
            "modules_s": mods,
            "idle_gaps": sorted(zip(labels(idle, spans),
                                    (g[1] - g[0] for g in idle)),
                                key=lambda x: -x[1]),
        })
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
        "devices": per_dev,
    }


def breakdown(red: dict, n: int = 10) -> dict:
    """Top device ops by time and the longest idle gaps, first device."""
    dev = red["devices"][0]
    ops = sorted(dev["ops_s"].items(), key=lambda x: -x[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in dev["idle_gaps"][:n]]}
