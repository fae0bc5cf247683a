"""What a run records, and the arithmetic the metric readers share.

A reader (``bench/metrics/<metric>.py``) gets one ``Run`` and returns a
number, or None where the run holds nothing for it to read. Times are
seconds on the host's clock from the start of the traffic; the window is
``[t_open, t_close]``, both on tick boundaries, and the requests due in it
are those due in ``[due_lo, due_hi)``.

Everything here comes from the benchmark's own records: when each request
was due, got a slot and got each token. The prefill work of the window is
each prompt's whole work (fixed by its length, however the program
chunks it), spread over the time from the start of the tick that gave the
request its slot to its first token, and taken in the share of that time
that lies in the window.
"""

from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Tuple

import numpy as np

from bench import work


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    rid: int
    due: float
    plen: int
    max_new: int
    slot_a: Optional[float] = None          # start and end of the first
    slot_t: Optional[float] = None          # tick holding a slot
    times: List[float] = dataclasses.field(default_factory=list)  # tokens


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    mix: dict
    peak: dict                              # the cell's chips together
    batch: int
    setup_s: float
    t_open: float
    t_close: float
    due_lo: float
    due_hi: float
    requests: List[Rec]
    ticks: List[Tuple[float, float, int]]   # (start, end, decode tokens)
    trace: Optional[dict] = None            # bench.trace.reduce output

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def inside(self, t: float) -> bool:
        return self.t_open < t <= self.t_close


def pct(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) \
        if len(xs) else None


def due_in_window(run: Run) -> List[Rec]:
    return [r for r in run.requests if run.due_lo <= r.due < run.due_hi]


def done_in_window(run: Run) -> List[Rec]:
    return [r for r in run.requests
            if len(r.times) == r.max_new and run.inside(r.times[-1])]


def decode_ctxs(run: Run) -> List[int]:
    """Keys each decoded token of the window attended: token j >= 1 of a
    request is decoded from position plen + j - 1 and sees plen + j."""
    return [r.plen + j for r in run.requests
            for j, t in enumerate(r.times) if j and run.inside(t)]


def prefills(run: Run) -> List[Tuple[int, float]]:
    """(prompt length, share of its prefill inside the window) of every
    request whose prefill overlaps the window."""
    out = []
    for r in run.requests:
        if r.slot_a is None or not r.times:
            continue
        a, b = r.slot_a, r.times[0]
        inside = min(b, run.t_close) - max(a, run.t_open)
        if inside > 0:
            out.append((r.plen, inside / (b - a)))
    return out


def prefill_flops(run: Run) -> float:
    """Model operations of the window's share of every prefill."""
    return sum(share * work.chunk_flops(run.config, 0, n, True)
               for n, share in prefills(run))


def window_flops(run: Run) -> float:
    """Model operations of every token the window processed."""
    return (sum(work.token_flops(run.config, n) for n in decode_ctxs(run))
            + prefill_flops(run))


def device(run: Run) -> Optional[dict]:
    return run.trace["devices"][0] if run.trace else None


def module_s(run: Run, name: str) -> List[float]:
    """Device seconds of each launch of the executable ``name``, on the
    first of the cell's chips: on a mesh every chip runs each launch."""
    dev = device(run)
    return list(dev["modules_s"].get(name, [])) if dev else []


def op_s(run: Run, pattern: str) -> float:
    """Device seconds of the ops whose names match ``pattern``, the mean
    over the cell's chips."""
    if not run.trace:
        return 0.0
    rx = re.compile(pattern)
    devs = run.trace["devices"]
    return sum(v for d in devs for k, v in d["ops_s"].items()
               if rx.search(k)) / len(devs)


def share(need_s: float, took_s: float) -> Optional[float]:
    """``need_s`` as a percentage of ``took_s``; None when nothing ran."""
    return 100.0 * need_s / took_s if took_s > 0 and need_s > 0 else None
