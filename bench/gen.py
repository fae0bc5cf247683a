"""Traffic generator: one general reader of the mix files in ``bench/traffic``.

A mix file fixes the shape of the load (arrival process, rate, length
ranges); the seed only picks the token ids and the order. Every seed gets
the same *set* of sizes and gaps: each block of ``block`` requests takes
the lengths and gaps at the block's evenly spaced quantiles of the
distributions, and the seed shuffles them within the block. So two seeds
offer the same work in a different order, and the spread between runs is
the system's, not the draw's.

Poisson blocks last exactly ``block_s`` seconds (the quantile gaps are
scaled to that sum, a rate of ``block / block_s``) and each block's
requests are due in ``[k * block_s, (k + 1) * block_s)``. A window that
starts and ends on block boundaries therefore holds the same requests'
sizes and gaps whatever the seed.

The length and gap distributions are those of ``serve/traffic.py``:
log-uniform lengths in ``[lo, hi]`` (``exp(uniform(log lo, log hi))``,
rounded) and exponential gaps (a Poisson process).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due_s: float          # seconds after the traffic starts
    prompt: np.ndarray    # int32 token ids
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose of one seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, stream])


def _log_uniform(lo: int, hi: int, u: np.ndarray) -> np.ndarray:
    return np.rint(np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
                   ).astype(np.int64)


def _quantiles(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation((np.arange(n) + 0.5) / n)


def requests(mix: dict, seed: int, vocab: int,
             horizon_s: float = 0.0) -> List[Req]:
    """The offered requests, sorted by due time.

    ``arrivals: "backlog"`` offers ``requests`` requests due at 0.
    ``arrivals: "poisson"`` offers blocks of ``block_s`` seconds until a
    block starts past ``horizon_s``."""
    rng = rng_for(seed, 1)
    block = int(mix["block"])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    cap = int(mix["max_tokens"])
    out: List[Req] = []
    start = 0.0
    while True:
        prompts = _log_uniform(p["lo"], p["hi"], _quantiles(block, rng))
        outs = _log_uniform(o["lo"], o["hi"], _quantiles(block, rng))
        if mix["arrivals"] == "poisson":
            gaps = -np.log1p(-_quantiles(block, rng))
            gaps *= mix["block_s"] / gaps.sum()
            dues = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        elif mix["arrivals"] == "backlog":
            dues = np.zeros(block)
        else:
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        for plen, olen, due in zip(prompts, outs, dues):
            plen = int(min(plen, cap - 1))
            out.append(Req(
                rid=len(out), due_s=float(due),
                prompt=rng.integers(0, vocab, size=plen, dtype=np.int32),
                max_new=int(max(1, min(olen, cap - plen)))))
        if mix["arrivals"] == "backlog":
            if len(out) >= int(mix["requests"]):
                return out[:int(mix["requests"])]
        else:
            start = (len(out) // block) * float(mix["block_s"])
            if start > horizon_s:
                return out
