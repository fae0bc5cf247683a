"""Whole step: model operations of every token processed in the window
(decoded tokens and prefill rows), over the window, as a share of peak
(%)."""
from bench import readers


def read(run):
    if not run.trace:
        return None
    return readers.share(readers.window_flops(run) / run.peak["flops_per_s"],
                         run.trace["window_s"])
