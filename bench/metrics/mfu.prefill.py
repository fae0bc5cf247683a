"""Whole step: model operations of the window's share of every prefill
over the device time of the prefill-chunk executable, as a share of peak
(%)."""
from bench import readers


def read(run):
    took = sum(readers.module_s(run, "jit_prefill_chunk"))
    return readers.share(readers.prefill_flops(run)
                         / run.peak["flops_per_s"], took)
