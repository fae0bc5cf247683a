"""Kernels: the least time the chip needs for the causal attention of the
window's share of every prefill (each prompt attended once over its live
context, by `bench/work.py`), over the device time of the
`flash_attention_paged` kernel (%)."""
from bench import readers, work


def read(run):
    flops = nbytes = 0.0
    for n, share in readers.prefills(run):
        f, b = work.prefill_kernel(run.config, [(0, n)])
        flops, nbytes = flops + share * f, nbytes + share * b
    need, _ = work.roofline_s(flops, nbytes, run.peak)
    return readers.share(need, readers.op_s(run, "flash_attention_paged"))
