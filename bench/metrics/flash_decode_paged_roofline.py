"""Kernels: the least time the chip needs for paged decode attention
over the live contexts of every token decoded in the window (by
`bench/work.py`), over the device time of the `flash_decode_paged`
kernel (%)."""
from bench import readers, work


def read(run):
    flops, nbytes = work.decode_kernel(run.config, readers.decode_ctxs(run))
    need, _ = work.roofline_s(flops, nbytes, run.peak)
    return readers.share(need, readers.op_s(run, "flash_decode_paged"))
