"""Device steps: mean device time of one launch of the prefill-chunk
executable (`jit_prefill_chunk`) in the window (ms)."""
from bench import readers


def read(run):
    s = readers.module_s(run, "jit_prefill_chunk")
    return 1e3 * sum(s) / len(s) if s else None
