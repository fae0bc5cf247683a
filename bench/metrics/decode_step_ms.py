"""Device steps: mean device time of one launch of the decode-step
executable (`jit_step`) in the window (ms)."""
from bench import readers


def read(run):
    s = readers.module_s(run, "jit_step")
    return 1e3 * sum(s) / len(s) if s else None
