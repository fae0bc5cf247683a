"""90th percentile, over the requests that complete in the window, of each
request's mean time per output token after the first (ms)."""
from bench import readers


def read(run):
    rs = [r for r in readers.done_in_window(run) if len(r.times) > 1]
    return readers.pct([1e3 * (r.times[-1] - r.times[0]) / (len(r.times) - 1)
                        for r in rs], 90)
