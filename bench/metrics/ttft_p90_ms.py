"""90th percentile, over every request due in the window, of the time
from its due time to its first token (ms)."""
from bench import readers


def read(run):
    rs = readers.due_in_window(run)
    return readers.pct([1e3 * (r.times[0] - r.due) for r in rs if r.times],
                       90)
