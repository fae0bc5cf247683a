"""Scheduler: 90th percentile, over requests due in the window, of the
time from due to the end of the first tick in which the request holds a
decode slot (ms)."""
from bench import readers


def read(run):
    rs = readers.due_in_window(run)
    return readers.pct([1e3 * (r.slot_t - r.due) for r in rs
                        if r.slot_t is not None], 90)
