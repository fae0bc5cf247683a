"""Scheduler and page pool: slots that decoded a token, over the batch,
averaged over the window's ticks (%)."""


def read(run):
    ticks = [n for a, b, n in run.ticks if run.inside(b)]
    return 100.0 * sum(ticks) / (len(ticks) * run.batch) if ticks else None
