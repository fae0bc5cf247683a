"""Output tokens emitted in the window over the window's length."""


def read(run):
    n = sum(1 for r in run.requests for t in r.times if run.inside(t))
    return n / run.window_s if n else None
