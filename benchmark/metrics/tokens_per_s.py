"""Every token a client received inside the window, over the window."""


def read(run):
    n = sum(1 for r in run.requests for t in r["times"]
            if run.t0 <= t < run.t1)
    return n / run.seconds
