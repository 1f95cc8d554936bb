"""95th percentile of every gap between two consecutive tokens of one
stream whose later token arrived inside the window, over all streams."""

from benchmark.stats import pct


def read(run):
    gaps = [(b - a) / 1e6 for r in run.requests
            for a, b in zip(r["times"], r["times"][1:])
            if run.t0 <= b < run.t1]
    return pct(gaps, 0.95) if gaps else None
