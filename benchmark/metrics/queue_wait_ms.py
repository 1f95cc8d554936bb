"""Mean time an admitted request waited in the scheduler's queue, from
its stream accepted to its admission (engine.stats(): queue_wait_ns /
admitted, over the window)."""


def read(run):
    d = run.engine_delta
    if "queue_wait_ns" not in d or not d.get("admitted"):
        return None
    return d["queue_wait_ns"] / d["admitted"] / 1e6
