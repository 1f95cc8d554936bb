"""Host time the decode loop spends charging KV blocks as sequences grow,
per decode step (engine.stats(): kv_grow_ns / steps, over the window)."""


def read(run):
    d = run.engine_delta
    if "kv_grow_ns" not in d or not d.get("steps"):
        return None
    return d["kv_grow_ns"] / d["steps"] / 1e6
