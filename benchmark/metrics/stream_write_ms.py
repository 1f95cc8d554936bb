"""Host time the decode loop spends writing tokens to their streams, per
decode step (engine.stats(): emit_write_ns / steps, over the window); each
admission's first token is written in it too."""


def read(run):
    d = run.engine_delta
    if "emit_write_ns" not in d or not d.get("steps"):
        return None
    return d["emit_write_ns"] / d["steps"] / 1e6
