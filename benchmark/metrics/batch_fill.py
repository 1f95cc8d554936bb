"""Tokens the decode steps emitted over the window per decode slot and
step (engine.stats(): (tokens_out - prefills) / (steps x slots)); the
first token of each admission comes from its prefill, not a step."""


def read(run):
    steps = run.engine_delta.get("steps", 0)
    if not steps:
        return None
    decoded = run.engine_delta["tokens_out"] - run.engine_delta["prefills"]
    return decoded / (steps * run.n_slots)
