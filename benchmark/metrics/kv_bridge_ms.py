"""Mean time per admission in the window from the prefill's outputs
ready to install() returned: K/V to host bytes, the block plane's
alloc, migrate and fetch, back to arrays, and the install into the
decode cache."""


def read(run):
    if run.spans is None:
        return None
    b = run.spans.of("bridge", run.t0, run.t1)
    return sum(e[2] - e[1] for e in b) / len(b) / 1e6 if b else None
