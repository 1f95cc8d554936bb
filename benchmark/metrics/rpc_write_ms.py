"""Host time a decode step spends handing its tokens to their streams
(ServingEngine._emit), summed per step, mean over the steps of the
window."""


def read(run):
    if run.spans is None:
        return None
    steps = run.spans.of("decode", run.t0, run.t1)
    emits = [e for e in run.spans.of("emit", run.t0, run.t1)
             if e[3].get("step")]
    if not steps or not emits:
        return None
    return sum(e[2] - e[1] for e in emits) / len(steps) / 1e6
