"""The whole serving step's share of the chip's bf16 peak in the traced
window: the FLOPs the model needs for the prefills and the active slots'
decode steps in it, over the window times the peak."""

from benchmark.peaks import peaks


def read(run):
    t = run.trace
    if not t or not t["devices"] or not (t["steps"] or t["prefills"]):
        return None
    d, fam = run.dims, run.fam
    flops = sum(fam.decode_flops(d, s["n"], s["ctx"]) for s in t["steps"])
    flops += sum(fam.prefill_flops(d, p["s"]) for p in t["prefills"])
    return 100.0 * flops / (t["window_s"]
                            * peaks(run.device_kind)["bf16_flops_per_s"])
