"""The whole MoE serving step's share of the chip's bf16 peak in the
traced window: the FLOPs the model needs for the prefills and the active
slots' decode steps in it, with the routed experts at the window's picks
that landed on held experts per token (``moe_local_picks`` /
``moe_picks`` x top-k), over the window times the peak."""

from benchmark.peaks import peaks


def read(run):
    t, e = run.trace, run.engine_delta
    if not t or not t["devices"] or not (t["steps"] or t["prefills"]) \
            or not e.get("moe_picks"):
        return None
    d, fam = run.dims, run.fam
    picks = d.topk * e["moe_local_picks"] / e["moe_picks"]
    flops = sum(fam.decode_flops(d, s["n"], s["ctx"], picks)
                for s in t["steps"])
    flops += sum(fam.prefill_flops(d, p["s"], picks) for p in t["prefills"])
    return 100.0 * flops / (t["window_s"]
                            * peaks(run.device_kind)["bf16_flops_per_s"])
