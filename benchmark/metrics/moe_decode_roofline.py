"""The MoE decode program's share of its roofline, in the traced window:
the least time the chip needs for the work of each step over the device
time of the decode programs.  The work: bf16 weights once, the held
experts' at the share of them the steps touched (the window's
``moe_experts_hit`` / (steps x MoE layers x held experts)), the latents
of the positions attended, and the FLOPs of the active slots with the
routed experts at the picks that landed on held experts."""

from benchmark.peaks import floor_s


def read(run):
    t, e = run.trace, run.engine_delta
    if not t or not t["steps"] or not t["program_s"].get("decode_step") \
            or not e.get("moe_picks") or not e.get("steps"):
        return None
    d, fam = run.dims, run.fam
    share = e["moe_experts_hit"] / (e["steps"] * d.n_moe * d.n_held)
    picks = d.topk * e["moe_local_picks"] / e["moe_picks"]
    need = sum(floor_s(fam.decode_flops(d, s["n"], s["ctx"], picks),
                       fam.decode_bytes(d, s["n"], s["ctx"], share),
                       run.device_kind)[0] for s in t["steps"])
    return 100.0 * need / t["program_s"]["decode_step"]
