"""The decode program's share of its roofline, in the traced window: the
least time the chip needs for the work of each step (bf16 weights once,
the K/V of the positions attended, the FLOPs of the active slots) over
the device time of the decode programs."""

from benchmark.peaks import floor_s


def read(run):
    t = run.trace
    if not t or not t["steps"] or not t["program_s"].get("decode_step"):
        return None
    d, fam = run.dims, run.fam
    need = sum(floor_s(fam.decode_flops(d, s["n"], s["ctx"]),
                       fam.decode_bytes(d, s["n"], s["ctx"]),
                       run.device_kind)[0] for s in t["steps"])
    return 100.0 * need / t["program_s"]["decode_step"]
