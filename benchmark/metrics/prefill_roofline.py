"""The prefill program's share of its roofline, in the traced window."""

from benchmark.peaks import floor_s


def read(run):
    t = run.trace
    if not t or not t["prefills"] or not t["program_s"].get("prefill"):
        return None
    d, fam = run.dims, run.fam
    need = sum(floor_s(fam.prefill_flops(d, p["s"]),
                       fam.prefill_bytes(d, p["s"]), run.device_kind)[0]
               for p in t["prefills"])
    return 100.0 * need / t["program_s"]["prefill"]
