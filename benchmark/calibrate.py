#!/usr/bin/env python3
"""Readings the benchmark's limits and rates are set from; run by hand on
the chip, never by the benchmark's own runs.  Every run shares one process
and its compilation cache.

    # the program's widest logit gap and the control's, seed by seed
    python benchmark/calibrate.py --workload xl_decode --seeds 1,2,3 \
        --seconds 15 --control
    # a cell with another slot count; an open-loop cell at several
    # offered rates (the knee sweep) takes --rates 2,4,8
    python benchmark/calibrate.py --workload small_sharegpt --seeds 5 \
        --seconds 15 --slots 32
    # a traced run whose trace events are kept as JSON
    python benchmark/calibrate.py --workload small_sharegpt --seeds 7 \
        --seconds 10 --trace-out chiprun_out/trace.json

One JSON line per run: the workload, seed, rate, and the run's result.
"""

from __future__ import annotations

import time

T_START = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--trace-out", default="")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark.run import build_native
    build_native()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from benchmark import harness
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t0 = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        for rate in rates:
            out = harness.run(
                args.workload, seed, args.seconds, bool(args.trace_out),
                t0, ROOT, control=args.control,
                traffic_overrides={"rate": rate} if rate else None,
                serving_overrides={"slots": args.slots} if args.slots
                else None,
                trace_out=args.trace_out or None)
            print(json.dumps({"calibrate": args.workload, "seed": seed,
                              "rate": rate, "result": out}), flush=True)
            t0 = time.monotonic_ns()
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — any failure is a non-zero exit
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
