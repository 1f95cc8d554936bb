#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the native core from this checkout before JAX is imported, keeps
JAX's compilation cache in ``.jax_cache/`` of the checkout, refuses any
platform but TPU, and prints as its last line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
which also end standard error.  Earlier lines report the phases.  A run
that cannot finish exits non-zero with no result line.
"""

from __future__ import annotations

import time

SETUP_T0 = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def build_native() -> None:
    script = os.path.join(ROOT, "native", "build.sh")
    if not os.path.exists(script):
        raise SystemExit(f"benchmark: {script} not found: run from a "
                         "checkout of the repository")
    r = subprocess.run(["bash", script], capture_output=True, text=True)
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-4000:] + r.stderr[-4000:])
        raise SystemExit(f"benchmark: native build failed "
                         f"(rc={r.returncode})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    build_native()                         # before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from benchmark import harness
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), SETUP_T0, ROOT)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # leave through os._exit on every path: the native runtime's fiber
    # workers and an init stuck past its watchdog are not waited for by
    # interpreter teardown
    try:
        code = main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(e.code, file=sys.stderr)
        code = 0 if e.code in (None, 0) else 2
    except BaseException:  # noqa: BLE001 — any failure is a non-zero exit
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
