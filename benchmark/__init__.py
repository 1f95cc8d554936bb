"""On-chip benchmark of the brpc_tpu serving path.

Everything a cell needs is found by name from ``BENCHMARK.json``:

    configs/<config>.json     model sizes; ``family`` names models/<family>.py
    models/<family>.py        weights from the seed, the plain float32
                              reference, FLOP and byte counts, the program's
                              model config
    traffic/<traffic>.json    parameters for the one generator (traffic.py)
    metrics/<metric>.py       one reader per metric:
                              ``read(run) -> float | None``

``run.py`` is the command; ``harness.py`` drives one run; ``loadgen.py`` is
the client process, which never imports JAX.  Importing this package
imports nothing else.
"""
