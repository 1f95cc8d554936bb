#!/usr/bin/env python3
"""The device's idle time split by the serving loop's own phases.

``ServingEngine`` names each phase of its decode loop in the profiler
trace as a ``serving/<phase>`` host span (``brpc_tpu/serving/engine.py``).
``split`` takes the device operations and the ``bench/window`` span that
``trace.load_events`` keeps, moves the device events onto the host's
clock by the offset that ``trace.reduce`` found, and gives every idle
interval of the window, by overlap, to the innermost ``serving/`` span
open in each part of it; what no such span covers is ``no_span_s``;
``steps`` counts the decode steps that start in the window.

Run as the benchmark's command, it makes the same run and, in a traced
run, prints one more line before the result:
``{"phase": "phases", "idle_s": {...}, "no_span_s": ..., "idle_total_s":
..., "steps": ...}``::

    python3 benchmark/phases.py --workload <name> --seed <n> \
        --seconds <s> --trace 1 [--excerpt PATH]

``--excerpt`` keeps the first tenth of a second of the traced window, with
the ``serving/`` spans, as JSON (a small trace to test against).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "serving/"


def load_spans(path: str) -> List[list]:
    """The ``serving/`` host events of an ``.xplane.pb``, in the form of
    ``trace.load_events``: ``[plane, line, name, start_ns, duration_ns,
    stats]``."""
    from jax.profiler import ProfileData
    out = []
    for pl in ProfileData.from_file(path).planes:
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append([pl.name, ln.name, ev.name, int(ev.start_ns),
                                int(ev.duration_ns),
                                {k: v for k, v in ev.stats}])
    return out


def innermost(spans: List[Tuple[int, int, str]]) -> List[Tuple[int, int,
                                                               str]]:
    """Disjoint pieces of time, each with the innermost span open in it.
    The spans come from one thread, so they nest; a child that outlasts
    its parent is cut at the parent's end."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []     # (end, name), innermost last
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        close_until(s)
        if stack and s > cursor:
            out.append((cursor, s, stack[-1][1]))
        cursor = max(cursor, s)
        if stack:
            e = min(e, stack[-1][0])
        if e > s:
            stack.append((e, name))
    close_until(float("inf"))
    return out


def split(events: List[list], spans: List[list],
          clock_offset_s: float) -> Dict:
    """Idle time of the traced window by innermost ``serving/`` span.

    ``events`` as ``trace.load_events`` gives them, ``spans`` as
    ``load_spans`` does, ``clock_offset_s`` from ``trace.reduce``."""
    from benchmark import trace as trace_mod
    w0, w1 = next((ev[3], ev[3] + ev[4]) for ev in events
                  if ev[2] == trace_mod.SPAN_PREFIX + trace_mod.WINDOW)
    shift = round(clock_offset_s * 1e9)
    busy = trace_mod._union([
        c for c in (trace_mod._clip(ev[3] + shift, ev[3] + shift + ev[4],
                                    w0, w1)
                    for ev in events
                    if trace_mod.DEVICE_PLANE.match(ev[0])
                    and ev[1] == trace_mod.OPS_LINE) if c])
    idle, cursor = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)

    pieces = innermost([(sp[3], sp[3] + sp[4], sp[2][len(SPAN_PREFIX):])
                        for sp in spans])
    by: Dict[str, int] = defaultdict(int)
    covered, j = 0, 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            ov = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if ov > 0:
                by[pieces[k][2]] += ov
                covered += ov
            k += 1
    total = sum(b - a for a, b in idle)
    return {"idle_s": {k: v / 1e9 for k, v in
                       sorted(by.items(), key=lambda kv: -kv[1])},
            "no_span_s": (total - covered) / 1e9,
            "idle_total_s": total / 1e9,
            "steps": sum(1 for sp in spans
                         if sp[2] == SPAN_PREFIX + "step"
                         and w0 <= sp[3] < w1)}


def excerpt(events: List[list], spans: List[list],
            seconds: float) -> List[list]:
    """``trace.excerpt`` of the window, with the ``serving/`` spans that
    start inside it."""
    from benchmark import trace as trace_mod
    out = trace_mod.excerpt(events, seconds)
    w0 = out[0][3]
    w1 = w0 + out[0][4]
    return out + [[sp[0], sp[1], sp[2], sp[3], sp[4],
                   {k: str(v) for k, v in sp[5].items()}]
                  for sp in spans if w0 <= sp[3] < w1]


@contextlib.contextmanager
def reporting(keep: Optional[str] = None):
    """While open, the harness's traced run also prints the ``phases``
    line and, with ``keep``, writes the excerpt there."""
    from benchmark import trace as trace_mod
    load_events, reduce = trace_mod.load_events, trace_mod.reduce
    seen: Dict[str, List[list]] = {}

    def load_and_keep(path):
        seen["spans"] = load_spans(path)
        return load_events(path)

    def reduce_and_split(events, *a, **kw):
        red = reduce(events, *a, **kw)
        spans = seen.pop("spans", [])
        print(json.dumps({"phase": "phases", **split(
            events, spans, red["clock_offset_s"])}), flush=True)
        if keep:
            with open(keep, "w") as f:
                json.dump({"events": excerpt(events, spans, 0.1)}, f)
        return red

    trace_mod.load_events, trace_mod.reduce = load_and_keep, \
        reduce_and_split
    try:
        yield
    finally:
        trace_mod.load_events, trace_mod.reduce = load_events, reduce


def main(argv=None) -> int:
    """``run.py``'s command, with the ``phases`` line in a traced run."""
    argv = list(sys.argv[1:] if argv is None else argv)
    keep = None
    if "--excerpt" in argv:
        i = argv.index("--excerpt")
        keep = argv[i + 1]
        del argv[i:i + 2]
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(bench_dir))
    sys.path.insert(0, bench_dir)
    import run                                      # benchmark/run.py
    with reporting(keep):
        return run.main(argv)


if __name__ == "__main__":
    # leave as run.py does: the native runtime's threads are not waited for
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
