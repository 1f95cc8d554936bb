"""From a profiler trace to the numbers the device metrics need.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three kinds of event, each as ``[plane, line, name, start_ns,
duration_ns, stats]``:

- device operations (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane),
- whole-program executions (line ``XLA Modules`` of the same planes),
- the benchmark's own host spans (``bench/<name>``, with their stats).

``reduce`` then gives, inside the span ``bench/window``:

- ``busy_s``: the union of the device-operation intervals, averaged over
  the devices that ran any;
- ``program_s``: device time of the programs of each kind of host span
  (a program, by its XLA module name, belongs to the innermost span that
  most of its executions run in; the benchmark blocks on the program
  inside that span);
- ``steps`` and ``prefills``: the stats of those host spans;
- ``top_ops`` and ``idle_by_activity``: the device operations that took
  most time, and the idle time by the innermost host span that was open
  in it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW = "window"
NO_SPAN = "outside_spans"


def load_events(path: str) -> List[list]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for pl in pd.planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        for ln in pl.lines:
            if device and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in ln.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append([pl.name, ln.name, ev.name, int(ev.start_ns),
                            int(ev.duration_ns),
                            {k: v for k, v in ev.stats} if not device
                            else {}])
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w0: int, w1: int):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def clock_offset(modules: List[Tuple[int, int]], spans: List[tuple],
                 kinds=("decode_step", "prefill"),
                 reach_ns: int = 5_000_000, step_ns: int = 50_000) -> int:
    """What to add to device times to put them on the host's clock.  The
    benchmark blocks on each decode and prefill program inside its host
    span, so the right shift puts the most device time of whole program
    executions inside such spans; the middle of the shifts that do is
    taken."""
    ivs = sorted((s, e) for n, s, e, _ in spans if n in kinds)
    if not ivs or not modules:
        return 0
    starts = [s for s, _ in ivs]

    def inside(shift: int) -> int:
        n = 0
        for ms, md in modules:
            i = bisect.bisect_right(starts, ms + shift) - 1
            if i >= 0 and ms + shift + md <= ivs[i][1]:
                n += md
        return n

    grid = list(range(-reach_ns, reach_ns + 1, step_ns))
    score = [inside(g) for g in grid]
    best = [g for g, sc in zip(grid, score) if sc == max(score)]
    return (best[0] + best[-1]) // 2


def reduce(events: List[list], top: int = 10) -> Dict:
    spans = [(ev[2][len(SPAN_PREFIX):], ev[3], ev[3] + ev[4], ev[5])
             for ev in events if not DEVICE_PLANE.match(ev[0])]
    windows = [(s, e) for n, s, e, _ in spans if n == WINDOW]
    if not windows:
        raise ValueError("trace holds no bench/window span")
    w0, w1 = windows[0]
    spans = [sp for sp in spans if sp[0] != WINDOW and sp[2] > w0
             and sp[1] < w1]

    dev = [ev for ev in events if DEVICE_PLANE.match(ev[0])]
    shift = clock_offset([(ev[3], ev[4]) for ev in dev
                          if ev[1] == MODULES_LINE], spans)
    ops_by_dev: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    op_time: Dict[str, int] = defaultdict(int)
    modules = []
    for plane, line, name, s, d, _ in dev:
        s += shift
        c = _clip(s, s + d, w0, w1)
        if line == OPS_LINE:
            if c:
                ops_by_dev[plane].append(c)
                op_time[name.split("{")[0][:120]] += c[1] - c[0]
        elif w0 <= s < w1:
            modules.append((s, d, name))

    unions = {p: _union(iv) for p, iv in ops_by_dev.items()}
    busy = [sum(e - s for s, e in u) for u in unions.values()]
    busy_ns = sum(busy) / len(busy) if busy else 0.0

    # each program (an XLA module, named with its fingerprint) belongs to
    # the kind of host span that most of its executions fall in, by their
    # midpoints on the host's clock
    by_start = sorted((s, e, n) for n, s, e, _ in spans if e > s)
    starts = [s for s, _, _ in by_start]

    def innermost(t: int) -> str:
        """The latest-starting span still open at t; spans on the one
        decode-loop thread nest, so a short walk back finds it."""
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(-1, i - 256), -1):
            if by_start[j][1] > t:
                return by_start[j][2]
        return NO_SPAN

    votes: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for ms, md, name in modules:
        votes[name][innermost(ms + md // 2)] += 1
    owner = {name: max(v.items(), key=lambda kv: kv[1])[0]
             for name, v in votes.items()}
    program_ns: Dict[str, int] = defaultdict(int)
    for ms, md, name in modules:
        program_ns[owner[name]] += md

    # idle gaps, by the innermost open host span at their midpoint
    idle: Dict[str, int] = defaultdict(int)
    u = next(iter(unions.values()), [])
    cursor = w0
    gaps = []
    for s, e in u + [(w1, w1)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    for gs, ge in gaps:
        idle[innermost((gs + ge) // 2)] += ge - gs

    return {
        "window_s": (w1 - w0) / 1e9,
        "clock_offset_s": shift / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(unions),
        "program_s": {k: v / 1e9 for k, v in program_ns.items()},
        "steps": [st for n, s, e, st in spans
                  if n == "decode_step" and s >= w0 and e <= w1],
        "prefills": [st for n, s, e, st in spans
                     if n == "prefill" and s >= w0 and e <= w1],
        "top_ops": [[k, v / 1e9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_activity": [[k, v / 1e9] for k, v in
                             sorted(idle.items(), key=lambda kv: -kv[1])
                             [:top]],
    }


def excerpt(events: List[list], seconds: float) -> List[list]:
    """The first `seconds` of the window: every event that starts in it,
    and the window span cut to it (a small trace to test against)."""
    w0 = next(ev[3] for ev in events if ev[2] == SPAN_PREFIX + WINDOW)
    w1 = w0 + int(seconds * 1e9)
    out = [ev for ev in events if w0 <= ev[3] < w1
           and ev[2] != SPAN_PREFIX + WINDOW]
    return [["/host:CPU", "python", SPAN_PREFIX + WINDOW, w0, w1 - w0, {}]] \
        + out


def summary(events: List[list]) -> Dict:
    """Planes, lines, event counts and the commonest names, to look at."""
    count: Dict[str, int] = defaultdict(int)
    names: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for plane, line, name, *_ in events:
        count[f"{plane} | {line}"] += 1
        names[f"{plane} | {line}"][name] += 1
    return {k: {"events": count[k],
                "top": sorted(v.items(), key=lambda kv: -kv[1])[:15]}
            for k, v in names.items()}


def raw_summary(path: str) -> Dict:
    """Every plane and line of the trace file with its event count and
    commonest names, the benchmark's filters aside."""
    from jax.profiler import ProfileData
    out: Dict = {}
    for pl in ProfileData.from_file(path).planes:
        for ln in pl.lines:
            names: Dict[str, int] = defaultdict(int)
            for ev in ln.events:
                names[ev.name] += 1
            out[f"{pl.name} | {ln.name}"] = sorted(
                names.items(), key=lambda kv: -kv[1])[:8]
    return out
