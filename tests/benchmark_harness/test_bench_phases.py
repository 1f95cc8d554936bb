"""The serving loop's own phases as the benchmark reads them: the idle
split by overlap across ``serving/`` spans (``benchmark/phases.py``) and
the readers of the engine's phase counters."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchutil  # noqa: E402

sys.path.insert(0, benchutil.ROOT)
from benchmark import phases, trace  # noqa: E402
from benchmark.spec import load_module  # noqa: E402

DEV = "/device:TPU:0"
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_phases_small.json")
READERS = ("queue_wait_ms", "stream_write_ms", "kv_grow_ms")


def _window(start, dur):
    return ["/host:CPU", "python", "bench/window", start, dur, {}]


def _span(name, start, dur):
    return ["/host:CPU", "serving-decode", "serving/" + name, start, dur, {}]


def _op(s, d):
    return [DEV, "XLA Ops", "fusion", s, d, {}]


def test_idle_split_by_overlap():
    """A window of 100 ns.  The device runs [0, 10) and [40, 50); a step
    [5, 60) holds dispatch [5, 12), sync [12, 45) and emit [45, 60);
    an idle gap [10, 40) crosses dispatch and sync, the gap [50, 100)
    crosses emit and then no span."""
    ev = [_window(0, 100), _op(0, 10), _op(40, 10)]
    spans = [_span("step", 5, 55), _span("dispatch", 5, 7),
             _span("sync", 12, 33), _span("emit", 45, 15)]
    r = phases.split(ev, spans, 0.0)
    assert r["idle_s"] == {"sync": pytest.approx(28e-9),
                           "emit": pytest.approx(10e-9),
                           "dispatch": pytest.approx(2e-9)}
    assert r["no_span_s"] == pytest.approx(40e-9)
    assert r["idle_total_s"] == pytest.approx(80e-9)
    assert r["steps"] == 1


def test_idle_split_takes_the_clock_offset():
    """The device clock 5 ns early: shifted, the op [0, 10) runs [5, 15)
    and the idle [15, 20) lies in the step, not before it."""
    ev = [_window(0, 20), _op(0, 10)]
    r = phases.split(ev, [_span("step", 15, 5)], 5e-9)
    assert r["idle_s"] == {"step": pytest.approx(5e-9)}
    assert r["no_span_s"] == pytest.approx(5e-9)    # [0, 5)


def test_innermost_pieces_of_nested_spans():
    """A parent with two children and time of its own between them, a
    child cut at its parent's end, and a span after a gap."""
    got = phases.innermost([(0, 10, "step"), (2, 4, "dispatch"),
                            (6, 12, "emit"), (20, 25, "idle")])
    assert got == [(0, 2, "step"), (2, 4, "dispatch"), (4, 6, "step"),
                   (6, 10, "emit"), (20, 25, "idle")]


class _Run:
    def __init__(self, delta):
        self.engine_delta = delta


@pytest.mark.parametrize("name,delta,want", [
    ("queue_wait_ms", {"queue_wait_ns": 30_000_000, "admitted": 3}, 10.0),
    ("stream_write_ms", {"emit_write_ns": 4_000_000, "steps": 8}, 0.5),
    ("kv_grow_ms", {"kv_grow_ns": 3_000_000, "steps": 12}, 0.25),
])
def test_counter_readers(name, delta, want):
    """Each reader divides the engine's counter over the window by its
    count, and reads nothing where the program keeps no such counter or
    nothing was counted."""
    r = load_module(os.path.join(benchutil.ROOT, "benchmark", "metrics",
                                 name + ".py"), "metric_" + name)
    assert r.read(_Run(delta)) == pytest.approx(want)
    count = "admitted" if name == "queue_wait_ms" else "steps"
    assert r.read(_Run({count: delta[count]})) is None
    assert r.read(_Run(dict(delta, **{count: 0}))) is None


RUN_CODE = r"""
import json, os, time
from benchmark import harness, phases
with phases.reporting(keep=%r):
    out = harness.run("tiny", 2 ** 32 + 9, 2.0, True, time.monotonic_ns(),
                      root=%r, require_tpu=False)
print(json.dumps(out), flush=True)
os._exit(0)
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny cell's traced run on the CPU with the three readers
    listed and the phases line on."""
    benchutil.need_fake_plugin()
    tmp = tmp_path_factory.mktemp("phases")
    root = benchutil.tiny_root(tmp / "root")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["per_layer"] += [
        {"name": n, "unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "test", "moves": "itl_p95_ms", "workloads": ["tiny"]}
        for n in READERS]
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    keep = str(tmp / "excerpt.json")
    p = benchutil.run_py(RUN_CODE % (keep, root), tmp, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith('{"phase": "phases"')]
    with open(keep) as f:
        ex = json.load(f)["events"]
    return {"out": benchutil.last_json(p.stdout), "phases": lines, "ex": ex}


def test_traced_run_reads_the_counters(traced):
    m = traced["out"]["metrics"]
    assert traced["out"]["correct"]
    for n in READERS:
        assert m[n]["value"] > 0, (n, m)


def test_traced_run_prints_the_idle_split(traced):
    """On the CPU no device operation is traced, so the whole window is
    idle, and the loop's spans cover all but a sliver of it (on the CPU
    the clients may have run out of requests by the traced second, and
    the loop then only waits)."""
    from brpc_tpu.serving.engine import PHASES
    assert len(traced["phases"]) == 1
    ph = traced["phases"][0]
    window = traced["out"]["device"]["window_s"]
    assert ph["idle_total_s"] == pytest.approx(window, rel=1e-6)
    assert ph["no_span_s"] <= 0.05 * ph["idle_total_s"], ph
    assert set(ph["idle_s"]) <= set(PHASES) | {"admit", "step", "emit"}
    assert sum(ph["idle_s"].values()) + ph["no_span_s"] == \
        pytest.approx(ph["idle_total_s"], rel=1e-6)


def test_excerpt_holds_the_loop_spans(traced):
    ex = traced["ex"]
    assert ex[0][2] == "bench/window"
    spans = [e for e in ex if e[2].startswith("serving/")]
    assert spans
    r = phases.split(ex, spans, _offset(ex))
    assert r["idle_total_s"] == pytest.approx(0.1, rel=1e-6)


def _offset(events):
    return trace.reduce([e for e in events if not e[2].startswith(
        phases.SPAN_PREFIX)])["clock_offset_s"]


def test_recorded_chip_excerpt():
    """A tenth of a second of a traced GPT-2 small run on one v5e, cut
    by ``phases.excerpt`` (device operations' names cut to 40
    characters): five decode steps and an admission.  The loop's spans
    nest, cover all but a sliver of the idle time, and the split and the
    midpoint reduction see the same idle total."""
    with open(FIXTURE) as f:
        ev = json.load(f)["events"]
    spans = [e for e in ev if e[2].startswith(phases.SPAN_PREFIX)]

    def of(name):
        return [(e[3], e[3] + e[4]) for e in spans
                if e[2] == phases.SPAN_PREFIX + name]

    steps, admits = sorted(of("step")), of("admit")
    assert len(steps) == 5 and len(admits) == 1
    # spans that start in the excerpt; the first emit's step began before
    for child, parents in (("dispatch", steps), ("emit", steps),
                           ("sync", steps + admits)):
        assert all(any(p0 <= c0 and c1 <= p1 for p0, p1 in parents)
                   for c0, c1 in of(child) if c0 >= steps[0][0]), child
    for child in ("prefill", "kv_d2h", "kv_alloc", "kv_migrate"):
        (c0, c1), = of(child)
        assert admits[0][0] <= c0 and c1 <= admits[0][1], child
    offset = _offset(ev)
    assert 0.5e-3 < offset < 2e-3
    r = phases.split(ev, spans, offset)
    assert r["steps"] == 5
    assert r["no_span_s"] <= 0.05 * r["idle_total_s"], r
    assert {"dispatch", "sync", "emit", "kv_grow", "kv_d2h"} <= \
        set(r["idle_s"]), r
    red = trace.reduce([e for e in ev if not e[2].startswith(
        phases.SPAN_PREFIX)])
    assert r["idle_total_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
