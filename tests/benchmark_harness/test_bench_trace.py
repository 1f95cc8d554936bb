"""The benchmark's yardstick on the CPU: the trace reduction, the FLOP and
byte counts, the table of peaks, percentiles and the traffic generator."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import peaks, stats, trace, traffic  # noqa: E402
from benchmark.spec import find_cell, load_module  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_small.json")
DEV = "/device:TPU:0"


def gpt2():
    return load_module(os.path.join(ROOT, "benchmark", "models", "gpt2.py"),
                       "family_gpt2")


def _host(name, start, dur, **st):
    return ["/host:CPU", "python", "bench/" + name, start, dur, st]


def _op(name, s, d):
    return [DEV, "XLA Ops", name, s, d, {}]


def _module(name, s, d):
    return [DEV, "XLA Modules", name, s, d, {}]


def test_reduce_by_hand():
    """A window of 100 ns: a decode [8, 40) around its step [10, 40),
    whose program runs on
    the device over [15, 35) as two overlapping ops, a prefill [50, 70)
    whose program runs over [55, 65), and an op that starts before the
    window and is clipped to it."""
    ev = [
        _host("window", 0, 100),
        _host("decode", 8, 32, n=2, ctx=30),
        _host("decode_step", 10, 30, n=2, ctx=30),
        _host("emit", 41, 9),
        _host("admit", 48, 30),
        _host("prefill", 50, 20, s=16),
        _module("step", 15, 20), _op("fusion.1", 15, 12),
        _op("fusion.2", 20, 15),
        _module("prefill", 55, 10), _op("fusion.3", 55, 10),
        _op("copy.9", -5, 8),
    ]
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [0, 3) + [15, 35) + [55, 65) = 3 + 20 + 10
    assert r["busy_s"] == pytest.approx(33e-9)
    assert r["devices"] == 1
    assert r["clock_offset_s"] == 0
    assert r["program_s"] == {"decode_step": pytest.approx(20e-9),
                              "prefill": pytest.approx(10e-9)}
    assert r["steps"] == [{"n": 2, "ctx": 30}]
    assert r["prefills"] == [{"s": 16}]
    idle = dict(r["idle_by_activity"])
    # gaps [3,15) mid 9 -> decode; [35,55) mid 45 -> emit;
    # [65,100) mid 82 -> no span
    assert idle["decode"] == pytest.approx(12e-9)
    assert idle["emit"] == pytest.approx(20e-9)
    assert idle[trace.NO_SPAN] == pytest.approx(35e-9)
    assert dict(r["top_ops"])["fusion.2"] == pytest.approx(15e-9)


def test_device_clock_offset_is_found():
    """Device times 0.7 ms early: every step's program (10 ms) would start
    before its span (12 ms), and a short prefill program would lie wholly
    before its span; the reduction shifts them back in."""
    ms = 1_000_000
    ev = [_host("window", 0, 100 * ms)]
    for i in range(5):
        t = 10 * ms + i * 15 * ms
        ev += [_host("decode_step", t, 12 * ms, n=1, ctx=10),
               _module("step", t + ms // 10 - 7 * ms // 10, 10 * ms),
               _op("fusion", t + ms // 10 - 7 * ms // 10, 10 * ms)]
    ev += [_host("admit", 88 * ms, 6 * ms),
           _host("prefill", 89 * ms, 2 * ms, s=8),
           _module("prefill", 89 * ms + ms // 10 - 7 * ms // 10, ms // 2)]
    r = trace.reduce(ev)
    assert 0.5e-3 <= r["clock_offset_s"] <= 2.5e-3
    assert r["program_s"]["decode_step"] == pytest.approx(50e-3)
    assert r["program_s"]["prefill"] == pytest.approx(0.5e-3)
    assert len(r["program_s"]) == 2


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        trace.reduce([_op("x", 0, 1)])


def test_recorded_trace_fixture():
    """A quarter second of a traced GPT-2 small run on one v5e, cut by
    trace.excerpt(): the reduction stays inside its window and finds the
    decode programs inside the decode spans."""
    with open(FIXTURE) as f:
        ev = json.load(f)["events"]
    r = trace.reduce(ev)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["steps"] and all(s["n"] >= 1 for s in r["steps"])
    assert 0 < r["program_s"]["decode_step"] < r["window_s"]
    assert 0 < r["program_s"]["prefill"] < r["window_s"]
    assert len(r["prefills"]) == 3
    # the device runs about 1 ms early against the host here
    assert 0.5e-3 < r["clock_offset_s"] < 2e-3
    idle = sum(v for _, v in r["idle_by_activity"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)


@pytest.mark.parametrize("name,params,flops0,per_ctx,kv", [
    ("gpt2-xl", 1_554_971_200, 3_109_942_400, 307_200, 307_200),
    ("gpt2-small", 123_532_032, 247_064_064, 36_864, 36_864),
])
def test_counts_by_hand(name, params, flops0, per_ctx, kv):
    """XL: 48 x 12 x 1600^2 + 50257 x 1600 matmul parameters; small:
    12 x 12 x 768^2 + 50257 x 768.  Decode FLOPs per token 2 x params +
    4 x layers x d_model x ctx; K/V 2 x layers x d_model x 2 bytes."""
    fam = gpt2()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        d = fam.dims(json.load(f))
    assert fam.matmul_params(d) == params
    assert fam.decode_flops(d, 1, 0) == flops0
    assert fam.decode_flops(d, 1, 10) - flops0 == 10 * per_ctx
    assert fam.kv_bytes_per_token(d) == kv
    assert fam.decode_bytes(d, 3, 100) == 2 * params + 100 * kv
    # prefill of s tokens: the LM head on the last position only
    s = 128
    head = 2 * d.vocab * d.d_model
    assert fam.prefill_flops(d, s) == (2 * (params - d.vocab * d.d_model) * s
                                       + 2 * d.n_layers * d.d_model * s
                                       * (s + 1) + head)


def test_peaks_and_floor():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = peaks.floor_s(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (1.0, "compute")
    t, bound = peaks.floor_s(1.0, 819e9 * 2, "TPU v5 lite")
    assert (t, bound) == (2.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.floor_s(1.0, 1.0, "cpu")


def test_nearest_rank_percentile():
    v = list(range(1, 11))
    assert stats.pct(v, 0.9) == 9
    assert stats.pct(v, 0.95) == 10
    assert stats.pct([5.0], 0.5) == 5.0
    with pytest.raises(ValueError):
        stats.pct([], 0.5)


@pytest.mark.parametrize("cell", ["xl_decode", "small_sharegpt"])
def test_every_seed_offers_the_same_work(cell):
    """Two seeds, one of them past 32 bits: the same prompt and output
    lengths in the same order (and, open loop, at the same times), with
    other tokens."""
    c = find_cell(cell)
    slots = c.config["serving"]["slots"]

    def sizes(seed):
        s = traffic.schedule(c.traffic, seed, 10.0, 50257, slots)
        if s["loop"] == "open":
            reqs = [r for r in s["requests"] if r["counted"]]
            assert all(0 <= r["due"] < 10.0 for r in reqs)
            assert len(reqs) == round(c.traffic["rate"] * 10)
        else:
            assert len(s["clients"]) == slots
            reqs = s["clients"][0][:c.traffic["block"]]
        return reqs, [(len(r["prompt"]), r["max_new"], r.get("due"))
                      for r in reqs]

    a, sa = sizes(7)
    b, sb = sizes(2 ** 31 + 12345)
    assert sa == sb
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert sizes(7)[0] == a
    for r in a:
        assert r["max_new"] >= 1 and len(r["prompt"]) + r["max_new"] <= 1024
        assert max(r["prompt"]) < 50257


def test_lengths_and_arrivals_keep_the_published_centre():
    """Exponential lengths from a mean or a median, scaled, bucketed and
    clipped; Poisson gaps of the stated rate."""
    t = {"scale": 0.5,
         "prompt_len": {"dist": "exponential", "mean": 200.0,
                        "buckets": [64, 128, 256]},
         "output_len": {"dist": "exponential", "median": 100.0, "min": 1,
                        "max": 10_000}}
    p = traffic.prompt_lengths(t, 4000)
    assert set(p) == {64, 128, 256}
    o = traffic.output_lengths(t, 4000)
    assert np.median(o) == pytest.approx(50.0, abs=1)
    assert np.mean(o) == pytest.approx(50.0 / np.log(2), rel=0.02)
    t["output_len"]["max"] = 60
    assert max(traffic.output_lengths(t, 4000)) == 60
    g = traffic.gaps(4.0, 4000)
    assert np.mean(g) == pytest.approx(0.25, rel=0.01)
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, rel=0.05)


def test_open_loop_offers_the_same_arrivals_to_every_seed():
    t = dict(find_cell("small_sharegpt").traffic, loop="open", rate=4.0,
             workers=8)
    a, b = (traffic.schedule(t, seed, 10.0, 50257, 16)["requests"]
            for seed in (3, 2 ** 33 + 1))
    counted = [r for r in a if r["counted"]]
    assert len(counted) == 40 and len(a) == 80
    assert all(0 <= r["due"] < 10.0 for r in counted)
    assert [(len(r["prompt"]), r["max_new"], r["due"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"], r["due"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
