"""The DeepSeek-V2 family (``benchmark/models/deepseek_v2.py``) in the
benchmark: its work counts at published widths, the readers of the MoE
metrics, and the comparison that decides ``correct`` driven through the
harness on the CPU at a tiny DeepSeek shape (``deepseek_tiny.py``).

A sound run agrees with the float32 reference and the float8 control
does not; each fault planted under the timed path (a step that returns
its cache unchanged, half of the slots' logits zeroed, half the bridge's
bytes zeroed, a token altered at emit) turns ``correct`` false.  The
served engine's routing counters reach the run's ``window`` line and
``/vars``.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchutil  # noqa: E402
import deepseek_tiny  # noqa: E402

sys.path.insert(0, benchutil.ROOT)
from benchmark.spec import find_cell, load_module  # noqa: E402

PUBLISHED = os.path.join(benchutil.ROOT, "benchmark", "configs",
                         "deepseek-v2-lite-ep4.json")


def fam():
    return load_module(deepseek_tiny.FAMILY, "family_deepseek_v2")


def published():
    with open(PUBLISHED) as f:
        return json.load(f)


def test_counts_at_published_widths():
    """One chip's share: 4,910,219,264 parameters besides the RMSNorm
    gains (9.82 GB in bf16) of the published 15,706,357,760.  A routed
    expert is 3 x 2048 x 1408 parameters; the held experts are 7.20 GB,
    the rest of what a decode step reads 2.21 GB (the router in f32); a
    token's latent is 27 x 576 bf16 values, 31,104 B."""
    import jax
    import numpy as np
    f = fam()
    config = published()
    d = f.dims(config)
    assert (d.n_layers, d.n_dense, d.n_moe, d.n_routed, d.n_held, d.topk) \
        == (27, 1, 26, 64, 16, 6)
    shapes = jax.eval_shape(lambda: f.weights(config, 0))
    size = {jax.tree_util.keystr(k): int(np.prod(x.shape)) for k, x in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    norms = sum(v for k, v in size.items() if "norm" in k)
    n = sum(size.values()) - norms
    assert n == 4_910_219_264 and norms == 27 * (2 * 2048 + 512) + 2048
    held = d.n_moe * d.n_held * f.expert_params(d)
    assert n + 3 * held == 15_706_357_760          # the other 48 experts
    assert f.expert_params(d) == 8_650_752
    assert f.kv_bytes_per_token(d) == 31_104
    assert f.local_picks(d) == 1.5
    rest = f.decode_bytes(d, 32, 0, 0.0)
    assert 2.20e9 < rest < 2.22e9
    assert f.decode_bytes(d, 32, 0, 1.0) - rest == 2 * held
    assert f.decode_bytes(d, 1, 10, 0.0) - rest == 10 * 31_104
    # decode: 2 x (params the token needs) + per position attended,
    # each head scores 576 values and sums 512
    base = 2 * f.matmul_params(d)
    assert f.decode_flops(d, 1, 0) == base
    assert f.decode_flops(d, 1, 1) - base == 2 * 27 * 16 * (576 + 512)
    assert f.decode_flops(d, 1, 0, picks=0.0) == base - 2 * 26 * 1.5 * \
        f.expert_params(d)
    assert f.hit_share(d, 32) == pytest.approx(1 - (58 / 64) ** 32)


class _Run:
    def __init__(self, fam_mod, d, trace, delta):
        self.fam, self.dims, self.trace = fam_mod, d, trace
        self.engine_delta, self.device_kind = delta, "TPU v5 lite"


def _reader(name):
    return load_module(os.path.join(benchutil.ROOT, "benchmark", "metrics",
                                    name + ".py"), "metric_" + name)


def test_moe_readers_by_hand():
    """Two traced steps and one prefill: the roofline takes the held
    experts at the window's hit share and the routed FLOPs at its local
    picks; both read nothing without the program's counters (a program
    that has none, as before the MoE path)."""
    from benchmark.peaks import floor_s, peaks
    f = fam()
    d = f.dims(published())
    trace = {"steps": [{"n": 32, "ctx": 5000}, {"n": 30, "ctx": 4000}],
             "prefills": [{"s": 128}], "program_s": {"decode_step": 0.04},
             "window_s": 1.0, "devices": 1}
    delta = {"steps": 100, "moe_picks": 100 * 31 * 6 * 26,
             "moe_local_picks": 100 * 31 * 6 * 26 // 4,
             "moe_experts_hit": 100 * 26 * 15}
    run = _Run(f, d, trace, delta)
    need = sum(floor_s(f.decode_flops(d, s["n"], s["ctx"], 1.5),
                       f.decode_bytes(d, s["n"], s["ctx"], 15 / 16),
                       "TPU v5 lite")[0] for s in trace["steps"])
    assert _reader("moe_decode_roofline").read(run) == \
        pytest.approx(100 * need / 0.04)
    flops = (f.decode_flops(d, 32, 5000, 1.5) + f.decode_flops(d, 30, 4000,
                                                                1.5)
             + f.prefill_flops(d, 128, 1.5))
    assert _reader("moe_serve_mfu").read(run) == \
        pytest.approx(100 * flops / peaks("TPU v5 lite")["bf16_flops_per_s"])
    bare = _Run(f, d, trace, {"steps": 100})
    for name in ("moe_decode_roofline", "moe_serve_mfu"):
        assert _reader(name).read(bare) is None
        assert _reader(name).read(_Run(f, d, None, delta)) is None


def test_the_cell_is_found_with_its_metrics():
    cell = find_cell("dsv2lite_sharegpt")
    assert cell.chips == 1 and cell.traffic_name == "sharegpt"
    assert cell.config["serving"]["slots"] == 32
    assert [m["name"] for m in cell.per_layer] == ["moe_decode_roofline",
                                                   "moe_serve_mfu"]
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                   "itl_p95_ms", "setup_s"}
    assert cell.config["reduced"] == ["n_routed_experts"]
    dep = cell.config["deployment"]
    assert (dep["chips"], dep["n_routed_experts"], dep["experts_here"]) \
        == (4, 64, [0, 16])


CODE = r"""
import json, os, time, urllib.request
from benchmark import harness
from benchmark.spec import find_cell

ROOT = %r


def step_keeps_cache(engine):
    orig = engine._jstep
    def step(p, c, t, a):
        out = orig(p, c, t, a)
        return (out[0], c) + tuple(out[2:])
    engine._jstep = step


def half_the_slots(engine):
    orig = engine._jstep
    def step(p, c, t, a):
        lg, *rest = orig(p, c, t, a)
        return (lg.at[lg.shape[0] // 2:].set(0.0), *rest)
    engine._jstep = step


def corrupt_bridge(engine):
    orig = engine.kv.seq_fetch
    def fetch(seq_id):
        b = bytearray(orig(seq_id))
        b[: len(b) // 2] = bytes(len(b) // 2)
        return bytes(b)
    engine.kv.seq_fetch = fetch


def alter_token(engine):
    orig = engine._emit
    def emit(seq, token):
        if seq.generated == 1:
            token = (token + 1) %% engine.cfg.vocab
        return orig(seq, token)
    engine._emit = emit


out = {}
for name, tamper in [("sound", None), ("step_keeps_cache", step_keeps_cache),
                     ("half_the_slots", half_the_slots),
                     ("corrupt_bridge", corrupt_bridge),
                     ("alter_token", alter_token)]:
    r = harness.run("tiny_ds", 2 ** 33 + 7, 2.0, False, time.monotonic_ns(),
                    root=ROOT, require_tpu=False, control=name == "sound",
                    tamper=tamper)
    out[name] = {"correct": r["correct"], "checks": r["checks"],
                 "attempted": r["attempted"], "failed": r["failed"],
                 "control": r.get("control")}

# the engine's counters on /vars, after one request
from brpc_tpu.rpc.server import Server
cell = find_cell("tiny_ds", ROOT)
fam = cell.family()
engine = harness.make_engine(cell, fam.program_model(cell.config),
                             fam.weights(cell.config, 11))
server = Server()
engine.register(server, harness.METHOD)
port = server.start("127.0.0.1:0")
engine.start()
toks = harness.generate(port, [3, 1, 4, 1, 5, 9, 2, 6], 5)
portal = urllib.request.urlopen(
    f"http://127.0.0.1:{port}/vars?filter=serving_moe", timeout=10
).read().decode()
stats = engine.stats()
engine.stop()
server.destroy()
out["vars"] = {"tokens": toks, "portal": portal,
               "stats": {k: v for k, v in stats.items()
                         if k.startswith("moe_") or k == "steps"}}
print(json.dumps(out), flush=True)
os._exit(0)
"""


@pytest.fixture(scope="module")
def legs(tmp_path_factory):
    benchutil.need_fake_plugin()
    tmp = tmp_path_factory.mktemp("ds")
    root = deepseek_tiny.root(tmp / "root")
    p = benchutil.run_py(CODE % root, tmp, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    windows = [json.loads(ln)["engine"] for ln in p.stdout.splitlines()
               if ln.startswith('{"phase": "window"')]
    return dict(benchutil.last_json(p.stdout), windows=windows)


def test_sound_run_agrees_with_the_reference(legs):
    r = legs["sound"]
    assert r["correct"], r
    assert r["attempted"] > 0 and r["failed"] == 0
    gap = r["checks"]["logit_gap_max"]
    assert 0.0 <= gap["value"] < gap["limit"]
    assert r["checks"]["checked_tokens_min"]["value"] >= 64


def test_lower_precision_fails_the_comparison(legs):
    c = legs["sound"]["control"]
    assert c["correct"] is False, c
    gap = c["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"], c


@pytest.mark.parametrize("fault", ["step_keeps_cache", "half_the_slots",
                                   "corrupt_bridge", "alter_token"])
def test_fault_turns_correct_false(legs, fault):
    r = legs[fault]
    assert not r["correct"], r
    gap = r["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"], r


def test_routing_counters_reach_the_window_line_and_vars(legs):
    """Every run's window line carries the step's routing counters: picks
    are 2 per active slot and MoE layer (top-2, 2 MoE layers), about half
    land on the 4 held experts of 8; /vars shows them as serving_moe_*."""
    w = legs["windows"][0]
    assert w["moe_picks"] > 0 and w["moe_picks"] % 4 == 0
    assert 0.25 < w["moe_local_picks"] / w["moe_picks"] < 0.75
    assert 0 < w["moe_experts_hit"] <= w["steps"] * 2 * 4
    v = legs["vars"]
    assert len(v["tokens"]) == 5
    portal = dict(ln.split(" : ", 1) for ln in
                  v["portal"].strip().splitlines())
    assert set(portal) == {"serving_moe_picks", "serving_moe_local_picks",
                           "serving_moe_experts_hit"}
    assert int(portal["serving_moe_picks"]) == v["stats"]["moe_picks"] == \
        v["stats"]["steps"] * 2 * 2
