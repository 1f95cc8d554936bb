"""The comparison that decides ``correct``, on the CPU at a tiny width.

The served path (``LLM.Generate`` over loopback, the serving engine, the
KV block plane on the fake PJRT plug-in) is driven by the harness exactly
as on the chip, the look for a chip aside, and then:

- a sound run agrees with the plain float32 reference, and the control
  (the reference with every matmul operand in float8), its tokens put in
  the served tokens' place, reads ``correct`` false by the same checks;
- each fault planted under the timed path turns ``correct`` false: a
  decode step that returns its cache unchanged, half of the slots' tokens
  left out, the K/V bridge's bytes corrupted, a token altered where it is
  produced.  (No cell spans chips, so no exchange between chips can be
  left out.)
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchutil  # noqa: E402

CODE = r"""
import json, os, sys, time
import jax.numpy as jnp
from benchmark import harness

root = sys.argv[1] if len(sys.argv) > 1 else None
ROOT = %r


def step_keeps_cache(engine):
    orig = engine._jstep
    def step(p, c, t, a):
        lg, _ = orig(p, c, t, a)
        return lg, c
    engine._jstep = step


def half_the_slots(engine):
    orig = engine._jstep
    def step(p, c, t, a):
        lg, c2 = orig(p, c, t, a)
        half = lg.shape[0] // 2
        return lg.at[half:].set(0.0), c2
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
    r = harness.run("tiny", 2 ** 33 + 5, 2.0, False, time.monotonic_ns(),
                    root=ROOT, require_tpu=False, control=name == "sound",
                    tamper=tamper)
    out[name] = {"correct": r["correct"], "checks": r["checks"],
                 "attempted": r["attempted"], "failed": r["failed"],
                 "control": r.get("control")}
print(json.dumps(out), flush=True)
os._exit(0)
"""


@pytest.fixture(scope="module")
def legs(tmp_path_factory):
    benchutil.need_fake_plugin()
    tmp = tmp_path_factory.mktemp("ref")
    root = benchutil.tiny_root(tmp / "root")
    p = benchutil.run_py(CODE % root, tmp, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return benchutil.last_json(p.stdout)


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
    assert c["checks"]["checked_tokens_min"] == \
        legs["sound"]["checks"]["checked_tokens_min"]


def test_traced_run_refuses_an_engine_without_a_wrapped_call():
    """A program that renames a call the traced run wraps stops the run
    with the name, rather than leaving the metrics that read it dark."""
    sys.path.insert(0, benchutil.ROOT)
    from benchmark.spans import Spans

    class Sched:
        def submit(self, seq):
            return seq

    class Engine:
        sched = Sched()

        def _admit(self, seq):
            return seq

    with pytest.raises(RuntimeError, match="ServingEngine._emit"):
        Spans().install(Engine())


@pytest.mark.parametrize("fault", ["step_keeps_cache", "half_the_slots",
                                   "corrupt_bridge", "alter_token"])
def test_fault_turns_correct_false(legs, fault):
    r = legs[fault]
    assert not r["correct"], r
    gap = r["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"], r
