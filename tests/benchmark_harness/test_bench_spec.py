"""BENCHMARK.json against its contract, the harness driven by data alone,
and the command's refusals."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchutil  # noqa: E402

ROOT = benchutil.ROOT
sys.path.insert(0, ROOT)
from benchmark.spec import find_cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_spec_keys_names_and_bounds(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    cfgs = {c["name"] for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in cfgs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = find_cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))
        for m in cell.per_layer:
            assert m["moves"] in names


NEW_METRIC = ({"name": "emits_per_step", "unit": "tokens", "better": "higher",
               "source": "host_clock", "layer": "RPC front",
               "moves": "itl_p95_ms", "workloads": ["tiny"]},
              '''"""Tokens handed to streams per decode step."""


def read(run):
    if run.spans is None:
        return None
    steps = run.spans.of("decode", run.t0, run.t1)
    emits = [e for e in run.spans.of("emit", run.t0, run.t1)
             if e[3].get("step")]
    return len(emits) / len(steps) if steps else None
''')

RUN_CODE = r"""
import json, os, time
from benchmark import harness
out = {}
for trace in (False, True):
    out[str(trace)] = harness.run("tiny", 77, 2.0, trace, time.monotonic_ns(),
                                  root=%r, require_tpu=False)
print(json.dumps(out), flush=True)
os._exit(0)
"""


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files with new entries: the harness finds and runs the cell, and the
    metric lands in the traced run's result line.  No file the benchmark
    already had is edited."""
    benchutil.need_fake_plugin()
    root = benchutil.tiny_root(tmp_path / "root", extra_metric=NEW_METRIC)
    for dirpath, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        for fn in files:
            if fn.endswith((".py", ".json")):
                src = os.path.join(dirpath, fn)
                dst = os.path.join(root, os.path.relpath(src, ROOT))
                assert _digest(src) == _digest(dst), src
    cell = find_cell("tiny", root)
    assert cell.config["name"] == "gpt2-tiny"
    assert cell.traffic["loop"] == "closed"
    assert "emits_per_step" in [m["name"] for m in cell.per_layer]
    assert cell.reader("emits_per_step").read is not None

    p = benchutil.run_py(RUN_CODE % root, tmp_path, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = benchutil.last_json(p.stdout)
    plain, traced = out["False"], out["True"]
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                     "setup_s"}
    assert list(plain)[-1] == "checks"
    assert traced["metrics"]["emits_per_step"]["value"] >= 1.0
    assert {"rpc_write_ms", "batch_fill"} <= set(traced["metrics"])
    assert traced["device"]["window_s"] > 0
    assert "breakdown" in traced


def test_run_refuses_a_platform_that_is_not_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "small_sharegpt", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_needs_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    paths, the command fails and prints no result."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "small_sharegpt", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
