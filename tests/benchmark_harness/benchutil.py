"""Helpers for the benchmark's own tests: a copy of the benchmark with a
tiny GPT-2 cell added as new files, and a runner for code that needs the
fake PJRT plug-in in a process of its own."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAKE_PLUGIN = os.path.join(ROOT, "brpc_tpu", "_native", "libpjrt_fake.so")

# two slots under three prompt lengths: the warm-up must not overrun the
# waiting room, which a closed loop sizes to its clients
TINY_CONFIG = {
    "name": "gpt2-tiny", "family": "gpt2", "source": "test",
    "vocab_size": 512, "n_positions": 128, "n_ctx": 128, "n_embd": 64,
    "n_head": 4, "n_layer": 2, "n_inner": None,
    "activation_function": "gelu_new", "layer_norm_epsilon": 1e-05,
    "serving": {"dtype": "bfloat16", "slots": 2, "max_len": 128,
                "kv_block_tokens": 16},
    # tiny widths read gaps of 0.01-0.05 against the float32 reference,
    # and 0.6-2.2 for the float8 control
    "check": {"max_logit_gap": 0.25},
    "reduced": [], "assumed": [],
}

TINY_TRAFFIC = {
    "loop": "closed", "clients": "slots", "block": 4,
    "prompt_len": {"dist": "exponential", "median": 14,
                   "buckets": [8, 16, 32]},
    "output_len": {"dist": "exponential", "median": 12, "min": 4,
                   "max": 40},
}


def need_fake_plugin():
    if not os.path.exists(FAKE_PLUGIN):
        subprocess.run(["bash", os.path.join(ROOT, "native", "build.sh")],
                       check=True, capture_output=True)


def tiny_root(tmp, cell="tiny", extra_metric=None):
    """A copy of BENCHMARK.json and benchmark/ under `tmp`, plus a tiny
    configuration, traffic mix and cell, each as new files and entries;
    `extra_metric` is (entry, reader source) for a new per-layer metric."""
    root = str(tmp)
    os.makedirs(root, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "benchmark", "configs",
                           "gpt2-tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny_mix.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "gpt2-tiny", "source": "test",
                            "file": "benchmark/configs/gpt2-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "gpt2-tiny",
                              "traffic": "tiny_mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "xl_decode" in m["workloads"]:
            m["workloads"].append(cell)
    if extra_metric is not None:
        entry, source = extra_metric
        spec["per_layer"].append(entry)
        with open(os.path.join(root, "benchmark", "metrics",
                               entry["name"] + ".py"), "w") as f:
            f.write(source)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def run_py(code, tmp, timeout=300):
    """Run `code` in a fresh interpreter on the CPU with the fake plug-in
    as the device plane and a compilation cache of its own under `tmp`;
    returns the CompletedProcess."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", TRPC_PJRT_PLUGIN=FAKE_PLUGIN,
               JAX_COMPILATION_CACHE_DIR=os.path.join(str(tmp), "jc"),
               PYTHONPATH=ROOT + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=ROOT)


def last_json(stdout):
    for line in reversed(stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(f"no JSON line in:\n{stdout[-2000:]}")
