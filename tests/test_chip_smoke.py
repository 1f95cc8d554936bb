"""chip_smoke.py rehearsed on the CPU (on-chip-measurement §2, rehearsal 1
and 2): its phases run end to end at a tiny width on the fake PJRT plugin
and an 8-device virtual mesh, and the script itself refuses to report a
result from a CPU.  The real run is the driver's, on the chip.

The phases run in a subprocess: a PJRT client is process-global state the
test runner must not inherit (same posture as tests/test_tpu_plane.py).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE_PLUGIN = os.path.join(REPO, "brpc_tpu", "_native", "libpjrt_fake.so")


def _run(args, env_extra=None, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO)


PHASES = r"""
import sys
import chip_smoke as cs
from brpc_tpu import tpu_plane
from brpc_tpu.serving.engine import tiny_config
assert tpu_plane.init(), tpu_plane.error()
cfg = tiny_config()
if sys.argv[1] == "one":
    cs.one_chip(cfg, seed=3, prompt_len=12, max_new=6)
else:
    cs.four_chips(cfg, seed=3, prompt_len=12, max_new=6)
print("PHASES-OK")
"""


@pytest.mark.parametrize("phase", ["one", "four"])
def test_chip_smoke_phases_on_cpu(phase):
    """One-chip phases (HbmEcho over tpu://, served tokens == the greedy
    reference, drained) and the four-chip phase (tp=4 serving vs one
    device, mesh add-merge vs numpy, KV over the d2d rail) at tiny width."""
    if not os.path.exists(FAKE_PLUGIN):
        pytest.skip("fake PJRT plugin not built (bash native/build.sh)")
    r = _run([sys.executable, "-c", PHASES, phase],
             {"TRPC_PJRT_PLUGIN": FAKE_PLUGIN})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "PHASES-OK" in r.stdout
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    got = {ln["phase"]: ln for ln in lines}
    if phase == "one":
        assert got["echo"]["transport"] == "device"
        assert got["serving"]["exact"] == got["serving"]["tokens"] == 24
    else:
        assert got["mesh_fanout"]["members"] == 4
        assert got["serving_tp"]["param_shard_devices"] == 4
        assert got["serving_tp"]["rail_local"] >= 4


def test_chip_smoke_refuses_the_cpu():
    """With no TPU the script exits non-zero, names the platform JAX
    found, and prints no result line."""
    r = _run([sys.executable, "chip_smoke.py"], {"JAX_PLATFORMS": "cpu"},
             timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout
