"""tools/mesh_cluster.py — the scatter-gather chaos harness (ISSUE 19).

A REAL multi-process mesh (root -> mixers -> leaves over loopback
sockets, deadline propagation + overload on in every child), exercised
two ways:

* a quick tier-1 smoke: tiny topology, the baseline press plus the
  expired_budget leg (every leaf slow, so propagated budgets MUST die
  server-side: native_deadline_drops_total > 0 is the tentpole's
  acceptance signal);
* the slow-marked churn battery: leaf SIGKILL mid-burst + recovery
  press, the slow-but-alive leaf bled by pressure steering, the naming
  flap and the mixer partition — the full acceptance topology.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HARNESS = os.path.join(REPO, "tools", "mesh_cluster.py")


def _run(tmp_path, *extra, timeout=600):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, HARNESS, "--json",
         "--workdir", str(tmp_path), *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert r.returncode == 0, \
        f"harness rc={r.returncode}\n{r.stdout[-4000:]}\n{r.stderr[-4000:]}"
    doc = json.loads(r.stdout.splitlines()[-1])
    assert doc["metric"] == "mesh_cluster"
    doc["by_leg"] = {leg["leg"]: leg for leg in doc["legs"]}
    return doc


def test_mesh_smoke_baseline_and_expired_budget(tmp_path):
    """1 mixer x 2 leaves: the baseline press succeeds end-to-end and
    the expired_budget leg (EVERY leaf slow — steering has nowhere to
    flee) proves budgets die server-side: deadline drops > 0."""
    doc = _run(tmp_path, "--mixers", "1", "--n-leaves", "2",
               "--fanout", "2", "--concurrency", "4",
               "--leg-s", "1", "--settle-s", "0.5",
               "--legs", "baseline,expired_budget")
    assert doc["ok"] is True, doc
    base = doc["by_leg"]["baseline"]["root"]
    assert base["success_rate"] >= 0.99, base
    assert base["admitted"] > 0
    # per-tier latency percentiles are reported for every leg
    assert base["p99_us"] > 0
    assert doc["deadline_drops_total"] > 0, \
        "no propagated budget died server-side under the all-slow leg"


@pytest.mark.slow
def test_mesh_churn_battery(tmp_path):
    """The acceptance topology (2 mixers x 4 leaves): success >= 99%
    after the first health-check interval post-kill, the slow leaf's
    share measurably bled below fair, and the naming flap + mixer
    partition legs hold."""
    doc = _run(tmp_path, "--mixers", "2", "--n-leaves", "4",
               "--fanout", "2", "--concurrency", "8",
               "--leg-s", "3", "--settle-s", "1",
               "--legs", "baseline,leaf_kill,slow_leaf,naming_flap,"
                         "expired_budget,mixer_partition",
               timeout=900)
    assert doc["ok"] is True, doc
    legs = doc["by_leg"]
    assert legs["leaf_kill_recovered"]["root"]["success_rate"] >= 0.99
    assert legs["naming_flap"]["root"]["success_rate"] >= 0.99
    fair = 1.0 / 4
    assert legs["slow_leaf"]["slow_share"] < fair * 0.6, legs["slow_leaf"]
    assert doc["deadline_drops_total"] > 0
