"""Test env: a virtual 8-device CPU platform before jax initializes.

Mirrors the reference's "multi-node without a cluster" strategy
(SURVEY.md §4): tests exercise real in-process transports and a real
multi-device mesh, no mocks — loopback TCP stands in for the network and
8 virtual CPU devices stand in for a TPU slice.  JAX_PLATFORMS=cpu is
all it takes (set here for this process and every subprocess the tests
spawn); the device-plane tests dlopen a PJRT plugin directly.
"""

import os
import sys

# A developer replaying a schedule-dependent abort (BENCH_NOTES.md
# "Schedule replay") may leave TRPC_SCHED_SEED exported — tier-1 must run
# unperturbed regardless (the seed-sweep/soak harnesses and
# test_sched_replay set the seed explicitly per subprocess).
os.environ.pop("TRPC_SCHED_SEED", None)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
