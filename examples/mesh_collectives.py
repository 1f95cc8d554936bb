"""Combo channels lowered to XLA collectives (SURVEY.md §2.9: when the
member set is a mesh axis, ParallelChannel fan-out+merge IS one collective
riding ICI — no per-member RPCs)."""
# Needs 8 devices: on the CPU, run with JAX_PLATFORMS=cpu (XLA_FLAGS then
# gives 8 virtual devices); on chips, 8 of them.  Fewer is an error, never
# a quiet switch to the CPU.
import os

os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=8").strip())

import _bootstrap  # noqa: F401,E402

import jax  # noqa: E402

if len(jax.devices()) < 8:
    raise SystemExit(
        f"needs 8 devices, found {len(jax.devices())} "
        f"{jax.devices()[0].platform}: run with JAX_PLATFORMS=cpu for a "
        "virtual 8-device CPU mesh")

import jax.numpy as jnp  # noqa: E402

from brpc_tpu.parallel.channels import (MeshParallelChannel,  # noqa: E402
                                        MeshPartitionChannel)
from brpc_tpu.parallel.collectives import bus_bandwidth_gbps  # noqa: E402
from brpc_tpu.parallel.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh({"dp": 2, "tp": 4})
    print("mesh:", dict(mesh.shape), "on", jax.devices()[0].platform)

    # ParallelChannel whose members are the tp axis: merge = all_reduce
    # (dim 0 is sharded over the axis — one shard per member)
    pch = MeshParallelChannel(mesh, "tp", merger="add")
    x = jnp.ones((8, 8))
    print("allreduce-merged fan-out:", pch.call_tensor(x)[0, 0],
          f"(= {mesh.shape['tp']} members summed)")

    # PartitionChannel on the axis: gather / reduce-scatter are the merges
    part = MeshPartitionChannel(mesh, "tp")
    print("all_gather merge shape:", part.call_gather(x).shape)
    print("reduce_scatter merge shape:",
          part.call_reduce_scatter(jnp.ones((16, 8))).shape)

    # the driver's ICI bus-bandwidth metric (BASELINE.json)
    gbps = bus_bandwidth_gbps(mesh, "tp", mbytes_per_shard=8)
    print(f"allreduce bus bandwidth over tp: {gbps:.2f} GB/s "
          f"(virtual CPU mesh — real number comes from TPU chips)")


if __name__ == "__main__":
    main()
