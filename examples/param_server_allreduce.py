"""Parameter-server gradient allreduce — the BASELINE.md stretch
acceptance config (≙ the reference's ParallelChannel parameter-server
workload, parallel_channel.h:185, retargeted at the mesh: each data-
parallel worker holds its local gradients for a REAL-sized parameter set
(ResNet-50's actual layer shapes, ~25.5M params) and the ParallelChannel
fan-out + "add" ResponseMerger IS one XLA allreduce riding ICI,
SURVEY §2.9 lowering table).

Prints one JSON line with the measured gradient-allreduce rate and the
synthetic bus-bandwidth probe (collectives.bus_bandwidth_gbps), and
verifies the merged gradients numerically against dense jnp."""
# Needs 8 devices: on the CPU, run with JAX_PLATFORMS=cpu (XLA_FLAGS then
# gives 8 virtual devices); on chips, 8 of them.  Fewer is an error, never
# a quiet switch to the CPU.
import os

os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=8").strip())

import _bootstrap  # noqa: F401,E402

import json
import os
import time

import jax  # noqa: E402

if len(jax.devices()) < 8:
    raise SystemExit(
        f"needs 8 devices, found {len(jax.devices())} "
        f"{jax.devices()[0].platform}: run with JAX_PLATFORMS=cpu for a "
        "virtual 8-device CPU mesh")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from brpc_tpu.parallel.channels import MeshParallelChannel  # noqa: E402
from brpc_tpu.parallel.collectives import bus_bandwidth_gbps  # noqa: E402
from brpc_tpu.parallel.mesh import make_mesh  # noqa: E402


def resnet50_param_shapes():
    """The actual ResNet-50 parameter shapes (conv/BN/fc), ~25.5M params
    — a 'real-sized param set', not a synthetic blob."""
    shapes = [("conv1", (7, 7, 3, 64)), ("bn1_scale", (64,)),
              ("bn1_bias", (64,))]
    in_ch = 64
    stage_planes = [(64, 3), (128, 4), (256, 6), (512, 3)]
    for s, (planes, blocks) in enumerate(stage_planes):
        out_ch = planes * 4
        for b in range(blocks):
            pfx = f"layer{s + 1}.{b}"
            shapes += [
                (f"{pfx}.conv1", (1, 1, in_ch, planes)),
                (f"{pfx}.bn1_scale", (planes,)),
                (f"{pfx}.bn1_bias", (planes,)),
                (f"{pfx}.conv2", (3, 3, planes, planes)),
                (f"{pfx}.bn2_scale", (planes,)),
                (f"{pfx}.bn2_bias", (planes,)),
                (f"{pfx}.conv3", (1, 1, planes, out_ch)),
                (f"{pfx}.bn3_scale", (out_ch,)),
                (f"{pfx}.bn3_bias", (out_ch,)),
            ]
            if b == 0:
                shapes += [(f"{pfx}.downsample", (1, 1, in_ch, out_ch)),
                           (f"{pfx}.bn_ds_scale", (out_ch,)),
                           (f"{pfx}.bn_ds_bias", (out_ch,))]
            in_ch = out_ch
    shapes += [("fc_w", (2048, 1000)), ("fc_b", (1000,))]
    return shapes


def run(iters: int = 3, dtype=jnp.float32, codec: str = "none"):
    mesh = make_mesh({"dp": len(jax.devices())})
    n = mesh.shape["dp"]
    ch = MeshParallelChannel(mesh, "dp", merger="add", codec=codec)

    shapes = resnet50_param_shapes()
    nparams = sum(int(np.prod(s)) for _, s in shapes)
    grad_bytes = nparams * jnp.dtype(dtype).itemsize

    # one flat gradient vector per worker (what the PS ships), worker i
    # holding a deterministic pattern so the merge is checkable
    flat = jnp.arange(nparams, dtype=dtype) % 97
    stacked = jnp.stack([flat * (i + 1) for i in range(n)])  # (n, P)
    from jax.sharding import NamedSharding, PartitionSpec
    stacked = jax.device_put(stacked,
                             NamedSharding(mesh, PartitionSpec("dp")))

    # numeric acceptance against the dense jnp sum
    merged = ch.call_tensor(stacked)
    expect = flat * (n * (n + 1) // 2)
    codec_err = codec_bound = None
    if codec == "none":
        np.testing.assert_allclose(np.asarray(merged[0]),
                                   np.asarray(expect), rtol=1e-5)
    else:
        # lossy-but-BOUNDED leg (ISSUE 8): the dequantize-then-reduce
        # sum's error is at most the per-worker codec bounds added
        # (parallel/quantize.py mirrors native/src/codec.h's formats)
        from brpc_tpu.parallel import quantize
        rows = np.asarray(jax.device_get(stacked))
        if codec == "int8":
            codec_bound = sum(
                quantize.int8_error_bound(jnp.asarray(rows[i]))
                for i in range(n))
        else:  # bf16: 8 explicit mantissa bits -> rel err <= 2^-9+ulp,
            # bounded per worker by max|shard| * 2^-8 (safe factor)
            codec_bound = sum(
                float(np.max(np.abs(rows[i]))) * 2.0 ** -8
                for i in range(n))
        codec_err = float(np.max(np.abs(
            np.asarray(merged[0]) - np.asarray(expect))))
        assert codec_err <= codec_bound, (
            f"{codec} allreduce error {codec_err} exceeds the "
            f"documented bound {codec_bound}")
        # the leg must actually be lossy (0 error would mean the codec
        # silently didn't engage)
        assert codec_err > 0.0, f"{codec} codec did not engage"

    # measured rate of the real gradient allreduce (first call above
    # already compiled + warmed the jit cache)
    ch.call_tensor(stacked)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ch.call_tensor(stacked)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    algbw = grad_bytes * iters / dt / 1e9
    busbw = algbw * 2 * (n - 1) / n

    return {
        "workload": "param_server_allreduce",
        "params": nparams,
        "grad_mbytes": round(grad_bytes / 1e6, 1),
        "devices": n,
        "platform": jax.devices()[0].platform,
        "numeric_check": "ok",
        "codec": codec,
        "codec_max_abs_err": codec_err,
        "codec_err_bound": codec_bound,
        "allreduce_algbw_gbps": round(algbw, 3),
        "allreduce_busbw_gbps": round(busbw, 3),
        # the driver's synthetic ICI probe (small shard: the number that
        # matters on CPU CI is that it RUNS; the real-chip run uses the
        # same code path at real sizes)
        "probe_busbw_gbps": round(
            bus_bandwidth_gbps(mesh, "dp", mbytes_per_shard=2.0,
                               iters=3), 3),
    }


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--codec", default="none",
                    choices=("none", "int8", "bf16"),
                    help="run the reduce leg through the quantizing "
                         "payload codec (lossy, asserted within its "
                         "documented bound)")
    args = ap.parse_args()
    print(json.dumps(run(codec=args.codec)))


if __name__ == "__main__":
    main()
