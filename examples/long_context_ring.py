"""Long-context forward with ring attention: sequence sharded over sp,
K/V blocks rotating on the ICI ring, O(S/n) HBM per chip
(parallel/ring_attention.py)."""
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from brpc_tpu.models import ModelConfig, apply, init  # noqa: E402
from brpc_tpu.models.transformer import place_params  # noqa: E402
from brpc_tpu.parallel.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = ModelConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                      d_ff=128, max_seq=4096, attn_impl="ring",
                      dtype=jnp.float32)
    params = place_params(init(jax.random.key(0), cfg), cfg, mesh)
    S = 4096  # 2048 per sp shard; K/V never materialize full-S per chip
    tokens = jax.device_put(jnp.zeros((2, S), jnp.int32),
                            NamedSharding(mesh, P("dp", None)))
    logits = jax.jit(lambda p, t: apply(p, t, cfg, mesh))(params, tokens)
    logits.block_until_ready()
    print(f"ring-attention forward: seq={S} over sp={mesh.shape['sp']} "
          f"→ logits {logits.shape} ok")


if __name__ == "__main__":
    main()
