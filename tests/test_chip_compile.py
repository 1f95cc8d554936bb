"""The main path's programs compiled for a described TPU v5e, at GPT-2-small
width (on-chip-measurement §2, rehearsal 3): what the chip's compiler
refuses, or what does not fit 16 GB of HBM, fails here at no chip time.

Nothing runs: the programs are compiled from shapes.  The topology is
described inside a module fixture, never while a module is imported, so
every xdist worker collects the same tests and only the worker given this
file loads libtpu's compiler.  All such compiles live in this one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9          # TPU v5e: 16 GB per chip
SLOTS, MAX_LEN, PROMPT = 4, 1024, 128


@pytest.fixture(scope="module")
def topo():
    # only a missing TPU compiler skips; any other failure to describe
    # the topology is a failure of these tests
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # no chip is opened: another process's libtpu lockfile is no conflict
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _gpt2_small():
    from brpc_tpu.models.transformer import ModelConfig
    return ModelConfig(vocab=50257, d_model=768, n_heads=12, n_layers=12,
                       d_ff=3072, max_seq=MAX_LEN, n_experts=0,
                       dtype=jnp.bfloat16)


def _shapes(tree, sharding_of):
    """ShapeDtypeStructs of `tree` with a sharding per leaf path."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=sharding_of(path)),
        tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return used


def _single(topo, fn, *tree_args):
    one = SingleDeviceSharding(topo.devices[0])
    args = [_shapes(a, lambda _p: one) for a in tree_args]
    return jax.jit(fn).lower(*args).compile()


def _params_and_cache(cfg, slots):
    from brpc_tpu.models import decode as D
    from brpc_tpu.models import transformer
    params = jax.eval_shape(lambda k: transformer.init(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: D.init_cache(cfg, slots, MAX_LEN))
    return params, cache


def test_prefill_compiles_for_one_v5e(topo):
    from brpc_tpu.models import decode as D
    cfg = _gpt2_small()
    params, _ = _params_and_cache(cfg, SLOTS)
    toks = jax.ShapeDtypeStruct((1, PROMPT), jnp.int32)
    compiled = _single(topo, lambda p, t: D.prefill(p, t, cfg), params, toks)
    _fits(compiled)


def test_decode_step_compiles_for_one_v5e(topo):
    from brpc_tpu.models import decode as D
    cfg = _gpt2_small()
    params, cache = _params_and_cache(cfg, SLOTS)
    toks = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)
    compiled = _single(
        topo, lambda p, c, t, a: D.decode_step(p, c, t, a, cfg),
        params, cache, toks, active)
    # the KV cache alone is 2 x 12 x 4 x 1024 x 768 bf16 = 151 MB
    assert _fits(compiled) > 150 * 10**6


def test_decode_step_compiles_tp4_on_v5e_2x2(topo):
    """The tp=4 serving step: params in the param_specs layout, the cache
    head-sharded, one program across the 2x2 host's four chips."""
    import numpy as np
    from jax.sharding import Mesh
    from brpc_tpu.models import decode as D
    from brpc_tpu.models.transformer import param_specs
    from brpc_tpu.parallel.mesh import prune_spec
    cfg = _gpt2_small()
    mesh = Mesh(np.array(topo.devices[:4]), ("tp",))
    params, cache = _params_and_cache(cfg, SLOTS)
    specs = param_specs(cfg)
    flat_specs = {jax.tree_util.keystr(p): s for p, s in
                  jax.tree_util.tree_flatten_with_path(
                      specs, is_leaf=lambda x: isinstance(x, P))[0]}
    pshapes = _shapes(params, lambda path: NamedSharding(
        mesh, prune_spec(flat_specs[jax.tree_util.keystr(path)], mesh)))
    kv_spec = NamedSharding(mesh, P(None, None, None, "tp", None))
    rep = NamedSharding(mesh, P())
    cshapes = _shapes(cache, lambda path: rep
                      if jax.tree_util.keystr(path) == "['pos']" else kv_spec)
    toks = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rep)
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=rep)
    compiled = jax.jit(
        lambda p, c, t, a: D.decode_step(p, c, t, a, cfg, mesh)).lower(
            pshapes, cshapes, toks, active).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo
