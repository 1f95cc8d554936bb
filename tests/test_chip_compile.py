"""The main path's programs compiled for a described TPU v5e, at GPT-2-small
width and at DeepSeek-V2-Lite's (one chip's share of its four-chip
deployment): what the chip's compiler refuses, or what does not fit 16 GB
of HBM, fails here at no chip time.

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
    """The tree the engine's programs read (decode.serving_params)."""
    from brpc_tpu.models import decode as D
    from brpc_tpu.models import transformer
    params = jax.eval_shape(
        lambda k: D.serving_params(transformer.init(k, cfg), cfg),
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
    specs = dict(param_specs(cfg))
    specs["head"] = specs["embed"]       # serving_params: embed's sharding
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



def _gpt2_xl():
    from brpc_tpu.models.transformer import ModelConfig
    return ModelConfig(vocab=50257, d_model=1600, n_heads=25, n_layers=48,
                       d_ff=6400, max_seq=MAX_LEN, n_experts=0,
                       dtype=jnp.bfloat16)


@pytest.mark.parametrize("model,slots,temp_bound", [
    # the small_sharegpt cell's step
    (_gpt2_small, 16, 64 * 10**6),
    # xl_decode's: its temporaries also hold the float32 embedding table's
    # copy, 50257 x 1600 x 4 bytes (321.6 MB)
    (_gpt2_xl, 2, 64 * 10**6 + 50257 * 1600 * 4),
], ids=["gpt2-small", "gpt2-xl"])
def test_decode_step_writes_cache_in_one_pass(topo, model, slots,
                                              temp_bound):
    """The cache is read in its own layout inside the layer loop and
    written once after it: no instruction of the cache's shape is a copy
    (a layout round trip) or a scatter, and the step's temporaries hold
    no whole-cache buffer (a per-layer scatter needs 2.42 GB of them at
    gpt2-small's shape)."""
    import re
    from brpc_tpu.models import decode as D
    cfg = model()
    params, cache = _params_and_cache(cfg, slots)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    compiled = _single(
        topo, lambda p, c, t, a: D.decode_step(p, c, t, a, cfg),
        params, cache, toks, active)
    shape = "bf16[%d,%d,%d,%d,%d]" % cache["k"].shape
    ops = re.findall(r"= %s\{[^}]*\} ([\w-]+)\(" % re.escape(shape),
                     compiled.as_text())
    assert not {"copy", "scatter"} & set(ops), ops
    assert "select" in ops, ops
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_bound, temp


# ---------------------------------------------------------------------------
# DeepSeek-V2-Lite, 16 of 64 experts held: the dsv2lite_sharegpt cell's
# programs at its sizes

DS_SLOTS, DS_PROMPT = 32, 512


def _deepseek(topo):
    """The program's config, the serving tree's shapes on one described
    chip (made by the benchmark's family, in bf16), and that sharding."""
    import json
    from benchmark.spec import ROOT, load_module
    fam = load_module(os.path.join(ROOT, "benchmark", "models",
                                   "deepseek_v2.py"), "family_deepseek_v2")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v2-lite-ep4.json")) as f:
        config = json.load(f)
    one = SingleDeviceSharding(topo.devices[0])
    params = _shapes(jax.eval_shape(lambda: fam.weights(config, 0)),
                     lambda _p: one)
    return fam.program_model(config), params, one


def _pool_worst(cfg):
    """Every slot at its longest sequence (1024 tokens) in the KV pool's
    16-token blocks of float32 latents."""
    from brpc_tpu.models import decode as D
    return DS_SLOTS * (MAX_LEN // 16) * 16 * D.kv_bytes_per_token(cfg)


def test_deepseek_decode_step_compiles_for_one_v5e(topo):
    """32 slots over a 1024-position latent cache: the weights (9.83 GB),
    the cache in and out, the step's temporaries and the pool's worst
    case (2.04 GB) fit the chip."""
    from brpc_tpu.models import decode as D
    cfg, params, one = _deepseek(topo)
    cache = _shapes(jax.eval_shape(
        lambda: D.init_cache(cfg, DS_SLOTS, MAX_LEN)), lambda _p: one)
    toks = jax.ShapeDtypeStruct((DS_SLOTS,), jnp.int32, sharding=one)
    active = jax.ShapeDtypeStruct((DS_SLOTS,), jnp.bool_, sharding=one)
    compiled = jax.jit(
        lambda p, c, t, a: D.decode_step(p, c, t, a, cfg)).lower(
            params, cache, toks, active).compile()
    used = _fits(compiled)
    assert used > D.param_bytes(params) + 27 * DS_SLOTS * MAX_LEN * 576 * 2
    assert used + _pool_worst(cfg) < HBM_BYTES, used


def test_deepseek_prefill_compiles_for_one_v5e(topo):
    """The traffic's longest prompt, 512 tokens, with the held experts as
    grouped matmuls."""
    from brpc_tpu.models import decode as D
    cfg, params, one = _deepseek(topo)
    toks = jax.ShapeDtypeStruct((1, DS_PROMPT), jnp.int32, sharding=one)
    compiled = jax.jit(lambda p, t: D.prefill(p, t, cfg)).lower(
        params, toks).compile()
    assert _fits(compiled) + _pool_worst(cfg) < HBM_BYTES
    assert "ragged" in compiled.as_text()
