"""The serving tree (models/decode.serving_params): the programs read
their matmul weights in cfg.dtype, cast once at load, never per call.

Checked on a bf16 config small enough for the CPU, on one device and on
the 8-device CPU mesh: the programs fed the serving tree match, bit for
bit, the arrangement that cast the float32 weights inside each program;
greedy tokens agree with transformer.apply on the float32 tree; the tree
is the size the engine reports; a float32 tree is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import decode as D
from brpc_tpu.models import transformer
from brpc_tpu.models.transformer import ModelConfig
from brpc_tpu.parallel.mesh import make_mesh
from brpc_tpu.serving.engine import tiny_config

SLOTS, STEPS = 2, 40
PROMPTS = ([5, 17, 3, 99, 42, 7, 64, 1, 88, 23, 11, 60],
           [101, 2, 37, 9, 14, 77, 120])
# a served token's apply() logit may lie this far below apply()'s best:
# the near-tie tolerance of chip_smoke.py's cross-layout token check
NEAR_TIE = 0.1


def _bf16():
    return tiny_config(dtype=jnp.bfloat16)


def _gpt2(d_model, n_heads, n_layers, d_ff):
    return ModelConfig(vocab=50257, d_model=d_model, n_heads=n_heads,
                       n_layers=n_layers, d_ff=d_ff, max_seq=1024,
                       n_experts=0, dtype=jnp.bfloat16)


def _mesh(kind):
    return None if kind == "one" else make_mesh({"dp": 2, "tp": 4})


def _params32(cfg, mesh):
    p = transformer.init(jax.random.PRNGKey(7), cfg)
    return p if mesh is None else transformer.place_params(p, cfg, mesh)


def _equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))


def _serve(cfg, mesh, prefill, step, params):
    """Prefill each prompt into its own slot, then STEPS greedy decode
    steps on both slots: every program output, and the tokens."""
    cache = D.init_cache(cfg, SLOTS, cfg.max_seq, mesh)
    outs, last = [], []
    for slot, prompt in enumerate(PROMPTS):
        logits, state = prefill(params, jnp.asarray([prompt], jnp.int32))
        outs.append((logits, state))
        cache = D.install(cache, slot, tuple(a[:, 0] for a in state),
                          len(prompt))
        last.append(int(np.argmax(np.asarray(logits[0]))))
    tokens = [[t] for t in last]
    active = jnp.ones((SLOTS,), bool)
    for _ in range(STEPS):
        logits, cache = step(params, cache, jnp.asarray(last, jnp.int32),
                             active)
        outs.append((logits, cache))
        last = [int(t) for t in np.argmax(np.asarray(logits), -1)]
        for s in range(SLOTS):
            tokens[s].append(last[s])
    return outs, tokens


@pytest.mark.parametrize("mesh_kind", ["one", "mesh"])
def test_serving_tree_matches_casting_inside_bit_for_bit(mesh_kind):
    """The parent's arrangement, casting inside one jitted call, and the
    serving tree give the same prefill logits and K/V and, over 40 decode
    steps, the same logits and cache, bit for bit."""
    cfg, mesh = _bf16(), _mesh(mesh_kind)
    p32 = _params32(cfg, mesh)
    sp = D.serving_params(p32, cfg)
    for name in D.MATMUL_WEIGHTS:
        assert sp["blocks"][name].dtype == jnp.bfloat16
        assert sp["blocks"][name].sharding == p32["blocks"][name].sharding
    assert sp["head"].dtype == jnp.bfloat16
    assert sp["head"].sharding == p32["embed"].sharding
    for leaf in ("embed", "pos"):
        assert sp[leaf] is p32[leaf]

    inside = _serve(
        cfg, mesh,
        jax.jit(lambda p, t: D.prefill(D.serving_params(p, cfg), t, cfg,
                                       mesh)),
        jax.jit(lambda p, c, t, a: D.decode_step(D.serving_params(p, cfg),
                                                 c, t, a, cfg, mesh)),
        p32)
    at_rest = _serve(
        cfg, mesh,
        jax.jit(lambda p, t: D.prefill(p, t, cfg, mesh)),
        jax.jit(lambda p, c, t, a: D.decode_step(p, c, t, a, cfg, mesh)),
        sp)
    assert inside[1] == at_rest[1]
    _equal(inside[0], at_rest[0])


@pytest.mark.parametrize("mesh_kind", ["one", "mesh"])
def test_greedy_tokens_agree_with_apply(mesh_kind):
    """Each token the serving tree's programs pick is apply()'s best on
    the float32 tree, teacher-forced, up to a near tie."""
    cfg, mesh = _bf16(), _mesh(mesh_kind)
    p32 = _params32(cfg, mesh)
    _, tokens = _serve(
        cfg, mesh, jax.jit(lambda p, t: D.prefill(p, t, cfg, mesh)),
        jax.jit(lambda p, c, t, a: D.decode_step(p, c, t, a, cfg, mesh)),
        D.serving_params(p32, cfg))
    apply = jax.jit(lambda p, t: transformer.apply(p, t, cfg))
    exact = 0
    for prompt, served in zip(PROMPTS, tokens):
        seq = jnp.asarray([prompt + served[:-1]], jnp.int32)
        lg = np.asarray(apply(p32, seq)[0, len(prompt) - 1:])
        gap = lg.max(-1) - lg[np.arange(len(served)), served]
        assert gap.max() <= NEAR_TIE, gap
        exact += int((np.argmax(lg, -1) == np.asarray(served)).sum())
    assert exact >= 0.9 * SLOTS * (STEPS + 1), exact


@pytest.mark.parametrize("name,cfg,f32_bytes,serving_bytes", [
    ("gpt2-xl", _gpt2(1600, 25, 48, 6400), 6_227_680_000, 3_439_382_400),
    ("gpt2-small", _gpt2(768, 12, 12, 3072), 497_427_456, 404_752_896),
])
def test_param_bytes_at_published_shapes(name, cfg, f32_bytes,
                                         serving_bytes):
    """Blocks in bf16, embed float32, head bf16, pos and LayerNorm
    float32; from shapes alone."""
    p32 = jax.eval_shape(lambda k: transformer.init(k, cfg),
                         jax.random.PRNGKey(0))
    sp = jax.eval_shape(lambda p: D.serving_params(p, cfg), p32)
    assert D.param_bytes(p32) == f32_bytes, name
    assert D.param_bytes(sp) == serving_bytes, name


def test_float32_config_keeps_every_leaf():
    """With cfg.dtype float32 the serving tree holds the same arrays, and
    `head` is embed itself, counted once."""
    cfg = tiny_config()
    p32 = transformer.init(jax.random.PRNGKey(0), cfg)
    sp = D.serving_params(p32, cfg)
    assert sp["head"] is p32["embed"]
    for a, b in zip(jax.tree.leaves(p32["blocks"]),
                    jax.tree.leaves(sp["blocks"])):
        assert a is b
    assert D.param_bytes(sp) == D.param_bytes(p32)


def test_float32_tree_is_refused():
    """A raw float32 tree, or one without `head`, fails at trace time in
    both programs instead of running float32 matmuls."""
    cfg = _bf16()
    p32 = transformer.init(jax.random.PRNGKey(0), cfg)
    cache = D.init_cache(cfg, SLOTS, cfg.max_seq)
    toks = jnp.zeros((SLOTS,), jnp.int32)
    active = jnp.ones((SLOTS,), bool)
    headless = dict(D.serving_params(p32, cfg))
    del headless["head"]
    for tree, what in ((p32, "blocks.wq"), (headless, "head")):
        with pytest.raises(ValueError, match=what):
            jax.jit(lambda p, t: D.prefill(p, t, cfg))(
                tree, jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(ValueError, match=what):
            jax.jit(lambda p, c, t, a: D.decode_step(p, c, t, a, cfg))(
                tree, cache, toks, active)


# the cache of the step tests below: short enough that a slot reaches
# its last position in a few steps
SHORT_LEN, SHORT_STEPS = 16, 4


@pytest.mark.parametrize("lengths,active", [
    ((5, 9), (True, True)),
    ((5, 9), (True, False)),
    # slot 1's last step writes at SHORT_LEN - 1, the clamp's edge
    ((5, SHORT_LEN - SHORT_STEPS), (True, True)),
], ids=["all_active", "one_inactive", "last_position"])
def test_step_matches_forward_and_writes_only_at_pos(lengths, active):
    """Float32, teacher-forced: each active slot's step logits are
    apply()'s at that position, and the step's cache differs from the one
    it was given only at [:, n, p_n] for each slot n, where an active
    slot holds prefill()'s K/V of its sequence; `pos` advances only for
    active slots."""
    cfg = tiny_config()
    p32 = transformer.init(jax.random.PRNGKey(3), cfg)
    sp = D.serving_params(p32, cfg)
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, cfg.vocab, n + SHORT_STEPS).tolist()
            for n in lengths]
    want = [np.asarray(transformer.apply(p32, jnp.asarray([s]), cfg)[0])
            for s in seqs]
    full = [D.prefill(sp, jnp.asarray([s], jnp.int32), cfg)[1] for s in seqs]
    cache = D.init_cache(cfg, SLOTS, SHORT_LEN)
    for n, s in enumerate(seqs):
        _, state = D.prefill(sp, jnp.asarray([s[:lengths[n]]], jnp.int32),
                             cfg)
        cache = D.install(cache, n, tuple(a[:, 0] for a in state),
                          lengths[n])
    step = jax.jit(lambda p, c, t, a: D.decode_step(p, c, t, a, cfg))
    on = jnp.asarray(active)
    for _ in range(SHORT_STEPS):
        pos = np.asarray(cache["pos"])
        feed = jnp.asarray([s[p] for s, p in zip(seqs, pos)], jnp.int32)
        logits, out = step(sp, cache, feed, on)
        np.testing.assert_array_equal(np.asarray(out["pos"]),
                                      pos + np.asarray(active))
        written = np.zeros(cache["k"].shape[:3], bool)
        written[:, np.arange(SLOTS), pos] = True
        for leaf, i in (("k", 0), ("v", 1)):
            old, new = np.asarray(cache[leaf]), np.asarray(out[leaf])
            np.testing.assert_array_equal(new[~written], old[~written])
            for n in np.flatnonzero(active):
                np.testing.assert_allclose(
                    new[:, n, pos[n]],
                    np.asarray(full[n][i][:, 0, pos[n]]), atol=1e-5)
        for n in np.flatnonzero(active):
            np.testing.assert_allclose(np.asarray(logits[n]),
                                       want[n][pos[n]], atol=1e-5)
        cache = out
