"""Latent attention with routed and shared experts (models/mla_moe.py),
served through models/decode.py, against the plain reference
(benchmark/models/deepseek_v2.py) at a tiny DeepSeek-V2 shape on the CPU:
hidden 64, 3 layers of which the first is dense, 8 routed experts of
which 4 are held, top-2, one shared expert, latent 32, rope 8."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark_harness"))
import deepseek_tiny  # noqa: E402

sys.path.insert(0, deepseek_tiny.ROOT)
from benchmark.spec import load_module  # noqa: E402
from brpc_tpu.models import decode as D  # noqa: E402
from brpc_tpu.models import mla_moe  # noqa: E402
from brpc_tpu.models.transformer import ModelConfig  # noqa: E402

PROMPT = [5, 17, 3, 99, 42, 7, 64, 1, 88, 23, 11, 60, 301, 2]
FORCED = [100, 7, 250, 31, 499, 12, 77, 404]     # decoded after PROMPT
SLOTS, SLOT = 3, 1


def fam():
    return load_module(deepseek_tiny.FAMILY, "family_deepseek_v2")


def _model(**over):
    config = deepseek_tiny.config(**over)
    f = fam()
    cfg = f.program_model(config)
    return f, f.dims(config), cfg, D.serving_params(f.weights(config, 3),
                                                    cfg)


def _served_logits(cfg, params):
    """Prefill PROMPT, carry its state over the host bytes into slot SLOT
    of a cache, then decode FORCED through it with the other slots idle:
    the logits after the prompt and after each forced token."""
    logits, state = jax.jit(lambda p, t: D.prefill(p, t, cfg))(
        params, jnp.asarray([PROMPT], jnp.int32))
    data = D.kv_to_bytes(tuple(a[:, 0] for a in state))
    assert len(data) == len(PROMPT) * D.kv_bytes_per_token(cfg)
    cache = D.install(D.init_cache(cfg, SLOTS, 64), SLOT,
                      D.kv_from_bytes(data, cfg, len(PROMPT)), len(PROMPT))
    step = jax.jit(lambda p, c, t, a: D.decode_step(p, c, t, a, cfg))
    active = jnp.zeros((SLOTS,), bool).at[SLOT].set(True)
    rows = [np.asarray(logits[0])]
    for tok in FORCED[:-1]:
        lg, cache, counts = step(params, cache,
                                 jnp.full((SLOTS,), tok, jnp.int32), active)
        rows.append(np.asarray(lg[SLOT]))
    assert int(cache["pos"][SLOT]) == len(PROMPT) + len(FORCED) - 1
    return np.stack(rows)


def _reference_logits(f, d, params):
    seq = jnp.asarray([PROMPT + FORCED[:-1]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(f.forward(params, seq, d))[0]
    return full[len(PROMPT) - 1:]


# float32 agrees to rounding.  bfloat16 rounds every matmul operand, the
# cached latents and each residual update to an 8-bit mantissa (a relative
# 2^-9 each): on logits up to 4 that leaves errors of median 0.011 and a
# tail to 0.09 at this seed, so the widest may be 0.2 and the median 0.03,
# each several times under what the float8 control moves them (a gap of
# 1.4 and more in tests/benchmark_harness/test_bench_deepseek.py).
@pytest.mark.parametrize("dtype,tol,median", [("float32", 1e-4, 1e-5),
                                              ("bfloat16", 0.2, 0.03)])
def test_prefill_then_decode_agrees_with_the_reference(dtype, tol, median):
    f, d, cfg, params = _model(serving={"dtype": dtype})
    got = _served_logits(cfg, params)
    want = _reference_logits(f, d, params)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)
    assert np.median(np.abs(got - want)) < median


# The bfloat16 program rounds no more than bfloat16 itself: its logits lie
# as far from the float32 reference as those of the reference with every
# matmul operand rounded to bfloat16 do (median ratio 0.81-1.17 over six
# seeds at both gains; a query rounded to float8 reads 4.2-4.3).  Gain 1.5
# on the query and key weights makes attention sharp, as a trained
# model's is, which doubles both errors alike.
@pytest.mark.parametrize("qk_gain", [1.0, 1.5])
def test_bfloat16_program_rounds_as_the_bfloat16_reference(qk_gain,
                                                           monkeypatch):
    f = fam()
    monkeypatch.setattr(f, "QK_GAIN", qk_gain)
    f._init.clear_cache()
    try:
        _, d, cfg, params = _model(serving={"dtype": "bfloat16"})
        got = _served_logits(cfg, params)
        want = _reference_logits(f, d, params)
        seq = jnp.asarray([PROMPT + FORCED[:-1]], jnp.int32)
        low = np.asarray(f.forward(params, seq, d, jnp.bfloat16))[0]
    finally:
        f._init.clear_cache()
    ratio = (np.median(np.abs(got - want))
             / np.median(np.abs(low[len(PROMPT) - 1:] - want)))
    assert ratio < 1.3, ratio


def test_absorbed_decode_attention_equals_the_decompressed_form():
    """Token t decoded against the latents of tokens 0..t-1 (Wk_b folded
    into the query, Wv_b into the output) gives prefill's attention
    output at t, where the latent is decompressed into per-head K/V, and
    the latent prefill caches for it."""
    _, _, cfg, params = _model(serving={"dtype": "float32"})
    w = jax.tree.map(lambda a: a[0], params["moe"])
    S = 12
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    want, lat = mla_moe._attn_prefill(x, w, cfg)
    # past positions only: the rest of the cache must not be read
    cache = jnp.full((1, cfg.latent, S), 7.0)
    for t in range(S):
        p = jnp.asarray([t])
        cos, sin = mla_moe._rope_tables(p, cfg)
        got, new = mla_moe._attn_decode(x[:, t], w, cache, p, cos, sin, cfg)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want[0, t]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[0]), np.asarray(lat[0, t]),
                                   atol=1e-6)
        cache = cache.at[0, :, t].set(new[0])


@pytest.mark.parametrize("form", ["every_held", "grouped"])
def test_expert_shares_add_up_to_the_uncut_layer(form):
    """Four chips' shares of the 8 routed experts (2 each): their
    outputs, less the shared expert that each of them computes too
    (counted once), add up to the layer that holds all 8."""
    def layer(cfg, w, h):
        if form == "grouped":
            return mla_moe.moe_grouped(h, w, cfg)
        return mla_moe.moe_every_held(h, w, cfg)[0]

    _, _, whole, params = _model(serving={"dtype": "float32"},
                                 n_routed_experts=8,
                                 deployment={"experts_here": [0, 8]})
    w = jax.tree.map(lambda a: a[0], params["moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (10, whole.d_model))
    uncut = layer(whole, w, h)
    shared = mla_moe._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    total = 0.0
    for lo in range(0, 8, 2):
        cfg = dataclasses.replace(whole, expert_lo=lo, n_held=2)
        part = {k: v[lo:lo + 2] if k.startswith("we_") else v
                for k, v in w.items()}
        total = total + layer(cfg, part, h)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(uncut), atol=1e-5)
    # the held experts do part of the work: dropping them changes it
    assert np.abs(np.asarray(uncut - shared)).max() > 0.05


def test_yarn_constants_at_published_sizes():
    """DeepSeek-V2-Lite: YaRN ramps between rope pairs 10 and 23
    (beta_fast 32, beta_slow 1, 4096 original positions), and scores
    scale by 192^-1/2 (0.1 x 0.707 x ln 40 + 1)^2 = 0.114721, in the
    program and the reference alike."""
    cfg = mla_moe.MlaMoeConfig()
    assert mla_moe.yarn_range(cfg) == (10, 23)
    assert mla_moe.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    with open(os.path.join(deepseek_tiny.ROOT, "benchmark", "configs",
                           "deepseek-v2-lite-ep4.json")) as f:
        d = fam().dims(json.load(f))
    assert fam().yarn_range(d) == (10, 23)
    assert fam().softmax_scale(d) == mla_moe.softmax_scale(cfg)
    f = mla_moe.rope_freqs(cfg)
    assert f[0] == 1.0 and f[9] == 10000 ** (-18 / 64)
    assert f[31] == pytest.approx(10000 ** (-62 / 64) / 40)


def test_bridge_round_trips_the_latent_state():
    """One latent array [L, S, kv_rank + rope] crosses the host bytes
    bit for bit; at published widths a token is 27 x 576 float32 values,
    62,208 bytes (the bf16 cache holds half that)."""
    assert D.kv_bytes_per_token(mla_moe.MlaMoeConfig(n_held=16)) == 62_208
    _, _, cfg, _ = _model()
    lat = np.random.default_rng(0).standard_normal(
        (cfg.n_layers, 9, cfg.latent)).astype(np.float32)
    back = D.kv_from_bytes(D.kv_to_bytes((lat,)), cfg, 9)
    assert len(back) == 1 and back[0].shape == lat.shape
    np.testing.assert_array_equal(back[0], lat)
    cache = D.install(D.init_cache(cfg, 2, 16), 1, back, 9)
    np.testing.assert_array_equal(
        np.asarray(cache["latent"][:, 1, :, :9], np.float32),
        np.asarray(jnp.asarray(lat, cfg.dtype), np.float32).transpose(
            0, 2, 1))
    assert cache["pos"].tolist() == [0, 9]


def test_routing_counters_equal_a_numpy_count():
    """On a fixed batch with one slot idle, the step's counters are the
    active slots' picks, those of held experts, and the distinct held
    experts picked, each summed over the MoE layers."""
    _, _, cfg, params = _model(serving={"dtype": "float32"})
    tokens = jnp.asarray([3, 77, 400, 9], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    cache = D.init_cache(cfg, 4, 16)
    cache["pos"] = jnp.asarray([5, 0, 2, 11], jnp.int32)
    _, _, counts = jax.jit(
        lambda p, c, t, a: D.decode_step(p, c, t, a, cfg))(
            params, cache, tokens, active)

    # the router inputs, layer by layer, from the program's own blocks
    x = params["embed"][tokens].astype(jnp.float32)
    p = cache["pos"]
    cos, sin = mla_moe._rope_tables(p, cfg)
    want = np.zeros(3, int)
    act = np.asarray(active)
    for i in range(cfg.n_layers):
        kind = "dense" if i < cfg.n_dense else "moe"
        w = jax.tree.map(lambda a: a[i if kind == "dense" else
                                     i - cfg.n_dense], params[kind])
        a, _ = mla_moe._attn_decode(x, w, cache["latent"][i], p, cos, sin,
                                    cfg)
        x = x + a
        h = mla_moe._rms(x, w["mlp_norm"], cfg.eps)
        if kind == "dense":
            x = x + mla_moe._dense_mlp(h, w, cfg)
            continue
        logits = np.asarray(h, np.float64) @ np.asarray(w["router"],
                                                        np.float64)
        picks = np.argsort(-logits, -1, kind="stable")[:, :cfg.topk][act]
        held = (picks >= cfg.expert_lo) & (picks < cfg.expert_lo
                                           + cfg.n_held)
        want += [picks.size, held.sum(), len(set(picks[held].tolist()))]
        x = x + mla_moe.moe_every_held(h, w, cfg)[0]
    assert want[1] > 0
    assert np.asarray(counts).tolist() == want.tolist()
    assert D.step_counters(cfg) == ("moe_picks", "moe_local_picks",
                                    "moe_experts_hit")
    assert D.step_counters(ModelConfig(n_experts=0)) == ()


def test_trees_and_configs_the_programs_refuse():
    """A tree whose matmul weights are not in the serving dtype, or with
    a leaf missing, is refused when traced; a mesh is refused (the MLA/MoE
    programs run on one chip); a dense ModelConfig that names experts is
    refused with what to use instead."""
    f = fam()
    config = deepseek_tiny.config(serving={"dtype": "bfloat16"})
    cfg = f.program_model(config)
    raw = f.weights(deepseek_tiny.config(serving={"dtype": "float32"}), 3)
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="moe.*we_gate"):
        D.prefill(raw, toks, cfg)
    with pytest.raises(ValueError, match="moe.*we_gate"):
        D.serving_params(raw, cfg)
    headless = {k: v for k, v in f.weights(config, 3).items()
                if k != "head"}
    with pytest.raises(ValueError, match="head"):
        D.prefill(headless, toks, cfg)
    from brpc_tpu.parallel.mesh import make_mesh
    with pytest.raises(ValueError, match="one chip"):
        D.init_cache(cfg, 2, 16, make_mesh({"dp": 8}))
    with pytest.raises(ValueError, match="MlaMoeConfig"):
        D.init_cache(ModelConfig(n_experts=4), 2, 16)
    with pytest.raises(ValueError, match="outside the router"):
        mla_moe.MlaMoeConfig(n_routed=8, expert_lo=6, n_held=4)
