"""GPT-2 (Radford et al. 2019; the Hugging Face ``gpt2`` configs): learned
positions, pre-LayerNorm blocks, multi-head attention, a tanh-GELU MLP
(``gelu_new``), a final LayerNorm and an LM head tied to the token
embedding.

This file holds, for every configuration of the family:

- ``weights``: random weights from the seed, made on the device in one
  jitted call, in the layout the serving engine takes (stacked per-layer
  leaves, float32 as the engine stores them).  The linear layers carry no
  bias: the served model has none, so the reference has none either.
- ``forward``/``gaps``/``control_tokens``: the plain reference, float32 at
  ``highest`` matmul precision, in ``jax.numpy`` alone.  It imports
  nothing of the program.
- the FLOP and byte counts of the work the model needs, for rooflines.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


@dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    n_positions: int
    eps: float

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def dims(config: dict) -> Dims:
    d = int(config["n_embd"])
    return Dims(vocab=int(config["vocab_size"]), d_model=d,
                n_heads=int(config["n_head"]),
                n_layers=int(config["n_layer"]),
                d_ff=int(config["n_inner"] or 4 * d),
                n_positions=int(config["n_positions"]),
                eps=float(config["layer_norm_epsilon"]))


def program_model(config: dict):
    """The serving engine's model config for this configuration."""
    from brpc_tpu.models.transformer import ModelConfig
    d = dims(config)
    return ModelConfig(vocab=d.vocab, d_model=d.d_model, n_heads=d.n_heads,
                       n_layers=d.n_layers, d_ff=d.d_ff,
                       max_seq=d.n_positions, n_experts=0,
                       dtype=getattr(jnp, config["serving"]["dtype"]))


# ---------------------------------------------------------------------------
# weights


def _key_data(seed: int) -> np.ndarray:
    from benchmark.traffic import seed_seq
    return seed_seq(seed, 0).generate_state(2, np.uint32)


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key_data, d: Dims):
    """Weights: normal with std 1/sqrt(fan_in); the query and key
    projections twice that, so that attention is sharp and each served
    token depends on the cached K/V; LayerNorm gains 1 + 0.1 N and biases
    0.1 N, so that a dropped LayerNorm parameter shows."""
    key = jax.random.wrap_key_data(key_data)
    ks = iter(jax.random.split(key, 16))
    L, D, H, hd, F = d.n_layers, d.d_model, d.n_heads, d.head_dim, d.d_ff

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "embed": normal((d.vocab, D), D ** -0.5),
        "pos": normal((d.n_positions, D), D ** -0.5),
        "ln_f": {"g": 1 + normal((D,), 0.1), "b": normal((D,), 0.1)},
        "blocks": {
            "ln1_g": 1 + normal((L, D), 0.1), "ln1_b": normal((L, D), 0.1),
            "ln2_g": 1 + normal((L, D), 0.1), "ln2_b": normal((L, D), 0.1),
            "wq": normal((L, D, H, hd), 2 * D ** -0.5),
            "wk": normal((L, D, H, hd), 2 * D ** -0.5),
            "wv": normal((L, D, H, hd), D ** -0.5),
            "wo": normal((L, H, hd, D), D ** -0.5),
            "w1": normal((L, D, F), D ** -0.5),
            "w2": normal((L, F, D), F ** -0.5),
        },
    }


def weights(config: dict, seed: int):
    return _init(jnp.asarray(_key_data(seed)), dims(config))


# ---------------------------------------------------------------------------
# the plain reference


def _ln(x, g, b, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi)
                                   * (x + 0.044715 * x ** 3)))


def forward(params, tokens, d: Dims, low=None):
    """tokens [B, T] -> logits [B, T, vocab], float32.  ``low`` (a dtype)
    rounds every matmul operand to it first: the control."""

    def r(a):
        return a if low is None else a.astype(low).astype(jnp.float32)

    def mm(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    T = tokens.shape[1]
    x = params["embed"][tokens] + params["pos"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, w):
        h = _ln(x, w["ln1_g"], w["ln1_b"], d.eps)
        q = mm("btd,dhk->bthk", h, w["wq"])
        k = mm("btd,dhk->bthk", h, w["wk"])
        v = mm("btd,dhk->bthk", h, w["wv"])
        s = mm("bthk,bshk->bhts", q, k) / np.sqrt(d.head_dim)
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        a = e / jnp.sum(e, -1, keepdims=True)
        o = mm("bhts,bshk->bthk", a, v)
        x = x + mm("bthk,hkd->btd", o, w["wo"])
        h = _ln(x, w["ln2_g"], w["ln2_b"], d.eps)
        x = x + mm("btf,fd->btd", _gelu_new(mm("btd,df->btf", h, w["w1"])),
                   w["w2"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _ln(x, params["ln_f"]["g"], params["ln_f"]["b"], d.eps)
    return mm("btd,vd->btv", x, params["embed"])


def _gap(logits, pick):
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, pick[..., None], -1)[..., 0]


@functools.partial(jax.jit, static_argnums=(3,))
def gaps(params, tokens, targets, d: Dims):
    """tokens [1, T], targets [T] (the token after each position) ->
    how far each target's reference logit lies below the best, [T]."""
    return _gap(forward(params, tokens, d)[0], targets)


@functools.partial(jax.jit, static_argnums=(2, 3))
def control_tokens(params, tokens, d: Dims, low):
    """The control: at each position the token that the reference in
    precision ``low`` puts first, [T]."""
    return jnp.argmax(forward(params, tokens, d, low)[0], -1)


# ---------------------------------------------------------------------------
# the work the model needs, at the configuration's bfloat16


BF16_BYTES = 2


def matmul_params(d: Dims) -> int:
    """Parameters that enter a matmul per token, the LM head included."""
    return d.n_layers * (4 * d.d_model * d.d_model
                         + 2 * d.d_model * d.d_ff) + d.vocab * d.d_model


def kv_bytes_per_token(d: Dims) -> int:
    return 2 * d.n_layers * d.d_model * BF16_BYTES


def decode_flops(d: Dims, n_active: int, ctx_sum: int) -> float:
    """One decode step of `n_active` sequences whose new tokens attend to
    `ctx_sum` positions in all (each its own length, the new one in)."""
    return (2.0 * matmul_params(d) * n_active
            + 4.0 * d.n_layers * d.d_model * ctx_sum)


def decode_bytes(d: Dims, n_active: int, ctx_sum: int) -> float:
    """Weights once; the K/V of the positions attended, the new one
    written in place of being read."""
    del n_active
    return (BF16_BYTES * matmul_params(d)
            + kv_bytes_per_token(d) * ctx_sum)


def prefill_flops(d: Dims, s: int) -> float:
    """A prompt of `s` tokens: every projection on every token, causal
    attention, the LM head on the last position only."""
    per_tok = d.n_layers * (4 * d.d_model * d.d_model
                            + 2 * d.d_model * d.d_ff)
    return (2.0 * per_tok * s + 2.0 * d.n_layers * d.d_model * s * (s + 1)
            + 2.0 * d.vocab * d.d_model)


def prefill_bytes(d: Dims, s: int) -> float:
    return BF16_BYTES * matmul_params(d) + kv_bytes_per_token(d) * s
