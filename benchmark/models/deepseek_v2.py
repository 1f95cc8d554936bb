"""DeepSeek-V2 (DeepSeek-AI 2024, arXiv:2405.04434; the Hugging Face
``deepseek-ai/DeepSeek-V2-Lite`` config): multi-head latent attention
without a query LoRA, YaRN rotary positions, RMSNorm, a leading dense
SwiGLU layer, then layers of routed SwiGLU experts (float32 softmax
router, greedy top-k, not renormalised) beside shared experts, and an
untied LM head.

A configuration holds one chip's share of an expert-parallel deployment:
``n_routed_experts`` is the number of experts held here, and
``deployment`` gives the router's width (``n_routed_experts`` there) and
which experts these are (``experts_here``).  The reference computes the
same share: the other experts' part of each layer is left out, as in the
program.

This file holds, for every configuration of the family:

- ``weights``: random weights from the seed, made on the device in the
  serving engine's layout (stacked per layer kind) and dtypes: every matmul
  weight in the configuration's dtype, made one layer (or one block of
  rows) at a time so that no float32 copy of a whole stacked leaf exists;
  the RMSNorm gains and the router in float32.
- ``forward``/``gaps``/``control_tokens``: the plain reference, float32 at
  ``highest`` matmul precision, in ``jax.numpy`` alone, one layer's weights
  upcast at a time inside a ``lax.scan``.  It imports nothing of the
  program.  Rotary positions turn interleaved pairs (2i, 2i+1): Hugging
  Face's modelling code permutes the rope columns of q and k to halves
  before ``rotate_half``, the same permutation for both, which leaves
  q_pe . k_pe equal to the interleaved rotation here.
- the FLOP and byte counts of the work the model needs, for rooflines;
  routed-expert work counts only the experts touched.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
F32 = jnp.float32
# what the program and this reference implement; anything else is refused
SUPPORTED = {"q_lora_rank": None, "moe_layer_freq": 1, "n_group": 1,
             "topk_group": 1, "topk_method": "greedy",
             "scoring_func": "softmax", "norm_topk_prob": False,
             "routed_scaling_factor": 1, "tie_word_embeddings": False,
             "hidden_act": "silu", "attention_bias": False}
QK_GAIN = 1.0        # the query's and key's std over 1/sqrt(fan_in)
ROUTER_GAIN = 3.0    # the router's std over 1/sqrt(fan_in)
NORM_STD = 0.1       # RMSNorm gains 1 + NORM_STD N


@dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    n_dense: int
    d_ff: int
    n_routed: int        # the router's width
    topk: int
    expert_lo: int
    n_held: int
    d_expert: int
    d_shared: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    rope_theta: float
    rope_factor: float
    rope_orig_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    eps: float
    n_positions: int
    dtype: str

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def latent(self) -> int:
        return self.kv_rank + self.qk_rope


def dims(config: dict) -> Dims:
    off = {k: config.get(k) for k, v in SUPPORTED.items()
           if config.get(k) != v}
    if off:
        raise ValueError(f"deepseek_v2: unsupported settings {off}")
    rs, dep = config["rope_scaling"], config["deployment"]
    lo, hi = dep["experts_here"]
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("deployment.experts_here does not hold "
                         "n_routed_experts experts")
    return Dims(vocab=int(config["vocab_size"]),
                d_model=int(config["hidden_size"]),
                n_heads=int(config["num_attention_heads"]),
                n_layers=int(config["num_hidden_layers"]),
                n_dense=int(config["first_k_dense_replace"]),
                d_ff=int(config["intermediate_size"]),
                n_routed=int(dep["n_routed_experts"]),
                topk=int(config["num_experts_per_tok"]),
                expert_lo=int(lo), n_held=int(config["n_routed_experts"]),
                d_expert=int(config["moe_intermediate_size"]),
                d_shared=int(config["n_shared_experts"]
                             * config["moe_intermediate_size"]),
                kv_rank=int(config["kv_lora_rank"]),
                qk_nope=int(config["qk_nope_head_dim"]),
                qk_rope=int(config["qk_rope_head_dim"]),
                v_dim=int(config["v_head_dim"]),
                rope_theta=float(config["rope_theta"]),
                rope_factor=float(rs["factor"]),
                rope_orig_max=int(rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"]),
                eps=float(config["rms_norm_eps"]),
                n_positions=int(config["max_position_embeddings"]),
                dtype=config["serving"]["dtype"])


def program_model(config: dict):
    """The serving engine's model config for this configuration."""
    from brpc_tpu.models.mla_moe import MlaMoeConfig
    d = dims(config)
    return MlaMoeConfig(
        vocab=d.vocab, d_model=d.d_model, n_heads=d.n_heads,
        n_layers=d.n_layers, n_dense=d.n_dense, d_ff=d.d_ff,
        n_routed=d.n_routed, topk=d.topk, expert_lo=d.expert_lo,
        n_held=d.n_held, d_expert=d.d_expert, d_shared=d.d_shared,
        kv_rank=d.kv_rank, qk_nope=d.qk_nope, qk_rope=d.qk_rope,
        v_dim=d.v_dim, rope_theta=d.rope_theta, rope_factor=d.rope_factor,
        rope_orig_max=d.rope_orig_max, beta_fast=d.beta_fast,
        beta_slow=d.beta_slow, mscale=d.mscale,
        mscale_all_dim=d.mscale_all_dim, eps=d.eps, max_seq=d.n_positions,
        dtype=getattr(jnp, d.dtype))


# ---------------------------------------------------------------------------
# weights


def _key_data(seed: int) -> np.ndarray:
    from benchmark.traffic import seed_seq
    return seed_seq(seed, 0).generate_state(2, np.uint32)


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key_data, d: Dims):
    """Weights: normal with std 1/sqrt(fan_in), the query and key
    projections too: YaRN's attention factor already scales the scores
    1.59 times over (nope+rope)^-1/2, which keeps attention sharp.  A
    further gain of 1.5 on q and k lets bf16's own rounding grow through
    the 27 layers until served tokens lie 1.8-2.4 logits below the
    reference's best, and the reference with its matmul operands rounded
    to bf16 lies as far (1.9-2.1; float8 5.9-6.8, TPU v5e), which leaves
    no room for a limit between sound and faulty; the router
    ROUTER_GAIN times that, so that the top-k carry most of the routing
    weight, as a trained router's do; RMSNorm gains 1 + NORM_STD N, so
    that a dropped gain shows."""
    key = jax.random.wrap_key_data(key_data)
    ks = iter(jax.random.split(key, 64))
    dt = getattr(jnp, d.dtype)
    D, H, R, C = d.d_model, d.n_heads, d.kv_rank, d.latent

    def normal(shape, std, n=1, dtype=dt):
        """[n, *shape], made one [*shape] at a time, or [*shape] in n
        blocks of rows if n is 0."""
        k = next(ks)
        if n == 0:
            rows = 16 if shape[0] % 16 == 0 else 1
            out = normal((shape[0] // rows,) + shape[1:], std, rows, dtype)
            return out.reshape(shape)
        one = lambda kk: (jax.random.normal(kk, shape, F32)  # noqa: E731
                          * std).astype(dtype)
        return jax.lax.map(one, jax.random.split(k, n))

    def gain(n, width):
        return 1 + normal((width,), NORM_STD, n, F32)

    def attention(n):
        return {
            "attn_norm": gain(n, D),
            "wq": normal((D, H, d.qk_nope + d.qk_rope), QK_GAIN * D ** -0.5,
                         n),
            # k_pe's columns at QK_GAIN; c's are normalised after
            "wkv_a": normal((D, C), jnp.where(jnp.arange(C) < R, 1.0,
                                              QK_GAIN) * D ** -0.5, n),
            "kv_norm": gain(n, R),
            "wk_b": normal((R, H, d.qk_nope), QK_GAIN * R ** -0.5, n),
            "wv_b": normal((R, H, d.v_dim), R ** -0.5, n),
            "wo": normal((H, d.v_dim, D), (H * d.v_dim) ** -0.5, n),
            "mlp_norm": gain(n, D),
        }

    def swiglu(n, prefix, width, experts=()):
        return {prefix + "gate": normal(experts + (D, width), D ** -0.5, n),
                prefix + "up": normal(experts + (D, width), D ** -0.5, n),
                prefix + "down": normal(experts + (width, D), width ** -0.5,
                                        n)}

    dense = dict(attention(d.n_dense), **swiglu(d.n_dense, "w_", d.d_ff))
    moe = dict(attention(d.n_moe),
               router=normal((D, d.n_routed), ROUTER_GAIN * D ** -0.5,
                             d.n_moe, F32),
               **swiglu(d.n_moe, "we_", d.d_expert, (d.n_held,)),
               **swiglu(d.n_moe, "ws_", d.d_shared))
    return {"embed": normal((d.vocab, D), D ** -0.5, 0),
            "head": normal((d.vocab, D), D ** -0.5, 0),
            "norm_f": 1 + normal((D,), NORM_STD, 0, F32),
            "dense": dense, "moe": moe}


def weights(config: dict, seed: int):
    return _init(jnp.asarray(_key_data(seed)), dims(config))


# ---------------------------------------------------------------------------
# the plain reference


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _silu(x):
    return x / (1 + jnp.exp(-x))


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(d: Dims):
    """The pair indices between which YaRN ramps (its correction range)."""
    def dim_at(turns):
        return (d.qk_rope * math.log(d.rope_orig_max / (turns * 2 * math.pi))
                / (2 * math.log(d.rope_theta)))
    return (max(math.floor(dim_at(d.beta_fast)), 0),
            min(math.ceil(dim_at(d.beta_slow)), d.qk_rope - 1))


def softmax_scale(d: Dims) -> float:
    return ((d.qk_nope + d.qk_rope) ** -0.5
            * _yarn_mscale(d.rope_factor, d.mscale_all_dim) ** 2)


def _rotate(x, t, d: Dims):
    """Turn each interleaved pair (2i, 2i+1) of x [B, T, ..., rope] by
    t * f_i, f_i the YaRN frequencies."""
    i = np.arange(d.qk_rope // 2)
    f0 = d.rope_theta ** (-2.0 * i / d.qk_rope)
    lo, hi = yarn_range(d)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0, 1)
    f = jnp.asarray(ramp * f0 / d.rope_factor + (1 - ramp) * f0, F32)
    ang = t.astype(F32)[:, None] * f                       # [T, rope/2]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    scale = (_yarn_mscale(d.rope_factor, d.mscale)
             / _yarn_mscale(d.rope_factor, d.mscale_all_dim))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def forward(params, tokens, d: Dims, low=None):
    """tokens [B, T] -> logits [B, T, vocab], float32.  ``low`` (a dtype)
    rounds every matmul operand to it first: the control."""

    def r(a):
        return a if low is None else a.astype(low).astype(F32)

    def mm(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    def swiglu(h, g, u, dn):
        return mm("btf,fd->btd", _silu(mm("btd,df->btf", h, g))
                  * mm("btd,df->btf", h, u), dn)

    T = tokens.shape[1]
    t = jnp.arange(T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = params["embed"][tokens].astype(F32)

    def attention(x, w):
        h = _rms(x, w["attn_norm"], d.eps)
        q = mm("btd,dhk->bthk", h, w["wq"])
        kva = mm("btd,dc->btc", h, w["wkv_a"])
        c = _rms(kva[..., :d.kv_rank], w["kv_norm"], d.eps)
        k_pe = _rotate(kva[..., d.kv_rank:], t, d)
        q_pe = _rotate(q[..., d.qk_nope:], t, d)
        k_nope = mm("btc,chk->bthk", c, w["wk_b"])
        v = mm("btc,chk->bthk", c, w["wv_b"])
        s = (mm("bthk,bshk->bhts", q[..., :d.qk_nope], k_nope)
             + mm("bthr,bsr->bhts", q_pe, k_pe)) * softmax_scale(d)
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        a = e / jnp.sum(e, -1, keepdims=True)
        return x + mm("bthk,hkd->btd", mm("bhts,bshk->bthk", a, v), w["wo"])

    def dense(x, w):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        x = attention(x, w)
        h = _rms(x, w["mlp_norm"], d.eps)
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), None

    def moe(x, w):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        x = attention(x, w)
        h = _rms(x, w["mlp_norm"], d.eps)
        logits = mm("btd,de->bte", h, w["router"])
        p = jnp.exp(logits - jnp.max(logits, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        topv, topi = jax.lax.top_k(p, d.topk)
        held = d.expert_lo + jnp.arange(d.n_held)
        gate = jnp.sum(jnp.where(topi[..., None] == held, topv[..., None],
                                 0.0), -2)                 # [B, T, held]
        g = mm("btd,edf->ebtf", h, w["we_gate"])
        u = mm("btd,edf->ebtf", h, w["we_up"])
        y = mm("ebtf,efd->ebtd", _silu(g) * u, w["we_down"])
        routed = jnp.einsum("ebtd,bte->btd", y, gate, precision=HIGHEST)
        shared = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
        return x + shared + routed, None

    x, _ = jax.lax.scan(dense, x, params["dense"])
    x, _ = jax.lax.scan(moe, x, params["moe"])
    x = _rms(x, params["norm_f"], d.eps)
    return mm("btd,vd->btv", x, params["head"].astype(F32))


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
F32_BYTES = 4


def expert_params(d: Dims) -> int:
    """One routed expert's matmul parameters (gate, up, down)."""
    return 3 * d.d_model * d.d_expert


def local_picks(d: Dims) -> float:
    """Picks a token makes of the held experts in one MoE layer, if the
    router picks uniformly: topk x n_held / n_routed (1.5 here)."""
    return d.topk * d.n_held / d.n_routed


def hit_share(d: Dims, n_tokens: int) -> float:
    """The share of held experts that `n_tokens` uniform top-k picks
    touch in one layer."""
    return 1.0 - (1.0 - d.topk / d.n_routed) ** n_tokens


def _attn_params(d: Dims) -> int:
    D, H = d.d_model, d.n_heads
    return (D * H * (d.qk_nope + d.qk_rope) + D * d.latent
            + d.kv_rank * H * (d.qk_nope + d.v_dim) + H * d.v_dim * D)


def matmul_params(d: Dims, picks: float = None) -> float:
    """Parameters that enter a matmul per token, the LM head included and
    the embedding (a gather) not: attention, the dense layers, the router
    and shared experts, and `picks` (default ``local_picks``) routed
    experts per MoE layer."""
    picks = local_picks(d) if picks is None else picks
    D = d.d_model
    return (d.n_layers * _attn_params(d) + d.n_dense * 3 * D * d.d_ff
            + d.n_moe * (3 * D * d.d_shared + D * d.n_routed
                         + picks * expert_params(d))
            + d.vocab * D)


def kv_bytes_per_token(d: Dims) -> int:
    """The latent a token adds to the cache, in bfloat16 (31,104 B)."""
    return d.n_layers * d.latent * BF16_BYTES


def _weight_bytes(d: Dims, share: float) -> float:
    """Every weight a call reads once, the held experts at `share`."""
    held = d.n_moe * d.n_held * expert_params(d)
    router = d.n_moe * d.d_model * d.n_routed
    rest = matmul_params(d, 0) - router
    return BF16_BYTES * (rest + share * held) + F32_BYTES * router


def decode_flops(d: Dims, n_active: int, ctx_sum: int,
                 picks: float = None) -> float:
    """One decode step of `n_active` sequences whose new tokens attend to
    `ctx_sum` positions in all, in the absorbed form: each head scores
    kv_rank + rope values of a cached latent and sums kv_rank of them."""
    return (2.0 * matmul_params(d, picks) * n_active
            + 2.0 * d.n_layers * d.n_heads * (d.latent + d.kv_rank)
            * ctx_sum)


def decode_bytes(d: Dims, n_active: int, ctx_sum: int,
                 share: float = None) -> float:
    """Weights once, the held experts at the share of them the step's
    tokens touch (`share`, default ``hit_share``); the latents of the
    positions attended."""
    share = hit_share(d, n_active) if share is None else share
    return _weight_bytes(d, share) + kv_bytes_per_token(d) * ctx_sum


def prefill_flops(d: Dims, s: int, picks: float = None) -> float:
    """A prompt of `s` tokens: every projection on every token (the latent
    decompressed into per-head K and V), causal attention, the LM head on
    the last position only."""
    per_tok = matmul_params(d, picks) - d.vocab * d.d_model
    return (2.0 * per_tok * s
            + d.n_layers * d.n_heads * (d.qk_nope + d.qk_rope + d.v_dim)
            * s * (s + 1) + 2.0 * d.vocab * d.d_model)


def prefill_bytes(d: Dims, s: int, share: float = None) -> float:
    share = hit_share(d, s) if share is None else share
    return _weight_bytes(d, share) + kv_bytes_per_token(d) * s
