"""Latent attention with routed and shared experts (DeepSeek-V2), served
through models/decode.py, which dispatches here by config type.

Per layer, with the residual stream x in float32:

    h = RMSNorm(x)
    MLA     q = h Wq [H, nope+rope]; [c, k_pe] = h Wkv_a; c = RMSNorm(c)
            k_nope_h = c Wk_b,h; v_h = c Wv_b,h; q_pe, k_pe turned by
            position (interleaved pairs, YaRN frequencies)
            s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * softmax_scale
            x += [softmax(s_h) v_h]_h Wo
    h = RMSNorm(x)
    dense   x += SwiGLU(h)                      (the first n_dense layers)
    MoE     p = softmax(h Wr) over all n_routed experts (float32);
            x += SwiGLU_shared(h) + sum_{e in top-k(p), e held} p_e E_e(h)

One chip holds the experts [expert_lo, expert_lo + n_held) of every MoE
layer, as one chip of an expert-parallel deployment does: the router keeps
its whole width and its top-k, and the layer adds what the held experts
give.  The other chips' experts are not computed and nothing stands in for
them; on one chip there is no exchange.

The cache holds one latent per token and layer, `[c, k_pe]`
(kv_rank + qk_rope values), instead of K and V per head.  `prefill`
decompresses it into per-head K/V over the prompt; `decode_step` never
does: it folds Wk_b into the query and Wv_b into the output, so attention
reads the latent cache alone.

The serving tree (`serving_params`): every matmul weight, the embedding
table included, in cfg.dtype; the RMSNorm gains and the router weight in
float32.  Stacked per layer kind, so that each kind is one `lax.scan`:

    embed [V, D]  head [V, D]  norm_f [D]
    dense/  attn_norm wq wkv_a kv_norm wk_b wv_b wo mlp_norm
            w_gate w_up [D, F]  w_down [F, D]               [n_dense, ...]
    moe/    the attention leaves, mlp_norm, router [D, n_routed],
            we_gate we_up [n_held, D, De]  we_down [n_held, De, D],
            ws_gate ws_up [D, Ds]  ws_down [Ds, D]          [n_moe, ...]

with wq [D, H, nope+rope], wkv_a [D, kv_rank+rope], wk_b [kv_rank, H,
nope], wv_b [kv_rank, H, v], wo [H, v, D].
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# leaves that enter float32 math; every other leaf is a matmul weight
F32_LEAVES = ("attn_norm", "kv_norm", "mlp_norm", "router", "norm_f")
# decode-step counters, summed over MoE layers (decode_step's third output)
STEP_COUNTERS = ("moe_picks", "moe_local_picks", "moe_experts_hit")


@dataclass(frozen=True)
class MlaMoeConfig:
    """DeepSeek-V2-Lite's published sizes by default, all experts of a
    layer held but for `n_held`/`expert_lo`."""
    vocab: int = 102400
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 27
    n_dense: int = 1            # leading dense layers
    d_ff: int = 10944           # their SwiGLU width
    n_routed: int = 64          # the router's width: every routed expert
    topk: int = 6
    expert_lo: int = 0          # experts held: [expert_lo, +n_held)
    n_held: int = 64
    d_expert: int = 1408
    d_shared: int = 2816        # the shared experts, as one SwiGLU
    kv_rank: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 40.0   # YaRN
    rope_orig_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    eps: float = 1e-6
    max_seq: int = 163840
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not (0 <= self.expert_lo and
                self.expert_lo + self.n_held <= self.n_routed):
            raise ValueError(f"held experts [{self.expert_lo}, "
                             f"{self.expert_lo + self.n_held}) outside the "
                             f"router's {self.n_routed}")
        if self.qk_rope % 2:
            raise ValueError("qk_rope turns pairs: it must be even")

    @property
    def latent(self) -> int:
        """Values cached per token and layer."""
        return self.kv_rank + self.qk_rope

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense


def serves(of) -> bool:
    """Whether `of`, a config or a cache, is this architecture's."""
    return isinstance(of, MlaMoeConfig) or (isinstance(of, dict)
                                            and "latent" in of)


# ---------------------------------------------------------------------------
# positions: YaRN-scaled rotary frequencies


def yarn_range(cfg: MlaMoeConfig) -> Tuple[int, int]:
    """The rope pair indices between which YaRN ramps from the original
    frequencies to the interpolated ones (10..23 at published sizes)."""
    def dim_at(turns: float) -> float:
        return (cfg.qk_rope * math.log(cfg.rope_orig_max
                                       / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    return (max(math.floor(dim_at(cfg.beta_fast)), 0),
            min(math.ceil(dim_at(cfg.beta_slow)), cfg.qk_rope - 1))


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(cfg: MlaMoeConfig) -> np.ndarray:
    """Angle per position of each rope pair i: f0_i/factor where the ramp
    is 1, f0_i where it is 0, f0_i = theta^(-2i/qk_rope)."""
    i = np.arange(cfg.qk_rope // 2, dtype=np.float64)
    f0 = cfg.rope_theta ** (-2.0 * i / cfg.qk_rope)
    lo, hi = yarn_range(cfg)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ramp * f0 / cfg.rope_factor + (1.0 - ramp) * f0


def softmax_scale(cfg: MlaMoeConfig) -> float:
    """(nope+rope)^-1/2 times YaRN's attention factor squared
    (0.114721 at published sizes)."""
    return ((cfg.qk_nope + cfg.qk_rope) ** -0.5
            * _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2)


def _rope_tables(pos, cfg: MlaMoeConfig):
    """cos, sin [*pos.shape, qk_rope/2] for integer positions `pos`."""
    scale = (_yarn_mscale(cfg.rope_factor, cfg.mscale)
             / _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim))
    ang = pos.astype(F32)[..., None] * jnp.asarray(rope_freqs(cfg), F32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """Turn each interleaved pair (2i, 2i+1) of x's last axis by its
    angle; cos/sin broadcast against x[..., ::2]."""
    x = x.astype(F32)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


# ---------------------------------------------------------------------------
# the serving tree


def _leaf_dtype(path, cfg: MlaMoeConfig):
    return jnp.dtype(F32 if path[-1].key in F32_LEAVES else cfg.dtype)


def serving_params(params: Dict, cfg: MlaMoeConfig) -> Dict:
    """The tree as it is, once checked: its weights are made in their
    serving dtypes (a float32 tree of the published model would not fit
    the chip, so none is cast here)."""
    check_serving(params, cfg)
    return params


def check_serving(params: Dict, cfg: MlaMoeConfig) -> None:
    """At trace time: the tree holds every leaf the programs read, each
    in its serving dtype, so no program casts a weight."""
    want = {"embed", "head", "norm_f", "dense", "moe"}
    if set(params) != want:
        raise ValueError(f"not an MLA/MoE serving tree: keys "
                         f"{sorted(params)}, want {sorted(want)}")
    bad = {jax.tree_util.keystr(path): str(x.dtype) for path, x in
           jax.tree_util.tree_flatten_with_path(params)[0]
           if x.dtype != _leaf_dtype(path, cfg)}
    if bad:
        raise ValueError(f"not a serving tree for {jnp.dtype(cfg.dtype)}: "
                         f"leaves in other dtypes {bad}")


def _no_mesh(mesh) -> None:
    if mesh is not None and not mesh.empty:
        raise ValueError("the MLA/MoE programs run on one chip: no mesh")


# ---------------------------------------------------------------------------
# cache


def state_shapes(cfg: MlaMoeConfig, length: int) -> Tuple[Tuple[int, ...]]:
    """One latent per token and layer: ([L, length, kv_rank + qk_rope],)."""
    return ((cfg.n_layers, length, cfg.latent),)


def init_cache(cfg: MlaMoeConfig, slots: int, max_len: int,
               mesh=None) -> Dict:
    """latent [L, slots, kv_rank + qk_rope, max_len] in cfg.dtype, positions
    minor (576 values a position would pad to 640 in the chip's tiles as
    the minor axis; 1024 positions do not), and the per-slot valid length
    `pos`."""
    _no_mesh(mesh)
    return {"latent": jnp.zeros((cfg.n_layers, slots, cfg.latent, max_len),
                                cfg.dtype),
            "pos": jnp.zeros((slots,), jnp.int32)}


def install(cache: Dict, slot: int, state: Tuple, length: int) -> Dict:
    """decode.install for this cache: the latent [L, S, C] into `slot`'s
    positions [0:S]."""
    (lat,) = state
    lat = jnp.asarray(lat, cache["latent"].dtype)
    out = dict(cache)
    out["latent"] = cache["latent"].at[:, slot, :, :lat.shape[1]].set(
        lat.transpose(0, 2, 1))
    out["pos"] = cache["pos"].at[slot].set(np.int32(length))
    return out


# ---------------------------------------------------------------------------
# the blocks


def _rms(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu(h, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", h, w_gate, preferred_element_type=F32)
    u = jnp.einsum("...d,df->...f", h, w_up, preferred_element_type=F32)
    a = (jax.nn.silu(g) * u).astype(h.dtype)
    return jnp.einsum("...f,fd->...d", a, w_down, preferred_element_type=F32)


def _latent(h, w, cos, sin, cfg: MlaMoeConfig):
    """The cached latent of hidden states h [..., D]: [RMSNorm(c), k_pe
    turned], in cfg.dtype."""
    kva = jnp.einsum("...d,dc->...c", h, w["wkv_a"],
                     preferred_element_type=F32)
    c = _rms(kva[..., :cfg.kv_rank], w["kv_norm"], cfg.eps)
    k_pe = _rope(kva[..., cfg.kv_rank:], cos, sin)
    return jnp.concatenate([c, k_pe], -1).astype(cfg.dtype)


def _query(h, w, cos, sin, cfg: MlaMoeConfig):
    """q_nope [..., H, nope] and q_pe [..., H, rope] turned, cfg.dtype."""
    q = jnp.einsum("...d,dhk->...hk", h, w["wq"], preferred_element_type=F32)
    q_pe = _rope(q[..., cfg.qk_nope:], cos[..., None, :], sin[..., None, :])
    return q[..., :cfg.qk_nope].astype(cfg.dtype), q_pe.astype(cfg.dtype)


def _attn_prefill(x, w, cfg: MlaMoeConfig):
    """Causal MLA over a whole prompt, the latent decompressed into
    per-head K/V: x [B, S, D] -> (x's update, latent [B, S, C])."""
    S = x.shape[1]
    with jax.named_scope("mla"):
        h = _rms(x, w["attn_norm"], cfg.eps).astype(cfg.dtype)
        cos, sin = _rope_tables(jnp.arange(S), cfg)
        lat = _latent(h, w, cos, sin, cfg)
        q_nope, q_pe = _query(h, w, cos, sin, cfg)
        c, k_pe = lat[..., :cfg.kv_rank], lat[..., cfg.kv_rank:]
        k_nope = jnp.einsum("bsc,chk->bshk", c, w["wk_b"])
        v = jnp.einsum("bsc,chk->bshk", c, w["wv_b"])
        s = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope,
                        preferred_element_type=F32)
             + jnp.einsum("bshr,btr->bhst", q_pe, k_pe,
                          preferred_element_type=F32)) * softmax_scale(cfg)
        mask = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
        o = jnp.einsum("bhst,bthk->bshk", p.astype(cfg.dtype), v)
        out = jnp.einsum("bshk,hkd->bsd", o, w["wo"],
                         preferred_element_type=F32)
    return out, lat


def _attn_decode(x, w, c, p, cos, sin, cfg: MlaMoeConfig):
    """One new token per slot against the latent cache, in the absorbed
    form: q~_h = Wk_b,h^T q_nope_h scores the cached c directly, and the
    softmax-weighted sum of c goes through Wv_b,h.  x [N, D], c one
    layer's cache [N, C, S], read only: positions [0, p) are the slot's
    past, and the new token's own latent joins the softmax beside them.
    -> (x's update, the new latents [N, C])."""
    with jax.named_scope("mla"):
        h = _rms(x, w["attn_norm"], cfg.eps).astype(cfg.dtype)
        new = _latent(h, w, cos, sin, cfg)                    # [N, C]
        q_nope, q_pe = _query(h, w, cos, sin, cfg)
        q_abs = jnp.einsum("nhk,chk->nhc", q_nope, w["wk_b"])
        q = jnp.concatenate([q_abs, q_pe], -1)                # [N, H, C]
        s_past = jnp.einsum("nhc,ncs->nhs", q, c,
                            preferred_element_type=F32) * softmax_scale(cfg)
        s_new = jnp.einsum("nhc,nc->nh", q, new,
                           preferred_element_type=F32) * softmax_scale(cfg)
        past = jnp.arange(c.shape[2])[None, None, :] < p[:, None, None]
        s_past = jnp.where(past, s_past, -1e30)
        top = jnp.maximum(jnp.max(s_past, -1), s_new)
        e_past, e_new = jnp.exp(s_past - top[..., None]), jnp.exp(s_new - top)
        o_lat = (jnp.einsum("nhs,ncs->nhc", e_past.astype(cfg.dtype), c,
                            preferred_element_type=F32)
                 + e_new[..., None] * new[:, None, :].astype(F32)) \
            / (jnp.sum(e_past, -1) + e_new)[..., None]
        o = jnp.einsum("nhc,chk->nhk", o_lat[..., :cfg.kv_rank].astype(
            cfg.dtype), w["wv_b"])
        out = jnp.einsum("nhk,hkd->nd", o, w["wo"],
                         preferred_element_type=F32)
    return out, new


def _route(h, w, cfg: MlaMoeConfig):
    """Top-k of the float32 softmax over all n_routed experts (greedy, not
    renormalised): (probabilities, expert ids) [..., k], and `hit`
    [..., k, n_held], where pick j of a token is held expert e."""
    with jax.named_scope("moe/router"):
        logits = jnp.einsum("...d,de->...e", h, w["router"],
                            precision=jax.lax.Precision.HIGHEST)
        topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   cfg.topk)
        hit = topi[..., None] == cfg.expert_lo + jnp.arange(cfg.n_held)
    return topv, topi, hit


def moe_every_held(h, w, cfg: MlaMoeConfig):
    """The MoE block for a few tokens (decode): every held expert on every
    token, each weighted by its gate, which is zero where the token did
    not pick it.  h [N, D] float32 -> (update [N, D], hit)."""
    topv, _, hit = _route(h, w, cfg)
    gates = jnp.sum(jnp.where(hit, topv[..., None], 0.0), -2)  # [N, held]
    hc = h.astype(cfg.dtype)
    with jax.named_scope("moe/experts"):
        g = jnp.einsum("nd,edf->enf", hc, w["we_gate"],
                       preferred_element_type=F32)
        u = jnp.einsum("nd,edf->enf", hc, w["we_up"],
                       preferred_element_type=F32)
        y = jnp.einsum("enf,efd->end", (jax.nn.silu(g) * u).astype(
            cfg.dtype), w["we_down"], preferred_element_type=F32)
        routed = jnp.einsum("end,ne->nd", y, gates)
    with jax.named_scope("moe/shared"):
        shared = _swiglu(hc, w["ws_gate"], w["ws_up"], w["ws_down"])
    return routed + shared, hit


def moe_grouped(h, w, cfg: MlaMoeConfig):
    """The MoE block for many tokens (prefill): each held expert only on
    the tokens that picked it, as grouped matmuls (`lax.ragged_dot`) over
    the picks sorted by expert; picks of experts held elsewhere sort last
    and fall in no group.  h [T, D] float32 -> update [T, D]."""
    T = h.shape[0]
    topv, topi, _ = _route(h, w, cfg)
    hc = h.astype(cfg.dtype)
    with jax.named_scope("moe/experts"):
        local = topi.reshape(-1) - cfg.expert_lo
        held = (local >= 0) & (local < cfg.n_held)
        order = jnp.argsort(jnp.where(held, local, cfg.n_held), stable=True)
        sizes = jnp.sum(local[:, None] == jnp.arange(cfg.n_held), 0,
                        dtype=jnp.int32)
        tok = order // cfg.topk
        xs = hc[tok]
        g = jax.lax.ragged_dot(xs, w["we_gate"], sizes,
                               preferred_element_type=F32)
        u = jax.lax.ragged_dot(xs, w["we_up"], sizes,
                               preferred_element_type=F32)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(cfg.dtype),
                               w["we_down"], sizes,
                               preferred_element_type=F32)
        gate = jnp.where(held, topv.reshape(-1), 0.0)[order]
        y = jnp.where(held[order][:, None], y * gate[:, None], 0.0)
        routed = jnp.zeros((T, h.shape[1]), F32).at[tok].add(y)
    with jax.named_scope("moe/shared"):
        shared = _swiglu(hc, w["ws_gate"], w["ws_up"], w["ws_down"])
    return routed + shared


def _dense_mlp(h, w, cfg: MlaMoeConfig):
    return _swiglu(h.astype(cfg.dtype), w["w_gate"], w["w_up"], w["w_down"])


def _head(x, params, cfg: MlaMoeConfig):
    x = _rms(x, params["norm_f"], cfg.eps).astype(cfg.dtype)
    return jnp.einsum("...d,vd->...v", x, params["head"],
                      preferred_element_type=F32)


# ---------------------------------------------------------------------------
# the programs


def prefill(params: Dict, tokens, cfg: MlaMoeConfig, mesh=None):
    """tokens [B, S] int32 -> (last-position logits [B, vocab] f32,
    (latent [L, B, S, C],))."""
    _no_mesh(mesh)
    check_serving(params, cfg)
    B, S = tokens.shape
    x = params["embed"][tokens].astype(F32)

    def dense(x, w):
        a, lat = _attn_prefill(x, w, cfg)
        x = x + a
        return x + _dense_mlp(_rms(x, w["mlp_norm"], cfg.eps), w, cfg), lat

    def moe(x, w):
        a, lat = _attn_prefill(x, w, cfg)
        x = x + a
        h = _rms(x, w["mlp_norm"], cfg.eps).reshape(B * S, -1)
        return x + moe_grouped(h, w, cfg).reshape(x.shape), lat

    x, lat0 = jax.lax.scan(dense, x, params["dense"])
    x, lat1 = jax.lax.scan(moe, x, params["moe"])
    return (_head(x[:, -1], params, cfg),
            (jnp.concatenate([lat0, lat1], 0),))


def decode_step(params: Dict, cache: Dict, tokens, active,
                cfg: MlaMoeConfig, mesh=None):
    """One token for every slot -> (logits [N, vocab] f32, new cache,
    counters int32 [3] in STEP_COUNTERS order, over the active slots and
    the MoE layers).  Inactive slots flow through the math and their
    `pos` stays, as in the dense transformer's step."""
    _no_mesh(mesh)
    check_serving(params, cfg)
    lat, pos = cache["latent"], cache["pos"]
    N, S = tokens.shape[0], lat.shape[3]
    p = jnp.minimum(pos, S - 1)
    cos, sin = _rope_tables(p, cfg)
    x = params["embed"][tokens].astype(F32)

    # the cache is read, not written, inside the layer loops; the new
    # latents go in after them, in the one pass that writes the step's
    # new cache (a scatter would have the compiler turn the whole cache
    # to a latent-minor layout and back, twice its size in copies)
    def attend(x, w, layer):
        a, new = _attn_decode(x, w, lat[layer], p, cos, sin, cfg)
        x = x + a
        return x, new, _rms(x, w["mlp_norm"], cfg.eps)

    def dense(x, xs):
        w, layer = xs
        x, new, h = attend(x, w, layer)
        return x + _dense_mlp(h, w, cfg), new

    def moe(x, xs):
        w, layer = xs
        x, new, h = attend(x, w, layer)
        m, hit = moe_every_held(h, w, cfg)
        hit = hit & active[:, None, None]
        counts = jnp.stack([jnp.sum(active) * cfg.topk, jnp.sum(hit),
                            jnp.sum(jnp.any(hit, (0, 1)))]).astype(jnp.int32)
        return x + m, (new, counts)

    x, new0 = jax.lax.scan(dense, x,
                           (params["dense"], jnp.arange(cfg.n_dense)))
    x, (new1, counts) = jax.lax.scan(
        moe, x, (params["moe"], cfg.n_dense + jnp.arange(cfg.n_moe)))
    new = jnp.concatenate([new0, new1], 0)                    # [L, N, C]
    at_p = jnp.arange(S) == p[:, None, None]                  # [N, 1, S]
    lat = jnp.where(at_p, new[..., None], lat)
    return (_head(x, params, cfg),
            {"latent": lat, "pos": pos + active.astype(jnp.int32)},
            counts.sum(0))
