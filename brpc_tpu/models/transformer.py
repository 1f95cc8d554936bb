"""Decoder-only transformer LM, TPU-first.

Design notes (why this shape, per the scaling-book recipe):
  - bf16 compute / f32 params: matmuls hit the MXU at full rate, optimizer
    state stays accurate.
  - static shapes everywhere; layers are a Python loop over stacked params
    (n_layers is static), each block wrapped in jax.checkpoint so the
    backward pass re-computes activations instead of holding them in HBM.
  - parallelism is expressed ONLY as sharding constraints; XLA inserts the
    collectives (psum for tp matmul partials, all-gather of K/V over sp,
    all-to-all for expert dispatch).  Axis convention from parallel/mesh.py:
      dp: batch    sp: sequence    tp: heads & ffn-hidden    ep: experts
  - sequence parallelism = activations sharded [B@dp, S@sp, D]; attention
    Q stays sequence-sharded while K/V are constrained head-sharded, so the
    compiler emits the all-gather-KV form of context parallelism (ring
    attention is the pallas upgrade path, ops/).
  - MoE every second block (cfg.moe_every>0): dense top-k dispatch via
    one-hot einsum — no ragged gather/scatter, so XLA can tile it; experts
    sharded over ep.

Capability lineage: the reference has no model code (SURVEY.md §5.7 —
"no ML parallelism"); this model exists to drive the framework's collective
data plane the way example/rdma_performance drives its RDMA path
(reference example/rdma_performance/client.cpp).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ModelConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 1024
    n_experts: int = 0       # 0 = dense-only
    moe_every: int = 2       # every k-th block is MoE (when n_experts > 0)
    moe_topk: int = 1
    dtype: Any = jnp.bfloat16
    # context parallelism over the sp axis (parallel/ring_attention.py):
    #   "gather"  — K/V all-gathered over sp (XLA-inserted; fine at short S)
    #   "ring"    — blockwise ring attention, K/V rotate via ppermute; peak
    #               HBM O(S/n) per chip — the long-context path
    #   "ulysses" — all-to-all head<->sequence re-shard, local attention
    attn_impl: str = "gather"

    def __post_init__(self):
        if self.attn_impl not in ("gather", "ring", "ulysses"):
            raise ValueError(
                f"attn_impl must be gather|ring|ulysses, "
                f"got {self.attn_impl!r}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_moe(self, layer: int) -> bool:
        return self.n_experts > 0 and self.moe_every > 0 and \
            (layer % self.moe_every == self.moe_every - 1)


# ---------------------------------------------------------------------------
# params


def init(rng, cfg: ModelConfig) -> Dict:
    """f32 param pytree; stacked per-layer leaves [L, ...]."""
    k = iter(jax.random.split(rng, 16 + 4 * cfg.n_layers))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in))

    L, D, H, hd, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                      cfg.head_dim, cfg.d_ff)
    p = {
        "embed": dense(next(k), (cfg.vocab, D), D),
        "pos": dense(next(k), (cfg.max_seq, D), D),
        "ln_f": {"g": jnp.ones((D,)), "b": jnp.zeros((D,))},
        "blocks": {
            "ln1_g": jnp.ones((L, D)), "ln1_b": jnp.zeros((L, D)),
            "ln2_g": jnp.ones((L, D)), "ln2_b": jnp.zeros((L, D)),
            "wq": dense(next(k), (L, D, H, hd), D),
            "wk": dense(next(k), (L, D, H, hd), D),
            "wv": dense(next(k), (L, D, H, hd), D),
            "wo": dense(next(k), (L, H, hd, D), D),
            "w1": dense(next(k), (L, D, F), D),
            "w2": dense(next(k), (L, F, D), F),
        },
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        # expert leaves are stacked over MoE layers only (moe_slot maps
        # layer index -> slot), not all L layers — half the expert HBM
        n_moe = sum(1 for i in range(L) if cfg.is_moe(i))
        if n_moe:
            p["moe"] = {
                "router": dense(next(k), (n_moe, D, E), D),
                "we1": dense(next(k), (n_moe, E, D, F), D),
                "we2": dense(next(k), (n_moe, E, F, D), F),
            }
    return p


def moe_slot(cfg: ModelConfig, layer: int) -> int:
    """Index into the stacked MoE leaves for a MoE layer."""
    return sum(1 for j in range(layer) if cfg.is_moe(j))


def param_specs(cfg: ModelConfig) -> Dict:
    """PartitionSpec tree matching init() — the tp/ep layout.

    Megatron split: qkv column-parallel on heads (tp), out-proj
    row-parallel; ffn w1 column- / w2 row-parallel; embeddings replicated
    (vocab is small relative to ffn; gather stays local).
    """
    specs = {
        "embed": P(None, None),
        "pos": P(None, None),
        "ln_f": {"g": P(None), "b": P(None)},
        "blocks": {
            "ln1_g": P(None, None), "ln1_b": P(None, None),
            "ln2_g": P(None, None), "ln2_b": P(None, None),
            "wq": P(None, None, "tp", None),
            "wk": P(None, None, "tp", None),
            "wv": P(None, None, "tp", None),
            "wo": P(None, "tp", None, None),
            "w1": P(None, None, "tp"),
            "w2": P(None, "tp", None),
        },
    }
    if cfg.n_experts > 0:
        specs["moe"] = {
            "router": P(None, None, None),
            "we1": P(None, "ep", None, "tp"),
            "we2": P(None, "ep", "tp", None),
        }
    return specs


def place_params(params: Dict, cfg: ModelConfig, mesh: Mesh) -> Dict:
    """Put a param tree on `mesh` with the param_specs layout (axes the
    mesh lacks replicate) — init() leaves every leaf on the default
    device, so an unplaced tree would start the whole model on device 0."""
    from brpc_tpu.parallel.mesh import prune_spec
    return jax.tree.map(
        lambda x, s: jax.device_put(
            x, NamedSharding(mesh, prune_spec(s, mesh))),
        params, param_specs(cfg), is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# forward


def _cs(x, mesh: Optional[Mesh], spec: P):
    """Sharding constraint; identity when no mesh (single chip).  Axes the
    mesh doesn't define drop to replicated (prune_spec), so the model runs
    unchanged on partial meshes (e.g. a (dp, sp) ring mesh without tp/ep)."""
    if mesh is None or mesh.empty:
        return x
    from brpc_tpu.parallel.mesh import prune_spec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, prune_spec(spec, mesh)))


def _layernorm(x, g, b):
    x = x.astype(jnp.float32)
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + 1e-5) * g + b)


def _attention(x, lp, i, cfg: ModelConfig, mesh):
    B, S, D = x.shape
    xc = x.astype(cfg.dtype)
    q = jnp.einsum("bsd,dhk->bshk", xc, lp["wq"][i].astype(cfg.dtype))
    kk = jnp.einsum("bsd,dhk->bshk", xc, lp["wk"][i].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xc, lp["wv"][i].astype(cfg.dtype))
    use_sp = (mesh is not None and not mesh.empty
              and "sp" in mesh.axis_names and mesh.shape["sp"] > 1)
    if cfg.attn_impl != "gather" and use_sp:
        # sequence-parallel attention: q/k/v all stay sequence-sharded;
        # the collective (ring ppermute / all-to-all) IS the data plane
        from brpc_tpu.parallel import ring_attention as ra
        q = _cs(q, mesh, P("dp", "sp", "tp", None))
        kk = _cs(kk, mesh, P("dp", "sp", "tp", None))
        v = _cs(v, mesh, P("dp", "sp", "tp", None))
        fn = (ra.ring_attention if cfg.attn_impl == "ring"
              else ra.ulysses_attention)
        o = fn(q, kk, v, mesh, axis="sp", causal=True)
    else:
        # q keeps the sequence shard; k/v go head-sharded → XLA all-gathers
        # their sequence over sp (all-gather context parallelism)
        q = _cs(q, mesh, P("dp", "sp", "tp", None))
        kk = _cs(kk, mesh, P("dp", None, "tp", None))
        v = _cs(v, mesh, P("dp", None, "tp", None))
        scores = jnp.einsum("bshk,bthk->bhst", q, kk) / np.sqrt(cfg.head_dim)
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores.astype(jnp.float32),
                           -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bhst,bthk->bshk", w, v)
    o = _cs(o, mesh, P("dp", "sp", "tp", None))
    out = jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype),
                     lp["wo"][i].astype(cfg.dtype))
    return _cs(out, mesh, P("dp", "sp", None))


def _ffn(x, lp, i, cfg: ModelConfig, mesh):
    xc = x.astype(cfg.dtype)
    h = jnp.einsum("bsd,df->bsf", xc, lp["w1"][i].astype(cfg.dtype))
    h = _cs(jax.nn.gelu(h), mesh, P("dp", "sp", "tp"))
    out = jnp.einsum("bsf,fd->bsd", h, lp["w2"][i].astype(cfg.dtype))
    return _cs(out, mesh, P("dp", "sp", None))


def _moe_ffn(x, mp, i, cfg: ModelConfig, mesh):
    """Dense top-k switch layer: one-hot dispatch keeps shapes static."""
    B, S, D = x.shape
    xc = x.astype(cfg.dtype)
    logits = jnp.einsum("bsd,de->bse", xc,
                        mp["router"][i].astype(cfg.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.moe_topk)
    oh = jax.nn.one_hot(topi, cfg.n_experts, dtype=probs.dtype)
    gates = (oh * topv[..., None]).sum(-2)          # [B,S,E]
    gates = (gates / (gates.sum(-1, keepdims=True) + 1e-9)).astype(cfg.dtype)
    # dense dispatch: compute every expert on every token, weight by gate.
    # experts sharded over ep → XLA partitions the E dim; gate-weighting is
    # the combine.  Ragged/pallas dispatch is the optimization path.
    h = jnp.einsum("bsd,edf->besf", xc, mp["we1"][i].astype(cfg.dtype))
    h = _cs(jax.nn.gelu(h), mesh, P("dp", "ep", "sp", "tp"))
    y = jnp.einsum("besf,efd->besd", h, mp["we2"][i].astype(cfg.dtype))
    out = jnp.einsum("besd,bse->bsd", y, gates)
    return _cs(out, mesh, P("dp", "sp", None))


def apply(params: Dict, tokens, cfg: ModelConfig,
          mesh: Optional[Mesh] = None):
    """tokens [B, S] int32 → logits [B, S, vocab] f32."""
    B, S = tokens.shape
    x = params["embed"][tokens] + params["pos"][:S][None]
    x = _cs(x, mesh, P("dp", "sp", None))
    lp, mp = params["blocks"], params.get("moe")

    def block(x, i):
        h = _layernorm(x, lp["ln1_g"][i], lp["ln1_b"][i])
        x = x + _attention(h, lp, i, cfg, mesh)
        h = _layernorm(x, lp["ln2_g"][i], lp["ln2_b"][i])
        if cfg.is_moe(i):
            x = x + _moe_ffn(h, mp, moe_slot(cfg, i), cfg, mesh)
        else:
            x = x + _ffn(h, lp, i, cfg, mesh)
        return _cs(x, mesh, P("dp", "sp", None))

    for i in range(cfg.n_layers):
        x = jax.checkpoint(block, static_argnums=(1,))(x, i)

    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(cfg.dtype),
                        params["embed"].astype(cfg.dtype))
    return logits.astype(jnp.float32)
