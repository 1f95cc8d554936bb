"""Incremental (KV-cached) decode over models/transformer.py parameters.

The serving plane's compute half: `prefill()` runs the full causal
forward over a prompt ONCE and hands back the per-layer K/V it produced;
`decode_step()` then extends N independent sequences by one token each
against a slot-based KV cache (static shapes: [L, slots, max_len, H, hd]
— a slot is a row the continuous batcher assigns/evicts per step, so the
jitted step never recompiles as sequences come and go).

Capability lineage: the reference has no model code (SURVEY.md §5.7);
this mirrors how vLLM-style engines split prefill from decode so the
batcher can interleave them — here the seam matters because prefill K/V
migrates prefill-device → decode-device through the tpu_plane block rail
(serving/kv_cache.py) before `install()` makes it visible to the step.

Both programs read a serving tree (`serving_params()`): the matmul
weights and a tied LM head `head` already in cfg.dtype, cast once when
the engine takes its parameters, never inside a program.  The embedding
table the gather reads, positions and LayerNorm stay float32.

Dense-only (cfg.n_experts == 0): the serving path drives the dense
transformer; MoE decode is an optimization path, not a serving
requirement.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from brpc_tpu.models.transformer import ModelConfig, _cs, _layernorm

# K/V serializes host-side as f32 (numpy has no bfloat16); the cache
# itself stays in cfg.dtype on device.
KV_WIRE_DTYPE = np.float32


def _check_dense(cfg: ModelConfig) -> None:
    if cfg.n_experts > 0:
        raise ValueError("decode path is dense-only (cfg.n_experts == 0)")


# the block weights that enter a matmul; with `head` they are what
# serving_params() casts to cfg.dtype
MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w1", "w2")


def kv_bytes_per_token(cfg: ModelConfig) -> int:
    """Host-wire bytes one token position contributes to a sequence's
    K/V: 2 (k+v) x L x H x head_dim f32 values."""
    return 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim * \
        KV_WIRE_DTYPE().itemsize


# ---------------------------------------------------------------------------
# the serving tree


def serving_params(params: Dict, cfg: ModelConfig) -> Dict:
    """A transformer.init()-layout tree -> the tree prefill() and
    decode_step() read: the block matmul weights in cfg.dtype, plus `head`,
    the tied LM head, = embed in cfg.dtype.  `embed` (the gather), `pos`
    and the LayerNorm leaves stay as they are, since they enter float32
    math.  A cast keeps its leaf's sharding (`head` takes embed's), so
    place the tree before this; with cfg.dtype float32 every leaf is the
    one it was."""
    _check_dense(cfg)
    blocks = dict(params["blocks"])
    for name in MATMUL_WEIGHTS:
        blocks[name] = blocks[name].astype(cfg.dtype)
    out = dict(params, blocks=blocks)
    out["head"] = params["embed"].astype(cfg.dtype)
    return out


def param_bytes(params: Dict) -> int:
    """Bytes of a parameter tree (shapes will do), each array once."""
    leaves = {id(x): x for x in jax.tree.leaves(params)}
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in leaves.values())


def _check_serving(params: Dict, cfg: ModelConfig) -> None:
    """At trace time: every matmul weight is in cfg.dtype, so no program
    casts a weight (or silently runs its matmuls in float32)."""
    want = jnp.dtype(cfg.dtype)
    got = {f"blocks.{n}": params["blocks"][n].dtype for n in MATMUL_WEIGHTS}
    got["head"] = params["head"].dtype if "head" in params else None
    bad = {k: str(v) for k, v in got.items() if v != want}
    if bad:
        raise ValueError(f"not a serving tree for {want} (use "
                         f"decode.serving_params): {bad}")


# ---------------------------------------------------------------------------
# cache


def init_cache(cfg: ModelConfig, slots: int, max_len: int,
               mesh: Optional[Mesh] = None) -> Dict:
    """Slot-based decode cache: k/v [L, slots, max_len, H, hd] in
    cfg.dtype plus per-slot valid length `pos` [slots] int32."""
    _check_dense(cfg)
    shape = (cfg.n_layers, slots, max_len, cfg.n_heads, cfg.head_dim)
    cache = {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((slots,), jnp.int32),
    }
    cache["k"] = _cs(cache["k"], mesh, P(None, "dp", None, "tp", None))
    cache["v"] = _cs(cache["v"], mesh, P(None, "dp", None, "tp", None))
    return cache


def cache_max_len(cache: Dict) -> int:
    return cache["k"].shape[2]


def install(cache: Dict, slot: int, k, v, length: int) -> Dict:
    """Make a migrated sequence's prefill K/V visible to decode_step:
    write k/v [L, S, H, hd] into `slot` at positions [0:S] and set the
    slot's valid length.  Eager (runs once per admit, outside the jitted
    step)."""
    k = jnp.asarray(k, cache["k"].dtype)
    v = jnp.asarray(v, cache["v"].dtype)
    s = int(k.shape[1])
    out = dict(cache)
    out["k"] = cache["k"].at[:, slot, :s].set(k)
    out["v"] = cache["v"].at[:, slot, :s].set(v)
    out["pos"] = cache["pos"].at[slot].set(np.int32(length))
    return out


def reset_slot(cache: Dict, slot: int) -> Dict:
    """Retire a slot (finish/evict/cancel): its row stops advancing and
    the stale K/V is dead weight the next install overwrites."""
    out = dict(cache)
    out["pos"] = cache["pos"].at[slot].set(np.int32(0))
    return out


# ---------------------------------------------------------------------------
# prefill


def prefill(params: Dict, tokens, cfg: ModelConfig,
            mesh: Optional[Mesh] = None) -> Tuple:
    """tokens [B, S] int32 -> (last-position logits [B, vocab] f32,
    k [L, B, S, H, hd], v [L, B, S, H, hd]).

    Same math as transformer.apply()'s gather branch, but inference-mode
    (no checkpoint) and the per-layer K/V survives as the migration
    payload instead of dying with the activations.  `params` is a
    serving_params() tree."""
    _check_dense(cfg)
    _check_serving(params, cfg)
    B, S = tokens.shape
    x = params["embed"][tokens] + params["pos"][:S][None]
    x = _cs(x, mesh, P("dp", "sp", None))
    lp = params["blocks"]
    ks, vs = [], []

    for i in range(cfg.n_layers):
        h = _layernorm(x, lp["ln1_g"][i], lp["ln1_b"][i])
        hc = h.astype(cfg.dtype)
        q = jnp.einsum("bsd,dhk->bshk", hc, lp["wq"][i])
        k = jnp.einsum("bsd,dhk->bshk", hc, lp["wk"][i])
        v = jnp.einsum("bsd,dhk->bshk", hc, lp["wv"][i])
        ks.append(k)
        vs.append(v)
        q = _cs(q, mesh, P("dp", "sp", "tp", None))
        k = _cs(k, mesh, P("dp", None, "tp", None))
        v = _cs(v, mesh, P("dp", None, "tp", None))
        scores = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(cfg.head_dim)
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None], scores.astype(jnp.float32),
                           -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bhst,bthk->bshk", w, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype), lp["wo"][i])
        h = _layernorm(x, lp["ln2_g"][i], lp["ln2_b"][i])
        hf = jnp.einsum("bsd,df->bsf", h.astype(cfg.dtype), lp["w1"][i])
        hf = jax.nn.gelu(hf)
        x = x + jnp.einsum("bsf,fd->bsd", hf, lp["w2"][i])
        x = _cs(x, mesh, P("dp", "sp", None))

    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    last = x[:, -1]
    logits = jnp.einsum("bd,vd->bv", last.astype(cfg.dtype), params["head"])
    return (logits.astype(jnp.float32),
            jnp.stack(ks), jnp.stack(vs))


# ---------------------------------------------------------------------------
# decode


def decode_step(params: Dict, cache: Dict, tokens, active,
                cfg: ModelConfig, mesh: Optional[Mesh] = None) -> Tuple:
    """One token for every slot: tokens [N] int32 (last emitted token per
    slot), active [N] bool -> (logits [N, vocab] f32, new cache).

    Inactive slots still flow through the math (static shapes) but their
    `pos` does not advance and their scatter lands on a clamped index the
    next install overwrites — the batcher just ignores their logits.
    `params` is a serving_params() tree."""
    _check_dense(cfg)
    _check_serving(params, cfg)
    k_cache, v_cache, pos = cache["k"], cache["v"], cache["pos"]
    N = tokens.shape[0]
    S = k_cache.shape[2]
    lp = params["blocks"]
    p = jnp.minimum(pos, S - 1)                       # write index per slot
    rows = jnp.arange(N)
    x = params["embed"][tokens] + params["pos"][p]    # [N, D]
    x = _cs(x, mesh, P("dp", None))
    valid = jnp.arange(S)[None, :] <= p[:, None]      # [N, S] causal window

    for i in range(cfg.n_layers):
        h = _layernorm(x, lp["ln1_g"][i], lp["ln1_b"][i])
        hc = h.astype(cfg.dtype)
        q = jnp.einsum("nd,dhk->nhk", hc, lp["wq"][i])
        k_new = jnp.einsum("nd,dhk->nhk", hc, lp["wk"][i])
        v_new = jnp.einsum("nd,dhk->nhk", hc, lp["wv"][i])
        k_cache = k_cache.at[i, rows, p].set(k_new)
        v_cache = v_cache.at[i, rows, p].set(v_new)
        q = _cs(q, mesh, P("dp", "tp", None))
        scores = jnp.einsum("nhk,nshk->nhs", q,
                            k_cache[i]) / np.sqrt(cfg.head_dim)
        scores = jnp.where(valid[:, None, :], scores.astype(jnp.float32),
                           -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("nhs,nshk->nhk", w, v_cache[i])
        x = x + jnp.einsum("nhk,hkd->nd", o.astype(cfg.dtype), lp["wo"][i])
        h = _layernorm(x, lp["ln2_g"][i], lp["ln2_b"][i])
        hf = jnp.einsum("nd,df->nf", h.astype(cfg.dtype), lp["w1"][i])
        hf = jax.nn.gelu(hf)
        x = x + jnp.einsum("nf,fd->nd", hf, lp["w2"][i])
        x = _cs(x, mesh, P("dp", None))

    x = _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    logits = jnp.einsum("nd,vd->nv", x.astype(cfg.dtype), params["head"])
    new_cache = {
        "k": k_cache,
        "v": v_cache,
        "pos": pos + active.astype(jnp.int32),
    }
    return logits.astype(jnp.float32), new_cache


# ---------------------------------------------------------------------------
# host-wire (de)serialization — the bytes the KV block plane migrates


def kv_to_bytes(k, v) -> bytes:
    """[L, S, H, hd] k/v pair -> contiguous f32 host bytes (k then v)."""
    ka = np.ascontiguousarray(np.asarray(k, KV_WIRE_DTYPE))
    va = np.ascontiguousarray(np.asarray(v, KV_WIRE_DTYPE))
    return ka.tobytes() + va.tobytes()


def kv_from_bytes(data: bytes, cfg: ModelConfig, length: int) -> Tuple:
    """Inverse of kv_to_bytes for a `length`-token sequence."""
    shape = (cfg.n_layers, length, cfg.n_heads, cfg.head_dim)
    n = int(np.prod(shape))
    flat = np.frombuffer(data, KV_WIRE_DTYPE, count=2 * n)
    return flat[:n].reshape(shape), flat[n:].reshape(shape)
