"""Ring attention + Ulysses sequence parallelism — long-context attention
over a mesh axis.

Capability lineage: the reference has NO sequence parallelism (SURVEY.md
§5.7 — what it has is PartitionChannel sharding of one big payload plus
streaming with windowed flow control); ring attention is the TPU-native
capability those map onto: the "one big payload" is the sequence sharded
over the `sp` mesh axis, and the "streaming" is K/V blocks rotating around
the ICI ring (ppermute) while each chip folds them into an online-softmax
accumulator (blockwise/flash-style), so peak HBM stays O(S/n) per chip.

Two first-class schemes (pick per workload):
  ring_attention     — K/V circulate over the axis; n-1 ppermute hops of
                       [B, S/n, H, K] each; compute/comm overlap comes from
                       XLA pipelining the scan body's einsums with the
                       collective-permute.
  ulysses_attention  — one all-to-all swaps sequence sharding for head
                       sharding, attention runs locally over the FULL
                       sequence per head group, a second all-to-all swaps
                       back.  Cheaper at moderate S (2 all-to-alls vs n-1
                       permutes) but needs n | heads.

Both are reverse-mode differentiable (lax.scan carries the ring state) and
compose with dp/tp sharding: shard_map maps dp/tp as plain sharded dims and
only sp participates in the collectives.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


# "masked" logit: finite so the online max stays NaN-free even for
# fully-masked blocks.  np.float32, NOT jnp: this module is imported
# lazily inside a jit trace (models/transformer.py attention body), and
# a module-level jnp constant materialized under tracing becomes a
# DynamicJaxprTracer that leaks past the trace (UnexpectedTracerError on
# the second jit).
_NEG = np.float32(-1e30)


def _ring_body(axis: str, n: int, idx, q, scale, causal, chunk, carry, step):
    """One ring step: fold the currently-held K/V block into the online
    softmax state, then pass it along the ring."""
    m, l, o, k, v = carry
    # whose K/V block do we hold after `step` hops? blocks travel +1 each
    # hop, so we now hold the block that started at (idx - step)
    src = (idx - step) % n

    def fold(args):
        m, l, o = args
        s = jnp.einsum("bchk,bdhk->bhcd", q, k).astype(jnp.float32) * scale
        if causal:
            qpos = idx * chunk + jnp.arange(chunk)
            kpos = src * chunk + jnp.arange(chunk)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))                # [B,H,C]
        p = jnp.exp(s - m_new[..., None])                # [B,H,C,Cd]
        alpha = jnp.exp(m - m_new)                       # [B,H,C]
        l_new = l * alpha + p.sum(-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhcd,bdhk->bhck", p, v.astype(jnp.float32))
        return m_new, l_new, o_new

    if causal:
        # skip fully-future blocks (max qpos < min kpos): on a causal ring
        # roughly half of all steps hold nothing visible — eliding the fold
        # halves attention FLOPs at long context
        visible = (idx * chunk + chunk - 1) >= (src * chunk)
        m, l, o = jax.lax.cond(visible, fold, lambda args: args, (m, l, o))
    else:
        m, l, o = fold((m, l, o))
    perm = [(i, (i + 1) % n) for i in range(n)]
    k = jax.lax.ppermute(k, axis, perm)
    v = jax.lax.ppermute(v, axis, perm)
    return (m, l, o, k, v), None


def _ring_shard(q, k, v, *, axis: str, causal: bool, scale: float):
    """Per-shard ring attention; shapes [B, C, H, K] with C = S/n."""
    n = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    B, C, H, K = q.shape
    m0 = jnp.full((B, H, C), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, C), jnp.float32)
    o0 = jnp.zeros((B, H, C, K), jnp.float32)
    body = partial(_ring_body, axis, n, idx, q, scale, causal, C)
    (m, l, o, k, v), _ = jax.lax.scan(
        lambda c, s: body(c, s), (m0, l0, o0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhck->bchk", out).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = True,
                   scale: Optional[float] = None):
    """Blockwise ring attention over mesh axis `axis`.

    q/k/v: [B, S, H, K] logically; sharded [B@dp, S@axis, H@tp, K].
    Returns [B, S, H, K] with the same sharding as q.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P("dp" if "dp" in mesh.axis_names else None, axis,
             "tp" if "tp" in mesh.axis_names else None, None)
    fn = jax.shard_map(
        partial(_ring_shard, axis=axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism


def _dense_local(q, k, v, causal, scale):
    s = jnp.einsum("bchk,bdhk->bhcd", q, k).astype(jnp.float32) * scale
    if causal:
        Sq = q.shape[1]
        mask = jnp.tril(jnp.ones((Sq, Sq), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhcd,bdhk->bchk", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def _ulysses_shard(q, k, v, *, axis: str, causal: bool, scale: float):
    """[B, S/n, H, K] → all-to-all → [B, S, H/n, K] → attend → back."""
    a2a = partial(jax.lax.all_to_all, axis_name=axis, split_axis=2,
                  concat_axis=1, tiled=True)
    q, k, v = a2a(q), a2a(k), a2a(v)
    o = _dense_local(q, k, v, causal, scale)
    return jax.lax.all_to_all(o, axis_name=axis, split_axis=1,
                              concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = True,
                      scale: Optional[float] = None):
    """All-to-all sequence parallelism (Ulysses style): requires
    axis_size | n_heads (heads are re-sharded during attention)."""
    n = mesh.shape[axis]
    tp = mesh.shape.get("tp", 1) if "tp" in mesh.axis_names else 1
    local_heads = q.shape[2] // tp
    if local_heads % n != 0:
        raise ValueError(
            f"ulysses needs axis size {n} to divide per-tp-shard heads "
            f"{local_heads} (n_heads {q.shape[2]} / tp {tp})")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    spec = P("dp" if "dp" in mesh.axis_names else None, axis,
             "tp" if "tp" in mesh.axis_names else None, None)
    fn = jax.shard_map(
        partial(_ulysses_shard, axis=axis, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
