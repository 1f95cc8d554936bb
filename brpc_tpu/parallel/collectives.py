"""XLA collectives over a mesh axis — the data plane ParallelChannel and
PartitionChannel lower onto (SURVEY.md §2.9: "AllGather/AllReduce fan-out +
merge over ICI; merger = XLA reduction op").

Everything here is shard_map over a Mesh: callers hand in a host-side global
array (or an already-sharded jax.Array) and name the axis; XLA emits the
collective and it rides ICI.  These are the primitive verbs; the RPC-flavored
API (fail_limit, CallMapper/ResponseMerger) lives in parallel/channels.py.

`bus_bandwidth_gbps` is the driver's "ICI allreduce bus-bw" metric
(BASELINE.json): algbw * 2*(n-1)/n, the standard ring-allreduce bus formula.
"""

from __future__ import annotations

import time
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _shmap(mesh: Mesh, axis: str, body: Callable, in_spec, out_spec):
    return jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                         out_specs=out_spec, check_vma=False)


@lru_cache(maxsize=None)
def _jitted(kind: str, mesh: Mesh, axis: str, extra):
    """One compiled executable per (verb, mesh, axis, extra) — jit caches by
    function identity, so the closure must be built once, not per call."""
    if kind == "all_reduce":
        red = {"add": jax.lax.psum, "max": jax.lax.pmax,
               "min": jax.lax.pmin}[extra]

        def body(s):
            return red(s, axis)

        return jax.jit(_shmap(mesh, axis, body, P(axis), P(axis)))
    if kind == "all_gather":

        def body(s):
            return jax.lax.all_gather(s, axis, tiled=extra)

        return jax.jit(_shmap(mesh, axis, body, P(axis), P()))
    if kind == "reduce_scatter":

        def body(s):
            return jax.lax.psum_scatter(s, axis, scatter_dimension=0,
                                        tiled=True)

        return jax.jit(_shmap(mesh, axis, body, P(axis), P(axis)))
    if kind == "ring_permute":
        n = mesh.shape[axis]
        perm = [(i, (i + extra) % n) for i in range(n)]

        def body(s):
            return jax.lax.ppermute(s, axis, perm)

        return jax.jit(_shmap(mesh, axis, body, P(axis), P(axis)))
    if kind == "all_to_all":

        def body(s):
            return jax.lax.all_to_all(s, axis, split_axis=1, concat_axis=0,
                                      tiled=True)

        return jax.jit(_shmap(mesh, axis, body, P(axis), P(None, axis)))
    raise ValueError(kind)


def all_reduce(mesh: Mesh, axis: str, x, op: str = "add"):
    """psum/pmax/pmin over one mesh axis; x is sharded on `axis` along dim 0.

    ≙ ParallelChannel broadcast + ResponseMerger when the merger is a
    reduction (reference parallel_channel.h:127).
    """
    return _jitted("all_reduce", mesh, axis, op)(x)


def all_gather(mesh: Mesh, axis: str, x, *, tiled: bool = True):
    """Gather shards along dim 0 of one mesh axis onto every member."""
    return _jitted("all_gather", mesh, axis, tiled)(x)


def reduce_scatter(mesh: Mesh, axis: str, x):
    """psum_scatter: reduce over the axis, leave each member 1/n of dim 0."""
    return _jitted("reduce_scatter", mesh, axis, None)(x)


def ring_permute(mesh: Mesh, axis: str, x, shift: int = 1):
    """ppermute ring step — the building block of ring attention and
    pipeline-parallel stage handoff."""
    return _jitted("ring_permute", mesh, axis, shift)(x)


def all_to_all(mesh: Mesh, axis: str, x):
    """The Ulysses sequence-parallel verb: reshard a 2D+ array from
    dim0-sharded to dim1-sharded (gather sequence, scatter heads).  The
    global value is unchanged; only the layout moves."""
    return _jitted("all_to_all", mesh, axis, None)(x)


def bus_bandwidth_gbps(mesh: Mesh, axis: str,
                       mbytes_per_shard: float = 64.0,
                       iters: int = 10,
                       dtype=jnp.bfloat16) -> float:
    """Measure allreduce bus bandwidth over a mesh axis.

    busbw = algbw * 2*(n-1)/n  (ring allreduce moves 2*(n-1)/n bytes per
    byte reduced).  This is the driver's ICI allreduce metric.
    """
    n = mesh.shape[axis]
    elems = int(mbytes_per_shard * 1e6 / jnp.dtype(dtype).itemsize)
    sharding = NamedSharding(mesh, P(axis))
    x = jax.device_put(
        jnp.ones((n * elems,), dtype=dtype), sharding)

    def body(s):
        return jax.lax.psum(s, axis)

    fn = jax.jit(_shmap(mesh, axis, body, P(axis), P(axis)))
    fn(x).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    y = x
    for _ in range(iters):
        y = fn(y)
    jax.block_until_ready(y)
    dt = time.perf_counter() - t0
    algbw = mbytes_per_shard * 1e6 * iters / dt / 1e9
    return algbw * 2 * (n - 1) / n
