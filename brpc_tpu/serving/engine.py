"""Serving front-end — stream-RPC ingress, one decode-loop thread, and
the eviction paths that keep the KV accounting exact.

Request wire format (method "LLM.Generate", payload JSON):

    {"prompt": [token ids...], "max_new_tokens": 8}
    {"prompt_len": 12, "max_new_tokens": 8}      # deterministic prompt

The handler accepts the call's stream, then submits to the scheduler —
which sheds with ELIMIT before any prefill compute or DMA (the PR-11
posture; the native per-method concurrency cap is the first gate in
front of this, see `method_cap`).  Each generated token rides the stream
as 4 little-endian bytes; a clean close is end-of-generation, an RST
carries the eviction/cancel code.

Every exit path — finish, preemption, slow-consumer timeout, stream RST,
RPC cancel, client socket death — funnels through `_end()`, which frees
the sequence's KV blocks exactly once; `assert_drained()` +
`tpu_plane.stats()["live_buffers"]` is the accounting proof the suite
pins.

The decode loop times its own phases (`PHASES`): each is a leaf call of
the loop thread, so no nanosecond is counted twice, and each adds to a
`<phase>_ns` counter in `stats()` and, where it has a span, opens
`jax.profiler.TraceAnnotation("serving/<phase>")` (a no-op unless a
profiler runs).  Spans that hold phases but count nothing:

    serving/admit   one admission (seq, slot, plen): prefill, kv_*, the
                    first token's sync and emit
    serving/step    one decode step (n, ctx): dispatch, sync, emit
    serving/emit    the step's tokens handed to their streams:
                    emit_write (no span of its own), kv_grow, retire
"""

from __future__ import annotations

import json
import os
import struct
import threading
import time
import weakref
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from brpc_tpu.metrics import bvar
from brpc_tpu.models import decode as D
from brpc_tpu.models.transformer import ModelConfig
from brpc_tpu.models import transformer
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.stream import StreamClosed, StreamReset, StreamTimeout
from brpc_tpu.serving import scheduler as S
from brpc_tpu.serving.kv_cache import KvBlockPlane
from brpc_tpu.utils import flags

flags.define_int32(
    "serving_slots",
    int(os.environ.get("TRPC_SERVING_SLOTS", "4")),
    "decode-batch slot count (engine.py); static jit shape",
    reloadable=False)
flags.define_int32(
    "serving_write_timeout_ms",
    int(os.environ.get("TRPC_SERVING_WRITE_TIMEOUT_MS", "2000")),
    "per-token stream write budget; a consumer slower than this is "
    "evicted (shed, not queued — engine.py)")

TOKEN_FMT = "<I"  # one generated token = 4 LE bytes on the stream

# the decode loop's leaf phases, each a `<phase>_ns` counter in stats():
#   idle        waiting for work (sched.work.wait)
#   prefill     the prefill program dispatched
#   kv_d2h      the prefill's state (K/V, or MLA's latent) to host bytes
#               (decode.kv_to_bytes), which waits for the prefill program
#               on the device
#   kv_alloc    the bytes into pool blocks (KvBlockPlane.seq_alloc)
#   kv_migrate  the blocks to the decode device (seq_migrate)
#   kv_fetch    the migrated blocks back to host bytes (seq_fetch)
#   kv_install  bytes to arrays and decode.install dispatched
#   dispatch    a decode step's inputs built and its program dispatched
#   sync        argmax of the logits copied to the host (every step and
#               every admission's first token), with the step's counters
#   emit_write  one token written to its stream (no span)
#   kv_grow     one more KV block charged (KvBlockPlane.seq_grow)
#   retire      a sequence retired (_end)
PHASES = ("idle", "prefill", "kv_d2h", "kv_alloc", "kv_migrate",
          "kv_fetch", "kv_install", "dispatch", "sync", "emit_write",
          "kv_grow", "retire")

_vars_engine = None   # weakref to the engine the serving_* bvars read


def _serving_var(key: str):
    engine = _vars_engine() if _vars_engine is not None else None
    return engine.stats().get(key, 0) if engine is not None else 0


class _Phase:
    """Times one leaf phase of the decode loop into `ns[name]` and, with
    `span`, names it `serving/<name>` in a profiler trace."""

    __slots__ = ("ns", "name", "ann", "t0")

    def __init__(self, ns: Dict[str, int], name: str, span: bool = True,
                 **stats):
        self.ns, self.name = ns, name
        self.ann = TraceAnnotation("serving/" + name, **stats) \
            if span else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.monotonic_ns()

    def __exit__(self, *exc):
        self.ns[self.name] += time.monotonic_ns() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)


def tiny_config(**over) -> ModelConfig:
    """The serving acceptance model: small enough that an 8-device CPU
    mesh prefills + decodes in test time, big enough that K/V spans
    multiple pool blocks per sequence."""
    kw = dict(vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
              max_seq=64, n_experts=0, dtype=jnp.float32)
    kw.update(over)
    return ModelConfig(**kw)


class ServingEngine:
    """Continuous-batching LLM server core.  One instance per process;
    `register()` it on a Server, `start()` the decode loop."""

    def __init__(self, cfg: Optional[ModelConfig] = None,
                 params: Optional[Dict] = None, mesh=None,
                 n_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 kv: Optional[KvBlockPlane] = None,
                 max_waiting: Optional[int] = None, seed: int = 0):
        self.cfg = cfg or tiny_config()
        self.mesh = mesh
        if params is None:
            params = transformer.init(jax.random.PRNGKey(seed), self.cfg)
        if mesh is not None:
            params = transformer.place_params(params, self.cfg, mesh)
        # the programs' weights in cfg.dtype from here on; the float32
        # tree is not kept
        self.params = D.serving_params(params, self.cfg)
        self.param_bytes = D.param_bytes(self.params)
        self.n_slots = n_slots or flags.get_flag("serving_slots")
        self.max_len = max_len or self.cfg.max_seq
        self.kv = kv or KvBlockPlane()
        self.sched = S.Scheduler(self.n_slots, self.kv,
                                 D.kv_bytes_per_token(self.cfg),
                                 max_waiting=max_waiting)
        self.cache = D.init_cache(self.cfg, self.n_slots, self.max_len,
                                  mesh)
        self._jstep = jax.jit(
            lambda p, c, t, a: D.decode_step(p, c, t, a, self.cfg,
                                             self.mesh))
        self._jprefill: Dict[int, object] = {}   # prompt len -> jitted fn
        # the step's own counters (decode.step_counters), summed over steps
        self.step_counts = dict.fromkeys(D.step_counters(self.cfg), 0)
        self._seq_ids = iter(range(1, 1 << 62))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # counters
        self.tokens_out = 0
        self.prefills = 0
        self.prefill_compiles = 0
        self.steps = 0
        self.preemptions = 0
        self.rails = {"local": 0, "host": 0, "none": 0}
        self.phase_ns: Dict[str, int] = dict.fromkeys(PHASES, 0)
        self._loop_t = [0, 0]    # the loop thread's start, end (ns)

    # -- ingress (handler threads) ------------------------------------------

    @property
    def method_cap(self) -> int:
        """Recommended ServerOptions.method_max_concurrency for the
        Generate method: what the scheduler could even hold.  The native
        cap sheds the rest with ELIMIT on the parse fiber — before the
        request ever reaches Python (the PR-11 first gate)."""
        return self.n_slots + self.sched.max_waiting + 1

    def register(self, server, method: str = "LLM.Generate") -> None:
        """Serve `method` on `server`, and expose `stats()` to operators
        as `serving_<key>` bvars, which read the engine registered last
        (each name is exposed once per process)."""
        global _vars_engine
        server.add_service(method, self.handle)
        _vars_engine = weakref.ref(self)
        for key in self.stats():
            bvar.PassiveStatus(lambda k=key: _serving_var(k),
                               f"serving_{key}")

    def handle(self, cntl, req: bytes):
        """The Generate handler: parse, accept the stream, submit."""
        try:
            body = json.loads(req.decode() or "{}")
            prompt = body.get("prompt")
            if prompt is None:
                plen = int(body.get("prompt_len", 8))
                prompt = [1 + (i % (self.cfg.vocab - 1))
                          for i in range(plen)]
            prompt = [int(t) % self.cfg.vocab for t in prompt]
            max_new = int(body.get("max_new_tokens", 8))
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            raise errors.RpcError(errors.EREQUEST, f"bad request: {e}")
        if not prompt or max_new < 1:
            raise errors.RpcError(errors.EREQUEST,
                                  "prompt and max_new_tokens required")
        if len(prompt) + max_new > self.max_len:
            raise errors.RpcError(
                errors.EREQUEST,
                f"prompt+max_new_tokens {len(prompt) + max_new} > "
                f"cache max_len {self.max_len}")
        st = cntl.accept_stream()
        if st is None:
            raise errors.RpcError(errors.EREQUEST,
                                  "Generate wants an attached stream")
        seq = S.Sequence(seq_id=next(self._seq_ids), prompt=prompt,
                         max_new_tokens=max_new, stream=st, cntl=cntl)
        try:
            self.sched.submit(seq)   # sheds ELIMIT before device work
        except errors.RpcError:
            st.rst(errors.ELIMIT)
            st.destroy()
            raise
        return json.dumps({"seq": seq.seq_id,
                           "prompt_len": len(prompt)}).encode()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop,
                                        name="serving-decode", daemon=True)
        self._thread.start()

    def stop(self, drain_timeout_s: float = 10.0) -> None:
        """Stop the loop and drain: every live or queued sequence is
        evicted and its blocks freed."""
        self._stop.set()
        self.sched.work.set()
        if self._thread is not None:
            self._thread.join(drain_timeout_s)
        for seq in self.sched.drain_waiting():
            self._end(seq, S.EVICTED, "server stopping",
                      rst_code=errors.ESTOP)
        for seq in self.sched.running():
            self._end(seq, S.EVICTED, "server stopping",
                      rst_code=errors.ESTOP)
        self.kv.free_all()

    def assert_drained(self) -> None:
        self.kv.assert_balanced()

    def stats(self) -> Dict[str, int]:
        t_start, t_end = self._loop_t
        d = {
            "tokens_out": self.tokens_out,
            "prefills": self.prefills,
            "prefill_compiles": self.prefill_compiles,
            "steps": self.steps,
            "preemptions": self.preemptions,
            "rail_local": self.rails["local"],
            "rail_host": self.rails["host"],
            "submitted": self.sched.submitted,
            "admitted": self.sched.admitted,
            "shed_queue": self.sched.shed_queue,
            "shed_budget": self.sched.shed_budget,
            "shed": self.sched.shed_queue + self.sched.shed_budget,
            "finished": self.sched.finished,
            "evicted": self.sched.evicted,
            "canceled": self.sched.canceled,
            "waiting": self.sched.waiting_depth(),
            "running": len(self.sched.running()),
            "queue_wait_ns": self.sched.queue_wait_ns,
            "param_bytes": self.param_bytes,
            "loop_ns": ((t_end or time.monotonic_ns()) - t_start
                        if t_start else 0),
        }
        d.update({f"{k}_ns": v for k, v in self.phase_ns.items()})
        d.update(self.step_counts)
        d.update(self.kv.stats())
        return d

    def _phase(self, name: str, span: bool = True, **stats) -> _Phase:
        return _Phase(self.phase_ns, name, span, **stats)

    # -- decode loop --------------------------------------------------------

    def _loop(self) -> None:
        self._loop_t = [time.monotonic_ns(), 0]
        try:
            while not self._stop.is_set():
                did = False
                # prefill/decode interleave: at most ONE admission per
                # step, so running streams keep their inter-token cadence
                # while the waiting room drains
                seq = self.sched.pop_admittable()
                if seq is not None:
                    self._admit(seq)
                    did = True
                batch = [s for s in self.sched.running()
                         if s.state == S.RUNNING]
                if batch:
                    self._decode_batch(batch)
                    did = True
                if not did:
                    with self._phase("idle"):
                        self.sched.work.wait(0.02)
                    self.sched.work.clear()
        finally:
            self._loop_t[1] = time.monotonic_ns()

    def _prefill_fn(self, plen: int):
        fn = self._jprefill.get(plen)
        if fn is None:
            fn = jax.jit(lambda p, t: D.prefill(p, t, self.cfg, self.mesh))
            self._jprefill[plen] = fn
            self.prefill_compiles += 1
        return fn

    def _admit(self, seq: S.Sequence) -> None:
        """Prefill one admitted sequence: compute its state, charge
        blocks, migrate prefill→decode device, install, emit the first
        token."""
        if seq.cntl is not None and seq.cntl.is_canceled():
            self._end(seq, S.CANCELED, "canceled before prefill")
            return
        plen = seq.prompt_len
        with TraceAnnotation("serving/admit", seq=seq.seq_id, slot=seq.slot,
                             plen=plen):
            # no wait for the prefill here: kv_to_bytes's copy waits for
            # it, and a wait of its own cost 1.5-3 ms an admission (GPT-2
            # small, 16 slots, one v5e)
            with self._phase("prefill"):
                toks = jnp.asarray([seq.prompt], jnp.int32)
                logits, state = self._prefill_fn(plen)(self.params, toks)
            with self._phase("kv_d2h"):
                kvb = D.kv_to_bytes(tuple(a[:, 0] for a in state))
            try:
                with self._phase("kv_alloc"):
                    self.kv.seq_alloc(seq.seq_id, kvb)
                with self._phase("kv_migrate"):
                    rail = self.kv.seq_migrate(seq.seq_id)
            except Exception as e:  # PoolExhausted or a plane fault
                self.kv.seq_free(seq.seq_id)
                self._end(seq, S.EVICTED, f"prefill shed: {e}",
                          rst_code=errors.ELIMIT)
                return
            self.rails[rail] = self.rails.get(rail, 0) + 1
            with self._phase("kv_fetch"):
                kvb = self.kv.seq_fetch(seq.seq_id)
            with self._phase("kv_install"):
                self.cache = D.install(self.cache, seq.slot,
                                       D.kv_from_bytes(kvb, self.cfg, plen),
                                       plen)
            self.prefills += 1
            with self._phase("sync"):
                first = int(np.asarray(jnp.argmax(logits[0])))
            if self._emit(seq, first) and \
                    seq.generated >= seq.max_new_tokens:
                self._end(seq, S.FINISHED, "max_new_tokens")

    def _decode_batch(self, batch) -> None:
        with TraceAnnotation("serving/step", n=len(batch),
                             ctx=sum(s.total_len for s in batch)):
            with self._phase("dispatch"):
                tokens = np.zeros((self.n_slots,), np.int32)
                active = np.zeros((self.n_slots,), bool)
                for s in batch:
                    tokens[s.slot] = s.last_token
                    active[s.slot] = True
                logits, self.cache, *counts = self._jstep(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(active))
            self.steps += 1
            with self._phase("sync"):
                # one wait for the argmax and the counters together
                nxt, counts = jax.device_get(
                    (jnp.argmax(logits, axis=-1), counts))
                for key, n in zip(self.step_counts,
                                  counts[0] if counts else ()):
                    self.step_counts[key] += int(n)
            with TraceAnnotation("serving/emit"):
                for s in batch:
                    if s.state != S.RUNNING:
                        continue  # evicted by a preemption earlier
                    if s.cntl is not None and s.cntl.is_canceled():
                        # the RPC cancel already RST the accepted stream
                        # natively; our job is only the block accounting
                        self._end(s, S.CANCELED, "rpc canceled", rst=False)
                        continue
                    if self._emit(s, int(nxt[s.slot])) and \
                            s.generated >= s.max_new_tokens:
                        self._end(s, S.FINISHED, "max_new_tokens")

    def _emit(self, seq: S.Sequence, token: int) -> bool:
        """Send one token and keep the block charge covering the
        sequence's total length; False when the sequence ended here."""
        try:
            with self._phase("emit_write", span=False):
                seq.stream.write(struct.pack(TOKEN_FMT, token),
                                 timeout_s=flags.get_flag(
                                     "serving_write_timeout_ms") / 1e3)
        except StreamReset:
            self._end(seq, S.CANCELED, "stream reset by peer", rst=False)
            return False
        except StreamClosed:
            self._end(seq, S.CANCELED, "peer gone", rst=False)
            return False
        except StreamTimeout:
            self._end(seq, S.EVICTED, "slow consumer",
                      rst_code=errors.ELIMIT)
            return False
        except errors.RpcError:
            self._end(seq, S.CANCELED, "connection failed", rst=False)
            return False
        seq.generated += 1
        seq.last_token = token
        self.tokens_out += 1
        return self._grow(seq)

    def _grow(self, seq: S.Sequence) -> bool:
        """Charge blocks for the sequence's grown K/V; preempt-by-
        eviction (youngest first) when the pool runs dry."""
        needed = self.kv.blocks_needed(
            seq.total_len * self.sched.bytes_per_token)
        while self.kv.seq_blocks(seq.seq_id) < needed:
            try:
                with self._phase("kv_grow"):
                    self.kv.seq_grow(seq.seq_id)
            except Exception:  # PoolExhausted
                victim = self.sched.preempt_victim()
                if victim is None:
                    victim = seq
                self.preemptions += 1
                self._end(victim, S.EVICTED, "preempted: KV pool dry",
                          rst_code=errors.ELIMIT)
                if victim is seq:
                    return False
        return True

    def _end(self, seq: S.Sequence, state: str, reason: str,
             rst: bool = True, rst_code: int = errors.ECANCELED) -> None:
        """The single retirement path: slot back, blocks freed exactly
        once, stream closed (clean for FINISHED, RST otherwise)."""
        with self._phase("retire"):
            self.sched.release(seq, state, reason)
            if seq.slot >= 0:
                self.cache = D.reset_slot(self.cache, seq.slot)
            self.kv.seq_free(seq.seq_id)
            try:
                if state == S.FINISHED:
                    seq.stream.close()
                elif rst:
                    seq.stream.rst(rst_code)
            except Exception:
                pass  # the peer may already be gone; accounting is done
