"""The benchmark's own spans around the serving engine's calls, for the
traced run only.

Each wrapper records ``(kind, start_ns, end_ns, stats)`` on the monotonic
clock, and the same span as a ``jax.profiler.TraceAnnotation`` named
``bench/<kind>`` so that the trace can put the device's idle gaps against
what the host was doing.  The programs (``decode_step``, ``prefill``) and
``install`` are blocked on inside their spans, so that each span holds its
device work: the traced run pays for that, the untraced one does not.

Spans (all on the engine's decode-loop thread, but ``submit``):

    submit       Scheduler.submit (a handler thread); stats seq
    admit        ServingEngine._admit; stats seq
    prefill      the prefill program, dispatch to outputs ready; stats s
    bridge       prefill outputs ready to ``install`` returned
    decode       ServingEngine._decode_batch; stats n, ctx
    decode_step  the decode program, dispatch to outputs ready; stats n, ctx
    emit         ServingEngine._emit, one token handed to its stream
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation


def _now() -> int:
    return time.monotonic_ns()


class Spans:
    def __init__(self):
        self.events: List[tuple] = []
        self._lock = threading.Lock()
        self._step: Optional[Dict] = None     # the decode step under way
        self._bridge: Optional[tuple] = None  # (start_ns, annotation)
        self._undo: List[tuple] = []

    def add(self, kind: str, t0: int, t1: int, **stats) -> None:
        with self._lock:
            self.events.append((kind, t0, t1, stats))

    def of(self, kind: str, t0: int, t1: int) -> List[tuple]:
        """Spans of `kind` that start inside [t0, t1)."""
        return [e for e in self.events if e[0] == kind and t0 <= e[1] < t1]

    def _patch(self, obj, name: str, fn) -> None:
        self._undo.append((obj, name, obj.__dict__.get(name)))
        setattr(obj, name, fn)

    def install(self, engine) -> None:
        """Wrap the engine's calls.  A call that is not there is an error,
        named, so that no metric that reads its spans goes dark unseen."""
        from brpc_tpu.serving import engine as engine_mod
        sched = engine.sched
        decode_mod = engine_mod.D
        missing = [f"{label}.{name}" for label, obj, name in (
            ("Scheduler", sched, "submit"),
            ("ServingEngine", engine, "_admit"),
            ("ServingEngine", engine, "_prefill_fn"),
            ("ServingEngine", engine, "_decode_batch"),
            ("ServingEngine", engine, "_jstep"),
            ("ServingEngine", engine, "_emit"),
            ("models.decode", decode_mod, "install"))
            if not hasattr(obj, name)]
        if missing:
            raise RuntimeError("the traced run wraps calls the program no "
                               "longer has: " + ", ".join(missing))

        def timed(kind, fn, stats_fn=None, block=False):
            def wrapper(*a, **kw):
                stats = stats_fn(*a) if stats_fn else {}
                t0 = _now()
                with TraceAnnotation("bench/" + kind, **stats):
                    out = fn(*a, **kw)
                    if block:
                        jax.block_until_ready(out)
                self.add(kind, t0, _now(), **stats)
                return out
            return wrapper

        orig_submit = sched.submit

        def submit(seq):
            self.add("submit", _now(), _now(), seq=seq.seq_id)
            return orig_submit(seq)
        self._patch(sched, "submit", submit)

        orig_admit = engine._admit

        def admit(seq):
            t0 = _now()
            try:
                with TraceAnnotation("bench/admit", seq=seq.seq_id):
                    return orig_admit(seq)
            finally:
                self._close_bridge(ok=False)
                self.add("admit", t0, _now(), seq=seq.seq_id)
        self._patch(engine, "_admit", admit)

        orig_prefill_fn = engine._prefill_fn

        def prefill_fn(plen):
            fn = timed("prefill", orig_prefill_fn(plen),
                       lambda p, t: {"s": int(t.shape[1])}, block=True)

            def wrapped(*a):
                out = fn(*a)
                ann = TraceAnnotation("bench/bridge")
                ann.__enter__()
                self._bridge = (_now(), ann)
                return out
            return wrapped
        self._patch(engine, "_prefill_fn", prefill_fn)

        orig_install = decode_mod.install

        def install(*a, **kw):
            out = orig_install(*a, **kw)
            jax.block_until_ready(out)
            self._close_bridge(ok=True)
            return out
        self._patch(decode_mod, "install", install)

        orig_decode = engine._decode_batch

        def decode_batch(batch):
            st = {"n": len(batch),
                  "ctx": int(sum(s.total_len for s in batch))}
            self._step = st
            t0 = _now()
            try:
                with TraceAnnotation("bench/decode", **st):
                    return orig_decode(batch)
            finally:
                self._step = None
                self.add("decode", t0, _now(), **st)
        self._patch(engine, "_decode_batch", decode_batch)

        self._patch(engine, "_jstep",
                    timed("decode_step", engine._jstep,
                          lambda *a: dict(self._step or {}), block=True))

        orig_emit = engine._emit

        def emit(seq, token):
            t0 = _now()
            with TraceAnnotation("bench/emit"):
                out = orig_emit(seq, token)
            self.add("emit", t0, _now(), step=self._step is not None)
            return out
        self._patch(engine, "_emit", emit)

    def _close_bridge(self, ok: bool) -> None:
        if self._bridge is None:
            return
        t0, ann = self._bridge
        self._bridge = None
        ann.__exit__(None, None, None)
        if ok:
            self.add("bridge", t0, _now())

    def uninstall(self) -> None:
        for obj, name, old in reversed(self._undo):
            if old is None:
                delattr(obj, name)
            else:
                setattr(obj, name, old)
        self._undo.clear()
