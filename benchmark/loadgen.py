#!/usr/bin/env python3
"""The client side of a run, in a process of its own that never imports JAX.

    python benchmark/loadgen.py <schedule.json> <results.json>

Reads the schedule (traffic.schedule() plus ``address``, ``method`` and
``drain_s``), opens its connections, prints ``READY``, then waits for one
line ``GO <t0_ns>`` on stdin: the window opens at ``t0_ns`` on the
system's monotonic clock, which the parent shares.  Every time it records
is nanoseconds on that clock.

Open loop: each request is handed to a free connection when it is due;
lateness is the send time minus the due time.  Closed loop: each client
sends its next request when its last one has ended, until the window
closes.  Requests counted in the window are waited for until ``drain_s``
past its close; one still open then is ``lost``.  Uncounted requests keep
the load on meanwhile and are reset once every counted one has ended.
"""

from __future__ import annotations

import json
import os
import queue
import struct
import sys
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

from brpc_tpu.rpc import errors  # noqa: E402
from brpc_tpu.rpc.channel import Channel, ChannelOptions  # noqa: E402
from brpc_tpu.rpc.stream import StreamReset, StreamTimeout  # noqa: E402

TOKEN_FMT = "<I"      # one served token: 4 little-endian bytes
READ_POLL_S = 0.5


def now() -> int:
    return time.monotonic_ns()


class Client:
    def __init__(self, sched: dict):
        self.s = sched
        self.stop = threading.Event()        # every counted request ended
        self.results = []
        self._lock = threading.Lock()
        self._counted_done = 0
        self.exhausted = 0

    def channel(self) -> Channel:
        return Channel(self.s["address"],
                       ChannelOptions(timeout_ms=60_000, max_retry=0))

    def _done(self, rec: dict) -> None:
        with self._lock:
            self.results.append(rec)
            if rec["counted"]:
                self._counted_done += 1

    def one(self, ch: Channel, req: dict, due_ns: int, counted: bool,
            deadline_ns: int) -> None:
        """Send one request and read its stream to the end."""
        rec = {"id": req["id"], "counted": counted, "due": due_ns,
               "max_new": req["max_new"], "prompt_len": len(req["prompt"]),
               "sent": now(), "tokens": [], "times": [], "status": "ok"}
        body = json.dumps({"prompt": req["prompt"],
                           "max_new_tokens": req["max_new"]}).encode()
        st = None
        try:
            _, st = ch.create_stream(self.s["method"], body)
            while True:
                try:
                    msg = st.read(timeout_s=READ_POLL_S)
                except StreamTimeout:
                    if now() > deadline_ns:
                        rec["status"] = "lost"
                        break
                    if not counted and self.stop.is_set():
                        rec["status"] = "abandoned"
                        break
                    continue
                if msg is None:
                    break
                rec["times"].append(now())
                rec["tokens"].append(struct.unpack(TOKEN_FMT, msg)[0])
        except errors.RpcError as e:
            rec["status"] = ("shed" if e.code == errors.ELIMIT
                             else f"error:{e.code}")
        except StreamReset as e:
            rec["status"] = f"reset:{e.code}"
        finally:
            if st is not None:
                if rec["status"] != "ok":
                    st.rst(errors.ECANCELED)
                st.destroy()
        self._done(rec)

    # -- open loop ----------------------------------------------------------

    def run_open(self) -> None:
        reqs = self.s["requests"]
        counted = sum(1 for r in reqs if r["counted"])
        work: queue.Queue = queue.Queue()
        chans = [self.channel() for _ in range(self.s["workers"])]
        print("READY", flush=True)
        t0 = self._wait_go()
        deadline = t0 + int((self.s["seconds"] + self.s["drain_s"]) * 1e9)

        def all_done() -> bool:
            with self._lock:
                return self._counted_done >= counted

        def worker(ch):
            while True:
                item = work.get()
                if item is None:
                    return
                req, due = item
                self.one(ch, req, due, req["counted"], deadline)

        ts = [threading.Thread(target=worker, args=(c,), daemon=True)
              for c in chans]
        for t in ts:
            t.start()
        for r in reqs:
            due = t0 + int(r["due"] * 1e9)
            while not all_done() and now() < min(due, deadline):
                time.sleep(max(0.0, min((due - now()) / 1e9, 0.05)))
            if all_done() or now() >= deadline:
                break
            work.put((r, due))
        while not all_done() and now() < deadline:
            time.sleep(0.01)
        self.stop.set()
        while True:                  # never handed out: a counted one is lost
            try:
                req, due = work.get_nowait()
            except queue.Empty:
                break
            if req["counted"]:
                self._done({"id": req["id"], "counted": True, "due": due,
                            "max_new": req["max_new"],
                            "prompt_len": len(req["prompt"]), "sent": None,
                            "tokens": [], "times": [], "status": "lost"})
        for _ in ts:
            work.put(None)
        for t in ts:
            t.join(timeout=self.s["drain_s"] + 5)
        for c in chans:
            c.close()

    # -- closed loop --------------------------------------------------------

    def run_closed(self) -> None:
        lists = self.s["clients"]
        chans = [self.channel() for _ in lists]
        print("READY", flush=True)
        t0 = self._wait_go()
        t1 = t0 + int(self.s["seconds"] * 1e9)
        deadline = t1 + int(self.s["drain_s"] * 1e9)

        def client(ch, lst):
            for req in lst:
                if now() >= t1:
                    return
                self.one(ch, req, now(), True, deadline)
            with self._lock:
                self.exhausted += 1

        ts = [threading.Thread(target=client, args=(c, lst), daemon=True)
              for c, lst in zip(chans, lists)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=(deadline - now()) / 1e9 + 5)
        for c in chans:
            c.close()

    def _wait_go(self) -> int:
        line = sys.stdin.readline().split()
        if len(line) != 2 or line[0] != "GO":
            raise SystemExit(f"loadgen: expected 'GO <t0_ns>', got {line}")
        t0 = int(line[1])
        while now() < t0:
            time.sleep(max(0.0, min((t0 - now()) / 1e9, 0.01)))
        return t0


def main() -> int:
    sched_path, out_path = sys.argv[1], sys.argv[2]
    with open(sched_path) as f:
        sched = json.load(f)
    c = Client(sched)
    if sched["loop"] == "open":
        c.run_open()
    else:
        c.run_closed()
    with open(out_path, "w") as f:
        json.dump({"requests": c.results, "exhausted": c.exhausted}, f)
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — any failure is a non-zero exit
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
