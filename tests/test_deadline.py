"""Deadline-budget propagation (ISSUE 19, meta tag 18).

Layers, reference-style (real loopback sockets, no mocks):

* the Controller surface — servers see the inbound budget
  (cntl.deadline_left_us) and handlers' downstream calls default to the
  inherited remainder minus the per-hop reserve, so the budget visibly
  SHRINKS tier by tier;
* the two server-side drop sites — the parse-fiber fast-drop (a crafted
  split frame whose tag-18 budget dies in read_buf answers EDEADLINE on
  the ShedOnCork rail, counted in native_deadline_drops) and the
  usercode-dequeue drop (queued work whose budget ran out answers
  EDEADLINE without running the handler: native_deadline_queue_drops);
* the wire A/B — TRPC_DEADLINE_PROPAGATE unset must be BYTE-IDENTICAL
  on the wire to =0 / ='', and the ON frame must differ from the OFF
  frame by exactly the tag-18 TLV (subprocess A/B, the
  TRPC_PAYLOAD_CODEC / TRPC_CLIENT_CORK proof shape);
* hedged mixers' losing-attempt cancel — the backup-race winner cancels
  the straggler (rpc_client_hedge_canceled);
* pressure-steered LB + health-check pacing units (no sockets).
"""

import ctypes
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from brpc_tpu.cluster.health_check import HealthChecker
from brpc_tpu.cluster.load_balancer import create_load_balancer
from brpc_tpu.cluster.naming import ServerNode
from brpc_tpu.metrics.native import read_native_metrics
from brpc_tpu.rpc import errors, wire_tags
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.server import Server
from brpc_tpu.utils import flags
from brpc_tpu.utils.endpoint import EndPoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def deadline_plane():
    """Arm the plane; restore the inert default (off) afterwards so
    unrelated tests in this process see today's behavior."""
    flags.set_flag("deadline_propagate", True)
    flags.set_flag("deadline_reserve_us", 2000)
    yield
    flags.set_flag("deadline_propagate", False)
    flags.set_flag("deadline_reserve_us", 2000)


# --- Controller surface: the budget arrives, and shrinks downstream ---------

def test_server_sees_inbound_budget(deadline_plane):
    seen = []

    def echo(cntl, payload):
        seen.append(cntl.deadline_left_us)
        return payload

    s = Server()
    s.add_service("Echo", echo)
    port = s.start("127.0.0.1:0")
    try:
        ch = Channel(f"127.0.0.1:{port}", ChannelOptions(max_retry=0))
        assert ch.call("Echo", b"x", timeout_ms=500) == b"x"
        ch.close()
    finally:
        s.destroy()
    assert len(seen) == 1
    # the stamped remainder: positive, at most the root timeout, and a
    # sane fraction of it (loopback spends microseconds, not hundreds
    # of milliseconds)
    assert seen[0] is not None and 0 < seen[0] <= 500_000
    assert seen[0] > 100_000, f"budget {seen[0]} lost too much in one hop"


def test_budget_shrinks_across_cascade(deadline_plane):
    """Root -> middle -> backend with NO explicit timeout on the middle
    tier's downstream call: each tier must see strictly less budget than
    the one above (inherited remainder minus the per-hop reserve)."""
    seen = {}

    backend = Server()

    def deep(cntl, payload):
        seen["backend"] = cntl.deadline_left_us
        return payload

    backend.add_service("Deep", deep)
    bport = backend.start("127.0.0.1:0")

    middle = Server()
    down = Channel(f"127.0.0.1:{bport}", ChannelOptions(max_retry=0))

    def relay(cntl, payload):
        seen["middle"] = cntl.deadline_left_us
        return down.call("Deep", payload)  # no timeout: inherits

    middle.add_service("Relay", relay)
    mport = middle.start("127.0.0.1:0")
    try:
        ch = Channel(f"127.0.0.1:{mport}", ChannelOptions(max_retry=0))
        assert ch.call("Relay", b"y", timeout_ms=500) == b"y"
        ch.close()
    finally:
        down.close()
        middle.destroy()
        backend.destroy()
    assert 0 < seen["backend"] < seen["middle"] <= 500_000
    # the downstream stamp is the inherited remainder minus the 2000us
    # reserve (plus elapsed handler time): the gap must show the reserve
    assert seen["middle"] - seen["backend"] >= 2000


def test_off_is_inert_in_process(deadline_plane):
    """Flag off: no budget surfaces server-side and the native drop
    counters stay flat (the wire-level proof is the subprocess A/B)."""
    flags.set_flag("deadline_propagate", False)
    seen = []

    def echo(cntl, payload):
        seen.append(cntl.deadline_left_us)
        return payload

    s = Server()
    s.add_service("Echo", echo)
    port = s.start("127.0.0.1:0")
    try:
        before = read_native_metrics()
        ch = Channel(f"127.0.0.1:{port}", ChannelOptions(max_retry=0))
        assert ch.call("Echo", b"q", timeout_ms=500) == b"q"
        ch.close()
        after = read_native_metrics()
    finally:
        s.destroy()
    assert seen == [None]
    assert after["native_deadline_drops"] == before["native_deadline_drops"]
    assert (after["native_deadline_queue_drops"]
            == before["native_deadline_queue_drops"])


# --- parse-fiber fast-drop: a crafted split frame dies in read_buf ----------

def _tlv(tag, data):
    return bytes([tag]) + struct.pack("<I", len(data)) + data


def _read_frame(sock):
    buf = b""
    while True:
        if len(buf) >= 12:
            ml, bl = struct.unpack(">II", buf[4:12])
            if len(buf) >= 12 + ml + bl:
                return buf[:12 + ml + bl]
        chunk = sock.recv(65536)
        assert chunk, "peer closed before a full frame"
        buf += chunk


def _meta_tlvs(frame):
    ml, _ = struct.unpack(">II", frame[4:12])
    meta, out, i = frame[12:12 + ml], [], 0
    while i + 5 <= len(meta):
        tag = meta[i]
        ln = struct.unpack("<I", meta[i + 1:i + 5])[0]
        out.append((tag, meta[i + 5:i + 5 + ln]))
        i += 5 + ln
    return out


def test_parse_fiber_drops_spent_budget(deadline_plane):
    """A frame whose tag-18 budget is already spent by the time the
    parse fiber drains it must be answered EDEADLINE WITHOUT dispatch:
    the first half of the frame arms the ingress anchor, the second
    half lands after the budget died in read_buf.  The handler-never-ran
    proof is the usercode counter staying flat."""
    s = Server()
    s.add_echo_service()
    port = s.start("127.0.0.1:0")
    try:
        before = read_native_metrics()
        meta = (_tlv(wire_tags.METHOD, b"Echo.echo")
                + _tlv(wire_tags.CORRELATION_ID, struct.pack("<Q", 77))
                + _tlv(wire_tags.DEADLINE_LEFT_US,
                       struct.pack("<Q", 5000)))  # 5ms budget
        payload = b"late-" * 50
        frame = (b"TRPC" + struct.pack(">II", len(meta), len(payload))
                 + meta + payload)
        c = socket.create_connection(("127.0.0.1", port), timeout=30)
        c.sendall(frame[:len(frame) // 2])  # partial: anchors read_arm_ns
        time.sleep(0.08)                    # 80ms >> the 5ms budget
        c.sendall(frame[len(frame) // 2:])
        resp = _read_frame(c)
        c.close()
        after = read_native_metrics()
        tags = dict(_meta_tlvs(resp))
        code = struct.unpack("<i", tags[wire_tags.ERROR_CODE])[0]
        assert code == errors.EDEADLINE
        corr = struct.unpack("<Q", tags[wire_tags.CORRELATION_ID])[0]
        assert corr == 77
        d = lambda k: after[k] - before[k]  # noqa: E731
        assert d("native_deadline_drops") == 1
        assert d("native_deadline_drops_inline_echo") == 1
        assert d("native_usercode_submitted") == 0  # never dispatched
    finally:
        s.destroy()


def test_parse_fiber_keeps_live_budget(deadline_plane):
    """Same split-frame shape with a budget that survives the wait: the
    request must execute normally (the shed is never early)."""
    s = Server()
    s.add_echo_service()
    port = s.start("127.0.0.1:0")
    try:
        before = read_native_metrics()
        meta = (_tlv(wire_tags.METHOD, b"Echo.echo")
                + _tlv(wire_tags.CORRELATION_ID, struct.pack("<Q", 78))
                + _tlv(wire_tags.DEADLINE_LEFT_US,
                       struct.pack("<Q", 2_000_000)))  # 2s budget
        payload = b"on-time"
        frame = (b"TRPC" + struct.pack(">II", len(meta), len(payload))
                 + meta + payload)
        c = socket.create_connection(("127.0.0.1", port), timeout=30)
        c.sendall(frame[:len(frame) // 2])
        time.sleep(0.05)
        c.sendall(frame[len(frame) // 2:])
        resp = _read_frame(c)
        c.close()
        after = read_native_metrics()
        tags = dict(_meta_tlvs(resp))
        assert wire_tags.ERROR_CODE not in tags  # success: echoed back
        assert resp.endswith(payload)
        assert (after["native_deadline_drops"]
                == before["native_deadline_drops"])
    finally:
        s.destroy()


# --- usercode-dequeue drop: queued work whose budget died is never run ------

def test_dequeue_drops_expired_queued_work(deadline_plane):
    """Six concurrent callers with tiny budgets against a slow handler
    on the (4-thread) usercode pool: work that outlives its budget in
    the queue must be answered EDEADLINE WITHOUT the handler running —
    executed + dropped accounts for every admitted call."""
    executed = []
    lock = threading.Lock()

    def slow(cntl, payload):
        with lock:
            executed.append(1)
        time.sleep(0.02)
        return payload

    s = Server()
    s.add_service("Slow", slow)
    port = s.start("127.0.0.1:0")
    try:
        before = read_native_metrics()
        results = []

        def hammer():
            ch = Channel(f"127.0.0.1:{port}",
                         ChannelOptions(max_retry=0, timeout_ms=10))
            got = {"ok": 0, "expired": 0, "other": 0}
            for _ in range(25):
                try:
                    ch.call("Slow", b"w")
                    got["ok"] += 1
                except errors.RpcError as e:
                    if e.code in (errors.EDEADLINE, errors.ERPCTIMEDOUT):
                        got["expired"] += 1
                    else:
                        got["other"] += 1
            ch.close()
            with lock:
                results.append(got)

        ts = [threading.Thread(target=hammer) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # the server keeps draining the backlog after the clients gave
        # up — wait for the queue to empty before reading the counters
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            m = read_native_metrics()
            if (m.get("native_usercode_queue_depth", 0) == 0
                    and m.get("native_usercode_running", 0) == 0):
                break
            time.sleep(0.05)
        after = read_native_metrics()
        drops = (after["native_deadline_queue_drops"]
                 - before["native_deadline_queue_drops"])
        assert drops > 0, "no queued work was dropped at dequeue"
        assert all(g["other"] == 0 for g in results), results
        # every admitted call either ran or was dropped — never both
        submitted = (after["native_usercode_submitted"]
                     - before["native_usercode_submitted"])
        assert len(executed) + drops == submitted
    finally:
        s.destroy()


# --- wire A/B: the flag off is byte-identical --------------------------------

_WIRE_CODE = r"""
import socket, struct, sys, threading
sys.path.insert(0, {repo!r})
lst = socket.socket()
lst.bind(("127.0.0.1", 0)); lst.listen(1)
port = lst.getsockname()[1]
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc import errors

captured = []


def serve():
    c, _ = lst.accept()
    buf = b""
    while True:
        if len(buf) >= 12:
            ml, bl = struct.unpack(">II", buf[4:12])
            if len(buf) >= 12 + ml + bl:
                captured.append(buf[:12 + ml + bl])
                break
        chunk = c.recv(65536)
        if not chunk:
            break
        buf += chunk
    c.close()


t = threading.Thread(target=serve)
t.start()
ch = Channel("127.0.0.1:%d" % port, ChannelOptions(max_retry=0))
try:
    ch.call("Probe", b"deadline-wire-proof", timeout_ms=300)
except errors.RpcError:
    pass  # no reply by design: only the REQUEST bytes matter
t.join(10)
ch.close()
assert captured, "no request frame captured"
print("FRAME", captured[0].hex())
"""


def _request_frame(extra_env) -> bytes:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("TRPC_DEADLINE_PROPAGATE", None)
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", _WIRE_CODE.format(repo=REPO)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert r.returncode == 0, f"wire child failed:\n{r.stdout}\n{r.stderr}"
    for ln in r.stdout.splitlines():
        if ln.startswith("FRAME "):
            return bytes.fromhex(ln.split()[1])
    raise AssertionError(f"no FRAME line:\n{r.stdout}")


def _strip_tag(frame, tag):
    ml, bl = struct.unpack(">II", frame[4:12])
    kept = b"".join(_tlv(t, v) for t, v in _meta_tlvs(frame) if t != tag)
    return b"TRPC" + struct.pack(">II", len(kept), bl) + kept \
        + frame[12 + ml:]


class TestWireByteIdenticalWhenOff:
    def test_unset_vs_zero_vs_empty(self):
        """TRPC_DEADLINE_PROPAGATE unset, =0 and ='' must put EXACTLY
        the same request bytes on the wire: the rail disabled adds no
        tag, no drift (subprocess A/B)."""
        a = _request_frame({})
        b = _request_frame({"TRPC_DEADLINE_PROPAGATE": "0"})
        c = _request_frame({"TRPC_DEADLINE_PROPAGATE": ""})
        assert a and a == b == c
        assert wire_tags.DEADLINE_LEFT_US not in dict(_meta_tlvs(a))

    def test_on_differs_by_exactly_the_budget_tlv(self):
        """The ON frame carries tag 18 with the remaining budget, and
        stripping that one TLV yields the OFF frame byte-for-byte: the
        feature adds nothing else to the wire."""
        off = _request_frame({})
        on = _request_frame({"TRPC_DEADLINE_PROPAGATE": "1"})
        tags = dict(_meta_tlvs(on))
        assert wire_tags.DEADLINE_LEFT_US in tags
        left = struct.unpack("<Q", tags[wire_tags.DEADLINE_LEFT_US])[0]
        assert 0 < left <= 300_000  # the 300ms root timeout, minus spent
        assert _strip_tag(on, wire_tags.DEADLINE_LEFT_US) == off


# --- hedged mixers: the losing attempt is canceled ---------------------------

def test_backup_race_cancels_the_loser(deadline_plane):
    """Both replicas answer slowly enough that the backup always fires;
    whichever attempt wins must CANCEL the other (call_cancel), counted
    in rpc_client_hedge_canceled — the straggler's server-side work
    stops instead of running for a waiter that is gone."""
    canceled_seen = threading.Event()

    def slow(cntl, payload):
        for _ in range(100):  # ~1s worst case, polls the cancel flag
            if cntl.is_canceled():
                canceled_seen.set()
                raise errors.RpcError(errors.ECANCELED, "superseded")
            time.sleep(0.01)
        return payload

    servers, ports = [], []
    try:
        for _ in range(2):
            s = Server()
            s.add_service("Work", slow)
            servers.append(s)
            ports.append(s.start("127.0.0.1:0"))
        ch = Channel(f"list://127.0.0.1:{ports[0]},127.0.0.1:{ports[1]}",
                     ChannelOptions(load_balancer="rr", max_retry=0,
                                    timeout_ms=5000, backup_request_ms=30))
        c0 = Channel._hedge_canceled.get_value()
        fired = 0
        for _ in range(4):
            cntl = Controller()
            assert ch.call("Work", b"h", cntl=cntl) == b"h"
            fired += bool(cntl.backup_fired)
        ch.close()
        assert fired > 0, "backup never fired at 30ms against ~1s handlers"
        assert Channel._hedge_canceled.get_value() > c0
        assert canceled_seen.wait(5), \
            "the losing attempt's handler never observed the cancel"
    finally:
        for s in servers:
            s.destroy()


# --- pressure-steered LB (units, no sockets) --------------------------------

def _n(port, weight=1):
    return ServerNode(EndPoint(ip="127.0.0.1", port=port), weight=weight)


class TestPressureSteering:
    def test_la_bleeds_pressured_node(self):
        lb = create_load_balancer("la")
        a, b = _n(1), _n(2)
        lb.add_servers_in_batch([a, b])
        lb.set_pressure(a, 0.9)
        picks = []
        for _ in range(600):
            node = lb.select()
            picks.append(node.endpoint.port)
            lb.feedback(node, 1000, False)
        share = picks.count(1) / len(picks)
        assert share < 0.35, f"pressured node kept {share:.2f} of traffic"

    def test_wrr_bleeds_pressured_node(self):
        lb = create_load_balancer("wrr")
        a, b = _n(1, weight=1), _n(2, weight=1)
        lb.add_servers_in_batch([a, b])
        lb.set_pressure(a, 0.9)
        picks = [lb.select().endpoint.port for _ in range(600)]
        share = picks.count(1) / len(picks)
        assert share < 0.2, f"pressured node kept {share:.2f} of traffic"

    def test_pressure_release_restores_share(self):
        lb = create_load_balancer("wrr")
        a, b = _n(1), _n(2)
        lb.add_servers_in_batch([a, b])
        lb.set_pressure(a, 0.9)
        lb.set_pressure(a, 0.0)  # recovered: steering must let go
        picks = [lb.select().endpoint.port for _ in range(200)]
        assert abs(picks.count(1) / len(picks) - 0.5) < 0.1

    def test_hashing_lbs_ignore_pressure(self):
        """Placement-stable LBs keep placement: set_pressure is a no-op
        (steering there would break consistent-hash affinity)."""
        lb = create_load_balancer("c_md5")
        a, b = _n(1), _n(2)
        lb.add_servers_in_batch([a, b])
        before = [lb.select(request_code=i).endpoint.port
                  for i in range(64)]
        lb.set_pressure(a, 1.0)
        after = [lb.select(request_code=i).endpoint.port
                 for i in range(64)]
        assert before == after


# --- health-check pacing: jitter + backoff while dead ------------------------

class TestHealthCheckPacing:
    def test_jitter_bounds(self):
        hc = HealthChecker(interval_s=1.0, probe=lambda n: False)
        vals = [hc._jittered(1.0) for _ in range(200)]
        assert all(0.75 <= v <= 1.25 for v in vals)
        assert max(vals) - min(vals) > 0.1, "jitter looks constant"
        hc.stop()

    def test_backoff_while_dead_then_instant_revive(self):
        """A node that stays dead is probed with exponentially growing
        (capped) gaps; the moment the probe passes it revives."""
        alive = threading.Event()
        probes = []

        def probe(node):
            probes.append(time.monotonic())
            return alive.is_set()

        revived = threading.Event()
        hc = HealthChecker(interval_s=0.05, probe=probe,
                           on_revive=lambda n: revived.set(),
                           max_backoff_s=0.4)
        node = _n(9999)
        hc.mark_broken(node)
        try:
            deadline = time.monotonic() + 10
            while len(probes) < 4 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(probes) >= 4, "probing stalled"
            assert hc.probe_backlog()[node] >= 4
            gaps = [b - a for a, b in zip(probes, probes[1:])]
            # exponential: later gaps dominate earlier ones (jitter is
            # ±25%, so a 2x step always orders)
            assert gaps[2] > gaps[0], f"no backoff growth: {gaps}"
            # capped: no gap exceeds max_backoff * (1 + jitter) + tick
            assert all(g < 0.4 * 1.25 + 0.1 for g in gaps), gaps
            alive.set()
            assert revived.wait(2.0), "revive never fired after recovery"
            assert node not in hc.broken_nodes()
        finally:
            alive.set()
            hc.stop()

    def test_checker_thread_exits_when_idle(self):
        hc = HealthChecker(interval_s=0.02, probe=lambda n: True,
                           on_revive=lambda n: None)
        hc.mark_broken(_n(9998))
        deadline = time.monotonic() + 5
        while hc.broken_nodes() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not hc.broken_nodes()
        time.sleep(0.15)  # a few ticks past empty: the thread parks
        assert hc._thread is not None and not hc._thread.is_alive()
