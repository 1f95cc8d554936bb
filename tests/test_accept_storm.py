"""Accept-path EMFILE/ENFILE backoff (ISSUE 16 satellite, rpc.cc
OnNewConnections).

The bug: fd exhaustion during accept used to return and wait for the
next epoll edge — but the listener is edge-triggered, so connections
already queued in the kernel backlog when EMFILE hit would NEVER be
announced again.  The fix parks the accept loop on an exponential
backoff timer (socket.h kick_timer) that re-kicks the listener's
processing fiber, and counts each pause in native_accept_backoffs.

Reference style (SURVEY §4): a real loopback server in a subprocess
(RLIMIT_NOFILE games must not poison the pytest process), raw sockets,
the native metrics dump for the counter proof.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout: float = 180.0, extra_env=None) -> str:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    for k in list(env):
        if k.startswith("TRPC_"):
            del env[k]
    env.update(extra_env or {})
    pre = ("import sys, os\n"
           f"sys.path.insert(0, {REPO!r})\n"
           "from brpc_tpu.rpc.server import Server\n")
    r = subprocess.run([sys.executable, "-c", pre + code],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, f"child failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


# Shared wire-level echo helper for the subprocess legs.
_ECHO_HELPERS = r"""
import errno, socket, struct, time
from brpc_tpu.metrics.native import read_native_metrics


def tlv(tag, data):
    return bytes([tag]) + struct.pack("<I", len(data)) + data


def echo(s, corr, payload):
    meta = tlv(1, b"Echo.echo") + tlv(2, struct.pack("<Q", corr))
    s.sendall(b"TRPC" + struct.pack(">II", len(meta), len(payload))
              + meta + payload)
    buf = b""
    while True:
        if len(buf) >= 12:
            ml, bl = struct.unpack(">II", buf[4:12])
            if len(buf) >= 12 + ml + bl:
                break
        chunk = s.recv(65536)
        assert chunk, "peer closed early"
        buf += chunk
    assert buf[12 + ml:12 + ml + bl] == payload


def poll_metric(name, floor, deadline_s=30.0):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        v = read_native_metrics().get(name, 0)
        if v >= floor:
            return v
        time.sleep(0.01)
    raise AssertionError("%s never reached %d: %r"
                         % (name, floor, read_native_metrics().get(name)))
"""


# Exhaust the process fd table with spare sockets, connect a burst of
# clients (the TCP handshakes complete via the kernel backlog even though
# accept4 is failing EMFILE), then free the fds.  Edge-triggered epoll
# guarantees no new readiness edge for the already-queued connections —
# only the backoff timer's re-kick can ever accept them.
_EMFILE_CODE = r"""
import errno, resource, socket, struct, time
from brpc_tpu.metrics.native import read_native_metrics

srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")


def tlv(tag, data):
    return bytes([tag]) + struct.pack("<I", len(data)) + data


def echo(s, corr, payload):
    meta = tlv(1, b"Echo.echo") + tlv(2, struct.pack("<Q", corr))
    s.sendall(b"TRPC" + struct.pack(">II", len(meta), len(payload))
              + meta + payload)
    buf = b""
    while True:
        if len(buf) >= 12:
            ml, bl = struct.unpack(">II", buf[4:12])
            if len(buf) >= 12 + ml + bl:
                break
        chunk = s.recv(65536)
        assert chunk, "peer closed early"
        buf += chunk
    assert buf[12 + ml:12 + ml + bl] == payload


# prove the accept path healthy before the storm
w = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
w.settimeout(30)
echo(w, 1, b"warm")

soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
resource.setrlimit(resource.RLIMIT_NOFILE, (min(soft, 256), hard))
NCONN = 4
# client sockets FIRST (their fds must exist before the table fills);
# connect() later needs no new fd, so the storm can start at zero-free
conns = [socket.socket() for _ in range(NCONN)]
for c in conns:
    c.settimeout(30)
spares = []
try:
    while True:
        try:
            spares.append(socket.socket())
        except OSError as e:
            assert e.errno == errno.EMFILE, e
            break
    for c in conns:
        c.connect(("127.0.0.1", srv.port))  # backlog handshake, no accept
    deadline = time.time() + 30
    while time.time() < deadline:
        if read_native_metrics().get("native_accept_backoffs", 0) >= 1:
            break
        time.sleep(0.01)
    m = read_native_metrics()
    assert m.get("native_accept_backoffs", 0) >= 1, m
finally:
    for sp in spares:
        sp.close()
    resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))

# fds are back, but the queued connections produce no new epoll edge:
# completing an echo on each one proves the timer re-kick resumed accept
for i, c in enumerate(conns):
    echo(c, 100 + i, b"storm-%d" % i)
    c.close()
w.close()
print("BACKOFFS", read_native_metrics()["native_accept_backoffs"])
srv.destroy()
print("OK")
"""


class TestAcceptBackoff:
    def test_emfile_backoff_rekicks_accept(self):
        out = _run(_EMFILE_CODE)
        assert "OK" in out
        backoffs = [int(l.split()[1]) for l in out.splitlines()
                    if l.startswith("BACKOFFS ")]
        assert backoffs and backoffs[0] >= 1


# A low-rate token bucket (burst 1) forces the listener to park on the
# pacing timer mid-burst: native_accept_paced counts the parks, and every
# connection is still served once its token arrives.
_PACING_CODE = _ECHO_HELPERS + r"""
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
N = 6
conns = [socket.create_connection(("127.0.0.1", srv.port), timeout=30)
         for _ in range(N)]
for c in conns:
    c.settimeout(30)
for i, c in enumerate(conns):
    echo(c, i + 1, b"paced-%d" % i)
    c.close()
paced = poll_metric("native_accept_paced", 1)
print("PACED", paced)
srv.destroy()
print("OK")
"""


# TRPC_ACCEPT_MAX_PENDING=2: silent connections pile up in the kernel
# backlog once two accepted ones are awaiting first bytes; the pending
# gauge is capped, and each first-bytes release re-kicks the parked
# listener until everyone is served.
_PENDING_CAP_CODE = _ECHO_HELPERS + r"""
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
N = 6
conns = [socket.create_connection(("127.0.0.1", srv.port), timeout=30)
         for _ in range(N)]
for c in conns:
    c.settimeout(30)
# all N handshakes complete via the backlog; only 2 may be accepted
poll_metric("native_accept_pending_handshakes", 2)
time.sleep(0.2)  # give the accept loop rope to (wrongly) run past the cap
g = read_native_metrics()["native_accept_pending_handshakes"]
assert g <= 2, g
poll_metric("native_accept_paced", 1)  # the park itself is counted
# speaking releases the charge and unblocks the next accept, in waves
for i, c in enumerate(conns):
    echo(c, i + 1, b"capped-%d" % i)
for c in conns:
    c.close()
deadline = time.time() + 30
while time.time() < deadline:
    if read_native_metrics()["native_accept_pending_handshakes"] == 0:
        break
    time.sleep(0.01)
assert read_native_metrics()["native_accept_pending_handshakes"] == 0
srv.destroy()
print("OK")
"""


# TRPC_IDLE_KICK_MS=50: after traffic stops, the heartbeat notices the
# quiet connection (native_conn_idle_kicks), shrinks its banked read-buf
# blocks (native_conn_shrinks), and the connection still answers echoes.
_IDLE_KICK_CODE = _ECHO_HELPERS + r"""
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
c = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
c.settimeout(30)
# a multi-block payload leaves refs capacity banked in the read buffer
echo(c, 1, b"x" * 150000)
poll_metric("native_conn_idle_kicks", 1)
poll_metric("native_conn_shrinks", 1)
shrunk = read_native_metrics()["native_conn_shrunk_bytes"]
assert shrunk > 0, shrunk
echo(c, 2, b"still-alive")  # the diet must not cost correctness
c.close()
srv.destroy()
print("OK")
"""


# Shard-confinement proof (acceptance: zero cross-shard hops at
# TRPC_SHARDS=2).  With idle kicks beating on live connections, the
# timer-arm counter grows during a pure-idle window while BOTH the
# foreign-arm counter (global-wheel fallback) and the cross-shard mailbox
# hop counter stay flat: every re-arm lands on the arming worker's own
# shard wheel and every kick dispatches via the socket's own shard group.
_SHARDED_IDLE_CODE = _ECHO_HELPERS + r"""
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
conns = [socket.create_connection(("127.0.0.1", srv.port), timeout=30)
         for _ in range(4)]
for i, c in enumerate(conns):
    c.settimeout(30)
    echo(c, i + 1, b"warm-%d" % i)  # first drain arms the idle kick
poll_metric("native_conn_idle_kicks", 1)
m0 = read_native_metrics()
time.sleep(0.6)  # ~30 beats across 4 conns at 20ms
m1 = read_native_metrics()
arms_d = m1["native_timer_arms"] - m0["native_timer_arms"]
foreign_d = (m1["native_timer_foreign_arms"]
             - m0["native_timer_foreign_arms"])
hops_d = m1["native_cross_shard_hops"] - m0["native_cross_shard_hops"]
assert arms_d > 0, (m0, m1)
assert foreign_d == 0, (arms_d, foreign_d, m0, m1)
assert hops_d == 0, (arms_d, hops_d, m0, m1)
for c in conns:
    c.close()
srv.destroy()
print("ARMS %d FOREIGN %d HOPS %d" % (arms_d, foreign_d, hops_d))
print("OK")
"""


# Memory diet: the per-connection parser state is first-byte-lazy — an
# accepted-but-silent connection costs no ConnState; the gauge moves only
# once bytes arrive on a path that pipelines (HTTP here — the native
# unary fast path needs no per-connection sequencer at all).
_LAZY_PARSE_CODE = _ECHO_HELPERS + r"""
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
c = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
c.settimeout(30)
time.sleep(0.3)  # accepted long ago; still silent
assert read_native_metrics()["native_conn_parse_states"] == 0
c.sendall(b"GET /nope HTTP/1.1\r\nHost: x\r\n\r\n")
assert c.recv(65536)  # any response: the parser state now exists
poll_metric("native_conn_parse_states", 1)
c.close()
srv.destroy()
print("OK")
"""


class TestAcceptPacing:
    def test_token_bucket_parks_then_serves_all(self):
        out = _run(_PACING_CODE, extra_env={
            "TRPC_ACCEPT_RATE": "20", "TRPC_ACCEPT_BURST": "1"})
        assert "OK" in out

    def test_pending_handshake_cap_releases_on_first_bytes(self):
        out = _run(_PENDING_CAP_CODE,
                   extra_env={"TRPC_ACCEPT_MAX_PENDING": "2"})
        assert "OK" in out


class TestIdleConnectionDiet:
    def test_idle_kick_shrinks_and_connection_survives(self):
        out = _run(_IDLE_KICK_CODE, extra_env={"TRPC_IDLE_KICK_MS": "50"})
        assert "OK" in out

    def test_parse_state_is_first_byte_lazy(self):
        out = _run(_LAZY_PARSE_CODE, extra_env={"TRPC_IDLE_KICK_MS": "0"})
        assert "OK" in out

    def test_sharded_idle_kicks_zero_foreign_arms_zero_hops(self):
        out = _run(_SHARDED_IDLE_CODE, extra_env={
            "TRPC_SHARDS": "2", "TRPC_REUSEPORT": "1",
            "TRPC_IDLE_KICK_MS": "20"})
        assert "OK" in out


class TestConnectionCannon:
    """rpc_press --connections (ISSUE 16 satellite): idle-connection
    cannon with a hot subset, per-leg percentiles in the JSON line."""

    def test_cannon_legs_and_json_shape(self):
        import json as _json

        from brpc_tpu.rpc.server import Server
        from brpc_tpu.tools import rpc_press

        srv = Server()
        srv.add_echo_service()
        port = srv.start("127.0.0.1:0")
        try:
            res = rpc_press.press_connections(
                f"127.0.0.1:{port}", "Echo", b"cannon",
                connections=300, hot=2, duration_s=0.5,
                churn_per_s=200.0, storms=2)
            assert res.opened >= 300, res.summary()
            assert res.failed == 0, res.summary()
            assert res.errors == 0, res.summary()
            assert res.reconnects > 0, res.summary()
            line = _json.loads(res.to_json_line())
            assert line["metric"] == "rpc_press_connections"
            assert line["storms"] == 2
            legs = {d["leg"]: d for d in line["legs"]}
            assert set(legs) == {"ramp", "churn", "storm"}
            for d in legs.values():
                # hot traffic flowed through every leg, tail intact
                assert d["calls"] > 0, line
                assert d["p50_us"] <= d["p99_us"] <= d["p999_us"], line
        finally:
            srv.destroy()
