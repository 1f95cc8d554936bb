"""Native hot-path telemetry plane (ISSUE 9; native/src/metrics.{h,cc}):
per-shard latency histograms for the method families that never leave
the native core, fast-path rpcz span capture, and cross-hop trace
propagation through TLV tags 7/8.

The sharded /status leg runs in a subprocess (TRPC_SHARDS is boot-frozen
per process, same shape as tests/test_shards.py); the trace-tree leg is
a real two-process proof: a client here, a serving process over there,
/rpcz?trace_id= assembling the client→server→downstream tree.
"""

import ctypes
import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(extra=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if extra:
        env.update(extra)
    return env


# --- acceptance: /status under echo load with TRPC_SHARDS=2 ----------------

_STATUS_CHILD = r"""
import sys, os, json, urllib.request
sys.path.insert(0, {repo!r})
from brpc_tpu.rpc.server import Server
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu._native import lib

srv = Server()
srv.add_echo_service()
port = srv.start("127.0.0.1:0")
assert lib().trpc_shard_count() == 2, lib().trpc_shard_count()
ch = Channel(f"127.0.0.1:{{port}}", ChannelOptions(max_retry=0))
for _ in range(300):
    ch.call("Echo", b"telemetry-status-probe")
st = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{{port}}/status", timeout=10).read())
out = {{
    "native": st["native_methods"],
    "shards": int(lib().trpc_shard_count()),
    "hops": int(lib().trpc_cross_shard_hops()),
}}
ch.close()
srv.destroy()
print("STATUS_JSON " + json.dumps(out))
"""


def test_status_reports_fastpath_latency_under_forced_shards():
    """Acceptance: under echo load with TRPC_SHARDS=2, /status shows
    non-zero qps and a sane p99 for the inline-dispatched native echo —
    the requests the Python LatencyRecorder never sees — while
    cross_shard_hops stays 0 (telemetry writes are shard-confined)."""
    r = subprocess.run(
        [sys.executable, "-c", _STATUS_CHILD.format(repo=REPO)],
        capture_output=True, text=True, timeout=180,
        env=_child_env({"TRPC_SHARDS": "2"}), cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stdout.splitlines()
            if l.startswith("STATUS_JSON ")][0]
    out = json.loads(line[len("STATUS_JSON "):])
    assert out["shards"] == 2
    echo = out["native"]["inline_echo"]
    assert echo["count"] >= 300, echo
    assert echo["qps"] > 0, echo
    # sane p99: positive, and within the histogram's measurable range
    assert 0 < echo["latency_99_us"] < 10_000_000, echo
    assert echo["latency_50_us"] <= echo["latency_99_us"] \
        <= echo["latency_999_us"] or echo["latency_50_us"] == 0, echo
    # the echo hot path must not have hopped reactors to be measured
    assert out["hops"] == 0, out
    # the client side of the same traffic is histogrammed too
    assert out["native"]["client_unary"]["count"] >= 300, out["native"]


# --- acceptance: two-process trace tree -------------------------------------

_TREE_SERVER = r"""
import sys, os
sys.path.insert(0, {repo!r})
from brpc_tpu.rpc import span  # defines enable_rpcz
from brpc_tpu.utils import flags
flags.set_flag("enable_rpcz", True)
from brpc_tpu.rpc.server import Server
from brpc_tpu.rpc.channel import Channel, ChannelOptions

holder = {{}}


def hop(cntl, req):
    # the middle hop: annotate, then call downstream (our own native
    # echo) — the downstream client span parents at this server span
    cntl.trace_annotate("hop handler ran")
    ch = Channel(f"127.0.0.1:{{holder['port']}}",
                 ChannelOptions(max_retry=0))
    ch.call("Echo", b"downstream")
    ch.close()
    return (str(cntl.trace_id) + ":" + str(cntl.span_id)).encode()


srv = Server()
srv.add_echo_service()
srv.add_service("Hop", hop)
port = srv.start("127.0.0.1:0")
holder["port"] = port
print("PORT %d" % port, flush=True)
sys.stdin.readline()  # parent closes stdin when done querying
srv.destroy()
"""


def test_two_process_rpcz_trace_tree():
    """Acceptance: /rpcz?trace_id= on the SERVING process returns the
    assembled client→server→downstream tree with consistent
    parent_span_id links — the client span lives in THIS process, the
    server/downstream/fast-path spans in the other one."""
    from brpc_tpu.rpc import span
    from brpc_tpu.utils import flags
    proc = subprocess.Popen(
        [sys.executable, "-c", _TREE_SERVER.format(repo=REPO)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=_child_env(), cwd=REPO)
    try:
        line = proc.stdout.readline()
        assert line.startswith("PORT "), line + proc.stderr.read()
        port = int(line.split()[1])

        flags.set_flag("enable_rpcz", True)
        try:
            from brpc_tpu.rpc.channel import Channel, ChannelOptions
            ch = Channel(f"127.0.0.1:{port}", ChannelOptions(max_retry=0))
            resp = ch.call("Hop", b"trace me")
            ch.close()
            # our client span (this process's half of the tree)
            ours = [s for s in span.recent_spans(50)
                    if s.kind == "client" and s.method == "Hop"]
            assert ours, "client span was not sampled"
            client = ours[0]
            # the handler surfaced the INBOUND ids on its Controller —
            # they must be exactly our client span's ids
            tid_s, sid_s = resp.decode().split(":")
            assert int(tid_s) == client.trace_id
            assert int(sid_s) == client.span_id

            url = (f"http://127.0.0.1:{port}/rpcz?"
                   f"trace_id={client.trace_id:x}&max_scan=200")
            # the server records its span AFTER writing the response, so
            # our query can win that race under load — poll briefly
            deadline = time.monotonic() + 10
            while True:
                remote = json.loads(
                    urllib.request.urlopen(url, timeout=10).read())
                by_kind = {}
                for s in remote:
                    by_kind.setdefault((s["kind"], s["method"]),
                                       []).append(s)
                server_sp = by_kind.get(("server", "Hop"), [None])[0]
                if server_sp is not None or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            assert server_sp is not None, remote
            # link 1: server span parents at OUR client span
            assert int(server_sp["parent_span_id"], 16) == client.span_id
            assert "hop handler ran" in "".join(server_sp["annotations"])
            # link 2: the downstream client span parents at the server
            ds = by_kind.get(("client", "Echo"), [None])[0]
            assert ds is not None, remote
            assert ds["parent_span_id"] == server_sp["span_id"]
            # link 3: the NATIVE fast-path span of the downstream echo
            # parents at the downstream client span — /rpcz finally
            # shows the inline-dispatched request
            native = by_kind.get(("server", "Echo (native inline)"),
                                 [None])[0]
            assert native is not None, remote
            assert native["parent_span_id"] == ds["span_id"]

            # the HTML tree renders the same chain indented
            html = urllib.request.urlopen(
                url + "&view=tree", timeout=10).read().decode()
            assert "Echo (native inline)" in html
            assert "&nbsp;" in html  # children actually indent
        finally:
            flags.set_flag("enable_rpcz", False)
            span.clear()
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=30)
        except Exception:
            proc.kill()


# --- native TRACEPRINTF twin + drain ----------------------------------------

def test_native_annotate_twin_rides_captured_span():
    """cntl.trace_annotate's native twin: with no Python span current the
    annotation parks in the thread's native buffer and rides the next
    native-captured span (here: the client-unary span of a raw native
    call, which also proves the drain surfaces fast-path spans)."""
    from brpc_tpu._native import lib
    from brpc_tpu.rpc import span
    from brpc_tpu.rpc.controller import Controller
    from brpc_tpu.utils import flags
    L = lib()
    flags.set_flag("enable_rpcz", True)
    try:
        srv = L.trpc_server_create()
        L.trpc_server_add_echo(srv)
        assert L.trpc_server_start(srv, b"127.0.0.1", 0) == 0
        port = L.trpc_server_port(srv)
        # no Python span exists -> the twin takes the native path
        Controller().trace_annotate("twin annotation")
        ch = L.trpc_channel_create(b"127.0.0.1", port)
        res = ctypes.c_void_p()
        assert L.trpc_channel_call(ch, b"Echo", b"x" * 16, 16, None, 0,
                                   2_000_000, ctypes.byref(res)) == 0
        L.trpc_result_destroy(res)
        L.trpc_channel_destroy(ch)
        deadline = time.monotonic() + 5
        found = []
        while time.monotonic() < deadline and not found:
            found = [s for s in span.recent_spans(100)
                     if s.kind == "client"
                     and "twin annotation" in "|".join(s.annotations)]
            time.sleep(0.05)
        assert found, span.recent_spans(20)
        # the native client span and the inline server span share a trace
        tid = found[0].trace_id
        kinds = {s.kind for s in span.recent_spans(100)
                 if s.trace_id == tid}
        assert kinds == {"client", "server"}, kinds
        L.trpc_server_destroy(srv)
    finally:
        flags.set_flag("enable_rpcz", False)
        span.clear()


def test_telemetry_off_stops_histogram_writes():
    """TRPC_TELEMETRY=0 is the A/B baseline: no histogram writes at all
    (the reloadable flag gates every record site)."""
    from brpc_tpu._native import lib
    L = lib()
    L.trpc_init(2)
    before_on = L.trpc_telemetry_active()
    try:
        L.trpc_set_telemetry(0)
        srv = L.trpc_server_create()
        L.trpc_server_add_echo(srv)
        assert L.trpc_server_start(srv, b"127.0.0.1", 0) == 0
        port = L.trpc_server_port(srv)
        base = int(L.trpc_telemetry_count(0))  # TF_INLINE_ECHO
        cbase = int(L.trpc_telemetry_count(4))  # TF_CLIENT_UNARY
        ch = L.trpc_channel_create(b"127.0.0.1", port)
        res = ctypes.c_void_p()
        for _ in range(20):
            assert L.trpc_channel_call(ch, b"Echo", b"y" * 8, 8, None, 0,
                                       2_000_000, ctypes.byref(res)) == 0
            L.trpc_result_destroy(res)
        L.trpc_channel_destroy(ch)
        assert int(L.trpc_telemetry_count(0)) == base
        assert int(L.trpc_telemetry_count(4)) == cbase
        # flipped back on, the same path records again (reloadable)
        L.trpc_set_telemetry(1)
        ch = L.trpc_channel_create(b"127.0.0.1", port)
        for _ in range(5):
            assert L.trpc_channel_call(ch, b"Echo", b"y" * 8, 8, None, 0,
                                       2_000_000, ctypes.byref(res)) == 0
            L.trpc_result_destroy(res)
        L.trpc_channel_destroy(ch)
        assert int(L.trpc_telemetry_count(0)) >= base + 5
        L.trpc_server_destroy(srv)
    finally:
        L.trpc_set_telemetry(1 if before_on else 0)


def test_rpc_press_json_summary_has_p999(tmp_path):
    """Satellite: rpc_press grows p999 + a one-JSON-line summary — the
    traffic cannon for the overload-control PR must emit machine-readable
    percentiles."""
    from brpc_tpu.rpc.server import Server
    from brpc_tpu.tools import rpc_press
    srv = Server()
    srv.add_echo_service()
    port = srv.start("127.0.0.1:0")
    try:
        res = rpc_press.press(f"127.0.0.1:{port}", "Echo", b"press",
                              concurrency=2, duration_s=1.0)
        assert res.calls > 0 and res.errors == 0
        assert "p999=" in res.summary()
        line = json.loads(res.to_json_line())
        assert line["metric"] == "rpc_press"
        assert line["calls"] == res.calls
        for k in ("p50_us", "p90_us", "p99_us", "p999_us", "qps"):
            assert k in line, line
        assert line["p99_us"] <= line["p999_us"] or line["p999_us"] >= 0
    finally:
        srv.destroy()
