"""Device data plane tests (native/src/tpu.cc + brpc_tpu/tpu_plane.py).

The plane binds a PJRT plugin at runtime.  These tests cover both halves
of the contract:

* WITHOUT a plugin (forced via TRPC_PJRT_PLUGIN=/nonexistent): the plane
  reports unavailable with a reason, tpu:// channels settle in an
  EXPLICIT "fallback_tcp" transport state (never a silent downgrade,
  ≙ rdma_endpoint.h:95 FALLBACK_TCP), and HbmEcho requests fail loudly.
* WITH a plugin (the installed libtpu on a TPU VM, or the fake plugin):
  an RPC attachment round-trips
  host->HBM->host through the plane, the handshake settles in "device",
  and the transfer counters advance.

Each scenario runs in a subprocess: a PJRT client is process-global state
the test runner must not inherit.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_extra=None, timeout=180):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


FALLBACK_CODE = r"""
from brpc_tpu import tpu_plane
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server
from brpc_tpu.rpc import errors

# the plane must fail loudly, with a reason
assert not tpu_plane.init(), "plane must be unavailable with a bogus plugin"
assert tpu_plane.error(), "unavailability must carry a reason"

srv = Server()
srv.add_service("Echo", lambda cntl, req: b"tcp:" + req)
srv.add_hbm_echo_service()
srv.start("127.0.0.1:0")

ch = Channel(f"tpu://0/0@127.0.0.1:{srv.port}",
             ChannelOptions(max_retry=0, timeout_ms=5000))
# plain calls still work over the TCP control plane...
assert ch.call("Echo", b"hi") == b"tcp:hi"
# ...and the handshake SETTLED EXPLICITLY in fallback (both ends probed)
assert ch.transport_state == "fallback_tcp", ch.transport_state
# device-dependent service fails loudly, not silently
try:
    ch.call("HbmEcho", b"x", attachment=b"a" * 1024)
    raise SystemExit("HbmEcho must fail without a device plane")
except errors.RpcError as e:
    assert "device plane unavailable" in str(e), e
ch.close()
srv.destroy()
print("FALLBACK-OK")
"""


def test_fallback_is_explicit():
    """tpu:// with no usable plugin: visible fallback_tcp state + loud
    HbmEcho failure (replaces the old silent TCP downgrade)."""
    r = _run(FALLBACK_CODE,
             env_extra={"TRPC_PJRT_PLUGIN": "/nonexistent/pjrt.so"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FALLBACK-OK" in r.stdout


DEVICE_CODE = r"""
from brpc_tpu import tpu_plane
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server

assert tpu_plane.init(timeout_s=120), tpu_plane.error()
before = tpu_plane.stats()

# raw plane round-trip: butex-woken completion, data integrity
data = bytes(bytearray(range(256)) * 1024)  # 256KB
buf = tpu_plane.h2d(data)
buf.wait()
assert buf.to_host() == data
buf.free()

# RPC attachment round-trip through HBM (HbmEcho, native end to end)
srv = Server()
srv.add_hbm_echo_service()
srv.start("127.0.0.1:0")
ch = Channel(f"tpu://0/0@127.0.0.1:{srv.port}",
             ChannelOptions(max_retry=0, timeout_ms=60_000))
from brpc_tpu.rpc.controller import Controller
cntl = Controller()
resp = ch.call("HbmEcho", b"ping", attachment=data, cntl=cntl)
assert resp == b"ping"
assert cntl.response_attachment == data
assert ch.transport_state == "device", ch.transport_state
after = tpu_plane.stats()
assert after["h2d_transfers"] >= before["h2d_transfers"] + 2
assert after["d2h_transfers"] >= before["d2h_transfers"] + 2
assert after["errors"] == before["errors"]
ch.close()
srv.destroy()
print("DEVICE-OK")
"""


FAKE_PLUGIN = os.path.join(REPO, "brpc_tpu", "_native", "libpjrt_fake.so")

FAKE_ENV = {
    "TRPC_PJRT_PLUGIN": FAKE_PLUGIN,
    # nonzero completion delay: every butex-wake path really parks
    "TRPC_FAKE_PJRT_DELAY_US": "2000",
}


def _need_fake():
    if not os.path.exists(FAKE_PLUGIN):
        pytest.skip("fake PJRT plugin not built (native/build.sh)")


def test_device_roundtrip_on_fake_plane():
    """The FULL device leg — raw plane round-trip, HbmEcho attachment
    through HBM, handshake settling in 'device', counters advancing — on
    the in-repo fake plugin, unskippable on any host (≙ the reference
    testing above the verbs layer without RDMA hardware)."""
    _need_fake()
    r = _run(DEVICE_CODE, env_extra=FAKE_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEVICE-OK" in r.stdout


ZERO_COPY_CODE = r"""
from brpc_tpu import tpu_plane
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.server import Server

assert tpu_plane.init(), tpu_plane.error()
srv = Server()
srv.add_hbm_echo_service()
srv.start("127.0.0.1:0")
ch = Channel(f"tpu://0/0@127.0.0.1:{srv.port}",
             ChannelOptions(max_retry=0, timeout_ms=30_000))
data = bytes(bytearray(range(256)) * 1024)  # 256KB, one IOBuf block
before = tpu_plane.stats()
cntl = Controller()
resp = ch.call("HbmEcho", b"ping", attachment=data, cntl=cntl)
assert resp == b"ping" and cntl.response_attachment == data
after = tpu_plane.stats()
# the single-block attachment DMAs from the block itself, both
# directions (client send + server send-back): pointer identity, no
# gather — a regression to silent gathering fails here
assert after["zero_copy_sends"] >= before["zero_copy_sends"] + 1, (before, after)
assert after["gather_copies"] == before["gather_copies"], (before, after)
ch.close()
srv.destroy()
print("ZERO-COPY-OK")
"""


def test_zero_copy_attachment_counters():
    _need_fake()
    r = _run(ZERO_COPY_CODE, env_extra=FAKE_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ZERO-COPY-OK" in r.stdout


FAULT_CODE = r"""
import os, time
from brpc_tpu import tpu_plane

assert tpu_plane.init(), tpu_plane.error()
assert tpu_plane.platform() == "fake"
assert tpu_plane.device_count() == 2
data = b"\xa5" * 4096

# second addressable device works end-to-end
b = tpu_plane.h2d(data, device=1)
b.wait()
assert b.to_host() == data
b.free()

# raw device-to-device: new buffer on the target device, source intact
b = tpu_plane.h2d(data, device=0)
b.wait()
c = tpu_plane.d2d(b, 1)
assert c.to_host() == data
assert b.to_host() == data  # source untouched
before_d2d = tpu_plane.stats()["d2d_transfers"]
assert before_d2d >= 1
b.free(); c.free()

# sync create failure surfaces at h2d() with the plane's reason
os.environ["TRPC_FAKE_PJRT_FAIL"] = "h2d"
try:
    tpu_plane.h2d(data)
    raise SystemExit("h2d must fail")
except IOError as e:
    assert "injected h2d failure" in str(e), e
del os.environ["TRPC_FAKE_PJRT_FAIL"]

# residency event completing WITH an error -> wait() raises IOError
os.environ["TRPC_FAKE_PJRT_FAIL"] = "ready"
b = tpu_plane.h2d(data)
try:
    b.wait()
    raise SystemExit("wait must fail")
except IOError:
    pass
b.free()
del os.environ["TRPC_FAKE_PJRT_FAIL"]

# copy event completing WITH an error -> to_host() raises IOError
os.environ["TRPC_FAKE_PJRT_FAIL"] = "d2h"
b = tpu_plane.h2d(data)
b.wait()
try:
    b.to_host()
    raise SystemExit("to_host must fail")
except IOError:
    pass
b.free()
del os.environ["TRPC_FAKE_PJRT_FAIL"]

# DROPPED copy event: the wait is BOUNDED (never wedges the thread) and
# the plane records the reason
os.environ["TRPC_FAKE_PJRT_DROP_D2H_EVENT"] = "1"
os.environ["TRPC_TPU_D2H_TIMEOUT_US"] = "300000"
b = tpu_plane.h2d(data)
b.wait()
t0 = time.monotonic()
try:
    b.to_host()
    raise SystemExit("dropped event must time out")
except IOError:
    elapsed = time.monotonic() - t0
    assert 0.2 < elapsed < 5.0, elapsed
assert "never completed" in tpu_plane.error(), tpu_plane.error()
b.free()
del os.environ["TRPC_FAKE_PJRT_DROP_D2H_EVENT"]

# the plane keeps working after every injected fault
b = tpu_plane.h2d(data)
b.wait()
assert b.to_host() == data
b.free()
stats = tpu_plane.stats()
assert stats["errors"] >= 3
assert stats["live_buffers"] == 0, stats
print("FAULTS-OK")
"""


DEVICE_STREAM_CODE = r"""
import time
from brpc_tpu import tpu_plane
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server
from brpc_tpu.rpc.stream import StreamProtocolError

assert tpu_plane.init(), tpu_plane.error()
accepted = []

def handler(cntl, req):
    accepted.append(cntl.accept_stream())
    return b"ok"

srv = Server()
srv.add_service("TensorSink", handler)
srv.start("127.0.0.1:0")

# --- LOCAL rail: tpu:// channel, handshake exchanges plane uids --------
ch = Channel(f"tpu://0/0@127.0.0.1:{srv.port}",
             ChannelOptions(max_retry=0, timeout_ms=30_000))
resp, st = ch.create_stream("TensorSink", b"")
assert resp == b"ok"
assert ch.transport_state == "device", ch.transport_state
server_half = accepted[0]

frames = [bytes([i]) * (64 * 1024) for i in range(8)]
before = tpu_plane.stats()
for f in frames:
    buf = tpu_plane.h2d(f, device=0)
    st.write_device(buf)  # ownership transfers to the stream
got = [server_half.read_device(device=1, timeout_s=30) for _ in frames]
after = tpu_plane.stats()
# 8 tensors moved dev0->dev1 on the local rail: one CopyToDevice each,
# ZERO host landings beyond the 8 creation h2ds, ZERO gathers
assert after["d2d_transfers"] == before["d2d_transfers"] + 8, (before, after)
assert after["gather_copies"] == before["gather_copies"], (before, after)
assert after["h2d_transfers"] == before["h2d_transfers"] + 8, (before, after)
assert after["d2h_transfers"] == before["d2h_transfers"], (before, after)
# content survives (the verification d2h comes after the accounting)
assert got[3].to_host() == frames[3]
for b in got:
    b.free()

# --- HOST rail: a POOLED connection never carries the tag-14/15 probe,
# so the socket has no shared-client evidence and the frame must fall
# back to explicit host bytes.  (A plain single channel would SocketMap-
# share the probed connection above and legitimately keep the local rail.)
ch2 = Channel(f"127.0.0.1:{srv.port}",
              ChannelOptions(connection_type="pooled"))
resp, st2 = ch2.create_stream("TensorSink", b"")
server_half2 = accepted[1]
b4 = tpu_plane.stats()
buf = tpu_plane.h2d(frames[5], device=0)
st2.write_device(buf)
# a host read on a device frame is a typed error and consumes nothing
deadline = time.monotonic() + 10
while server_half2.pending_bytes == 0 and time.monotonic() < deadline:
    time.sleep(0.01)
try:
    server_half2.read(timeout_s=1)
    raise SystemExit("read() must reject a device frame")
except StreamProtocolError:
    pass
rbuf = server_half2.read_device(device=1, timeout_s=30)
assert rbuf.to_host() == frames[5]
rbuf.free()
a4 = tpu_plane.stats()
assert a4["d2d_transfers"] == b4["d2d_transfers"], (b4, a4)   # no rail
assert a4["d2h_transfers"] >= b4["d2h_transfers"] + 1          # explicit
assert a4["gather_copies"] == b4["gather_copies"], (b4, a4)

for s in (st, st2, *accepted):
    s.destroy()
ch.close(); ch2.close(); srv.destroy()
live = tpu_plane.stats()["live_buffers"]
assert live == 0, live
print("DEVICE-STREAM-OK")
"""


def test_device_payload_streams():
    """Tensor streams: multi-frame dev0->dev1 over the LOCAL rail (handle
    passing + CopyToDevice, zero host copies) and the explicit HOST rail
    on a plain channel — the 'tensor streams overlapping compute' row of
    SURVEY §2.9."""
    _need_fake()
    r = _run(DEVICE_STREAM_CODE, env_extra=FAKE_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEVICE-STREAM-OK" in r.stdout


def test_fault_injection_on_fake_plane():
    """Failed/late/dropped completion events: errors surface with
    reasons, the d2h wait is bounded, the plane survives."""
    _need_fake()
    r = _run(FAULT_CODE, env_extra=FAKE_ENV)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FAULTS-OK" in r.stdout


def test_device_roundtrip_on_real_plane():
    """Full data-plane round-trip on the installed libtpu.  Skipped where
    no chip is attached (CPU CI) — before libtpu is ever loaded."""
    from brpc_tpu.tpu_plane import chip_attached
    if not chip_attached():
        pytest.skip("no TPU attached (no /dev/accel*, no vfio TPU group)")
    pytest.importorskip("libtpu")
    env = {k: v for k, v in os.environ.items() if k != "TRPC_PJRT_PLUGIN"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", DEVICE_CODE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEVICE-OK" in r.stdout
