"""Cross-host rail selection (VERDICT Weak #4): tensors between processes
that do NOT share one PJRT client must take the HOST rail (explicit d2h
landing zone on the wire), proven by the rail-selection counter
native_stream_device_host_rail — and a same-process control proves the
LOCAL rail (handle passing) still engages when both ends share a client.

Two real processes, each with its own fake-PJRT plane (distinct
tpu_plane_uid, tpu.cc:426), talking over real loopback TCP.  See the
architecture ruling in PARITY.md ("cross-host tensors belong to XLA
collectives; streams own intra-process chip-to-chip").
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE_PLUGIN = os.path.join(REPO, "brpc_tpu", "_native", "libpjrt_fake.so")


def _need_fake():
    if not os.path.exists(FAKE_PLUGIN):
        pytest.skip("fake PJRT plugin not built (native/build.sh)")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["TRPC_PJRT_PLUGIN"] = FAKE_PLUGIN
    return env


_SERVER = r"""
import sys, threading, time
from brpc_tpu import tpu_plane
from brpc_tpu.rpc.server import Server

assert tpu_plane.init(), tpu_plane.error()

srv = Server()
def dev_echo(cntl, req):
    st = cntl.accept_stream()
    def pump():
        buf = st.read_device(device=0, timeout_s=30)
        data = buf.to_host()
        buf.free()
        st.write(data)  # echo the tensor BYTES back as host data
        st.close()
    threading.Thread(target=pump, daemon=True).start()
    return b"ok"
srv.add_service("DevEcho", dev_echo)
srv.start("127.0.0.1:0")
print("PORT", srv.port, flush=True)
print("UID", tpu_plane.lib().trpc_tpu_plane_uid(), flush=True)
sys.stdin.readline()  # parked until the parent closes stdin
srv.destroy()
"""

_CLIENT = r"""
import ctypes, sys
from brpc_tpu import tpu_plane
from brpc_tpu._native import lib
from brpc_tpu.rpc.channel import Channel, ChannelOptions

port = int(sys.argv[1])
assert tpu_plane.init(), tpu_plane.error()
print("UID", lib().trpc_tpu_plane_uid(), flush=True)

def counter(name):
    buf = ctypes.create_string_buffer(1 << 16)
    n = lib().trpc_native_metrics_dump(buf, len(buf))
    for line in buf.raw[:n].decode().splitlines():
        if line.startswith(name + " "):
            return int(line.split()[1])
    raise AssertionError(name)

ch = Channel(f"tpu://0/0@127.0.0.1:{port}",
             ChannelOptions(max_retry=0, timeout_ms=30000))
resp, st = ch.create_stream("DevEcho", b"")
assert resp == b"ok"
data = bytes(bytearray(range(256)) * 128)  # 32KB tensor
buf = tpu_plane.h2d(data)
buf.wait()
st.write_device(buf, timeout_s=30)
echoed = st.read(timeout_s=30)
assert echoed == data, "tensor bytes corrupted across the host rail"
# the rail-selection counter is the proof: different plane uids =>
# the device frame carried an explicit d2h landing zone (host rail),
# and the local (handle-passing) rail never engaged
host = counter("native_stream_device_host_rail")
local = counter("native_stream_device_local_rail")
assert host == 1, f"host rail count {host}"
assert local == 0, f"local rail engaged cross-process: {local}"
st.destroy()
ch.close()
print("CROSS-HOST-RAIL-OK", flush=True)
"""

_LOCAL_CONTROL = r"""
import ctypes, threading
from brpc_tpu import tpu_plane
from brpc_tpu._native import lib
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server

assert tpu_plane.init(), tpu_plane.error()

def counter(name):
    buf = ctypes.create_string_buffer(1 << 16)
    n = lib().trpc_native_metrics_dump(buf, len(buf))
    for line in buf.raw[:n].decode().splitlines():
        if line.startswith(name + " "):
            return int(line.split()[1])
    raise AssertionError(name)

srv = Server()
def dev_echo(cntl, req):
    st = cntl.accept_stream()
    def pump():
        buf = st.read_device(device=1, timeout_s=30)
        data = buf.to_host()
        buf.free()
        st.write(data)
        st.close()
    threading.Thread(target=pump, daemon=True).start()
    return b"ok"
srv.add_service("DevEcho", dev_echo)
srv.start("127.0.0.1:0")

ch = Channel(f"tpu://0/0@127.0.0.1:{srv.port}",
             ChannelOptions(max_retry=0, timeout_ms=30000))
resp, st = ch.create_stream("DevEcho", b"")
data = bytes(bytearray(range(256)) * 128)
buf = tpu_plane.h2d(data)
buf.wait()
st.write_device(buf, timeout_s=30)
assert st.read(timeout_s=30) == data
# both ends share THIS process's PJRT client: the local rail must engage
assert counter("native_stream_device_local_rail") == 1
assert counter("native_stream_device_host_rail") == 0
st.destroy()
ch.close()
srv.destroy()
print("LOCAL-RAIL-OK", flush=True)
"""


def test_cross_process_tensors_take_host_rail():
    _need_fake()
    server = subprocess.Popen(
        [sys.executable, "-c", _SERVER], env=_env(),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        port_line = server.stdout.readline().strip()
        assert port_line.startswith("PORT "), port_line
        port = int(port_line.split()[1])
        server_uid = int(server.stdout.readline().split()[1])
        client = subprocess.run(
            [sys.executable, "-c", _CLIENT, str(port)], env=_env(),
            capture_output=True, text=True, timeout=180)
        assert client.returncode == 0, client.stdout + client.stderr
        assert "CROSS-HOST-RAIL-OK" in client.stdout
        client_uid = int(
            [ln for ln in client.stdout.splitlines()
             if ln.startswith("UID ")][0].split()[1])
        # the premise the rail decision rests on: distinct PJRT clients
        assert server_uid != client_uid
    finally:
        try:
            server.stdin.close()
            server.wait(timeout=30)
        except Exception:
            server.kill()


def test_same_process_control_takes_local_rail():
    _need_fake()
    r = subprocess.run([sys.executable, "-c", _LOCAL_CONTROL], env=_env(),
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LOCAL-RAIL-OK" in r.stdout
