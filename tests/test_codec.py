"""Payload-codec rail (ISSUE 8 tentpole, native/src/codec.h).

Reference test style (SURVEY §4): real loopback servers, raw sockets for
the wire proofs, counters through the native metrics dump.  The
boot-sensitive legs (TRPC_PAYLOAD_CODEC resolution, shard counts) run in
subprocesses — the same A/B-by-subprocess shape as TRPC_CLIENT_CORK.

Covers the acceptance criteria:
  * exactly 1 codec encode per N-way fan-out group, proven by
    native_codec_encodes vs native_fanout_subcalls against a server in
    ANOTHER process (so server-side encodes can't pollute the counter)
  * codec disabled is byte-identical on the wire (subprocess A/B)
  * lossless codecs roundtrip byte-exact across chained multi-block
    IOBufs; int8/bf16 error bounds hold incl. denormals and all-zero
    blocks
  * decode stays on the owning shard (cross_shard_hops untouched at
    TRPC_SHARDS=2 with the codec on)
"""

import ctypes
import math
import os
import struct
import subprocess
import sys
import time

import pytest

from brpc_tpu._native import lib
from brpc_tpu.rpc import codec as codec_mod
from brpc_tpu.rpc.channel import Channel
from brpc_tpu.rpc.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter(name: str) -> int:
    buf = ctypes.create_string_buffer(1 << 16)
    n = lib().trpc_native_metrics_dump(buf, len(buf))
    for line in buf.raw[:n].decode().splitlines():
        if line.startswith(name + " "):
            return int(line.split()[1])
    raise AssertionError(f"{name} missing from native metrics dump")


@pytest.fixture(autouse=True)
def _codec_defaults():
    """Every test leaves the process-global codec in the state the
    session was launched with (mirrors the TRPC_INLINE_DISPATCH
    fixture): a codec left on would silently change later suites."""
    L = lib()
    yield
    env = os.environ.get("TRPC_PAYLOAD_CODEC", "none") or "none"
    L.trpc_set_payload_codec(int(L.trpc_codec_id(env.encode())))
    L.trpc_set_codec_min_bytes(
        int(os.environ.get("TRPC_CODEC_MIN_BYTES", "") or 256))


def _f32(vals):
    return struct.pack("<%df" % len(vals), *vals)


def _unf32(data):
    return struct.unpack("<%df" % (len(data) // 4), data)


# --- property tests over CHAINED multi-block IOBufs -------------------------


class TestChainedRoundtrips:
    # chunk sizes force: byte-fragmented chains (element straddles every
    # block seam), misaligned odd chunks, pooled-block chains, dedicated
    # big blocks, and the single-block case
    CHUNKS = (1, 3, 7, 1000, 8192, 64 * 1024, 1 << 30)

    def test_snappy_lossless_byte_exact(self):
        data = (b"the quick brown fox jumps over the lazy dog! " * 4000)
        for chunk in self.CHUNKS:
            rc, _ = codec_mod.roundtrip_chained(data, "snappy", chunk)
            assert rc == 0, f"snappy not byte-exact at chunk={chunk}"
        # ragged (prime) length too — chunk framing must not assume
        # alignment
        rc, _ = codec_mod.roundtrip_chained(data[:100003], "snappy", 7)
        assert rc == 0

    @pytest.mark.parametrize("codec,rel_bound", [
        ("bf16", 2.0 ** -8),     # 8 explicit mantissa bits, RNE
        ("int8", 1.0 / 127.0),   # per-block scale bound (codec.h)
    ])
    def test_quantizer_error_bounds(self, codec, rel_bound):
        vals = [math.sin(i * 0.01) * 50.0 for i in range(20000)]
        data = _f32(vals)
        maxabs = max(abs(v) for v in vals)
        for chunk in self.CHUNKS:
            rc, err = codec_mod.roundtrip_chained(data, codec, chunk)
            assert rc == 1, f"{codec} unexpectedly exact at chunk={chunk}"
            # block maxima <= global max: the global bound dominates
            assert err <= maxabs * rel_bound + 1e-30, \
                f"{codec} err {err} over bound at chunk={chunk}"

    def test_int8_all_zero_blocks_exact(self):
        data = _f32([0.0] * 4096)
        rc, err = codec_mod.roundtrip_chained(data, "int8", 100)
        assert rc == 0 and err == 0.0  # scale-0 blocks decode exact zeros

    def test_int8_denormal_blocks(self):
        # fully-denormal blocks: scale underflows -> encoded as zeros;
        # the error is the denormal magnitude itself (≪ any real bound)
        denorm = 1.0e-42
        data = _f32([denorm, -denorm] * 2048)
        rc, err = codec_mod.roundtrip_chained(data, "int8", 64)
        assert rc in (0, 1)
        # bound by the f32 image of the literal (the denormal itself)
        f32_denorm = struct.unpack("<f", struct.pack("<f", denorm))[0]
        assert err <= f32_denorm

    def test_bf16_specials(self):
        vals = [0.0, -0.0, 1.0e-42, -1.0e-42, 3.0e38, -3.0e38,
                float("inf"), float("-inf")] * 512
        data = _f32(vals)
        rc, err = codec_mod.roundtrip_chained(data, "bf16", 13)
        assert rc in (0, 1)
        # inf stays inf (diff 0), zeros exact, denormals flush tiny
        assert err <= 3.0e38 * 2.0 ** -8

    def test_bf16_nan_stays_nan(self):
        data = _f32([float("nan")] * 1024)
        enc, applied = codec_mod.encode(data, "bf16")
        assert applied == codec_mod.CODEC_BF16
        dec = codec_mod.decode(enc, "bf16")
        assert all(math.isnan(v) for v in _unf32(dec))

    def test_int8_mixed_magnitude_blocks_use_local_scale(self):
        # one tiny block + one huge block: per-BLOCK scales keep the tiny
        # block's error proportional to ITS max, not the global max
        tiny = [1.0e-3 * math.cos(i) for i in range(256)]
        huge = [1.0e6 * math.sin(i) for i in range(256)]
        data = _f32(tiny + huge)
        enc, applied = codec_mod.encode(data, "int8")
        assert applied == codec_mod.CODEC_INT8
        out = _unf32(codec_mod.decode(enc, "int8"))
        tiny_err = max(abs(a - b) for a, b in zip(tiny, out[:256]))
        assert tiny_err <= max(map(abs, tiny)) / 127.0 + 1e-30


class TestCodecModule:
    def test_quantizers_decline_non_f32_parts(self):
        enc, applied = codec_mod.encode(b"x" * 1001, "bf16")  # not %4
        assert applied == 0 and enc == b"x" * 1001

    def test_snappy_declines_incompressible(self):
        rnd = os.urandom(256 * 1024)
        enc, applied = codec_mod.encode(rnd, "snappy")
        assert applied == 0 and enc == rnd

    def test_corrupt_decode_raises(self):
        with pytest.raises(ValueError):
            codec_mod.decode(b"\xff" * 64, "snappy")
        with pytest.raises(ValueError):
            codec_mod.decode(b"\xff" * 7, "int8")

    def test_names_and_flag(self):
        assert codec_mod.id_of("int8") == 3
        assert codec_mod.name_of(1) == "snappy"
        from brpc_tpu.utils import flags
        flags.set_flag("payload_codec", "bf16")
        assert codec_mod.active() == "bf16"
        with pytest.raises(Exception):
            flags.set_flag("payload_codec", "nonsense")
        flags.set_flag("payload_codec", "none")


# --- subprocess echo server (counters must isolate the CLIENT side) ---------

_SERVER_CODE = r"""
import sys, time
sys.path.insert(0, {repo!r})
from brpc_tpu.rpc.server import Server
srv = Server()
srv.add_echo_service()
srv.start("127.0.0.1:0")
print("PORT", srv.port, flush=True)
time.sleep(120)
"""


@pytest.fixture()
def remote_server():
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    p = subprocess.Popen(
        [sys.executable, "-c", _SERVER_CODE.format(repo=REPO)],
        stdout=subprocess.PIPE, text=True, env=env, cwd=REPO)
    try:
        line = p.stdout.readline()
        assert line.startswith("PORT "), f"server child said {line!r}"
        yield int(line.split()[1])
    finally:
        p.terminate()
        p.wait(timeout=30)


class TestCodecOncePerFanoutGroup:
    def test_one_encode_per_group(self, remote_server):
        """THE acceptance counter proof: an N-way broadcast through the
        serialize-once fan-out encodes its shared payload exactly ONCE
        (the server is another process, so its response encodes cannot
        leak into this process's counter)."""
        from brpc_tpu.parallel.channels import ParallelChannel
        L = lib()
        L.trpc_set_payload_codec(1)  # snappy (lossless: merge checkable)
        L.trpc_set_codec_min_bytes(0)
        n = 4
        pc = ParallelChannel()
        chans = [Channel(f"127.0.0.1:{remote_server}") for _ in range(n)]
        for c in chans:
            pc.add_channel(c)
        payload = b"codec-once fan-out payload " * 512
        e0 = _counter("native_codec_encodes")
        d0 = _counter("native_codec_decodes")
        s0 = _counter("native_fanout_subcalls")
        g0 = _counter("native_fanout_calls")
        out = pc.call("Echo.echo", payload)
        e1 = _counter("native_codec_encodes")
        d1 = _counter("native_codec_decodes")
        s1 = _counter("native_fanout_subcalls")
        g1 = _counter("native_fanout_calls")
        assert out == payload * n
        assert g1 - g0 == 1 and s1 - s0 == n
        # 1 encode for the whole N-way group (payload only: no attachment)
        assert e1 - e0 == 1, f"expected 1 group encode, got {e1 - e0}"
        # every member's response decoded client-side, on arrival
        assert d1 - d0 == n
        for c in chans:
            c.close()
        pc.close()

    def test_unary_attachment_roundtrip(self, remote_server):
        """Unary path with a large f32 attachment: quantized on the way
        out, response attachment mirrored and decoded — the --attach-ab
        data path, asserted for error bounds."""
        from brpc_tpu.rpc.controller import Controller
        L = lib()
        L.trpc_set_payload_codec(3)  # int8
        L.trpc_set_codec_min_bytes(0)
        vals = [math.sin(i * 0.05) * 8.0 for i in range(65536)]
        attach = _f32(vals)
        ch = Channel(f"127.0.0.1:{remote_server}")
        cntl = Controller()
        e0 = _counter("native_codec_encodes")
        out = ch.call("Echo.echo", b"pay!", attachment=attach, cntl=cntl)
        assert out == b"pay!"  # 4 bytes: under no gate? min_bytes=0,
        # but %4==0... "pay!" is 4 bytes -> eligible; echo returns the
        # dequantized image of the dequantized image; compare the
        # ATTACHMENT against the one-pass bound doubled (two lossy hops)
        got = _unf32(cntl.response_attachment)
        bound = 2 * (8.0 / 127.0) + 1e-6
        assert len(got) == len(vals)
        assert max(abs(a - b) for a, b in zip(vals, got)) <= bound
        assert _counter("native_codec_encodes") > e0
        ch.close()


# --- wire A/B: codec off is byte-identical ----------------------------------

_WIRE_CODE = r"""
import socket, struct, sys
sys.path.insert(0, {repo!r})
from brpc_tpu.rpc.server import Server
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")


def tlv(tag, data):
    return bytes([tag]) + struct.pack("<I", len(data)) + data


s = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
burst = b""
for i in range(10):
    meta = tlv(1, b"Echo.echo") + tlv(2, struct.pack("<Q", 9100 + i))
    payload = (b"codec-wire-proof-%03d " % i) * 40
    burst += b"TRPC" + struct.pack(">II", len(meta), len(payload)) \
        + meta + payload
s.sendall(burst)
buf = b""
frames = []
while len(frames) < 10:
    while True:
        if len(buf) >= 12:
            ml, bl = struct.unpack(">II", buf[4:12])
            if len(buf) >= 12 + ml + bl:
                break
        chunk = s.recv(65536)
        assert chunk, "peer closed early"
        buf += chunk
    total = 12 + ml + bl
    frames.append(buf[:total]); buf = buf[total:]
s.close()
for f in frames:
    print("FRAME", f.hex())
srv.destroy()
"""


def _wire_frames(extra_env) -> list:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("TRPC_PAYLOAD_CODEC", None)
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", _WIRE_CODE.format(repo=REPO)],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)
    assert r.returncode == 0, f"wire child failed:\n{r.stdout}\n{r.stderr}"
    return [ln for ln in r.stdout.splitlines() if ln.startswith("FRAME ")]


class TestWireByteIdenticalWhenOff:
    def test_unset_vs_none_vs_prebuild(self):
        """TRPC_PAYLOAD_CODEC unset, =none, and ='' must put EXACTLY the
        same response bytes on the wire (the subprocess A/B shape of the
        TRPC_CLIENT_CORK proof): the rail disabled adds no tags, no
        codec pass, no drift."""
        a = _wire_frames({})
        b = _wire_frames({"TRPC_PAYLOAD_CODEC": "none"})
        c = _wire_frames({"TRPC_PAYLOAD_CODEC": ""})
        assert a and a == b == c


class TestShardConfinement:
    def test_decode_stays_on_owning_shard(self):
        """TRPC_SHARDS=2 with the codec ON: parse→decode→dispatch→
        encode→respond must stay on each connection's owning reactor —
        the codec adds ZERO cross-shard hops (tentpole leg (d))."""
        code = r"""
import ctypes, sys
sys.path.insert(0, {repo!r})
from brpc_tpu._native import lib
from brpc_tpu.rpc.server import Server
from brpc_tpu.rpc.channel import Channel
import struct
L = lib()
srv = Server(); srv.add_echo_service(); srv.start("127.0.0.1:0")
L.trpc_set_payload_codec(3); L.trpc_set_codec_min_bytes(0)


def counter(name):
    buf = ctypes.create_string_buffer(1 << 16)
    n = L.trpc_native_metrics_dump(buf, len(buf))
    for line in buf.raw[:n].decode().splitlines():
        if line.startswith(name + " "):
            return int(line.split()[1])
    raise AssertionError(name)


chans = [Channel("127.0.0.1:%d" % srv.port) for _ in range(4)]
payload = struct.pack("<4096f", *[i * 0.25 for i in range(4096)])
h0 = counter("native_cross_shard_hops")
d0 = counter("native_codec_decodes")
for _ in range(8):
    for ch in chans:
        ch.call("Echo.echo", payload)
h1 = counter("native_cross_shard_hops")
d1 = counter("native_codec_decodes")
print("HOPS", h1 - h0, "DECODES", d1 - d0, "SHARDS",
      int(L.trpc_shard_count()), flush=True)
assert int(L.trpc_shard_count()) == 2
assert d1 - d0 >= 64          # 32 server + 32 client decodes
assert h1 - h0 == 0, "codec work hopped shards"
for ch in chans:
    ch.close()
srv.destroy()
print("OK")
""".format(repo=REPO)
        env = dict(os.environ)
        env["TRPC_SHARDS"] = "2"
        env.setdefault("JAX_PLATFORMS", "cpu")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=180,
                           env=env, cwd=REPO)
        assert r.returncode == 0 and "OK" in r.stdout, \
            f"sharded codec child failed:\n{r.stdout}\n{r.stderr}"


class TestCompressOrthogonality:
    def test_compressed_requests_skip_the_codec_rail(self):
        """compress (tag 6, Python-side) and codec (tags 16/17, native)
        are orthogonal rails: a compressed request must NOT be
        double-encoded, and must still roundtrip."""
        srv = Server()
        srv.add_echo_service()
        srv.start("127.0.0.1:0")
        L = lib()
        L.trpc_set_payload_codec(1)
        L.trpc_set_codec_min_bytes(0)
        ch = Channel(f"127.0.0.1:{srv.port}",
                     request_compress_type=3)  # snappy via compress.py
        payload = b"both rails configured, compress wins " * 200
        e0 = _counter("native_codec_encodes")
        assert ch.call("Echo.echo", payload) == payload
        assert _counter("native_codec_encodes") == e0
        ch.close()
        srv.destroy()

    def test_compressed_responses_not_quantized(self):
        """Regression: the server mirrors the request codec on responses
        — but a response the usercode layer COMPRESSED (tag 6) must not
        be quantized on top (a lossy pass over compressed bytes corrupts
        them).  Sweep payload paddings so at least one compressed length
        is 4-aligned (the case int8 would have mangled)."""
        from brpc_tpu.rpc.controller import Controller

        def h(cntl, payload):
            cntl.response_compress_type = 2  # zlib
            return payload

        srv = Server()
        srv.add_service("Z.z", h)
        srv.start("127.0.0.1:0")
        L = lib()
        L.trpc_set_payload_codec(3)  # int8: lossy if misapplied
        L.trpc_set_codec_min_bytes(0)
        ch = Channel(f"127.0.0.1:{srv.port}")
        for pad in range(8):
            body = _f32([float(i % 251) for i in range(1024)]) \
                + b"q" * pad * 4
            cntl = Controller()
            out = ch.call("Z.z", body, cntl=cntl)
            # the request leg IS lossy (int8 over the f32 part when
            # 4-aligned); the response decompression must still succeed
            # and match what the server received
            assert len(out) == len(body)
        ch.close()
        srv.destroy()
