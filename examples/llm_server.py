"""LLM serving flagship: continuous batching + KV block plane + token
streaming (ISSUE 14's end-to-end proof; ≙ the role example/
rdma_performance plays for the reference's RDMA path — the workload that
earns the transport).

One process hosts everything: a pjit decode loop over the 8-device CPU
mesh, the KV-cache block plane on the (fake-plugin) PJRT device plane,
and a TRPC server streaming one token per decode step to each client.

    * N_CLIENTS concurrent clients stream full generations (retrying
      ELIMIT sheds like real clients, so every one of them finishes)
    * one client cancels MID-STREAM via Controller.start_cancel — the
      engine evicts the sequence and frees its blocks
    * a no-retry burst offered beyond the block budget is SHED with
      ELIMIT (never queued) by the scheduler + the native per-method cap
    * prefill→decode KV migration rides the tpu_d2d local rail
      (stats()["d2d_transfers"] delta printed in the proof line)

The last stdout line is a JSON proof block tests/test_examples.py
asserts on (balanced accounting, local-rail migrations, sheds, cancel)."""
import _bootstrap  # noqa: F401

import os

os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=8").strip())
# on the CPU (JAX_PLATFORMS=cpu) the device plane is the fake PJRT plugin
# the native build installs next to the core .so; elsewhere it is the
# installed libtpu, and a down plane is an error
if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("TRPC_PJRT_PLUGIN", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "brpc_tpu", "_native", "libpjrt_fake.so"))

import json           # noqa: E402
import struct         # noqa: E402
import threading      # noqa: E402
import time           # noqa: E402

from brpc_tpu import tpu_plane                       # noqa: E402
from brpc_tpu.parallel.mesh import make_mesh         # noqa: E402
from brpc_tpu.rpc import errors                      # noqa: E402
from brpc_tpu.rpc.channel import Channel, ChannelOptions  # noqa: E402
from brpc_tpu.rpc.server import Server, ServerOptions     # noqa: E402
from brpc_tpu.serving import ServingEngine           # noqa: E402
from brpc_tpu.serving.engine import TOKEN_FMT, tiny_config  # noqa: E402
from brpc_tpu.serving.kv_cache import KvBlockPlane   # noqa: E402

N_CLIENTS = 8      # full-generation streamers (the acceptance floor)
N_BURST = 12       # no-retry offered load beyond the budget (shed bait)
MAX_NEW = 8


def _pct(xs, p):
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, int(p * len(s)))]


def main():
    if not tpu_plane.init(timeout_s=120):
        raise SystemExit(f"device plane down: {tpu_plane.error()}")
    stats0 = tpu_plane.stats()
    mesh = make_mesh({"dp": 2, "tp": 4})
    engine = ServingEngine(cfg=tiny_config(), mesh=mesh,
                           kv=KvBlockPlane(block_bytes=4096, n_blocks=48),
                           n_slots=4, max_waiting=4)
    server = Server(ServerOptions(
        # the PR-11 native gate in front of the scheduler: anything past
        # what the batcher could even hold sheds on the parse fiber
        method_max_concurrency={"LLM.Generate": engine.method_cap}))
    engine.register(server)
    port = server.start("127.0.0.1:0")
    engine.start()
    addr = f"127.0.0.1:{port}"
    print(f"serving on {addr} (plane={tpu_plane.platform()}, "
          f"slots=4, blocks=48)")

    lock = threading.Lock()
    out = {"streamed": 0, "tokens": 0, "shed": 0, "errors": 0,
           "cancel_reset": 0, "ttft_ms": [], "gap_ms": []}

    def generate(i, retry=True):
        """One full-generation client; retries sheds so it always
        finishes (the burst clients don't)."""
        ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
        req = json.dumps({"prompt_len": 10 + i % 4,
                          "max_new_tokens": MAX_NEW}).encode()
        try:
            while True:
                t0 = time.monotonic()
                try:
                    _, st = ch.create_stream("LLM.Generate", req)
                    break
                except errors.RpcError as e:
                    if e.code != errors.ELIMIT or not retry:
                        with lock:
                            out["shed" if e.code == errors.ELIMIT
                                else "errors"] += 1
                        return
                    with lock:
                        out["shed"] += 1
                    time.sleep(0.1)
            n, last = 0, None
            while True:
                msg = st.read(timeout_s=120)
                if msg is None:
                    break
                now = time.monotonic()
                tok = struct.unpack(TOKEN_FMT, msg)[0]
                assert tok < 128, tok
                with lock:
                    out["tokens"] += 1
                    if n == 0:
                        out["ttft_ms"].append((now - t0) * 1e3)
                    else:
                        out["gap_ms"].append((now - last) * 1e3)
                n, last = n + 1, now
            st.destroy()
            with lock:
                out["streamed"] += 1 if n == MAX_NEW else 0
        finally:
            ch.close()

    def cancel_client():
        """Reads two tokens, then RSTs the stream mid-decode (the wire
        form every cancel takes once the handshake response is out): the
        engine must evict the sequence and free its blocks."""
        ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
        try:
            while True:
                try:
                    _, st = ch.create_stream(
                        "LLM.Generate",
                        json.dumps({"prompt_len": 12,
                                    "max_new_tokens": 32}).encode())
                    break
                except errors.RpcError as e:
                    if e.code != errors.ELIMIT:
                        raise
                    time.sleep(0.1)
            for _ in range(2):
                st.read(timeout_s=120)
            st.rst(errors.ECANCELED)
            with lock:
                out["cancel_reset"] += 1
            st.destroy()
        finally:
            ch.close()

    threads = [threading.Thread(target=generate, args=(i,))
               for i in range(N_CLIENTS)]
    threads.append(threading.Thread(target=cancel_client))
    for t in threads:
        t.start()
        time.sleep(0.02)
    # wait until the batch is hot, then offer a no-retry burst the
    # budget cannot hold — the plane must SHED it, not queue it
    deadline = time.monotonic() + 60
    while engine.stats()["running"] < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    burst = [threading.Thread(target=generate, args=(100 + i, False))
             for i in range(N_BURST)]
    for t in burst:
        t.start()
    for t in threads + burst:
        t.join(180)
    # the decode loop notices the mid-stream RST on its next write to
    # that sequence; wait for the eviction to land before draining
    deadline = time.monotonic() + 60
    while engine.stats()["canceled"] < 1 and time.monotonic() < deadline:
        time.sleep(0.05)

    engine.stop()
    engine.assert_drained()
    es = engine.stats()
    stats1 = tpu_plane.stats()
    d2d = stats1.get("d2d_transfers", 0) - stats0.get("d2d_transfers", 0)
    print(f"engine: {json.dumps({k: v for k, v in es.items() if v})}")
    proof = {
        "metric": "llm_server",
        "clients": N_CLIENTS,
        "streamed": out["streamed"],
        "tokens": out["tokens"],
        "tokens_out": es["tokens_out"],
        "shed_client": out["shed"],
        "shed_server": es["shed"],
        "canceled": es["canceled"],
        "cancel_reset": out["cancel_reset"],
        "finished": es["finished"],
        "rail_local": es["rail_local"],
        "d2d_delta": d2d,
        "live_buffers_end": stats1.get("live_buffers", 0),
        "balanced": True,  # assert_drained() above would have thrown
        "ttft_ms_p50": round(_pct(out["ttft_ms"], .5), 1),
        "ttft_ms_p99": round(_pct(out["ttft_ms"], .99), 1),
        "itl_ms_p50": round(_pct(out["gap_ms"], .5), 1),
        "itl_ms_p99": round(_pct(out["gap_ms"], .99), 1),
    }
    server.destroy()
    print(json.dumps(proof))


if __name__ == "__main__":
    main()
