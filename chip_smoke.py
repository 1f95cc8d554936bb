#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on one TPU v5e, in ONE process.

    python chip_smoke.py             # one chip: build, HbmEcho RPC, serving
    python chip_smoke.py --chips 4   # four chips: the cross-chip phase only

1. Builds the native core from this checkout (``bash native/build.sh``, a
   subprocess that never imports JAX), before JAX is imported.
2. Device plane + RPC: a ``Server`` with the native HbmEcho service, dialed
   on a ``tpu://`` channel that must settle in ``device``; 8 calls each at
   64 KiB and 16 MiB attachments echo byte-for-byte through HBM.
3. Serving at GPT-2-small width (random weights from ``--seed``): a
   ``ServingEngine`` on the same ``Server`` answers 4 concurrent
   ``LLM.Generate`` streams (prompt 128, 32 new tokens), and every token
   equals an in-process greedy decode of the same params with no RPC.

With ``--chips 4`` it runs instead the paths that exist only across chips:
the same 4 streams served tensor-parallel on ``make_mesh({"tp": 4})`` and
checked token by token against one device of the host, a
``MeshParallelChannel`` add-merge against numpy, and the prefill->decode KV
hop over the d2d rail to device 1.

Every phase prints one JSON line; any failure exits non-zero with no result
line.  The last line is the result: {"ok": true, "device": {...}}.  With no
TPU (JAX finds another platform) it exits non-zero naming what it found.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PLANE_INIT_TIMEOUT_S = 120.0


def log(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def build_native() -> None:
    script = os.path.join(REPO, "native", "build.sh")
    if not os.path.exists(script):
        raise SystemExit(f"chip_smoke: {script} not found: run from a "
                         "checkout of the repository")
    t0 = time.monotonic()
    r = subprocess.run(["bash", script], capture_output=True, text=True)
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-4000:] + r.stderr[-4000:])
        raise SystemExit(f"chip_smoke: native build failed "
                         f"(rc={r.returncode})")
    log("build", seconds=time.monotonic() - t0)


def gpt2_small():
    """GPT-2 small (Radford et al. 2019; HF `gpt2` config): learned
    positions, pre-LN, GELU, multi-head attention — what ModelConfig
    expresses unchanged.  bf16 compute."""
    import jax.numpy as jnp
    from brpc_tpu.models.transformer import ModelConfig
    return ModelConfig(vocab=50257, d_model=768, n_heads=12, n_layers=12,
                       d_ff=3072, max_seq=1024, n_experts=0,
                       dtype=jnp.bfloat16)


def make_engine(cfg, mesh=None, seed: int = 0, n_slots: int = 4,
                prompt_len: int = 128, max_new: int = 32):
    """A ServingEngine whose KV block plane is sized for this traffic:
    16-token blocks (a 4 KiB default block would cut one GPT-2-small
    sequence into thousands of DMAs) and a budget for every slot's
    prompt plus its new tokens."""
    from brpc_tpu.models import decode as D
    from brpc_tpu.serving import ServingEngine
    from brpc_tpu.serving.kv_cache import KvBlockPlane
    block = 16 * D.kv_bytes_per_token(cfg)
    per_seq = -(-(prompt_len + max_new) // 16)
    kv = KvBlockPlane(block_bytes=block, n_blocks=n_slots * per_seq + 8)
    return ServingEngine(cfg=cfg, mesh=mesh, n_slots=n_slots, kv=kv,
                         max_waiting=n_slots, seed=seed)


def echo_phase(port: int, seed: int, calls: int = 8,
               sizes=(64 << 10, 16 << 20)) -> dict:
    """HbmEcho over a tpu:// channel: byte-equal echoes, the handshake in
    `device`, plane counters advanced, every device buffer freed."""
    import numpy as np
    from brpc_tpu import tpu_plane
    from brpc_tpu.rpc.channel import Channel, ChannelOptions
    from brpc_tpu.rpc.controller import Controller
    rng = np.random.default_rng(seed)
    s0 = tpu_plane.stats()
    ch = Channel(f"tpu://0/0@127.0.0.1:{port}",
                 ChannelOptions(max_retry=0, timeout_ms=120_000))
    t0 = time.monotonic()
    try:
        for size in sizes:
            for _ in range(calls):
                data = rng.bytes(size)
                cntl = Controller()
                resp = ch.call("HbmEcho", b"ping", attachment=data,
                               cntl=cntl)
                if resp != b"ping" or cntl.response_attachment != data:
                    raise AssertionError(f"HbmEcho echo differs at "
                                         f"{size} B")
        state = ch.transport_state
    finally:
        ch.close()
    secs = time.monotonic() - t0
    if state != "device":
        raise AssertionError(f"tpu:// channel settled in {state!r}")
    deadline = time.monotonic() + 10
    while tpu_plane.stats()["live_buffers"] and time.monotonic() < deadline:
        time.sleep(0.01)
    s1 = tpu_plane.stats()
    n = calls * len(sizes)
    for k in ("h2d_transfers", "d2h_transfers"):
        if s1[k] - s0[k] < n:
            raise AssertionError(f"{k} advanced {s1[k] - s0[k]} < {n}")
    if s1["errors"] or s1["live_buffers"]:
        raise AssertionError(f"plane errors={s1['errors']} "
                             f"live_buffers={s1['live_buffers']}")
    return {"transport": state, "calls": n, "seconds": secs,
            "h2d": s1["h2d_transfers"] - s0["h2d_transfers"],
            "d2h": s1["d2h_transfers"] - s0["d2h_transfers"],
            "zero_copy_sends": s1["zero_copy_sends"] - s0["zero_copy_sends"],
            "gather_copies": s1["gather_copies"] - s0["gather_copies"]}


def prompts(cfg, n: int, length: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab, length)]
            for _ in range(n)]


def generate_streams(port: int, reqs, max_new: int, method="LLM.Generate"):
    """Concurrent Generate streams over RPC; each returns its tokens."""
    from brpc_tpu.rpc.channel import Channel, ChannelOptions
    from brpc_tpu.serving.engine import TOKEN_FMT
    out = [None] * len(reqs)
    errs = []

    def one(i):
        ch = Channel(f"127.0.0.1:{port}",
                     ChannelOptions(timeout_ms=600_000, max_retry=0))
        try:
            body = json.dumps({"prompt": reqs[i],
                               "max_new_tokens": max_new}).encode()
            _, st = ch.create_stream(method, body)
            toks = []
            while True:
                msg = st.read(timeout_s=600)
                if msg is None:
                    break
                toks.append(struct.unpack(TOKEN_FMT, msg)[0])
            st.destroy()
            out[i] = toks
        except Exception as e:  # noqa: BLE001 — re-raised below
            errs.append(f"stream {i}: {e!r}")
        finally:
            ch.close()

    ts = [threading.Thread(target=one, args=(i,)) for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise RuntimeError("; ".join(errs))
    for i, toks in enumerate(out):
        if len(toks) != max_new:
            raise AssertionError(f"stream {i} finished with {len(toks)} of "
                                 f"{max_new} tokens")
    return out


class GreedyReference:
    """Greedy decode of one prompt, in process, with no RPC and no KV
    block plane: the same prefill/decode_step math on the same params."""

    def __init__(self, cfg, params, n_slots: int, max_len: int):
        import jax
        from brpc_tpu.models import decode as D
        self.cfg, self.params = cfg, params
        self.n_slots, self.max_len = n_slots, max_len
        self._prefill = jax.jit(lambda p, t: D.prefill(p, t, cfg))
        self._step = jax.jit(
            lambda p, c, t, a: D.decode_step(p, c, t, a, cfg))

    def logits(self, prompt, tokens):
        """Per-position logits [len(tokens), vocab] with `tokens` forced
        (teacher forcing): row i is what greedy would pick token i from."""
        import jax.numpy as jnp
        import numpy as np
        from brpc_tpu.models import decode as D
        first, state = self._prefill(self.params,
                                     jnp.asarray([prompt], jnp.int32))
        cache = D.init_cache(self.cfg, self.n_slots, self.max_len)
        cache = D.install(cache, 0, tuple(a[:, 0] for a in state),
                          len(prompt))
        rows = [np.asarray(first[0])]
        active = np.zeros((self.n_slots,), bool)
        active[0] = True
        for tok in tokens[:-1]:
            t = np.zeros((self.n_slots,), np.int32)
            t[0] = tok
            lg, cache = self._step(self.params, cache, jnp.asarray(t),
                                   jnp.asarray(active))
            rows.append(np.asarray(lg[0]))
        return np.stack(rows)


def check_tokens(ref: GreedyReference, reqs, served, tol: float = 0.0):
    """Each served token against the reference's logits at its position
    (teacher-forced on the served tokens).  tol=0: the token must be the
    reference's argmax.  tol>0: its reference logit must be within `tol`
    of the max (a near-tie another reduction order may break the other
    way).  Returns (exact matches, total, worst logit gap)."""
    import numpy as np
    exact, total, worst = 0, 0, 0.0
    for prompt, toks in zip(reqs, served):
        lg = ref.logits(prompt, toks)
        for i, tok in enumerate(toks):
            gap = float(lg[i].max() - lg[i][tok])
            worst = max(worst, gap)
            exact += int(int(np.argmax(lg[i])) == tok)
            total += 1
            if gap > tol:
                raise AssertionError(
                    f"token {i} of a stream: served {tok}, reference "
                    f"argmax {int(np.argmax(lg[i]))} (logit gap {gap})")
    return exact, total, worst


def serve_and_check(engine, port: int, ref: GreedyReference, seed: int,
                    prompt_len: int, max_new: int, tol: float,
                    method: str = "LLM.Generate") -> dict:
    """Warm-up streams (compiles prefill at this length, install, the
    decode step; not timed as requests), then the timed streams, checked
    against the reference."""
    from brpc_tpu.utils import jaxenv
    cfg = engine.cfg
    n = engine.n_slots
    t0 = time.monotonic()
    generate_streams(port, prompts(cfg, n, prompt_len, seed + 1), 2,
                     method)
    compile_s = time.monotonic() - t0
    warm_cache = jaxenv.cache_counts()
    reqs = prompts(cfg, n, prompt_len, seed)
    t0 = time.monotonic()
    served = generate_streams(port, reqs, max_new, method)
    request_s = time.monotonic() - t0
    exact, total, worst = check_tokens(ref, reqs, served, tol)
    return {"streams": n, "tokens": total, "exact": exact,
            "worst_logit_gap": worst, "compile_seconds": compile_s,
            "request_seconds": request_s,
            "warmup_cache_hits": warm_cache["hits"],
            "warmup_cache_misses": warm_cache["misses"]}


def prefill_vs_apply(cfg, params, prompt) -> float:
    """max |prefill last logits - transformer.apply last logits|."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from brpc_tpu.models import decode as D
    from brpc_tpu.models import transformer
    toks = jnp.asarray([prompt], jnp.int32)
    a = jax.jit(lambda p, t: D.prefill(p, t, cfg))(params, toks)[0]
    b = jax.jit(lambda p, t: transformer.apply(p, t, cfg))(params, toks)
    d = float(np.max(np.abs(np.asarray(a[0]) - np.asarray(b[0, -1]))))
    if not np.isfinite(d):
        raise AssertionError("prefill logits are not finite")
    return d


def one_chip(cfg, seed: int, prompt_len: int = 128,
             max_new: int = 32) -> None:
    """Phases 2 and 3 on one device, one Server."""
    from brpc_tpu import tpu_plane
    from brpc_tpu.rpc.server import Server, ServerOptions

    engine = make_engine(cfg, seed=seed, prompt_len=prompt_len,
                         max_new=max_new)
    server = Server(ServerOptions(
        method_max_concurrency={"LLM.Generate": engine.method_cap}))
    server.add_hbm_echo_service()
    engine.register(server)
    port = server.start("127.0.0.1:0")
    try:
        log("echo", **echo_phase(port, seed))
        s0 = tpu_plane.stats()
        engine.start()
        ref = GreedyReference(cfg, engine.params, engine.n_slots,
                              engine.max_len)
        res = serve_and_check(engine, port, ref, seed, prompt_len,
                              max_new, tol=0.0)
        if res["exact"] != res["tokens"]:
            raise AssertionError(f"served tokens differ: {res}")
        engine.stop()
        engine.assert_drained()
        s1 = tpu_plane.stats()
        if s1["live_buffers"] or s1["errors"] != s0["errors"]:
            raise AssertionError(f"plane after serving: {s1}")
        res["max_abs_logit_delta_vs_apply"] = prefill_vs_apply(
            cfg, engine.params, prompts(cfg, 1, prompt_len, seed)[0])
        res["kv_rails"] = dict(engine.rails)
        res["kv_block_bytes"] = engine.kv.block_bytes
        res["kv_h2d"] = s1["h2d_transfers"] - s0["h2d_transfers"]
        log("serving", **res)
    finally:
        engine.stop()
        server.destroy()


def four_chips(cfg, seed: int, n_devices: int = 4, prompt_len: int = 128,
               max_new: int = 32, tol: float = 0.1) -> None:
    """The cross-chip paths: tp-sharded serving vs one device, the mesh
    fan-out collective vs numpy, and the d2d KV rail."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from brpc_tpu import tpu_plane
    from brpc_tpu.parallel.channels import MeshParallelChannel
    from brpc_tpu.parallel.mesh import make_mesh
    from brpc_tpu.rpc.server import Server, ServerOptions

    mesh = make_mesh({"tp": n_devices}, devices=jax.devices()[:n_devices])
    x = np.random.default_rng(seed).standard_normal(
        (2 * n_devices, 1024)).astype(np.float32)
    got = np.asarray(MeshParallelChannel(mesh, "tp", merger="add")
                     .call_tensor(jnp.asarray(x)))
    want = np.tile(x.reshape(n_devices, 2, -1).sum(0), (n_devices, 1))
    err = float(np.max(np.abs(got - want)))
    if err > 1e-4:
        raise AssertionError(f"mesh add-merge differs from numpy by {err}")
    log("mesh_fanout", members=n_devices, max_abs_err=err)

    engine = make_engine(cfg, mesh=mesh, seed=seed, prompt_len=prompt_len,
                         max_new=max_new)
    wq = engine.params["blocks"]["wq"]
    shard_devs = {s.device for s in wq.addressable_shards}
    if len(shard_devs) != n_devices:
        raise AssertionError(f"tp params span {len(shard_devs)} devices")
    server = Server(ServerOptions(
        method_max_concurrency={"LLM.Generate": engine.method_cap}))
    engine.register(server)
    port = server.start("127.0.0.1:0")
    try:
        s0 = tpu_plane.stats()
        engine.start()
        # the single-device side of the comparison: the same params,
        # gathered onto device 0 of this host
        one = jax.device_put(engine.params, jax.devices()[0])
        ref = GreedyReference(cfg, one, engine.n_slots, engine.max_len)
        res = serve_and_check(engine, port, ref, seed, prompt_len,
                              max_new, tol=tol)
        engine.stop()
        engine.assert_drained()
        s1 = tpu_plane.stats()
        es = engine.stats()
        if es["rail_local"] < engine.n_slots or \
                s1["d2d_transfers"] <= s0["d2d_transfers"]:
            raise AssertionError(f"KV did not ride the d2d rail: {es} {s1}")
        if s1["live_buffers"] or s1["errors"] != s0["errors"]:
            raise AssertionError(f"plane after serving: {s1}")
        res.update(tp=n_devices, param_shard_devices=len(shard_devs),
                   decode_device=engine.kv.decode_device,
                   rail_local=es["rail_local"],
                   d2d_transfers=s1["d2d_transfers"] - s0["d2d_transfers"],
                   d2d_bytes=s1["d2d_bytes"] - s0["d2d_bytes"],
                   near_tie_tol=tol)
        log("serving_tp", **res)
    finally:
        engine.stop()
        server.destroy()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, REPO)

    build_native()                     # before JAX is imported

    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{d0.platform!r} ({d0.device_kind}) x{len(devs)}")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devs)} {d0.device_kind}")
    from brpc_tpu import tpu_plane
    from brpc_tpu.utils import jaxenv
    cache_dir = jaxenv.enable_compile_cache()
    if not tpu_plane.init(timeout_s=PLANE_INIT_TIMEOUT_S):
        raise SystemExit(f"chip_smoke: device plane down: "
                         f"{tpu_plane.error()}")
    log("device", platform=d0.platform, kind=d0.device_kind,
        count=len(devs), plane_platform=tpu_plane.platform(),
        plane_devices=tpu_plane.device_count(),
        pjrt_api_header=tpu_plane.api_version()["header"],
        pjrt_api_libtpu=tpu_plane.api_version()["plugin"],
        jax=jax.__version__, compile_cache=cache_dir)

    cfg = gpt2_small()
    if args.chips == 4:
        four_chips(cfg, args.seed)
    else:
        one_chip(cfg, args.seed)
    log("compile_cache", **jaxenv.cache_counts())
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    # leave through os._exit on every path: the native runtime's fiber
    # workers, the engine thread and an init stuck past its watchdog are
    # not waited for by interpreter teardown
    try:
        code = main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(e.code, file=sys.stderr)
        code = 0 if e.code in (None, 0) else 1
    except BaseException:  # noqa: BLE001 — any failure is a non-zero exit
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
