"""One run of one cell, in the process that owns the chip.

Set-up (counted in ``setup_s``): the device plane, the weights from the
seed, the serving engine and its ``Server``, every prompt length of the
cell's traffic served once through ``LLM.Generate`` (which compiles or
loads prefill and ``install`` at that length, the decode step and the
small programs around them), and the client process.  Then the window:
the client process offers the traffic for ``seconds``; nothing compiles
inside it.  After it: the peak device memory, the engine taken down, and
the check against the plain reference, which regenerates the weights from
the seed.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import struct
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import spec as spec_mod
from benchmark import trace as trace_mod
from benchmark import traffic as traffic_mod
from benchmark.spans import Spans
from benchmark.stats import pct
from brpc_tpu import tpu_plane
from brpc_tpu.models import decode as D
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server, ServerOptions
from brpc_tpu.serving import ServingEngine
from brpc_tpu.serving.kv_cache import KvBlockPlane
from brpc_tpu.utils import jaxenv

PLANE_INIT_TIMEOUT_S = 120.0
DRAIN_S = 60.0            # counted requests are waited for this long
CHECK_TOKENS = 512        # served tokens the reference checks, at least
CHECK_MAX_SEQS = 24
CHECK_MIN_TOKENS = 64     # fewer checked tokens than this is no check
TRACE_MAX_S = 10.0        # the traced part of a --trace 1 window
METHOD = "LLM.Generate"
TOKEN_FMT = "<I"
CONTROL_DTYPE = "float8_e4m3fn"   # one step below the served bfloat16


def log(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def now() -> int:
    return time.monotonic_ns()


def sleep_until(t_ns: int) -> None:
    while True:
        left = (t_ns - now()) / 1e9
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


class RunData:
    """What the metric readers see of one run (times in ns on the
    monotonic clock)."""

    def __init__(self, cell, fam, seconds: float, setup_s: float,
                 t0: int, requests: List[dict], spans: Optional[Spans],
                 engine_delta: Dict[str, int], n_slots: int,
                 trace: Optional[dict], device_kind: str):
        self.cell, self.fam = cell, fam
        self.dims = fam.dims(cell.config)
        self.seconds, self.setup_s = seconds, setup_s
        self.t0, self.t1 = t0, t0 + int(seconds * 1e9)
        self.requests, self.spans = requests, spans
        self.engine_delta, self.n_slots = engine_delta, n_slots
        self.trace, self.device_kind = trace, device_kind
        self.counted = [r for r in requests if r["counted"]]

    @staticmethod
    def ok(r: dict) -> bool:
        return r["status"] == "ok" and len(r["tokens"]) == r["max_new"]


# ---------------------------------------------------------------------------
# set-up


def device_check(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX found platform "
                         f"{d0.platform!r} ({d0.device_kind}) x{len(devs)}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips; JAX "
                         f"found {len(devs)} {d0.device_kind}")
    return devs


def make_engine(cell, mcfg, params):
    sv = cell.config["serving"]
    slots, bt = int(sv["slots"]), int(sv["kv_block_tokens"])
    tr = cell.traffic
    outstanding = traffic_mod.max_outstanding(tr, slots)
    per_seq = -(-(traffic_mod.max_prompt(tr) + traffic_mod.max_output(tr))
                // bt)
    per_prompt = -(-traffic_mod.max_prompt(tr) // bt)
    # no shed at this traffic: every slot at its longest, every request
    # the generator can have open waiting with its longest prompt
    kv = KvBlockPlane(block_bytes=bt * D.kv_bytes_per_token(mcfg),
                      n_blocks=slots * per_seq + outstanding * per_prompt)
    return ServingEngine(cfg=mcfg, params=params, n_slots=slots,
                         max_len=int(sv["max_len"]), kv=kv,
                         max_waiting=outstanding)


def generate(port: int, prompt: List[int], max_new: int) -> List[int]:
    ch = Channel(f"127.0.0.1:{port}",
                 ChannelOptions(timeout_ms=600_000, max_retry=0))
    try:
        _, st = ch.create_stream(METHOD, json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new}).encode())
        toks = []
        while True:
            msg = st.read(timeout_s=600)
            if msg is None:
                break
            toks.append(struct.unpack(TOKEN_FMT, msg)[0])
        st.destroy()
        return toks
    finally:
        ch.close()


def warm_up(port: int, cell, vocab: int, seed: int, slots: int) -> None:
    """Each prompt length of the traffic once, through the served path:
    prefill and install at every length, the decode step on several slots,
    the first-token and retire paths.  At most `slots` at once, which the
    waiting room always holds."""
    rng = np.random.default_rng(traffic_mod.seed_seq(seed, 3))
    lens = sorted(set(int(v) for v in
                      cell.traffic["prompt_len"]["buckets"]))
    prompts = {n: rng.integers(0, vocab, n).tolist() for n in lens}
    out: Dict[int, List[int]] = {}
    errs: List[str] = []

    def one(n):
        try:
            out[n] = generate(port, prompts[n], 3)
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(f"{n}: {e!r}")

    for i in range(0, len(lens), slots):
        ts = [threading.Thread(target=one, args=(n,))
              for n in lens[i:i + slots]]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    if errs or any(len(out.get(n, [])) != 3 for n in lens):
        raise RuntimeError(f"warm-up failed: {errs} {out}")


class Compiles:
    """Compilations so far: every trace that went to the backend (a
    persistent-cache hit counts too), and the cache's hits and misses."""

    def __init__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1

    def snapshot(self) -> Dict[str, int]:
        c = jaxenv.cache_counts()
        return {"events": self.events, "cache_hits": c["hits"],
                "cache_misses": c["misses"]}


def _delta(a: Dict, b: Dict) -> Dict:
    return {k: b[k] - a[k] for k in b
            if isinstance(b.get(k), (int, float)) and k in a}


# ---------------------------------------------------------------------------
# the window


def window(cell, port: int, sched: dict, seconds: float, trace: bool,
           engine, compiles: Compiles, tmp: str, setup_t0: int):
    sched = dict(sched, address=f"127.0.0.1:{port}", method=METHOD,
                 drain_s=DRAIN_S)
    sched_path = os.path.join(tmp, "schedule.json")
    out_path = os.path.join(tmp, "results.json")
    err_path = os.path.join(tmp, "loadgen.err")
    with open(sched_path, "w") as f:
        json.dump(sched, f)
    env = dict(os.environ)
    env.pop("TRPC_PJRT_PLUGIN", None)     # the client is a plain TCP peer
    with open(err_path, "w") as err:
        child = subprocess.Popen(
            [sys.executable, os.path.join(spec_mod.BENCH_DIR, "loadgen.py"),
             sched_path, out_path], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=err, text=True, env=env,
            cwd=cell.root)
    spans = None
    trace_dir = None
    try:
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"loadgen did not start: {ready!r}")
        if trace:
            spans = Spans()
            spans.install(engine)
        t0 = now() + 50_000_000
        child.stdin.write(f"GO {t0}\n")
        child.stdin.flush()
        t1 = t0 + int(seconds * 1e9)
        setup_s = (t0 - setup_t0) / 1e9
        sleep_until(t0)
        e0, p0, c0 = engine.stats(), tpu_plane.stats(), compiles.snapshot()
        if trace:
            dur = min(TRACE_MAX_S, seconds / 2)
            sleep_until(t0 + int((seconds - dur) / 2 * 1e9))
            trace_dir = tempfile.mkdtemp(prefix="trace-", dir=tmp)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ann = jax.profiler.TraceAnnotation("bench/window")
            ann.__enter__()
            sleep_until(now() + int(dur * 1e9))
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        sleep_until(t1)
        e1, p1, c1 = engine.stats(), tpu_plane.stats(), compiles.snapshot()
        child.wait(timeout=seconds + DRAIN_S + 60)
    except BaseException:
        child.kill()
        child.wait()
        raise
    finally:
        if spans is not None:
            spans.uninstall()
    if child.returncode != 0:
        with open(err_path) as f:
            raise RuntimeError(f"loadgen exited {child.returncode}: "
                               f"{f.read()[-3000:]}")
    with open(out_path) as f:
        res = json.load(f)
    log("window", compiles_in_window=_delta(c0, c1),
        engine=_delta(e0, e1), plane=_delta(p0, p1),
        clients_out_of_requests=res["exhausted"])
    return {"t0": t0, "setup_s": setup_s, "requests": res["requests"],
            "spans": spans, "engine_delta": _delta(e0, e1),
            "trace_dir": trace_dir}


def traffic_summary(requests: List[dict], t0: int, t1: int) -> dict:
    counted = [r for r in requests if r["counted"]]
    status: Dict[str, int] = {}
    for r in counted:
        status[r["status"]] = status.get(r["status"], 0) + 1
    late = sorted((r["sent"] - r["due"]) / 1e6 for r in counted
                  if r["sent"] is not None)
    ttft = [(r["times"][0] - r["due"]) / 1e6 for r in counted
            if RunData.ok(r)]
    return {"due": len(counted),
            "sent": sum(1 for r in counted if r["sent"] is not None),
            "finished": sum(1 for r in counted if RunData.ok(r)),
            "shed": status.get("shed", 0), "status": status,
            "in_window": sum(1 for r in counted if t0 <= r["due"] < t1),
            "send_late_p50_ms": pct(late, 0.5) if late else None,
            "send_late_p95_ms": pct(late, 0.95) if late else None,
            "ttft_p50_ms": pct(ttft, 0.5) if ttft else None,
            "ttft_p90_ms": pct(ttft, 0.9) if ttft else None}


# ---------------------------------------------------------------------------
# the check against the plain reference


def sample(requests: List[dict], seed: int) -> List[dict]:
    """The longest finished request, then others drawn from the seed,
    until CHECK_TOKENS served tokens are in."""
    fin = [r for r in requests if r["counted"] and RunData.ok(r)]
    if not fin:
        return []
    fin.sort(key=lambda r: r["id"])
    longest = max(fin, key=lambda r: (len(r["tokens"]), r["prompt_len"]))
    rest = [r for r in fin if r is not longest]
    rng = np.random.default_rng(traffic_mod.seed_seq(seed, 2))
    rng.shuffle(rest)
    out, n = [longest], len(longest["tokens"])
    for r in rest:
        if n >= CHECK_TOKENS or len(out) >= CHECK_MAX_SEQS:
            break
        out.append(r)
        n += len(r["tokens"])
    return out


def reference_gaps(cell, fam, seed: int, picked: List[dict],
                   prompts: Dict[int, List[int]], control: bool = False):
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sampled requests, and the number of
    tokens checked.  With ``control``, the tokens that the control (the
    reference in float8) puts first at the same positions, after the same
    prompts and served tokens, take the served tokens' place."""
    d = fam.dims(cell.config)
    need = traffic_mod.max_prompt(cell.traffic) + \
        traffic_mod.max_output(cell.traffic)
    T = min(d.n_positions, -(-need // 128) * 128)
    params = fam.weights(cell.config, seed)
    widest, n = 0.0, 0
    for r in picked:
        prompt, served = prompts[r["id"]], r["tokens"]
        P, N = len(prompt), len(served)
        seq = np.zeros((1, T), np.int32)
        seq[0, :P + N - 1] = prompt + served[:-1]
        seq = jnp.asarray(seq)
        tgt = np.zeros((T,), np.int32)
        if control:
            tgt[P - 1:P - 1 + N] = np.asarray(fam.control_tokens(
                params, seq, d, getattr(jnp, CONTROL_DTYPE)))[P - 1:P - 1 + N]
        else:
            tgt[P - 1:P - 1 + N] = served
        g = np.asarray(fam.gaps(params, seq, jnp.asarray(tgt),
                                d))[P - 1:P - 1 + N]
        widest = max(widest, float(g.max()))
        n += N
    del params
    return widest, n


def checks(cell, requests, widest: float, n_checked: int) -> Dict:
    lim = cell.config["check"]
    counted = [r for r in requests if r["counted"]]
    lost = sum(1 for r in counted if r["status"] == "lost")
    short = sum(1 for r in counted if r["status"] == "ok"
                and len(r["tokens"]) != r["max_new"])
    bad_ids = sum(1 for r in counted for t in r["tokens"]
                  if not 0 <= t < cell.config["vocab_size"])
    return {
        "logit_gap_max": {"value": widest, "limit": lim["max_logit_gap"],
                          "ok": widest <= lim["max_logit_gap"]},
        "checked_tokens_min": {"value": n_checked,
                               "limit": CHECK_MIN_TOKENS,
                               "ok": n_checked >= CHECK_MIN_TOKENS},
        "lost_max": {"value": lost, "limit": 0, "ok": lost == 0},
        "wrong_length_max": {"value": short, "limit": 0, "ok": short == 0},
        "bad_token_ids_max": {"value": bad_ids, "limit": 0,
                              "ok": bad_ids == 0},
    }


# ---------------------------------------------------------------------------
# one run


def device_info(devs) -> dict:
    peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _serve(cell, fam, seed, seconds, trace, devs, compiles, tmp,
           setup_t0, tamper):
    """Set up, warm up, run the window, read the peak memory, and take
    the engine and its server down again, whatever happened."""
    d = fam.dims(cell.config)
    params = jax.block_until_ready(fam.weights(cell.config, seed))
    engine = make_engine(cell, fam.program_model(cell.config), params)
    del params
    if tamper is not None:
        tamper(engine)
    server = Server(ServerOptions(
        method_max_concurrency={METHOD: engine.method_cap}))
    try:
        engine.register(server, METHOD)
        port = server.start("127.0.0.1:0")
        engine.start()
        warm_up(port, cell, d.vocab, seed, engine.n_slots)
        log("warm_up", seconds=(now() - setup_t0) / 1e9,
            **{f"compile_{k}": v for k, v in compiles.snapshot().items()})
        sched = traffic_mod.schedule(cell.traffic, seed, seconds, d.vocab,
                                     engine.n_slots)
        w = window(cell, port, sched, seconds, trace, engine, compiles,
                   tmp, setup_t0)
        dev = device_info(devs)
        log("memory", **{k: v for k, v in
                         (jax.local_devices()[0].memory_stats() or {}).items()
                         if k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit", "largest_free_block_bytes",
                                  "num_allocs")})
        engine.stop()
        engine.assert_drained()
        return sched, w, dev, engine.n_slots
    finally:
        engine.stop()
        server.destroy()


def run(workload: str, seed: int, seconds: float, trace: bool,
        setup_t0: int, root: str = spec_mod.ROOT,
        require_tpu: bool = True, control: bool = False,
        tamper=None, traffic_overrides: Optional[dict] = None,
        serving_overrides: Optional[dict] = None,
        trace_out: Optional[str] = None) -> dict:
    """One run; returns the result line as a dict.  The keyword options
    serve calibration and the harness's own tests, never the command:
    ``control`` adds ``control``, the same comparison made with the
    control's tokens in the served tokens' place; ``tamper(engine)``
    breaks the served path; ``traffic_overrides`` and
    ``serving_overrides`` replace traffic parameters (a rate sweep) and
    the configuration's serving ones (a slot count); ``trace_out`` keeps
    the trace's events as JSON."""

    cell = spec_mod.find_cell(workload, root)
    cell.traffic.update(traffic_overrides or {})
    cell.config["serving"].update(serving_overrides or {})
    devs = device_check(cell.chips) if require_tpu else jax.devices()
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__,
        compile_cache=jaxenv.enable_compile_cache())
    compiles = Compiles()
    if not tpu_plane.init(timeout_s=PLANE_INIT_TIMEOUT_S):
        raise SystemExit(f"benchmark: device plane down: "
                         f"{tpu_plane.error()}")
    fam = cell.family()
    d = fam.dims(cell.config)
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        sched, w, dev, n_slots = _serve(cell, fam, seed, seconds, trace,
                                        devs, compiles, tmp, setup_t0,
                                        tamper)
        gc.collect()
        log("teardown", live_array_bytes=sum(
            a.nbytes for a in jax.live_arrays()),
            plane_live_buffers=tpu_plane.stats()["live_buffers"])

        requests = w["requests"]
        log("traffic", **traffic_summary(requests, w["t0"], w["t0"] + int(
            seconds * 1e9)))
        if sched["loop"] == "open":
            prompts = {r["id"]: r["prompt"] for r in sched["requests"]}
        else:
            prompts = {r["id"]: r["prompt"] for c in sched["clients"]
                       for r in c}
        picked = sample(requests, seed)
        t_ref = now()
        widest, n_checked = reference_gaps(cell, fam, seed, picked, prompts)
        log("reference", seconds=(now() - t_ref) / 1e9,
            sequences=len(picked), tokens=n_checked)
        chk = checks(cell, requests, widest, n_checked)
        ctl = None
        if control:
            ctl = checks(cell, requests, *reference_gaps(
                cell, fam, seed, picked, prompts, control=True))

        red = None
        if w["trace_dir"]:
            paths = glob.glob(os.path.join(w["trace_dir"], "**",
                                           "*.xplane.pb"), recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            events = trace_mod.load_events(paths[0])
            red = trace_mod.reduce(events)
            if trace_out:
                with open(trace_out, "w") as f:
                    json.dump({"raw": trace_mod.raw_summary(paths[0]),
                               "summary": trace_mod.summary(events),
                               "events": trace_mod.excerpt(events, 0.25)},
                              f)
        data = RunData(cell, fam, seconds, w["setup_s"], w["t0"], requests,
                       w["spans"], w["engine_delta"], n_slots, red,
                       dev["kind"])
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            v = cell.reader(m["name"]).read(data)
            if v is None:
                # on the chip every metric listed for the cell reads; on
                # the CPU the trace has no device to read
                if not trace or require_tpu:
                    raise RuntimeError(f"metric {m['name']} read nothing "
                                       f"in cell {cell.name}")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        counted = data.counted
        out = {"correct": all(c["ok"] for c in chk.values()),
               "attempted": len(counted),
               "failed": sum(1 for r in counted if not RunData.ok(r)),
               "metrics": metrics, "device": dev}
        if red is not None:
            out["device"]["busy_s"] = red["busy_s"]
            out["device"]["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["idle_by_activity"]}
        if ctl is not None:
            out["control"] = {"correct": all(c["ok"] for c in ctl.values()),
                              "checks": {k: {"value": c["value"],
                                             "limit": c["limit"]}
                                         for k, c in ctl.items()}}
        out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                         for k, c in chk.items()}
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
