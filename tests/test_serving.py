"""Serving-plane tests (brpc_tpu/serving/): continuous batching,
KV-block accounting, and every cancellation surface (ISSUE 14).

The style rule (SURVEY §4) holds: real loopback sockets, a real
multi-device CPU mesh, no mocks.  The engine legs run in subprocesses —
a PJRT client is process-global state the test runner must not inherit
(same posture as tests/test_tpu_plane.py) — and each prints an OK
marker only after `assert_drained()` + `stats()["live_buffers"] == 0`
proved the block accounting balanced to zero.

In-process legs cover the scheduler's admission arithmetic, which needs
no device: both shed reasons (waiting room vs block budget) must be
ELIMIT *before* any prefill compute, per the PR-11 posture.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE_PLUGIN = os.path.join(REPO, "brpc_tpu", "_native", "libpjrt_fake.so")

SERVE_ENV = {
    "TRPC_PJRT_PLUGIN": FAKE_PLUGIN,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "JAX_PLATFORMS": "cpu",
}


def _need_fake():
    if not os.path.exists(FAKE_PLUGIN):
        pytest.skip("fake PJRT plugin not built (bash native/build.sh)")


def _run(code: str, env_extra=None, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# in-process: admission arithmetic (no device plane involved)
# ---------------------------------------------------------------------------


def test_scheduler_sheds_queue_and_budget_with_elimit():
    """Both shed reasons raise ELIMIT at submit() — before any prefill
    compute or DMA — and stay distinct in the counters."""
    from brpc_tpu.rpc import errors
    from brpc_tpu.serving.kv_cache import KvBlockPlane
    from brpc_tpu.serving.scheduler import Scheduler, Sequence

    kv = KvBlockPlane(block_bytes=4096, n_blocks=4)
    sched = Scheduler(n_slots=1, kv=kv, bytes_per_token=1024,
                      max_waiting=2)
    sid = iter(range(1, 100))

    def seq(plen):
        return Sequence(seq_id=next(sid), prompt=[1] * plen,
                        max_new_tokens=4)

    sched.submit(seq(12))            # 3 of 4 blocks committed
    with pytest.raises(errors.RpcError) as e:
        sched.submit(seq(12))        # 3 + 3 > 4 -> budget shed
    assert e.value.code == errors.ELIMIT
    assert sched.shed_budget == 1 and sched.shed_queue == 0
    sched.submit(seq(4))             # 3 + 1 == 4 still fits
    with pytest.raises(errors.RpcError) as e:
        sched.submit(seq(1))         # waiting room (2) is full
    assert e.value.code == errors.ELIMIT
    assert sched.shed_queue == 1 and sched.shed_budget == 1
    assert sched.submitted == 4 and sched.waiting_depth() == 2


def test_scheduler_release_is_idempotent_and_lifo_preemption():
    """release() counts a sequence once even when cancel races finish,
    and preempt_victim() picks the YOUNGEST admit (least work wasted)."""
    from brpc_tpu.serving.kv_cache import KvBlockPlane
    from brpc_tpu.serving import scheduler as S

    kv = KvBlockPlane(block_bytes=4096, n_blocks=8)
    sched = S.Scheduler(n_slots=2, kv=kv, bytes_per_token=1024,
                        max_waiting=4)
    a = S.Sequence(seq_id=1, prompt=[1] * 4, max_new_tokens=4)
    b = S.Sequence(seq_id=2, prompt=[1] * 4, max_new_tokens=4)
    sched.submit(a)
    sched.submit(b)
    assert sched.pop_admittable() is a and a.state == S.RUNNING
    assert sched.pop_admittable() is b
    assert b.admit_ns >= a.admit_ns
    assert sched.preempt_victim() is b          # youngest first
    sched.release(b, S.EVICTED, "preempted")
    sched.release(b, S.CANCELED, "racing cancel")   # second flip ignored
    assert b.state == S.EVICTED
    assert sched.evicted == 1 and sched.canceled == 0
    sched.release(a, S.FINISHED)
    assert sched.finished == 1 and not sched.has_work()


# ---------------------------------------------------------------------------
# subprocess: every cancellation surface frees the blocks (fake plugin)
# ---------------------------------------------------------------------------

CANCEL_CODE = r"""
import json, os, signal, struct, subprocess, sys, threading, time
from brpc_tpu import tpu_plane
from brpc_tpu.parallel.mesh import make_mesh
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.server import Server, ServerOptions
from brpc_tpu.rpc.stream import StreamReset
from brpc_tpu.serving import ServingEngine
from brpc_tpu.serving.engine import TOKEN_FMT, tiny_config
from brpc_tpu.serving.kv_cache import KvBlockPlane

assert tpu_plane.init(), tpu_plane.error()
s0 = tpu_plane.stats()
mesh = make_mesh({"dp": 2, "tp": 4})
engine = ServingEngine(cfg=tiny_config(), mesh=mesh,
                       kv=KvBlockPlane(block_bytes=4096, n_blocks=32),
                       n_slots=2, max_waiting=4)
server = Server(ServerOptions(
    method_max_concurrency={"LLM.Generate": engine.method_cap}))
engine.register(server)
addr = f"127.0.0.1:{server.start('127.0.0.1:0')}"
engine.start()


def open_stream(plen=12, max_new=24, cntl=None):
    ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
    payload = json.dumps({"prompt_len": plen,
                          "max_new_tokens": max_new}).encode()
    while True:
        try:
            _, st = ch.create_stream("LLM.Generate", payload, cntl=cntl)
            return ch, st
        except errors.RpcError as e:
            assert e.code == errors.ELIMIT, e
            time.sleep(0.05)


def wait_stat(key, floor, deadline_s=90):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if engine.stats()[key] >= floor:
            return
        time.sleep(0.05)
    raise SystemExit(f"{key} never reached {floor}: {engine.stats()}")


# -- leg 1: mid-stream RST (the wire form every post-handshake cancel
#    takes); the engine's next write raises StreamReset -> eviction
ch, st = open_stream()
for _ in range(2):
    assert st.read(timeout_s=120) is not None
st.rst(errors.ECANCELED)
st.destroy(); ch.close()
wait_stat("canceled", 1)

# -- leg 2: abrupt client disconnect (channel close takes the stream's
#    socket down); server write path must still free the blocks
ch, st = open_stream()
assert st.read(timeout_s=120) is not None
ch.close()
wait_stat("canceled", 2)

# -- leg 3: explicit Controller.start_cancel racing the in-flight
#    create_stream handshake — whichever side wins (ECANCELED from the
#    call, an RST'd stream, or a full generation when the cancel lost
#    the race entirely), the accounting must settle
got = {}
cntl = Controller()
def call():
    ch3 = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
    try:
        _, st3 = ch3.create_stream(
            "LLM.Generate",
            json.dumps({"prompt_len": 12, "max_new_tokens": 24}).encode(),
            cntl=cntl)
        try:
            while st3.read(timeout_s=60) is not None:
                pass
            got["end"] = "eof"
        except StreamReset:
            got["end"] = "reset"
        st3.destroy()
    except errors.RpcError as e:
        got["code"] = e.code
    finally:
        ch3.close()
t = threading.Thread(target=call)
t.start()
cntl.start_cancel()
t.join(120)
assert got.get("code") in (None, errors.ECANCELED, errors.ELIMIT), got
assert got.get("code") is not None or got.get("end") in ("eof", "reset"), got

# -- leg 4: SIGKILL the client process mid-stream; the kernel closes the
#    socket and the engine must evict on its next token write
child = r'''
import json, sys, time
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.channel import Channel, ChannelOptions
ch = Channel(sys.argv[1], ChannelOptions(timeout_ms=60000, max_retry=0))
while True:
    try:
        _, st = ch.create_stream("LLM.Generate", json.dumps(
            {"prompt_len": 10, "max_new_tokens": 48}).encode())
        break
    except errors.RpcError:
        time.sleep(0.05)
st.read(timeout_s=120); st.read(timeout_s=120)
print("READY", flush=True)
time.sleep(600)
'''
env = dict(os.environ)
env.pop("TRPC_PJRT_PLUGIN", None)   # the child is a pure TCP client
p = subprocess.Popen([sys.executable, "-c", child, addr],
                     stdout=subprocess.PIPE, text=True, env=env)
line = p.stdout.readline()
assert "READY" in line, line
os.kill(p.pid, signal.SIGKILL)
p.wait()
canceled_floor = 3 + (1 if got.get("end") == "reset" else 0)
wait_stat("canceled", canceled_floor)

# -- the proof: nothing leaked through any of the four surfaces
deadline = time.monotonic() + 60
while engine.stats()["kv_live_seqs"] > 0 and time.monotonic() < deadline:
    time.sleep(0.05)
engine.stop()
engine.assert_drained()
es = engine.stats()
assert es["canceled"] >= canceled_floor, es
assert es["rail_local"] > 0, es          # prefill->decode rode tpu_d2d
s1 = tpu_plane.stats()
assert s1["d2d_transfers"] > s0["d2d_transfers"], (s0, s1)
assert s1["live_buffers"] == 0, s1       # balanced to zero
server.destroy()
print("CANCEL-OK")
"""


def test_every_cancel_surface_frees_blocks():
    """Mid-stream RST, abrupt disconnect, explicit RPC cancel, and a
    SIGKILL'd client: four ways a consumer dies, one accounting
    invariant — blocks freed exactly once, device plane balanced."""
    _need_fake()
    r = _run(CANCEL_CODE, env_extra=SERVE_ENV)
    assert r.returncode == 0 and "CANCEL-OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


# ---------------------------------------------------------------------------
# subprocess: deterministic budget shed + preemption + host-rail codec
# ---------------------------------------------------------------------------

PREEMPT_CODE = r"""
import json, threading, time
from brpc_tpu import tpu_plane
from brpc_tpu.parallel.mesh import make_mesh
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server, ServerOptions
from brpc_tpu.rpc.stream import StreamReset
from brpc_tpu.serving import ServingEngine
from brpc_tpu.serving.engine import tiny_config
from brpc_tpu.serving.kv_cache import KvBlockPlane

assert tpu_plane.init(), tpu_plane.error()
mesh = make_mesh({"dp": 2, "tp": 4})

# tiny_config: kv_bytes_per_token = 2 layers * 2 (k+v) * 4 heads *
# 16 head_dim * 4 bytes = 1024 -> 4 tokens per 4096-byte block.
# A 7-block pool holds ONE 28-token sequence exactly; two 12-prompt /
# 16-new sequences (3 prompt blocks each) both admit optimistically and
# collide during growth -> the YOUNGER one must be preempted.
engine = ServingEngine(cfg=tiny_config(), mesh=mesh,
                       kv=KvBlockPlane(block_bytes=4096, n_blocks=7,
                                       rail="local"),
                       n_slots=2, max_waiting=2)
server = Server()
engine.register(server)
addr = f"127.0.0.1:{server.start('127.0.0.1:0')}"
engine.start()

# -- deterministic budget shed: a 40-token prompt needs 10 > 7 blocks;
#    submit() sheds it with ELIMIT before any prefill compute
ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
try:
    ch.create_stream("LLM.Generate", json.dumps(
        {"prompt_len": 40, "max_new_tokens": 8}).encode())
    raise SystemExit("over-budget prompt must shed")
except errors.RpcError as e:
    assert e.code == errors.ELIMIT, e
ch.close()
assert engine.stats()["shed_budget"] >= 1, engine.stats()

# -- preemption-by-eviction: A admitted first (older), B second; when
#    growth exhausts the pool the LIFO victim is B — A always finishes
results = {}
def client(name):
    ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
    payload = json.dumps({"prompt_len": 12,
                          "max_new_tokens": 16}).encode()
    try:
        while True:
            try:
                _, st = ch.create_stream("LLM.Generate", payload)
                break
            except errors.RpcError as e:
                assert e.code == errors.ELIMIT, e
                time.sleep(0.05)
        n = 0
        try:
            while st.read(timeout_s=120) is not None:
                n += 1
            results[name] = ("eof", n)
        except StreamReset as e:
            results[name] = ("reset", n)
        st.destroy()
    finally:
        ch.close()

ta = threading.Thread(target=client, args=("A",))
ta.start()
deadline = time.monotonic() + 60
while engine.stats()["admitted"] < 1 and time.monotonic() < deadline:
    time.sleep(0.01)                      # B must be the YOUNGER admit
tb = threading.Thread(target=client, args=("B",))
tb.start()
ta.join(180); tb.join(180)
assert results["A"] == ("eof", 16), results     # the elder finished
assert results["B"][0] == "reset", results      # the younger evicted
es = engine.stats()
assert es["preemptions"] >= 1 and es["evicted"] >= 1, es
assert es["finished"] >= 1, es
engine.stop()
engine.assert_drained()
server.destroy()

# -- host-rail codec leg: migration lands on the host, int8 transcodes
#    the landing bytes, and the generation still completes end-to-end
engine2 = ServingEngine(cfg=tiny_config(), mesh=mesh,
                        kv=KvBlockPlane(block_bytes=4096, n_blocks=32,
                                        rail="host", codec="int8"),
                        n_slots=2, max_waiting=2)
server2 = Server()
engine2.register(server2, method="LLM.Generate")
addr2 = f"127.0.0.1:{server2.start('127.0.0.1:0')}"
engine2.start()
ch = Channel(addr2, ChannelOptions(timeout_ms=60000, max_retry=0))
_, st = ch.create_stream("LLM.Generate", json.dumps(
    {"prompt_len": 12, "max_new_tokens": 8}).encode())
n = 0
while st.read(timeout_s=120) is not None:
    n += 1
st.destroy(); ch.close()
assert n == 8, n
es2 = engine2.stats()
assert es2["rail_host"] >= 1 and es2["kv_migrations_host"] >= 3, es2
assert es2["kv_codec_bytes"] > 0, es2
engine2.stop()
engine2.assert_drained()
server2.destroy()
assert tpu_plane.stats()["live_buffers"] == 0, tpu_plane.stats()
print("PREEMPT-OK")
"""


def test_budget_shed_preemption_and_host_codec():
    """Deterministic legs the example can't pin: an over-budget prompt
    sheds at submit (never queues), pool-dry growth preempts the
    youngest sequence (elder finishes, younger RSTs), and the host-rail
    int8 codec transcodes migration bytes without breaking decode."""
    _need_fake()
    r = _run(PREEMPT_CODE, env_extra=SERVE_ENV)
    assert r.returncode == 0 and "PREEMPT-OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


# ---------------------------------------------------------------------------
# the decode loop's phase counters and spans
# ---------------------------------------------------------------------------


def test_queue_wait_sums_admission_waits():
    """queue_wait_ns is the sum of admit_ns - submit_ns over the admitted
    sequences, and a sequence still waiting adds nothing."""
    from brpc_tpu.serving.kv_cache import KvBlockPlane
    from brpc_tpu.serving import scheduler as S

    sched = S.Scheduler(n_slots=2, kv=KvBlockPlane(block_bytes=4096,
                                                   n_blocks=8),
                        bytes_per_token=1024, max_waiting=4)
    seqs = [S.Sequence(seq_id=i, prompt=[1] * 4, max_new_tokens=4)
            for i in range(3)]
    for s in seqs:
        sched.submit(s)
    admitted = [sched.pop_admittable(), sched.pop_admittable()]
    assert sched.pop_admittable() is None          # both slots taken
    assert admitted == seqs[:2]
    assert sched.queue_wait_ns == sum(s.admit_ns - s.submit_ns
                                      for s in admitted)
    assert sched.queue_wait_ns > 0


PHASES_CODE = r"""
import glob, json, os, tempfile, threading, urllib.request
import jax
from jax.profiler import ProfileData
from brpc_tpu import tpu_plane
from brpc_tpu.metrics import bvar
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server
from brpc_tpu.serving import ServingEngine
from brpc_tpu.serving.engine import tiny_config
from brpc_tpu.serving.kv_cache import KvBlockPlane

assert tpu_plane.init(), tpu_plane.error()
engine = ServingEngine(cfg=tiny_config(),
                       kv=KvBlockPlane(block_bytes=4096, n_blocks=48),
                       n_slots=2, max_waiting=6)
server = Server()
engine.register(server)
addr = f"127.0.0.1:{server.start('127.0.0.1:0')}"
engine.start()


def generate(max_new):
    ch = Channel(addr, ChannelOptions(timeout_ms=60000, max_retry=0))
    try:
        _, st = ch.create_stream("LLM.Generate", json.dumps(
            {"prompt_len": 12, "max_new_tokens": max_new}).encode())
        n = 0
        while st.read(timeout_s=120) is not None:
            n += 1
        st.destroy()
        return n
    finally:
        ch.close()


assert generate(4) == 4                      # compiles, outside the trace
trace_dir = tempfile.mkdtemp()
jax.profiler.start_trace(trace_dir)
got = []
def client():
    for _ in range(2):
        got.append(generate(16))
ts = [threading.Thread(target=client) for _ in range(3)]
for t in ts:
    t.start()
for t in ts:
    t.join(120)
jax.profiler.stop_trace()
assert got == [16] * 6, got
portal = urllib.request.urlopen(
    f"http://{addr}/vars?filter=serving_", timeout=10).read().decode()
engine.stop()
engine.assert_drained()
stats = engine.stats()
server.destroy()

# a second engine: the serving_* vars follow the one registered last
engine2 = ServingEngine(cfg=tiny_config(),
                        kv=KvBlockPlane(block_bytes=4096, n_blocks=8),
                        n_slots=1, max_waiting=1)
server2 = Server()
engine2.register(server2)
second = bvar.describe_exposed("serving_tokens_out")
server2.destroy()

spans = []
path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                 recursive=True)[0]
for pl in ProfileData.from_file(path).planes:
    for ln in pl.lines:
        for ev in ln.events:
            if ev.name.startswith("serving/"):
                spans.append([f"{pl.name}|{ln.name}", ev.name[8:],
                              int(ev.start_ns), int(ev.duration_ns),
                              {k: str(v) for k, v in ev.stats}])
print("PHASES " + json.dumps({"stats": stats, "spans": spans,
                              "portal": portal, "second": second}))
"""

LEAF_SPANS = ("idle", "prefill", "kv_d2h", "kv_alloc", "kv_migrate",
              "kv_fetch", "kv_install", "dispatch", "sync", "kv_grow",
              "retire")


@pytest.fixture(scope="module")
def phases_run():
    """The tiny engine serving six requests on two slots (three clients,
    so requests queue) under a profiler trace."""
    _need_fake()
    r = _run(PHASES_CODE, env_extra=SERVE_ENV)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("PHASES ")]
    assert r.returncode == 0 and line, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return __import__("json").loads(line[-1][len("PHASES "):])


def test_every_phase_counter_moves(phases_run):
    from brpc_tpu.serving.engine import PHASES
    st = phases_run["stats"]
    for ph in PHASES:
        assert st[f"{ph}_ns"] > 0, (ph, st)
    assert st["queue_wait_ns"] > 0 and st["loop_ns"] > 0, st
    assert st["prefill_compiles"] == 1, st      # one prompt length
    assert st["kv_grown_blocks"] > 0, st


def test_phase_counters_cover_the_loop(phases_run):
    """The leaf phases never overlap, so they sum to at most the loop's
    time, and the loop does little outside them."""
    from brpc_tpu.serving.engine import PHASES
    st = phases_run["stats"]
    leaves = sum(st[f"{ph}_ns"] for ph in PHASES)
    assert leaves <= st["loop_ns"], st
    assert st["loop_ns"] - leaves <= 0.05 * st["loop_ns"], st


def test_phase_spans_nest_in_the_trace(phases_run):
    """serving/step holds dispatch, sync and emit; serving/admit holds
    prefill and the kv_* hops; the counted spans are disjoint; the spans
    carry their stats."""
    spans = phases_run["spans"]
    lines = {s[0] for s in spans if s[1] == "step"}
    assert len(lines) == 1, lines                 # the decode-loop thread
    spans = [s for s in spans if s[0] in lines]

    def of(name):
        return [(s[2], s[2] + s[3], s[4]) for s in spans if s[1] == name]

    def inside(child, parents):
        return all(any(p0 <= c0 and c1 <= p1 for p0, p1, _ in parents)
                   for c0, c1, _ in of(child))

    steps, admits = of("step"), of("admit")
    assert len(steps) >= 16 and len(admits) == 6, (len(steps), len(admits))
    for child in ("dispatch", "emit"):
        assert of(child) and inside(child, steps), child
    for child in ("prefill", "kv_d2h", "kv_alloc", "kv_migrate",
                  "kv_fetch", "kv_install"):
        assert len(of(child)) == 6 and inside(child, admits), child
    assert len(of("sync")) == len(steps) + len(admits)
    assert of("kv_grow") and inside("kv_grow", steps + admits)
    assert all(int(st["n"]) >= 1 and int(st["ctx"]) >= 12
               for _, _, st in steps)
    assert sorted(int(st["plen"]) for _, _, st in admits) == [12] * 6
    assert {int(st["slot"]) for _, _, st in admits} <= {0, 1}
    leaves = sorted((s[2], s[2] + s[3], s[1]) for s in spans
                    if s[1] in LEAF_SPANS)
    for a, b in zip(leaves, leaves[1:]):
        assert a[1] <= b[0], (a, b)


def test_serving_stats_on_vars(phases_run):
    """register() exposes stats() as serving_<key> bvars on /vars; a
    later engine's register() moves them to that engine."""
    portal = dict(ln.split(" : ", 1) for ln in
                  phases_run["portal"].strip().splitlines())
    assert int(portal["serving_tokens_out"]) == \
        phases_run["stats"]["tokens_out"] == 4 + 6 * 16
    assert "serving_emit_write_ns" in portal
    assert int(portal["serving_param_bytes"]) == \
        phases_run["stats"]["param_bytes"] > 0
    assert phases_run["second"] == "0"


# ---------------------------------------------------------------------------
# the engine's programs read their weights as they are held
# ---------------------------------------------------------------------------

MECHANISM_CODE = r"""
import json, re
import jax, jax.numpy as jnp
from brpc_tpu import tpu_plane
from brpc_tpu.models import decode as D, transformer
from brpc_tpu.parallel.mesh import make_mesh
from brpc_tpu.serving import ServingEngine
from brpc_tpu.serving.engine import tiny_config
from brpc_tpu.serving.kv_cache import KvBlockPlane

assert tpu_plane.init(), tpu_plane.error()
cfg = tiny_config(dtype=jnp.bfloat16)
p32 = jax.eval_shape(lambda k: transformer.init(k, cfg), jax.random.PRNGKey(0))
out = {"param_bytes": D.param_bytes(
    jax.eval_shape(lambda p: D.serving_params(p, cfg), p32))}
for name, mesh in (("one", None), ("mesh", make_mesh({"dp": 2, "tp": 4}))):
    engine = ServingEngine(cfg=cfg, mesh=mesh, n_slots=2, max_waiting=1,
                           kv=KvBlockPlane(block_bytes=4096, n_blocks=8))
    toks, active = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    text = engine._jstep.lower(engine.params, engine.cache, toks,
                               active).as_text()
    text += engine._prefill_fn(12).lower(
        engine.params, jnp.zeros((1, 12), jnp.int32)).as_text()
    out[name] = {"converts": re.findall(
                     r"stablehlo\.convert [^:]*: \(tensor<([^>]*)>\)", text),
                 "param_bytes": engine.stats()["param_bytes"]}
    engine.stop()
print("MECH " + json.dumps(out))
"""


def test_programs_cast_no_weight():
    """Lowered for a bf16 model, on one device and on the CPU mesh, the
    engine's decode step and prefill convert no weight: no StableHLO
    convert takes a float32 operand shaped like a weight stack, one
    layer's weight, or the embedding.  Activations still convert."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.models import decode as D
    from brpc_tpu.models import transformer
    from brpc_tpu.serving.engine import tiny_config
    _need_fake()
    r = _run(MECHANISM_CODE, env_extra=SERVE_ENV)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("MECH ")]
    assert r.returncode == 0 and line, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = __import__("json").loads(line[-1][len("MECH "):])
    cfg = tiny_config(dtype=jnp.bfloat16)
    p32 = jax.eval_shape(lambda k: transformer.init(k, cfg),
                         jax.random.PRNGKey(0))
    shapes = [p32["embed"].shape]
    for name in D.MATMUL_WEIGHTS:
        stack = p32["blocks"][name].shape
        shapes += [stack, stack[1:]]
    weights = {"x".join(map(str, s)) + "xf32" for s in shapes}
    for mesh in ("one", "mesh"):
        got = out[mesh]["converts"]
        assert got, mesh                  # the pattern finds converts
        assert not weights & set(got), (mesh, sorted(weights & set(got)))
        assert out[mesh]["param_bytes"] == out["param_bytes"]
