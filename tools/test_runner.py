"""Sharded test runner (reference capability: tools/test_runner.py +
the cmake py_test registration that shards/parallelizes the suite,
unittests/CMakeLists.txt; hang detection per tools/check_ctest_hung.py).

Splits the test FILES deterministically across N shards (sorted order,
round-robin) and runs each shard as one pytest invocation with a hard
timeout — a stuck test kills the shard with a named report instead of
hanging CI.

    python tools/test_runner.py --shards 4 --shard 1
    python tools/test_runner.py --only test_book test_models

Shard 0 (and single-shard runs) first runs the static gates: `ruff
check` over the codebase (skipped with a notice when ruff is not
installed — the container image does not bake it in; pass `--ci` to
make a missing ruff a hard failure) and `tools/proglint.py` over the
example programs (the model zoo), the serve_lint_* serving sweep
(`--all`), the host-side concurrency lint (`--concurrency`, pinned at
zero unsuppressed findings) and the cross-view program contracts
(`--contracts`), so a program-level regression fails CI before any
test executes. `--no-lint` skips all the gates.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import subprocess
import sys

# zoo models proglint verifies as the example-program gate (small/fast
# builds; the full zoo is covered by tests/test_analysis.py)
LINT_MODELS = ("mnist", "smallnet")

# the serving programs (the full view and the paged slot-pool views)
# are linted in is-test mode via `proglint --all`, which
# auto-discovers every serve_lint_* entry of models/transformer — a new
# serving view only needs a serve_lint_ function to join the gate, not
# an edit here (ISSUE 8/9; docs/serving.md)

# a sharded-lookup training program (table marked __sharded__, lazy-adam
# over the combined embedding) — the verifier must stay green on marked
# programs (ISSUE 14; docs/performance.md 'Sharded embedding tables')
LINT_SHARDED_MODULES = (
    "paddle_tpu.distributed.sharded_table:lint_program",
)


def shard_files(all_files, shards, shard):
    return [f for i, f in enumerate(sorted(all_files))
            if i % shards == shard]


def run_lint_gate(root: str, timeout: int, ci: bool = False) -> int:
    """ruff over the repo (when installed) + proglint over the example
    programs. Returns 0 when everything passes or is skipped. Under
    ``ci=True`` a missing ruff is a hard failure instead of a
    skip-with-notice — a CI image without the configured linter is a
    broken image, not an optional check."""
    try:
        if shutil.which("ruff"):
            print("test_runner: lint gate — ruff check")
            r = subprocess.run(["ruff", "check", "."], cwd=root,
                               timeout=timeout)
            if r.returncode:
                return r.returncode
        elif ci:
            print("test_runner: lint gate — ruff not installed and --ci "
                  "set: failing (config: pyproject.toml [tool.ruff])")
            return 1
        else:
            print("test_runner: lint gate — ruff not installed, skipping "
                  "(config: pyproject.toml [tool.ruff])")
        print(f"test_runner: lint gate — proglint over example programs "
              f"{list(LINT_MODELS)}")
        cmd = [sys.executable, os.path.join(root, "tools", "proglint.py")]
        for m in LINT_MODELS:
            cmd += ["--model", m]
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        r = subprocess.run(cmd, cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # serving prefill/decode programs, linted as inference programs
        # (auto-discovered serve_lint_* sweep — no hand list to rot)
        print("test_runner: lint gate — proglint --all over the "
              "serve_lint_* serving programs (is-test)")
        scmd = [sys.executable, os.path.join(root, "tools", "proglint.py"),
                "--all", "--is-test"]
        r = subprocess.run(scmd, cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # concurrency lint over the host-side orchestration packages:
        # the tree must stay at ZERO unsuppressed findings (fix the
        # race or add a justified __lint_suppress__ —
        # docs/static_analysis.md "Concurrency lint")
        print("test_runner: lint gate — proglint --concurrency "
              "(zero-unsuppressed-findings baseline)")
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "proglint.py"),
             "--concurrency", "--strict"],
            cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # cross-view program contracts over the decoder_lm family:
        # shared persistables, rng salts, donation coherence and the
        # geometry records must agree across every serving view
        print("test_runner: lint gate — proglint --contracts over the "
              "decoder_lm family")
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "proglint.py"),
             "--contracts"],
            cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # sharded-embedding example program (train mode: the __sharded__
        # mark is metadata — the lowered fast path swaps runtime arrays,
        # never program structure, so the verifier must not notice)
        print(f"test_runner: lint gate — proglint over sharded-table "
              f"program {list(LINT_SHARDED_MODULES)}")
        dcmd = [sys.executable, os.path.join(root, "tools", "proglint.py")]
        for m in LINT_SHARDED_MODULES:
            dcmd += ["--module", m]
        r = subprocess.run(dcmd, cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # memory observability gate: mem_probe --smoke (compiled
        # breakdown + estimator band + donation audit on mnist and the
        # serving decode program) and proglint --memory on the decode
        # executable — a donation regression (a state buffer that stops
        # aliasing in input_output_alias) fails CI here, before any
        # test runs (docs/observability.md "Memory observability")
        print("test_runner: lint gate — mem_probe --smoke")
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "mem_probe.py"),
             "--smoke"], cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # the donation audit over the PAGED decode program — the shared
        # page pool (and the int8 scale planes, when configured) must
        # keep aliasing in input_output_alias across the page-table
        # gather/scatter rewrite (ISSUE 17; docs/serving.md "Paged KV
        # cache")
        print("test_runner: lint gate — proglint --memory over the "
              "paged decode program")
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "proglint.py"),
             "--memory", "--is-test", "--module",
             "paddle_tpu.models.transformer:serve_lint_decode_paged"],
            cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # speculative-decoding smoke: the draft-verify slot engine must
        # emit the EXACT greedy stream of the sequential slot scheduler
        # with zero steady-state compiles (forbid_compiles held over the
        # whole generation) — the losslessness contract of ISSUE 19
        # (docs/serving.md "Speculative decoding")
        print("test_runner: lint gate — spec-decode smoke (draft-verify "
              "greedy parity + zero steady-state recompiles)")
        r = subprocess.run([sys.executable, "-c", _SPEC_SMOKE],
                           cwd=root, timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # SPMD gates, on 8 virtual CPU devices (the same harness the
        # multi-chip tests use — tests/conftest.py): proglint --sharding
        # proves every persistable of the example programs resolves to a
        # PartitionSpec under a dp mesh, then the smoke trains mnist one
        # step over dp=8 and demands bit-parity with the single-device
        # oracle plus zero steady-state recompiles under forbid_compiles
        # (docs/performance.md "SPMD execution")
        spmd_env = dict(env)
        spmd_env["XLA_FLAGS"] = (
            spmd_env.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
        print(f"test_runner: lint gate — proglint --sharding over "
              f"{list(LINT_MODELS)} (8 virtual devices)")
        r = subprocess.run(cmd + ["--sharding"], cwd=root,
                           timeout=timeout, env=spmd_env)
        if r.returncode:
            return r.returncode
        print("test_runner: lint gate — SPMD smoke (dp=8 mnist parity "
              "+ zero steady-state recompiles)")
        r = subprocess.run([sys.executable, "-c", _SPMD_SMOKE],
                           cwd=root, timeout=timeout, env=spmd_env)
        if r.returncode:
            return r.returncode
        # pass-pipeline smoke: apply ALL passes to the example programs
        # and lint the post-pass programs, under the autotune
        # measurement-forbidden guard — proves (a) the rewritten zoo
        # programs stay verifier-green and (b) with the committed table
        # present the whole build path performs ZERO timing
        # measurements (paddle_tpu/passes/autotune.py CI contract)
        print("test_runner: lint gate — pass-pipeline smoke "
              "(proglint --passes, measurement-forbidden)")
        r = subprocess.run(cmd + ["--passes"], cwd=root,
                           timeout=timeout, env=env)
        if r.returncode:
            return r.returncode
        # distributed-tracing smoke: produce a two-role spool (client
        # span -> traceparent -> server child spans) and run the
        # trace_collect integrity gate over it — monotonic timestamps,
        # parents resolve, flow events pair up (docs/observability.md
        # "Distributed tracing & flight recorder")
        print("test_runner: lint gate — trace spool smoke + "
              "trace_collect --check")
        import tempfile
        with tempfile.TemporaryDirectory(prefix="trace_smoke_") as d:
            r = subprocess.run(
                [sys.executable, "-c", _TRACE_SMOKE, d],
                cwd=root, timeout=timeout, env=env)
            if r.returncode:
                return r.returncode
            r = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "trace_collect.py"),
                 d, "--check"],
                cwd=root, timeout=timeout, env=env)
            if r.returncode:
                return r.returncode
        # router duo smoke: a supervised router + 2 replica processes,
        # one replica SIGKILLed, the SAME request id re-dispatched and
        # completed on the survivor — then the merged trace must stitch
        # the client -> router -> replica span chain (ISSUE 13)
        print("test_runner: lint gate — router duo smoke + "
              "trace_collect --check --chain client,router,replica")
        with tempfile.TemporaryDirectory(prefix="router_smoke_") as d:
            smoke_env = dict(env)
            smoke_env.pop("FLAGS_trace_role", None)
            smoke_env["FLAGS_trace_spool_dir"] = d
            r = subprocess.run(
                [sys.executable, "-c", _ROUTER_SMOKE, d],
                cwd=root, timeout=timeout, env=smoke_env)
            if r.returncode:
                return r.returncode
            r = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "trace_collect.py"),
                 d, "--check", "--chain", "client,router,replica"],
                cwd=root, timeout=timeout, env=env)
            if r.returncode:
                return r.returncode
        # autoscaler smoke: a supervised router + 1 replica, a
        # SYNTHETIC SLO breach driving one real reconcile cycle —
        # scale up to 2 (spawn + readyz), clear, drain back down to 1
        # — with traced client calls before and after, so the merged
        # spool must still stitch the full span chain (ISSUE 16)
        print("test_runner: lint gate — autoscaler smoke + "
              "trace_collect --check --chain client,router,replica")
        with tempfile.TemporaryDirectory(prefix="autoscaler_smoke_") as d:
            smoke_env = dict(env)
            smoke_env.pop("FLAGS_trace_role", None)
            smoke_env["FLAGS_trace_spool_dir"] = d
            r = subprocess.run(
                [sys.executable, "-c", _AUTOSCALER_SMOKE, d],
                cwd=root, timeout=timeout, env=smoke_env)
            if r.returncode:
                return r.returncode
            r = subprocess.run(
                [sys.executable,
                 os.path.join(root, "tools", "trace_collect.py"),
                 d, "--check", "--chain", "client,router,replica"],
                cwd=root, timeout=timeout, env=env)
        return r.returncode
    except subprocess.TimeoutExpired:
        sys.exit(f"test_runner: lint gate exceeded {timeout}s")


# the SPMD smoke: one jit dispatch under Mesh + NamedSharding is the
# PRODUCT path (ISSUE 18) — train mnist one step over a dp=8 mesh of
# virtual CPU devices and demand (a) the loss bit-match (rtol 1e-6
# ceiling) the single-device oracle, (b) further steps perform ZERO new
# XLA compiles (embed_cache.compile_count, the backend_compile_duration
# listener) with the serving forbid_compiles guard held
_SPMD_SMOKE = """
import numpy as np
import jax
assert len(jax.devices()) == 8, jax.devices()
import paddle_tpu.fluid as fluid
from paddle_tpu import models
from paddle_tpu.parallel import DistributeConfig, make_mesh
from paddle_tpu.ops.embed_cache import compile_count
from paddle_tpu.serving.metrics import forbid_compiles

rng = np.random.RandomState(0)
feeds = {"pixel": rng.rand(32, 1, 28, 28).astype("float32"),
         "label": rng.randint(0, 10, (32, 1)).astype("int64")}

def build():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    startup.random_seed = 1
    with fluid.program_guard(main, startup):
        loss, _, _ = models.mnist.build()
    return main, startup, loss

main, startup, loss = build()
scope = fluid.Scope()
exe = fluid.Executor(fluid.TPUPlace())
exe.run(startup, scope=scope)
ref = np.asarray(exe.run(main, feed=feeds, fetch_list=[loss],
                         scope=scope)[0])

main, startup, loss = build()
mesh = make_mesh({"dp": 8})
prog = fluid.CompiledProgram(main).with_sharding(
    DistributeConfig(mesh=mesh, data_axis="dp"))
scope = fluid.Scope()
exe = fluid.Executor(fluid.TPUPlace())
exe.run(startup, scope=scope)
got = np.asarray(exe.run(prog, feed=feeds, fetch_list=[loss],
                         scope=scope)[0])
assert np.all(np.isfinite(got)), got
np.testing.assert_allclose(got, ref, rtol=1e-6)

base = compile_count()
with forbid_compiles():
    for _ in range(3):
        last = np.asarray(exe.run(prog, feed=feeds, fetch_list=[loss],
                                  scope=scope)[0])
delta = compile_count() - base
assert delta == 0, f"{delta} steady-state recompiles"
assert np.all(np.isfinite(last)), last
print("spmd smoke ok: dp=8 one-step parity, 0 steady-state recompiles")
"""


# the spec-decode smoke: one slot engine WITH a verify view
# vs one without, same weights discipline (per-engine init is seeded by
# program build), greedy over a mixed prompt set — the draft-verify
# stream must be token-for-token identical, and the whole speculative
# generation must run under forbid_compiles after warmup (one verify
# executable serves every draft-length mix via the win_len feed)
_SPEC_SMOKE = """
import numpy as np
from paddle_tpu.models import transformer as T
from paddle_tpu.serving import engine as seng
from paddle_tpu.serving import metrics as smetrics

CFG = dict(prompt_len=8, max_new=8, vocab=32, d_model=16, d_inner=32,
           n_head=2, n_layer=2)
rng = np.random.RandomState(3)
prompts = [rng.randint(1, 32, (int(n),)) for n in (3, 7, 8, 5)]

spec = seng.make_slot_model(
    "lm_spec_smoke",
    T.build_decoder_lm_programs(**CFG, prompt_buckets=(4, 8),
                                modes=T.slot_modes(spec=True),
                                n_slots=4, spec_k=3))
spec.warmup()
base = seng.make_slot_model(
    "lm_base_smoke",
    T.build_decoder_lm_programs(**CFG, prompt_buckets=(4, 8),
                                modes=T.slot_modes(), n_slots=4))
base.warmup()

want = base.generate(prompts, max_new=6)
with smetrics.forbid_compiles():
    got = spec.generate(prompts, max_new=6)
for i, (a, b) in enumerate(zip(want, got)):
    np.testing.assert_array_equal(a, b, err_msg=f"prompt {i}")
disp = smetrics.DECODE_STEPS.labels(model="lm_spec_smoke").value
prop = smetrics.SPEC_PROPOSED.labels(model="lm_spec_smoke").value
acc = smetrics.SPEC_ACCEPTED.labels(model="lm_spec_smoke").value
assert acc <= prop, (acc, prop)
print(f"spec smoke ok: greedy parity over {len(prompts)} prompts, "
      f"{int(disp)} verify dispatches, {int(acc)}/{int(prop)} drafts "
      f"accepted, 0 steady-state recompiles")
"""


# the trace smoke run: one process plays both roles (two spool files =
# two process tracks), propagating the context the way the real RPC
# layers do — client_span -> to_traceparent -> extract/activate -> spans
_TRACE_SMOKE = """
import sys, time
from paddle_tpu.observability import spool, tracing
from paddle_tpu.observability import trace_context as tctx
d = sys.argv[1]
client = spool.SpanSpool(d, role="client")
tracing.add_sink(client)
with tctx.client_span("rpc.call"):
    header = tctx.current().to_traceparent()
tracing.remove_sink(client); client.close()
server = spool.SpanSpool(d, role="server")
tracing.add_sink(server)
with tctx.activate(tctx.from_traceparent(header)):
    with tctx.span("server.handle"):
        with tctx.span("server.work"):
            time.sleep(0.001)
tracing.remove_sink(server); server.close()
"""


# the router duo smoke: this process is the CLIENT (role set via the
# flags API so the router/replica children do not inherit it from env);
# the router subprocess supervises two replica processes. One replica
# is SIGKILLed and the same request id must complete on the survivor.
_ROUTER_SMOKE = """
import json, os, signal, socket, subprocess, sys, time
d = sys.argv[1]
from paddle_tpu import flags
flags.set("trace_role", "client")
from paddle_tpu.observability import spool
from paddle_tpu.observability import trace_context as tctx

SPEC = {"model": {"kind": "decoder_lm", "name": "lm", "params": {
    "prompt_len": 8, "max_new": 8, "vocab": 32, "d_model": 16,
    "d_inner": 32, "n_head": 2, "n_layer": 2}}}

def call(endpoint, req, timeout=60.0):
    host, port = endpoint.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=timeout) as s:
        s.sendall((json.dumps(req) + "\\n").encode())
        line = s.makefile("rb").readline()
    assert line, "router closed the connection"
    return json.loads(line)

ef = os.path.join(d, "router.endpoint")
proc = subprocess.Popen(
    [sys.executable, "-m", "paddle_tpu.serving.router",
     "--spec-json", json.dumps(SPEC), "--replicas", "2",
     "--endpoint-file", ef],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
try:
    deadline = time.monotonic() + 300
    while not os.path.exists(ef):
        assert time.monotonic() < deadline, "router endpoint never appeared"
        assert proc.poll() is None, "router died during startup"
        time.sleep(0.1)
    endpoint = open(ef).read().strip()
    while True:
        assert time.monotonic() < deadline, "replicas never both ready"
        try:
            rz = call(endpoint, {"method": "readyz"}, 5.0)
        except (ConnectionError, OSError):
            time.sleep(0.2)
            continue
        if rz.get("ready") and rz["replicas"].count("ready") == 2:
            break
        time.sleep(0.2)

    def gen(req_id):
        req = {"method": "generate", "model": "lm", "req_id": req_id,
               "prompts": [[1, 2, 3]], "max_new": 4,
               "temperature": 0.0, "top_k": 0}
        with tctx.client_span("serving.generate"):
            tctx.inject(req)
            return call(endpoint, req)

    r1 = gen("duo-smoke-1")
    assert r1.get("ok"), r1
    victim = r1["routed_replica"]
    stats = call(endpoint, {"method": "router_stats"})["stats"]
    pid = next(s["pid"] for s in stats["replicas"]
               if s["index"] == victim)
    os.kill(pid, signal.SIGKILL)
    r2 = gen("duo-smoke-1")     # same id: sticky target is dead
    assert r2.get("ok"), r2
    assert r2["routed_replica"] != victim, r2
    assert r2["tokens"] == r1["tokens"], (r1, r2)  # greedy: same stream
finally:
    proc.terminate()
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
spool.shutdown()
print("router duo smoke ok")
"""


# the autoscaler smoke: the router runs as a subprocess (its own spool
# role) supervising ONE replica; this process is the client AND hosts
# the control loop, driving the router's scale RPCs against a synthetic
# SLO breach — a real scale-up (spawn + readyz) and a real drain-based
# scale-down in one run, with traced generate calls at sizes 1, 2, 1.
_AUTOSCALER_SMOKE = """
import json, os, socket, subprocess, sys, time
d = sys.argv[1]
from paddle_tpu import flags
flags.set("trace_role", "client")
from paddle_tpu.observability import spool
from paddle_tpu.observability import trace_context as tctx
from paddle_tpu.serving.autoscaler import Autoscaler, AutoscalePolicy

SPEC = {"model": {"kind": "decoder_lm", "name": "lm", "params": {
    "prompt_len": 8, "max_new": 8, "vocab": 32, "d_model": 16,
    "d_inner": 32, "n_head": 2, "n_layer": 2}}}

def call(endpoint, req, timeout=60.0):
    host, port = endpoint.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=timeout) as s:
        s.sendall((json.dumps(req) + "\\n").encode())
        line = s.makefile("rb").readline()
    assert line, "router closed the connection"
    return json.loads(line)

class RpcRouter:
    # the reconciler's actuator arm over the router's admin RPCs —
    # the smoke proves the loop closes ACROSS the process boundary
    def __init__(self, endpoint):
        self.endpoint = endpoint
    def scale_up(self, count=1, spec=None, endpoints=None):
        req = {"method": "router_scale_up", "count": count}
        if spec is not None:
            req["spec"] = spec
        return call(self.endpoint, req, 120.0)
    def scale_down(self, index=None):
        req = {"method": "router_scale_down"}
        if index is not None:
            req["replica"] = index
        return call(self.endpoint, req, 120.0)
    def stats(self):
        return call(self.endpoint, {"method": "router_stats"})["stats"]

class SyntheticSource:
    # fleet shape is REAL (router_stats); the SLO signal is scripted
    def __init__(self, router):
        self.router = router
        self.p99 = 0.0
    def poll(self, now=None, slo_s=0.0):
        st = self.router.stats()
        return {"fleet": st, "size": st["size"], "ready": st["ready"],
                "queue_depth": 0, "p99": self.p99,
                "attainment": 1.0 if self.p99 <= slo_s else 0.0}

ef = os.path.join(d, "router.endpoint")
proc = subprocess.Popen(
    [sys.executable, "-m", "paddle_tpu.serving.router",
     "--spec-json", json.dumps(SPEC), "--replicas", "1",
     "--endpoint-file", ef],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
try:
    deadline = time.monotonic() + 300
    while not os.path.exists(ef):
        assert time.monotonic() < deadline, "router endpoint never appeared"
        assert proc.poll() is None, "router died during startup"
        time.sleep(0.1)
    endpoint = open(ef).read().strip()
    def ready_count():
        try:
            rz = call(endpoint, {"method": "readyz"}, 5.0)
        except (ConnectionError, OSError):
            return -1
        return rz["replicas"].count("ready") if rz.get("ready") else 0
    while ready_count() < 1:
        assert time.monotonic() < deadline, "replica never ready"
        time.sleep(0.2)

    def gen(req_id):
        req = {"method": "generate", "model": "lm", "req_id": req_id,
               "prompts": [[1, 2, 3]], "max_new": 4,
               "temperature": 0.0, "top_k": 0}
        with tctx.client_span("serving.generate"):
            tctx.inject(req)
            return call(endpoint, req)

    r1 = gen("asc-smoke-1")
    assert r1.get("ok"), r1

    router = RpcRouter(endpoint)
    src = SyntheticSource(router)
    asc = Autoscaler(router=router, policy=AutoscalePolicy(
        slo_queue_wait_p99_s=0.05, min_replicas=1, max_replicas=2,
        breach_window_s=0.2, clear_window_s=0.2, cooldown_s=0.3,
        window_s=5.0, scale_spec=SPEC), source=src)

    src.p99 = 1.0                       # synthetic sustained breach
    t = 0.0
    while router.stats()["size"] < 2:
        assert t < 10.0, "breach never produced a scale-up"
        asc.step(now=t)
        t += 0.25
    while ready_count() < 2:
        assert time.monotonic() < deadline, "scale-up replica not ready"
        time.sleep(0.2)
    r2 = gen("asc-smoke-2")
    assert r2.get("ok"), r2

    src.p99 = 0.0                       # clear: drain back down
    while router.stats()["size"] > 1:
        assert t < 20.0, "clear never produced a scale-down"
        asc.step(now=t)
        t += 0.25
    down = [x for x in asc.decisions if x["action"] == "scale_down"]
    assert down and down[0].get("drained") is True, asc.decisions
    assert ready_count() == 1
    r3 = gen("asc-smoke-3")
    assert r3.get("ok"), r3
    assert r3["tokens"] == r1["tokens"], (r1, r3)   # greedy: same stream
finally:
    proc.terminate()
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
spool.shutdown()
print("autoscaler smoke ok")
"""


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--shard", type=int, default=0)
    ap.add_argument("--timeout", type=int, default=2400,
                    help="whole-shard timeout in seconds")
    ap.add_argument("--only", nargs="*", default=None,
                    help="test module names (without .py) to run instead")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the ruff + proglint static gates")
    ap.add_argument("--ci", action="store_true",
                    help="CI mode: a missing ruff binary fails the lint "
                         "gate instead of being skipped with a notice")
    args = ap.parse_args(argv)
    if not (0 <= args.shard < args.shards):
        ap.error(f"--shard must be in [0, {args.shards}) — got "
                 f"{args.shard} (shards are 0-based)")

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not args.no_lint and args.shard == 0:
        rc = run_lint_gate(root, args.timeout, ci=args.ci)
        if rc:
            sys.exit(f"test_runner: lint gate failed (rc={rc})")
    tests_dir = os.path.join(root, "tests")
    if args.only:
        files = [os.path.join(tests_dir, f"{m}.py") for m in args.only]
        missing = [f for f in files if not os.path.exists(f)]
        if missing:
            sys.exit(f"test_runner: no such test files: {missing}")
    else:
        files = shard_files(glob.glob(os.path.join(tests_dir, "test_*.py")),
                            args.shards, args.shard)
    if not files:
        print("test_runner: empty shard, nothing to do")
        return 0
    rel = [os.path.relpath(f, root) for f in files]
    print(f"test_runner: shard {args.shard}/{args.shards} -> "
          f"{len(rel)} files")
    cmd = [sys.executable, "-m", "pytest", "-q", *rel]
    try:
        r = subprocess.run(cmd, cwd=root, timeout=args.timeout)
    except subprocess.TimeoutExpired:
        sys.exit(f"test_runner: shard exceeded {args.timeout}s "
                 f"(hung test among: {rel})")
    return r.returncode


if __name__ == "__main__":
    sys.exit(main())
