"""The host's time named from inside the program (ISSUE 24): the spans
of the scheduler loop, the engine's admit / step / _run and the
executor's dispatch; the off path (no span recorded, no clock read that
the parent did not make); and the compile stages as program counters
fed by the one ``jax.monitoring`` listener of ``observability.runtime``.
CPU, tiny models; nothing here is a device number."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.models import transformer as T
from paddle_tpu.observability import runtime as obs_runtime
from paddle_tpu.observability import tracing
from paddle_tpu.serving import engine as seng

_LM_CFG = dict(prompt_len=8, max_new=8, vocab=32, d_model=16,
               d_inner=32, n_head=2, n_layer=2)
_CACHE = {}

# these prefixes select program executions for the benchmark's device
# metrics (chipbench/layer_metrics/decode_step_device_ms.json,
# prefill_device_ms.json): no new span may start with them
RESERVED = ("serving.decode_step", "serving.prefill@")
ENGINE_SPANS = (
    "serving.admit.reserve", "serving.prefill.feeds",
    "serving.prefill.args", "serving.prefill.dispatch",
    "serving.prefill.fetch", "serving.decode.feeds",
    "serving.decode.args", "serving.decode.dispatch",
    "serving.decode.fetch", "serving.decode.commit")
LOOP_SPANS = ("serving.sched.idle", "serving.sched.commit")


def _engine(spec=False):
    key = "spec" if spec else "paged"
    m = _CACHE.get(key)
    if m is None:
        kw = dict(spec_k=2) if spec else {}
        m = seng.make_slot_model(
            "lm_spans_" + key,
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=T.slot_modes("paged", spec=spec), n_slots=4,
                page_size=4, **kw))
        m.warmup()
        _CACHE[key] = m
    m.reset()
    return m


@pytest.fixture
def tracer():
    tr = tracing.default_tracer()
    tr.reset()
    tr.start()
    try:
        yield tr
    finally:
        tr.stop()
        tr.reset()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def _inside(inner, outer, slack=1e-4):
    return (inner.start_s >= outer[0] - slack
            and inner.end_s <= outer[1] + slack)


# ------------------------------------------------------------- spans, on

def test_admit_and_three_steps_record_every_engine_span(tracer):
    m = _engine()
    tracer.reset()                       # drop the warm-up's spans
    t_admit0 = time.perf_counter()
    slot, _first, done = m.admit(np.array([1, 2, 3]), max_new=6)
    t_admit1 = time.perf_counter()
    assert done is None
    step_windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        assert m.step()
        step_windows.append((t0, time.perf_counter()))
    spans = _by_name(tracer.spans())
    for name in ENGINE_SPANS:
        assert name in spans, (name, sorted(spans))
        assert not name.startswith(RESERVED)
    # the admission: reserve before the prefill span, the rest inside it
    (prefill,) = spans["serving.prefill@4"]
    (reserve,) = spans["serving.admit.reserve"]
    assert _inside(reserve, (t_admit0, t_admit1))
    assert reserve.end_s <= prefill.start_s + 1e-4
    for part in ("feeds", "args", "dispatch", "fetch"):
        (s,) = spans["serving.prefill." + part]
        assert _inside(s, (prefill.start_s, prefill.end_s)), part
        # parented under the prefill span (one trace per admission)
        assert s.trace_id == prefill.trace_id
        assert s.parent_id == prefill.span_id
    # each step: feeds, args, dispatch, fetch, commit, in that order,
    # inside the step's own interval
    order = ("feeds", "args", "dispatch", "fetch", "commit")
    for i, win in enumerate(step_windows):
        parts = [spans["serving.decode." + p][i] for p in order]
        assert all(_inside(s, win) for s in parts)
        for a, b in zip(parts, parts[1:]):
            assert a.end_s <= b.start_s + 1e-6
    assert all(len(spans["serving.decode." + p]) == 3 for p in order)


def test_verify_step_records_the_decode_spans(tracer):
    m = _engine(spec=True)
    tracer.reset()
    m.admit(np.array([5, 6, 5, 6, 5]), max_new=6)
    assert m.step()
    spans = _by_name(tracer.spans())
    for part in ("feeds", "args", "dispatch", "fetch", "commit"):
        assert len(spans["serving.decode." + part]) == 1, part


def test_scheduler_loop_records_idle_and_commit(tracer):
    m = _engine()
    tracer.reset()
    srv = serving.ModelServer()
    try:
        srv.add_model(m)
        time.sleep(0.12)                 # at least two whole waits
        out = srv.generate(m.name, [np.array([1, 2, 3])], max_new=4,
                           timeout=60)
        assert len(out[0]) == 4
    finally:
        srv.stop()
    spans = _by_name(tracer.spans())
    for name in LOOP_SPANS:
        assert name in spans and not name.startswith(RESERVED)
    idle = spans["serving.sched.idle"]
    assert len(idle) >= 2
    assert all(0.0 < s.duration_s < 0.2 for s in idle)   # one per wait
    # three decode steps after the first token: one commit each, after
    # the engine's own commit of that step, inside the step's interval
    commits = spans["serving.sched.commit"]
    steps = spans["serving.decode_step"]
    assert len(commits) == 3 and len(steps) == 3
    for c, e, st in zip(commits, spans["serving.decode.commit"], steps):
        assert e.end_s <= c.start_s + 1e-6
        assert st.start_s <= e.start_s and c.start_s == st.end_s
    # no wait overlaps a step
    for s in idle:
        assert all(s.end_s <= st.start_s or s.start_s >= st.end_s
                   for st in steps)


# ------------------------------------------------------------ spans, off

class _CountingClock:
    """Stands in for a module's ``time``: counts ``perf_counter``."""

    def __init__(self):
        self.calls = 0

    def perf_counter(self):
        self.calls += 1
        return time.perf_counter()

    def __getattr__(self, name):
        return getattr(time, name)


def _forbid_record(monkeypatch):
    def boom(self, *a, **kw):
        raise AssertionError("Tracer.record called with tracing off")
    monkeypatch.setattr(tracing.Tracer, "record", boom)


def test_tracing_off_engine_records_nothing_and_reads_no_clock(
        monkeypatch):
    """``admit``, ``step`` and ``_run`` read the clock only for spans:
    off, they read it 0 times (as the parent's code: it had no clock in
    the engine at all) and never reach ``Tracer.record``."""
    assert not tracing.active()
    m = _engine()
    _forbid_record(monkeypatch)
    clock = _CountingClock()
    monkeypatch.setattr(seng, "time", clock)
    m.admit(np.array([1, 2, 3]), max_new=5)
    for _ in range(3):
        assert m.step()
    assert clock.calls == 0


def test_tracing_off_loop_reads_the_clock_once_per_step(monkeypatch):
    """The scheduler loop's one ``perf_counter`` per pool step (``now``,
    for the gap between tokens) is the parent's; the new spans add none.
    Two requests that differ by three steps differ by three reads."""
    from paddle_tpu.serving import server as sserver
    assert not tracing.active()
    m = _engine()
    _forbid_record(monkeypatch)
    srv = serving.ModelServer()
    try:
        srv.add_model(m)
        clock = _CountingClock()
        monkeypatch.setattr(sserver, "time", clock)
        reads = []
        for max_new in (3, 6):          # 2 and 5 decode steps
            before = clock.calls
            out = srv.generate(m.name, [np.array([1, 2, 3])],
                               max_new=max_new, timeout=60)
            assert len(out[0]) == max_new
            time.sleep(0.12)            # idle waits: they read nothing
            reads.append(clock.calls - before)
    finally:
        srv.stop()
    assert reads[1] - reads[0] == 3, reads


def _fc_program(label):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 2, act="relu"))
    main.desc._obs_name = label
    return main, startup, loss


def test_tracing_off_executor_reads_the_clock_once_per_run(monkeypatch):
    """``Executor.run`` keeps the parent's single ``perf_counter``
    (``t_dispatch``, for the step telemetry); ``executor.prepare`` and
    ``executor.dispatch`` add none, in the executor or in the block."""
    from paddle_tpu.core import executor as cexe
    from paddle_tpu.core import lowering as clow
    assert not tracing.active()
    main, startup, loss = _fc_program("spans_test.off")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    _forbid_record(monkeypatch)
    c_exe, c_low = _CountingClock(), _CountingClock()
    monkeypatch.setattr(cexe, "time", c_exe)
    monkeypatch.setattr(clow, "time", c_low)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert c_exe.calls == 3 and c_low.calls == 0


# ---------------------------------------------------- executor spans, on

def test_executor_prepare_and_dispatch_spans(tracer):
    main, startup, loss = _fc_program("spans_test.exe")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    tracer.reset()
    t0 = time.perf_counter()
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[loss.name], scope=scope)
    spans = _by_name(tracer.spans())
    (run,) = spans["executor.run"]
    (prep,) = spans["executor.prepare"]
    (disp,) = spans["executor.dispatch"]
    # prepare starts at the entry of run(), before the executor.run span
    # opens (the feeds are converted and placed first), and ends where
    # dispatch starts; dispatch ends inside executor.run
    assert t0 <= prep.start_s <= run.start_s
    assert prep.end_s == disp.start_s
    assert run.start_s <= disp.start_s and disp.end_s <= run.end_s


# -------------------------------------------------------- compile stages

def _stage_values(program):
    return {stage: (obs_runtime.COMPILE_SECONDS.labels(stage, program)
                    .value,
                    obs_runtime.COMPILE_EVENTS.labels(stage, program)
                    .value)
            for stage in ("trace", "lower", "backend_compile")}


def test_first_dispatch_counts_under_the_blocks_label():
    label = "spans_test.first_dispatch"
    main, startup, loss = _fc_program(label)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    before = _stage_values(label)
    assert all(v == (0.0, 0.0) for v in before.values())
    t0 = time.perf_counter()
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    wall = time.perf_counter() - t0
    after = _stage_values(label)
    for stage, (seconds, events) in after.items():
        assert events >= 1 and 0.0 < seconds <= wall, (stage, after)
    # a second dispatch of the same signature compiles nothing
    exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert _stage_values(label) == after


def test_a_jit_outside_any_dispatch_counts_as_other():
    obs_runtime.install_compile_listener()
    other = obs_runtime.OTHER_PROGRAM
    before = _stage_values(other)
    n0 = obs_runtime.backend_compile_count()
    jax.jit(lambda v: v * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    after = _stage_values(other)
    for stage in after:
        assert after[stage][1] >= before[stage][1] + 1, stage
        assert after[stage][0] > before[stage][0], stage
    assert obs_runtime.backend_compile_count() >= n0 + 1
    # and the label of a dispatch does not outlive it
    with obs_runtime.dispatching("spans_test.scoped"):
        with obs_runtime.dispatching("spans_test.inner"):
            pass
        jax.jit(lambda v: v - 2.0)(jnp.ones(5)).block_until_ready()
    assert obs_runtime.COMPILE_EVENTS.labels(
        "trace", "spans_test.scoped").value >= 1
    assert obs_runtime.COMPILE_EVENTS.labels(
        "trace", "spans_test.inner").value == 0
    mid = _stage_values(other)
    jax.jit(lambda v: v / 5.0)(jnp.ones(3)).block_until_ready()
    assert _stage_values(other)["trace"][1] >= mid["trace"][1] + 1


def test_a_jit_that_calls_a_jit_counts_its_trace_time_once():
    """jax fires the inner jit's trace event before the outer's and
    inside its duration; summed as they come, 0.15 s of inner tracing
    would count twice. Counted once, the stage stays under the wall."""
    obs_runtime.install_compile_listener()

    @jax.jit
    def inner(v):
        time.sleep(0.05)                 # the Python of a lowering rule
        return jnp.sin(v)

    @jax.jit
    def outer(v):
        return inner(v).sum() + inner(v[:3]).sum() + inner(v[:2]).sum()

    label = "spans_test.nested"
    t0 = time.perf_counter()
    with obs_runtime.dispatching(label):
        outer(jnp.ones(4)).block_until_ready()
    wall = time.perf_counter() - t0
    traced = obs_runtime.COMPILE_SECONDS.labels("trace", label).value
    assert 0.15 <= traced <= wall, (traced, wall)
    assert obs_runtime.COMPILE_EVENTS.labels("trace", label).value >= 4


@pytest.mark.parametrize("intervals, want", [
    # two siblings, then the caller that contains both
    ([(1.0, 2.0), (3.0, 4.0), (0.0, 5.0)], [1.0, 1.0, 3.0]),
    # disjoint top-level events keep their whole length
    ([(0.0, 1.0), (2.0, 3.5)], [1.0, 1.5]),
    # a caller that starts inside an earlier event counts the rest only
    ([(0.0, 2.0), (1.0, 3.0)], [2.0, 1.0]),
    # clock jitter: the inner event "starts" a hair before its caller
    ([(0.999, 2.0), (1.0, 2.5)], [1.001, 0.5]),
])
def test_own_seconds_counts_wall_time_once(intervals, want):
    import threading
    got = []

    def run():          # a fresh thread: its own interval lists
        for a, b in intervals:
            got.append(obs_runtime._own_seconds("trace", a, b))
    t = threading.Thread(target=run)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert got == pytest.approx(want)


def test_own_seconds_absorbs_thousands_of_nested_events(monkeypatch):
    """One traced program fires ~10 000 nested trace events inside its
    caller's (the trainer's scan: 9 433 on the chip). Older work on the
    thread must not leak into the caller, the caller is reduced by all
    of its callees, and past the memory bound the older half collapses
    without losing its seconds."""
    import threading
    n = 10_000
    got = []

    def run(bound):
        monkeypatch.setattr(obs_runtime, "_MAX_INTERVALS", bound)
        got.clear()
        t0 = 100.0
        for i in range(50):              # earlier top-level compiles
            got.append(obs_runtime._own_seconds("lower", i, i + 0.5))
        for i in range(n):               # 1 ms each, 1 ms apart
            got.append(obs_runtime._own_seconds(
                "lower", t0 + 0.002 * i, t0 + 0.002 * i + 0.001))
        got.append(obs_runtime._own_seconds("lower", t0 - 0.5,
                                            t0 + 0.002 * n))

    for bound in (1 << 17, 64):
        # a fresh thread each time: its own interval lists
        t = threading.Thread(target=run, args=(bound,))
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
        assert sum(got[:50]) == pytest.approx(25.0)
        assert sum(got[50:-1]) == pytest.approx(0.001 * n)
        if bound > n:
            # the caller's event counts its wall time once
            assert sum(got[50:]) == pytest.approx(0.002 * n + 0.5)
        else:
            # past the bound it may be miscounted, never negative, and
            # never above its own duration
            assert 0.0 <= got[-1] <= 0.002 * n + 0.5


def test_recompile_inside_a_traced_run_shows_inside_its_executor_run(
        tracer):
    label = "spans_test.recompile"
    main, startup, loss = _fc_program(label)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[loss.name], scope=scope)
    tracer.reset()
    # a second signature (batch 3) of the same block: jit compiles again
    exe.run(main, feed={"x": np.ones((3, 4), np.float32)},
            fetch_list=[loss.name], scope=scope)
    spans = _by_name(tracer.spans())
    (run,) = spans["executor.run"]
    for stage in ("trace", "lower", "backend_compile"):
        mine = [s for s in spans.get("compile." + stage, [])
                if s.args["program"] == label]
        assert mine, (stage, sorted(spans))
        assert all(_inside(s, (run.start_s, run.end_s)) for s in mine)
    # and inside executor.dispatch, which holds the jitted call
    (disp,) = spans["executor.dispatch"]
    assert all(_inside(s, (disp.start_s, disp.end_s))
               for s in spans["compile.backend_compile"])


def test_embed_cache_compile_count_reads_the_one_listener():
    from paddle_tpu.ops import embed_cache as ec
    n0 = ec.compile_count()
    assert n0 == obs_runtime.backend_compile_count()
    jax.jit(lambda v: v * 7.0 - 1.0)(jnp.ones(9)).block_until_ready()
    assert ec.compile_count() >= n0 + 1
    assert ec.compile_count() == obs_runtime.backend_compile_count()


def test_compile_families_are_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics
    exporters._preregister_catalog()
    text = metrics.default_registry().render_prometheus()
    for fam in ("paddle_compile_seconds_total",
                "paddle_compile_events_total"):
        assert metrics.default_registry().get(fam) is not None
        assert f"# TYPE {fam} counter" in text


def test_only_one_compile_listener_in_the_program():
    import os
    import re
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu")
    hits = []
    for d, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"register_event_duration_secs_listener",
                                 fh.read()):
                        hits.append(os.path.relpath(os.path.join(d, f),
                                                    root))
    assert hits == [os.path.join("observability", "runtime.py")], hits


# ------------------------------------------------- the folded trace tool

def test_trace_collect_merges_profiler_csvs(tmp_path):
    from paddle_tpu.fluid import profiler
    from tools import trace_collect
    files = []
    for rank in range(2):
        profiler.reset_profiler()
        profiler.start_profiler()
        with profiler.record_event(f"rank{rank}/step"):
            pass
        path = str(tmp_path / f"r{rank}.csv")
        profiler.export_spans(path)
        profiler.stop_profiler(profile_path=None)
        files.append(path)
    out = str(tmp_path / "merged.json")
    arg = ",".join(f"trainer{r}={p}" for r, p in enumerate(files))
    assert trace_collect.main(["--profile_path", arg, "-o", out]) == 0
    import json
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    lanes = {e["pid"]: e["name"] for e in events if e["ph"] == "X"}
    assert lanes == {0: "rank0/step", 1: "rank1/step"}
    assert {e["args"]["name"] for e in events if e["ph"] == "M"} == {
        "trainer0", "trainer1"}
    with pytest.raises(ValueError):
        trace_collect.parse_profile_paths("a=,b=c")
    assert trace_collect.parse_profile_paths("x.csv") == [(None, "x.csv")]
