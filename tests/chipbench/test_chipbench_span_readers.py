"""The readers of the program's own spans and compile counters
(``span_seconds``, ``program_counter_total``, ``fetch_lag_ms``): their
arithmetic on hand-made observations and on the recorded v5e trace of
``data/small_trace.json``, None where there is nothing to read (the
parent, which records no such span and has no such family), and a tiny
CPU serve cell whose real spans every new metric's file can read.
Nothing measured here is a device number."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import fetch_lag_ms  # noqa: E402
from chipbench.layer_metrics import program_counter_total  # noqa: E402
from chipbench.layer_metrics import span_seconds  # noqa: E402

NEW = ("sched_host_ms_per_step", "sched_idle_wait_pct",
       "admit_host_ms_per_prefill", "exe_host_ms_per_dispatch",
       "setup_trace_s", "setup_lower_s", "setup_xla_s",
       "fetch_lag_ms.decode")
MS = 1e6        # nanoseconds


def _obs(host_spans, t0=0.0, t1=1000 * MS, units=None, modules=None):
    return {"units": units or {},
            "reduced": {"t0_ns": t0, "t1_ns": t1,
                        "window_s": (t1 - t0) / 1e9,
                        "host_spans": host_spans,
                        "modules": modules or {}}}


SPANS = [
    # two decode steps: feeds 1 ms, args 2 ms, dispatch 3 ms, then the
    # fetch (device busy: not host work), commits 0.5 + 1.5 ms
    ("serving.decode.feeds", 10 * MS, 11 * MS),
    ("serving.decode.args", 11 * MS, 13 * MS),
    ("serving.decode.dispatch", 13 * MS, 16 * MS),
    ("serving.decode.fetch", 16 * MS, 400 * MS),
    ("serving.decode.commit", 400 * MS, 400.5 * MS),
    ("serving.sched.commit", 400.5 * MS, 402 * MS),
    ("serving.decode_step", 10 * MS, 400.5 * MS),
    ("serving.decode.feeds", 410 * MS, 411 * MS),
    ("serving.decode.args", 411 * MS, 413 * MS),
    ("serving.decode.dispatch", 413 * MS, 416 * MS),
    ("serving.decode.fetch", 416 * MS, 800 * MS),
    ("serving.decode.commit", 800 * MS, 800.5 * MS),
    ("serving.sched.commit", 800.5 * MS, 802 * MS),
    # a wait that started before the window and one that outlasts it
    ("serving.sched.idle", -30 * MS, 5 * MS),
    ("serving.sched.idle", 980 * MS, 1030 * MS),
    ("executor.prepare", 900 * MS, 902 * MS),
    ("executor.dispatch", 902 * MS, 908 * MS),
    ("executor.run", 901 * MS, 909 * MS),
    ("executor.run", 950 * MS, 960 * MS),
]
HOST_WORK = ["serving.decode.feeds", "serving.decode.args",
             "serving.decode.dispatch", "serving.decode.commit",
             "serving.sched.commit"]


def test_span_seconds_per_unit_adds_the_named_spans():
    obs = _obs(SPANS, units={"decode_steps": 2, "prefills": 0})
    # (1 + 2 + 3 + 0.5 + 1.5) ms a step; the fetch and the per-slot
    # serving.decode_step span are not host work and are not named
    assert span_seconds.read(obs, HOST_WORK, "unit:decode_steps") \
        == pytest.approx(8.0)
    assert span_seconds.read(obs, ["serving.decode.fetch"],
                             "unit:decode_steps") == pytest.approx(384.0)


def test_span_seconds_window_share_cuts_spans_to_the_window():
    obs = _obs(SPANS)
    # 5 ms of the first wait and 20 ms of the last lie inside 1000 ms
    assert span_seconds.read(obs, ["serving.sched.idle"], "window") \
        == pytest.approx(2.5)


def test_span_seconds_per_span_of_a_named_prefix():
    obs = _obs(SPANS)
    # 2 + 6 ms over the two executor.run spans of the window
    assert span_seconds.read(obs, ["executor.prepare", "executor.dispatch"],
                             "spans:executor.run") == pytest.approx(4.0)


@pytest.mark.parametrize("prefixes, per, units", [
    (["serving.nothing"], "window", {}),                 # no such span
    (HOST_WORK, "unit:decode_steps", {"decode_steps": 0}),   # no unit
    (HOST_WORK, "unit:missing", {}),
    (["executor.prepare"], "spans:chipbench.nothing", {}),
])
def test_span_seconds_reads_none_when_there_is_nothing(prefixes, per,
                                                       units):
    assert span_seconds.read(_obs(SPANS, units=units), prefixes, per) is None


def test_span_seconds_refuses_an_unknown_per():
    with pytest.raises(ValueError):
        span_seconds.read(_obs(SPANS), HOST_WORK, "second")


def test_program_counter_total_sums_a_stage_without_other():
    from paddle_tpu.observability import metrics
    fam = metrics.counter("chipbench_test_compile_seconds_total", "test",
                          labelnames=("stage", "program"))
    fam.labels("trace", "lm.decode_paged").inc(1.5)
    fam.labels("trace", "lm.prefill_paged@128").inc(0.25)
    fam.labels("trace", "other").inc(100.0)
    fam.labels("lower", "lm.decode_paged").inc(4.0)
    read = program_counter_total.read
    assert read({}, fam.name, "trace") == pytest.approx(1.75)
    assert read({}, fam.name, "lower") == pytest.approx(4.0)
    # a family that exists reads 0.0 for a stage that saw no event ...
    assert read({}, fam.name, "backend_compile") == 0.0
    # ... and None only where the program has no such family
    assert read({}, "chipbench_test_no_such_family", "trace") is None


def test_program_counter_total_reads_the_programs_compile_families():
    import numpy as np
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 2))
    main.desc._obs_name = "chipbench_test.reader"
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    read = program_counter_total.read
    exe.run(startup, scope=scope)
    before = {s: read({}, "paddle_compile_seconds_total", s)
              for s in ("trace", "lower", "backend_compile")}
    exe.run(main, feed={"x": np.ones((2, 3), np.float32)},
            fetch_list=[loss.name], scope=scope)
    for stage, was in before.items():
        assert read({}, "paddle_compile_seconds_total", stage) > was


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


def test_fetch_lag_reads_a_known_clock_offset(trace):
    """Three program executions recorded on a v5e (0.48 ms each); a
    fetch that starts 3 ms before each and ends 0.1 ms after it, seen
    through a host clock 1.5 ms ahead of the device's: the reader gives
    1.6 ms."""
    marks = tr.host_marks(trace, ("chipbench.window_open",
                                  "chipbench.window_close"))
    t0, t1 = marks["chipbench.window_open"], marks["chipbench.window_close"]
    modules = tr.device_ops(trace, tr.MODULES_LINE)
    (runs,) = modules.values()
    assert len(runs) == 3
    offset, true_lag = 1.5 * MS, 0.1 * MS
    spans = []
    for _name, start, dur in runs:
        spans.append(("serving.decode.fetch", start - 3 * MS + offset,
                      start + dur + true_lag + offset))
        spans.append(("serving.decode.dispatch", start - 4 * MS + offset,
                      start - 3 * MS + offset))
    red = tr.reduce_window(trace, t0, t1, spans)
    got = fetch_lag_ms.read({"reduced": red}, "serving.decode.fetch")
    assert got == pytest.approx(1.6, abs=1e-6)
    # a host clock 0.3 ms BEHIND the device's reads negative (the sign
    # is kept); an offset longer than the execution itself finds nothing
    behind = [(n, a - offset - 0.3 * MS, b - offset - 0.3 * MS)
              for n, a, b in spans]
    red = tr.reduce_window(trace, t0, t1, behind)
    assert fetch_lag_ms.read({"reduced": red}, "serving.decode.fetch") \
        == pytest.approx(-0.2, abs=1e-6)
    far = [(n, a - 3 * offset, b - 3 * offset) for n, a, b in spans]
    red = tr.reduce_window(trace, t0, t1, far)
    assert fetch_lag_ms.read({"reduced": red},
                             "serving.decode.fetch") is None


def test_fetch_lag_takes_the_execution_it_overlaps_longest():
    modules = {"/device:TPU:0": [["prefill", 0.0, 40 * MS],
                                 ["decode", 50 * MS, 400 * MS],
                                 ["decode", 460 * MS, 400 * MS]]}
    spans = [("serving.decode.fetch", 30 * MS, 451 * MS),
             ("serving.decode.fetch", 455 * MS, 862 * MS)]
    obs = _obs(spans, modules=modules)
    # lags 1 ms and 2 ms: the median of two
    assert fetch_lag_ms.read(obs, "serving.decode.fetch") \
        == pytest.approx(1.5)


def test_fetch_lag_reads_none_without_spans_or_executions():
    modules = {"/device:TPU:0": [["decode", 50 * MS, 400 * MS]]}
    assert fetch_lag_ms.read(_obs([], modules=modules),
                             "serving.decode.fetch") is None
    assert fetch_lag_ms.read(
        _obs([("serving.decode.fetch", 60 * MS, 451 * MS)]),
        "serving.decode.fetch") is None
    # a fetch cut by the window's edge is not a whole fetch
    assert fetch_lag_ms.read(
        _obs([("serving.decode.fetch", -5 * MS, 451 * MS)],
             modules=modules), "serving.decode.fetch") is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_file_fits_its_reader_and_the_parent(name):
    """The arguments of ``<metric>.json`` are its reader's; on the
    observations of a program that records none of the new spans (the
    parent) a span reader gives None and does not raise."""
    import importlib
    spec = harness.load_json("layer_metrics", name + ".json")
    reader = importlib.import_module(
        "chipbench.layer_metrics." + spec["reader"])
    old_spans = [("serving.decode_step", 10 * MS, 400 * MS),
                 ("serving.prefill@128", 500 * MS, 550 * MS),
                 ("executor.run", 600 * MS, 700 * MS)]
    obs = _obs(old_spans, units={"decode_steps": 1, "prefills": 1},
               modules={"/device:TPU:0": [["decode", 12 * MS, 380 * MS]]})
    got = reader.read(obs, **spec["args"])
    if spec["reader"] == "program_counter_total":
        assert got is not None and got >= 0.0   # this tree has the family
    else:
        assert got is None


def test_a_tiny_serve_cell_feeds_every_span_reader():
    """The program's real spans, from a tiny closed-loop cell on the CPU
    with the tracer on, through each serving metric's file (the device's
    side of ``fetch_lag_ms`` is stood in by the fetch spans themselves:
    a lag of exactly 0)."""
    import chipbench_tiny as tiny
    from paddle_tpu.observability import tracing
    tracer = tracing.default_tracer()
    tracer.reset()
    tracer.start()
    try:
        run, obs = tiny.run_cell(tiny.serve_config(),
                                 tiny.serve_traffic("closed_decode"), 5)
    finally:
        tracer.stop()
    spans = [(s.name, s.start_s * 1e9, s.end_s * 1e9)
             for s in tracer.spans()]
    tracer.reset()
    assert obs["correct"] and obs["units"]["decode_steps"] > 0
    lo = min(a for _n, a, _b in spans)
    hi = max(b for _n, _a, b in spans)
    fetches = [["decode", a, b - a] for n, a, b in spans
               if n == "serving.decode.fetch"]
    obs["reduced"] = _obs(spans, lo, hi,
                          modules={"/device:TPU:0": fetches})["reduced"]
    # every span of the run, set-up included: units are the whole run's
    obs["units"] = {
        "decode_steps": sum(n == "serving.decode.fetch"
                            for n, _a, _b in spans),
        "prefills": sum(n == "serving.prefill.fetch"
                        for n, _a, _b in spans)}
    values = {}
    for name in NEW:
        spec = harness.load_json("layer_metrics", name + ".json")
        if "exe_host" in name:
            continue                     # the trainer's (test_..._train)
        import importlib
        reader = importlib.import_module(
            "chipbench.layer_metrics." + spec["reader"])
        values[name] = reader.read(obs, **spec["args"])
    assert all(v is not None for v in values.values()), values
    assert values["sched_host_ms_per_step"] > 0.0
    assert values["admit_host_ms_per_prefill"] > 0.0
    assert 0.0 < values["sched_idle_wait_pct"] < 100.0
    assert values["fetch_lag_ms.decode"] == pytest.approx(0.0, abs=1e-9)
    assert values["setup_trace_s"] > 0.0 and values["setup_xla_s"] > 0.0
