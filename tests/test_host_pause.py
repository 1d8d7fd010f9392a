"""The pause watch (``observability/pause_watch.py``), the count of
dispatches that found the device dry (``serving/engine.py:_launch``) and
the scheduler loop's counted back-off: the detection rule as a pure
function, counters and the span's bounds and arguments, one real thread
that loses the GIL to a single C call, the watch thread's life with
everything off / on / off again, and stubs for the device's answer.
No test waits on luck: each loop carries its own deadline."""

import threading
import time
import types

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import (exporters, flight_recorder,
                                      pause_watch, spool, tracing)
from paddle_tpu.serving import metrics as smetrics

TICK, SLACK = pause_watch.TICK_S, pause_watch.SLACK_S


def _watch_threads():
    return [t for t in threading.enumerate()
            if t.name == pause_watch.THREAD_NAME]


@pytest.fixture
def everything_off():
    """No listener of any kind, before and after: another test of this
    worker may have left one on."""
    def off():
        tracing.default_tracer().stop()
        spool.shutdown()
        flight_recorder.shutdown()
        exporters.shutdown()
        obs.disable()
    off()
    tracing.default_tracer().reset()
    yield
    off()
    tracing.default_tracer().reset()


def _counts():
    return {cause: (pause_watch.PAUSES.labels(cause=cause).value,
                    pause_watch.PAUSE_SECONDS.labels(cause=cause).value)
            for cause in ("stopped", "busy")}


# ------------------------------------------------------------ the rule

@pytest.mark.parametrize("lost, cpu, want", [
    (0.0, 0.0, None),                     # woke on time
    (0.004, 0.004, None),                 # a wait for the GIL
    (SLACK, 0.0, None),                   # the slack itself is no pause
    (SLACK + 1e-6, 0.0, "stopped"),
    (0.119, 0.0, "stopped"),              # PR 37's stall: no CPU at all
    (0.119, 0.011, "stopped"),            # the tick's own work before it
    (0.100, 0.0499, "stopped"),           # just under half
    (0.100, 0.050, "busy"),               # the cut: half the lost time
    (0.100, 0.100, "busy"),               # one thread ran all through it
    (0.100, 1.300, "busy"),               # thirteen cores did
    (0.530, 0.520, "busy"),               # a C call that held the GIL
])
def test_detect_names_the_cause_at_the_cut(lost, cpu, want):
    # from zero on both clocks: the cut is then exact in floating point
    got = pause_watch.detect(0.0, lost, 0.0, cpu)
    if want is None:
        assert got is None
    else:
        assert got == (want, pytest.approx(lost))


def test_a_sleep_that_ends_early_is_no_pause():
    assert pause_watch.detect(1000.0, 999.999, 5.0, 5.0) is None


# ------------------------------------------ counters, span and its args

def test_a_pause_is_counted_and_recorded_over_the_lost_time_alone(
        everything_off):
    tracer = tracing.default_tracer()
    watch = pause_watch.PauseWatch()            # never started: no thread
    before = _counts()
    tracer.start()
    try:
        assert watch.observe(10.0, 10.004, 1.0, 1.004) is None
        assert watch.observe(20.0, 20.119, 1.0, 1.002) == "stopped"
        assert watch.observe(30.0, 30.250, 2.0, 2.240) == "busy"
    finally:
        tracer.stop()
    after = _counts()
    assert after["stopped"][0] - before["stopped"][0] == 1
    assert after["busy"][0] - before["busy"][0] == 1
    assert after["stopped"][1] - before["stopped"][1] \
        == pytest.approx(0.119)
    assert after["busy"][1] - before["busy"][1] == pytest.approx(0.250)
    spans = [s for s in tracer.spans() if s.name == pause_watch.SPAN]
    assert [(s.start_s, s.end_s) for s in spans] == [
        (20.0, 20.119), (30.0, 30.250)]       # intended wake, actual wake
    stopped, busy = (s.args for s in spans)
    assert stopped["cause"] == "stopped" and busy["cause"] == "busy"
    assert stopped["lost_ms"] == pytest.approx(119.0)
    assert stopped["cpu_ms"] == pytest.approx(2.0)
    assert busy["lost_ms"] == pytest.approx(250.0)
    assert busy["cpu_ms"] == pytest.approx(240.0)
    for args in (stopped, busy):
        assert set(args) == {"cause", "lost_ms", "cpu_ms", "nivcsw_total",
                             "throttled_ms_total", "nivcsw_rise",
                             "throttled_ms_rise"}
        assert isinstance(args["nivcsw_total"], int)
        assert args["nivcsw_rise"] >= 0
        assert (args["throttled_ms_total"] is None) \
            == (args["throttled_ms_rise"] is None)
        assert args["throttled_ms_total"] is None \
            or args["throttled_ms_rise"] >= 0.0
    # cumulative, and each span carries the rise since the reading
    # before it (the watch's start, then the pause before)
    assert busy["nivcsw_total"] - stopped["nivcsw_total"] \
        == busy["nivcsw_rise"]


def test_with_no_span_capture_a_pause_is_counted_and_not_recorded(
        everything_off):
    watch = pause_watch.PauseWatch()
    before = _counts()
    assert watch.observe(20.0, 20.119, 1.0, 1.0) == "stopped"
    assert _counts()["stopped"][0] - before["stopped"][0] == 1
    assert not [s for s in tracing.default_tracer().spans()
                if s.name == pause_watch.SPAN]


def test_the_totals_are_cumulative_readings_or_none():
    totals = pause_watch.PauseWatch().totals()
    assert set(totals) == {"nivcsw_total", "throttled_ms_total"}
    assert totals["nivcsw_total"] >= 0
    source = pause_watch._throttle_source()
    if source is None:
        assert totals["throttled_ms_total"] is None
    else:
        assert source[1] in ("throttled_usec", "throttled_time")
        assert totals["throttled_ms_total"] >= 0.0


# --------------------------------------------------- one real thread

def test_a_c_call_that_holds_the_gil_is_found_as_a_busy_pause(
        everything_off):
    """``sum(range(n))`` is ONE call into C that never lets the GIL go:
    the watch's thread cannot run while the process does."""
    tracer = tracing.default_tracer()
    n = 2_000_000
    deadline = time.perf_counter() + 60.0
    tracer.start()
    try:
        time.sleep(3 * TICK)                    # the watch is in its loop
        found = []
        while not found and time.perf_counter() < deadline:
            t0 = time.perf_counter()
            sum(range(n))
            held = time.perf_counter() - t0
            if held < 0.3:                      # want > 100 ms, with room
                n *= 2
                continue
            time.sleep(5 * TICK)                # let it wake and record
            found = [s for s in tracer.spans()
                     if s.name == pause_watch.SPAN
                     and s.args["cause"] == "busy"
                     and s.start_s >= t0 - TICK
                     and s.args["lost_ms"] >= 100.0]
    finally:
        tracer.stop()
    assert found, "no busy pause found in 60 s of trying"
    span = found[0]
    assert span.end_s - span.start_s == pytest.approx(
        span.args["lost_ms"] / 1e3)
    assert span.end_s - span.start_s <= held + 0.5
    # the process ran through it: that is what busy means
    assert span.args["cpu_ms"] >= 0.5 * span.args["lost_ms"]
    # the watch records pauses and nothing else: a profile with none in
    # it holds no span of the watch's
    assert {s.name for s in tracer.spans()
            if s.name.startswith("host.")} == {pause_watch.SPAN}


# ------------------------------------------------- the thread's life

def test_no_watch_thread_with_everything_off(everything_off):
    assert not pause_watch.running() and not _watch_threads()


def test_one_watch_thread_between_start_and_stop_of_the_default_tracer(
        everything_off):
    tracer = tracing.default_tracer()
    tracer.start()
    try:
        assert len(_watch_threads()) == 1 and pause_watch.running()
        tracer.start()                          # a second start: still one
        assert len(_watch_threads()) == 1
        assert _watch_threads()[0].daemon
    finally:
        tracer.stop()
    assert not _watch_threads() and not pause_watch.running()
    tracer.stop()                               # a second stop: no error
    assert not _watch_threads()


def test_a_tracer_of_ones_own_starts_no_watch(everything_off):
    mine = tracing.Tracer()
    mine.start()
    mine.add_sink(print)
    assert not _watch_threads()
    mine.stop()
    mine.remove_sink(print)
    assert not _watch_threads()


def test_the_watch_lives_from_the_first_sink_to_the_last(everything_off):
    a, b = (lambda span: None), (lambda span: None)
    tracing.add_sink(a)
    try:
        assert len(_watch_threads()) == 1
        tracing.add_sink(b)
        assert len(_watch_threads()) == 1
        tracing.remove_sink(a)
        assert len(_watch_threads()) == 1       # b still listens
    finally:
        tracing.remove_sink(a)
        tracing.remove_sink(b)
    assert not _watch_threads()


def test_step_telemetry_holds_the_watch(everything_off):
    obs.enable()
    try:
        assert len(_watch_threads()) == 1
    finally:
        obs.disable()
    assert not _watch_threads()


def test_two_listeners_share_one_watch(everything_off):
    tracer = tracing.default_tracer()
    obs.enable()
    tracer.start()
    assert len(_watch_threads()) == 1
    obs.disable()
    assert len(_watch_threads()) == 1           # the tracer still listens
    tracer.stop()
    assert not _watch_threads()


def test_the_scrape_flag_holds_the_watch(everything_off, monkeypatch):
    monkeypatch.setenv("FLAGS_metrics_port", "0")
    try:
        assert exporters.ensure_started()
        assert len(_watch_threads()) == 1
    finally:
        exporters.shutdown()
    assert not _watch_threads()


# ------------------------------------------- dispatches that found it dry

class _Out:
    """What a dispatch returns first, as far as ``_launch`` asks it."""

    def __init__(self, ready):
        self.ready = ready
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


class _Block:
    """A compiled block's face toward ``_launch``: no state, no consts,
    and a call that returns the output it was told to."""
    obs_label = "stub.block"
    sig = types.SimpleNamespace(state_names=(), const_names=())
    _exes = types.SimpleNamespace(note=lambda *a: None)

    def __init__(self):
        self.next_out = None

    def fn(self, state, consts, feeds, seed):
        return [self.next_out], {}


def _engine(name):
    from paddle_tpu.serving import engine
    return engine.GenerativeModel(name, {}, init=False)


def _starved(name):
    return {view: smetrics.DISPATCH_STARVED.labels(
        model=name, view=view).value for view in ("decode", "prefill")}


def test_launch_counts_a_dispatch_whose_predecessor_is_ready(
        everything_off):
    eng, cb = _engine("pr53_dry"), _Block()
    outs = [_Out(True), _Out(False), _Out(True), _Out(True)]
    keys = [("decode_paged",), ("decode_paged",), ("prefill_paged", 128),
            ("decode_paged",)]
    seen = []
    for out, key in zip(outs, keys):
        cb.next_out = out
        got, kind = eng._launch(cb, key, {})
        assert got is out
        assert kind == "serving." + ("prefill" if "prefill" in key[0]
                                     else "decode")
        seen.append(_starved("pr53_dry"))
    # 1st: nothing before it. 2nd: its predecessor was ready -> dry.
    # 3rd (a prefill): its predecessor still runs. 4th: ready again.
    assert seen == [{"decode": 0, "prefill": 0},
                    {"decode": 1, "prefill": 0},
                    {"decode": 1, "prefill": 0},
                    {"decode": 2, "prefill": 0}]
    # one question a dispatch, of the PREVIOUS output alone
    assert [o.asked for o in outs] == [1, 1, 1, 0]
    cb.next_out = _Out(False)
    eng._launch(cb, ("prefill_paged", 128), {})
    assert _starved("pr53_dry") == {"decode": 2, "prefill": 1}


def test_launch_asks_nothing_under_a_mesh_or_of_a_plain_array(
        everything_off):
    import numpy as np
    eng, cb = _engine("pr53_mesh"), _Block()
    first = cb.next_out = _Out(True)
    eng._launch(cb, ("decode_paged",), {})
    eng.dist = object()                          # a mesh: never asked
    cb.next_out = np.zeros((2, 1), np.int32)     # has no is_ready
    eng._launch(cb, ("decode_paged",), {})
    assert first.asked == 0
    eng.dist = None
    cb.next_out = _Out(True)
    eng._launch(cb, ("decode_paged",), {})       # previous: the ndarray
    assert _starved("pr53_mesh") == {"decode": 0, "prefill": 0}


def test_a_deleted_array_is_not_asked_whether_it_is_ready(everything_off):
    eng, cb = _engine("pr53_deleted"), _Block()
    gone = cb.next_out = _Out(True)
    gone.is_deleted = lambda: True
    eng._launch(cb, ("decode_paged",), {})
    cb.next_out = _Out(False)
    eng._launch(cb, ("decode_paged",), {})
    assert gone.asked == 0
    assert _starved("pr53_deleted") == {"decode": 0, "prefill": 0}


def test_the_marker_is_recorded_only_while_tracing(everything_off):
    tracer = tracing.default_tracer()
    eng, cb = _engine("pr53_marker"), _Block()

    def dry_pair(key):
        cb.next_out = _Out(True)
        eng._launch(cb, key, {})
        cb.next_out = _Out(True)
        eng._launch(cb, key, {})

    eng._prev_output = None
    dry_pair(("decode_paged",))                  # counted, not recorded
    assert not [s for s in tracer.spans()
                if s.name.startswith("serving.starved")]
    tracer.start()
    try:
        eng._prev_output = None
        dry_pair(("decode_paged",))
        eng._prev_output = None
        dry_pair(("prefill_paged", 64))
    finally:
        tracer.stop()
    marks = [s for s in tracer.spans()
             if s.name.startswith("serving.starved")]
    assert [s.name for s in marks] == ["serving.starved.decode",
                                       "serving.starved.prefill"]
    assert all(s.end_s == s.start_s for s in marks)       # zero-length
    assert all(s.args == {"model": "pr53_marker"} for s in marks)
    # each lies at the start of the dispatch it belongs to
    args = [s for s in tracer.spans() if s.name.endswith(".args")]
    assert marks[0].start_s == args[1].start_s
    assert _starved("pr53_marker") == {"decode": 2, "prefill": 1}


# ------------------------------------ the scheduler's counted back-off

def test_a_swallowed_scheduler_error_is_counted_and_named(everything_off):
    from paddle_tpu.serving import server
    child = smetrics.SCHEDULER_ERRORS.labels(model="pr53_loop")
    turns = []

    def reap():
        turns.append(time.perf_counter())
        if len(turns) == 1:
            raise KeyError("a map lost its slot")
        me.running = False                       # second turn: leave
        raise ValueError("and once more, on the way out")

    me = types.SimpleNamespace(
        engine=None, running=True, name="pr53_loop",
        _reap_cancelled=reap, _m_sched_errors=child)
    tracer = tracing.default_tracer()
    tracer.start()
    try:
        t0 = time.perf_counter()
        server._SlotHostedModel._loop(me)
        took = time.perf_counter() - t0
    finally:
        tracer.stop()
    assert child.value == 2 and len(turns) == 2
    assert took >= 0.1                           # behaviour unchanged: 2 x 50 ms
    errs = [s for s in tracer.spans() if s.name == "serving.sched.error"]
    assert [s.args["error"] for s in errs] == ["KeyError", "ValueError"]
    assert all(s.args["model"] == "pr53_loop" for s in errs)
    assert all(s.end_s - s.start_s >= 0.05 for s in errs)
    # and with tracing off: counted, nothing recorded
    tracer.reset()
    me.running, turns[:] = True, []
    server._SlotHostedModel._loop(me)
    assert child.value == 4
    assert not tracer.spans()
