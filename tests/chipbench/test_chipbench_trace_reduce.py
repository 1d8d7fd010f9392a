"""The trace reduction on a small trace recorded on a v5e chip (three
dispatches of one jitted step — a Pallas row gather, a matmul fusion —
between the window marks; data/small_trace.json is ``load_xplane`` of
the profiler's file) and on hand-made intervals."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import trace_reduce as tr  # noqa: E402

MARKS = ("chipbench.window_open", "chipbench.window_close")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(trace):
    m = tr.host_marks(trace, MARKS)
    steps = [(n, s, s + d) for p in trace["planes"] if p["name"] == "/host:CPU"
             for ln in p["lines"] for n, s, d in ln["events"]
             if n == "chipbench.step"]
    return tr.reduce_window(trace, m[MARKS[0]], m[MARKS[1]], steps)


def test_recorded_trace_has_one_device_and_three_steps(trace):
    ops = tr.device_ops(trace)
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 15          # 5 ops x 3 dispatches
    assert sum(tr.is_custom_call(e) for e in ops["/device:TPU:0"]) == 3


def test_busy_union_and_idle_share(reduced):
    # three dispatches of ~0.48 ms in a ~37.8 ms window
    assert reduced["window_s"] == pytest.approx(0.0378168, rel=1e-4)
    assert reduced["busy_s"] == pytest.approx(0.00144206, rel=1e-4)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.96187, abs=1e-4)


def test_custom_call_selection_is_the_pallas_gather(reduced):
    top_name, top_s = reduced["top_ops"][0]
    assert "tpu_custom_call" in top_name and "f32[2048,1024]" in top_name
    assert tr.mean_seconds(reduced, tr.is_custom_call) == \
        pytest.approx(top_s) == pytest.approx(0.00137013, rel=1e-4)
    assert tr.mean_seconds(reduced, lambda e: not tr.is_custom_call(e)) \
        == pytest.approx(reduced["busy_s"] - top_s, rel=1e-3)


def test_gap_attribution_to_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the step annotations cover ~1.4 ms each (the device's work shows
    # ~1.2 ms earlier on its own clock, so it falls outside them); the
    # sleeps between the steps are covered by no span
    assert gaps["chipbench.step"] == pytest.approx(0.0042, abs=0.0005)
    assert gaps[tr.UNATTRIBUTED] > 0.03
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_ops_go_to_spans_by_their_program_execution(reduced):
    assert len(tr.spans_named(reduced, "chipbench.step")) == 3
    # the device's clock runs ~1.2 ms behind the host's in this trace
    # (each execution ENDS before the host span that launched it
    # begins), so ops are matched through whole program executions:
    # a span that covers most of an execution owns all of its ops
    mods = reduced["modules"]["/device:TPU:0"]
    assert [m[0][:8] for m in mods] == ["jit_step"] * 3
    first = [(mods[0][1] + 100_000.0, mods[0][1] + 2_000_000.0)]
    assert tr.busy_seconds(reduced, first) == \
        pytest.approx(reduced["busy_s"] / 3, rel=0.01)
    assert tr.mean_seconds(reduced, tr.is_custom_call, first) == \
        pytest.approx(0.00137013 / 3, rel=0.01)
    barely = [(mods[0][1] - 1000.0, mods[0][1] + 100_000.0)]   # < half
    assert tr.busy_seconds(reduced, barely) == 0.0
    everything = [(reduced["t0_ns"], reduced["t1_ns"])]
    assert tr.busy_seconds(reduced, everything) == \
        pytest.approx(reduced["busy_s"], rel=1e-6)


@pytest.mark.parametrize("hlo,want", [
    ('%fn.22 = f32[98304,1024]{1,0:T(8,128)} custom-call(s32[98304]{0} %a, '
     'f32[49152,1024]{1,0} %b), custom_call_target="tpu_custom_call", '
     'operand_layout_constraints={}', "fn.22 custom-call f32[98304,1024] tpu_custom_call "),
    ('%copy.46 = f32[6144,16,16,64]{3,2,1,0:T(8,128)} copy(f32[6144,16,16,64]'
     '{3,2,1,0} %p)', "copy.46 copy f32[6144,16,16,64] "),
    ('%all-reduce-start.3 = (bf16[1024,4096]{1,0}, bf16[1024,4096]{1,0}) '
     'all-reduce-start(bf16[1024,4096]{1,0} %g), replica_groups={{0,1,2,3}}',
     "all-reduce-start.3 all-reduce-start bf16[1024,4096] "),
    ("not hlo text", "not hlo text")])
def test_short_op_name(hlo, want):
    assert tr.short_op_name(hlo) == want


def test_collectives_sync_from_op_line_async_from_async_line():
    ar = "all-reduce.1 all-reduce f32[8] "
    start = "all-reduce-start.3 all-reduce-start bf16[8] "
    done = "all-reduce-done.3 all-reduce-done bf16[8] "
    fus = "fusion.2 fusion f32[8] "
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [
            [ar, 0.0, 10.0], [start, 20.0, 1.0], [fus, 21.0, 30.0],
            [done, 51.0, 9.0]]},
        {"name": tr.ASYNC_LINE, "events": [[start, 20.0, 40.0]]}]}]}
    assert tr.is_collective([ar, 0, 1]) and not tr.is_collective([fus, 0, 1])
    got = tr.collective_intervals(trace, 0.0, 100.0)["/device:TPU:0"]
    assert sorted(got) == [(0.0, 10.0), (20.0, 60.0)]
    compute = tr.clip([[fus, 21.0, 30.0]], 0.0, 100.0)
    # in flight 50, of which 30 under the fusion: 20 exposed
    assert tr.total(got) == 50.0
    assert tr.total(tr.subtract(got, compute)) == 20.0


@pytest.mark.parametrize("intervals,holes,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [[0, 2], [3, 5], [7, 10]]),
    ([(0, 10)], [(-5, 20)], []),
    ([(0, 4), (2, 8)], [], [[0, 8]]),
    ([(0, 4), (6, 8)], [(3, 7)], [[0, 3], [7, 8]])])
def test_interval_arithmetic(intervals, holes, want):
    assert tr.subtract(intervals, holes) == want


def test_innermost_span_takes_the_gap_and_short_gaps_are_lumped():
    idle = [(0.0, 1000.0), (10_000.0, 110_000.0)]
    spans = [("outer", 0.0, 200_000.0), ("inner", 20_000.0, 30_000.0),
             ("inner", 20_000.0, 30_000.0)]       # duplicates count once
    got = tr.attribute_gaps(idle, spans)
    assert got == pytest.approx({tr.BETWEEN_OPS: 1000e-9,
                                 "inner": 10_000e-9, "outer": 90_000e-9})
    assert tr.attribute_gaps([(0.0, 50_000.0)], []) == \
        pytest.approx({tr.UNATTRIBUTED: 50_000e-9})


def test_missing_window_mark_is_an_error(trace):
    with pytest.raises(ValueError):
        tr.host_marks(trace, ("chipbench.nope",))
