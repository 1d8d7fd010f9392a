"""The reader of the program's pause and starved-dispatch spans
(``host_gaps``): its clipping at both edges of the window, 0.0 where the
program has the counter family and the window holds no span, None where
the family is missing (the parent), its four metric files and their
entries in BENCHMARK.json, and the gap attribution that hands a pause's
idle time to ``host.pause`` and not to the scheduler span around it.
Nothing measured here is a device number."""

import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import host_gaps  # noqa: E402

MS = 1e6        # nanoseconds
BENCH = harness.load_benchmark()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
SERVE = E2E["serve_tokens_per_s"]["workloads"]
NEW = {
    "host_pause_pct.decode": ("pause_pct", "device", "serve_tokens_per_s"),
    "host_pause_pct.prefill": ("pause_pct", "device", "ttft_p50_ms"),
    "host_pause_pct.train": ("pause_pct", "device",
                             "train_tokens_per_s_chip"),
    "dispatch_starved_pct.decode": ("starved_pct", "scheduler",
                                    "serve_tokens_per_s"),
}


def _obs(host_spans, t0=0.0, t1=1000 * MS, units=None):
    return {"units": units if units is not None else {"decode_steps": 50},
            "reduced": {"t0_ns": t0, "t1_ns": t1,
                        "window_s": (t1 - t0) / 1e9,
                        "host_spans": host_spans}}


@pytest.fixture(autouse=True)
def _the_program_has_both_families():
    """The families are declared where the program's modules are
    imported; a benchmark run has imported them long before it reads."""
    from paddle_tpu.observability import pause_watch  # noqa: F401
    from paddle_tpu.serving import metrics  # noqa: F401


@pytest.mark.parametrize("spans, want", [
    # one pause of 100 ms wholly inside a window of 1 s
    ([("host.pause", 200 * MS, 300 * MS)], 10.0),
    # cut at the window's opening: 40 of 100 ms lie inside
    ([("host.pause", -60 * MS, 40 * MS)], 4.0),
    # cut at its close: 30 of 100 ms
    ([("host.pause", 970 * MS, 1070 * MS)], 3.0),
    # both edges, and one wholly outside that counts for nothing
    ([("host.pause", -60 * MS, 40 * MS), ("host.pause", 970 * MS, 1070 * MS),
      ("host.pause", 1200 * MS, 1300 * MS),
      ("host.pause", -500 * MS, -400 * MS)], 7.0),
    # a pause that covers the whole window reads 100
    ([("host.pause", -10 * MS, 1010 * MS)], 100.0),
    # other spans are not pauses: a scheduler span that contains one, a
    # name that only begins alike
    ([("serving.decode.dispatch", 100 * MS, 215 * MS),
      ("host.pause_of_another_kind", 100 * MS, 200 * MS),
      ("host.pause", 110 * MS, 211 * MS)], 10.1),
])
def test_pause_pct_cuts_the_pauses_to_the_window(spans, want):
    assert host_gaps.read(_obs(spans), "pause_pct") == pytest.approx(want)


@pytest.mark.parametrize("spans, steps, want", [
    # two dry decode dispatches in 50 steps
    ([("serving.starved.decode", 10 * MS, 10 * MS),
      ("serving.starved.decode", 500 * MS, 500 * MS)], 50, 4.0),
    # markers before the opening and at or after the close are not of
    # the window; one AT the opening is
    ([("serving.starved.decode", -1 * MS, -1 * MS),
      ("serving.starved.decode", 0.0, 0.0),
      ("serving.starved.decode", 1000 * MS, 1000 * MS),
      ("serving.starved.decode", 1001 * MS, 1001 * MS)], 50, 2.0),
    # the prefill side's markers are another count
    ([("serving.starved.prefill", 10 * MS, 10 * MS),
      ("serving.starved.decode", 20 * MS, 20 * MS)], 100, 1.0),
])
def test_starved_pct_counts_the_markers_of_the_window(spans, steps, want):
    obs = _obs(spans, units={"decode_steps": steps})
    assert host_gaps.read(obs, "starved_pct") == pytest.approx(want)


@pytest.mark.parametrize("what", ["pause_pct", "starved_pct"])
def test_a_clean_window_reads_zero_where_the_family_exists(what):
    spans = [("serving.decode.dispatch", 10 * MS, 13 * MS)]
    assert host_gaps.read(_obs(spans), what) == 0.0
    assert host_gaps.read(_obs([]), what) == 0.0


@pytest.mark.parametrize("what", ["pause_pct", "starved_pct"])
def test_a_program_without_the_family_reads_none(what, monkeypatch):
    monkeypatch.setitem(host_gaps.FAMILY, what, "chipbench_test_no_such")
    spans = [("host.pause", 200 * MS, 300 * MS),
             ("serving.starved.decode", 10 * MS, 10 * MS)]
    assert host_gaps.read(_obs(spans), what) is None


@pytest.mark.parametrize("units", [{}, {"decode_steps": 0},
                                   {"steps": 12}])
def test_starved_pct_reads_none_without_a_step_to_divide_by(units):
    spans = [("serving.starved.decode", 10 * MS, 10 * MS)]
    assert host_gaps.read(_obs(spans, units=units), "starved_pct") is None
    # the pause share divides by the window and needs no unit
    assert host_gaps.read(_obs(spans, units=units), "pause_pct") == 0.0


def test_a_result_line_keeps_a_zero_and_leaves_a_none_out(monkeypatch):
    bench = {"per_layer": [
        {"name": "host_pause_pct.decode", "unit": "%"},
        {"name": "dispatch_starved_pct.decode", "unit": "%"}]}
    got = harness.read_layer_metrics(bench, "any", _obs([]))
    assert got == {"host_pause_pct.decode": {"value": 0.0, "unit": "%"},
                   "dispatch_starved_pct.decode": {"value": 0.0,
                                                   "unit": "%"}}
    monkeypatch.setitem(host_gaps.FAMILY, "pause_pct", "chipbench_test_no")
    monkeypatch.setitem(host_gaps.FAMILY, "starved_pct", "chipbench_test_no")
    assert harness.read_layer_metrics(bench, "any", _obs([])) == {}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_metric_file_names_the_reader_and_its_entry_is_whole(name):
    what, layer, moves = NEW[name]
    assert harness.load_json("layer_metrics", name + ".json") \
        == {"reader": "host_gaps", "args": {"what": what}}
    reader = importlib.import_module("chipbench.layer_metrics.host_gaps")
    assert callable(reader.read)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["unit"] == "%" and entry["better"] == "lower"
    assert entry["source"] == "program_span"
    assert entry["layer"] == layer and entry["moves"] == moves
    # every cell that reports the metric it moves, and no other
    assert entry["workloads"] == E2E[moves]["workloads"]


def test_the_four_are_appended_behind_the_eighty_that_were_there():
    # by position, not from the end: a later PR appends behind these
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[79] == "attn_ms_per_prefill"       # PR 51's last
    assert names[80:84] == ["host_pause_pct.decode",
                            "host_pause_pct.prefill",
                            "host_pause_pct.train",
                            "dispatch_starved_pct.decode"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_its_pause_share(cell):
    names = [m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)]
    assert sum(n.startswith("host_pause_pct.") for n in names) == 1
    assert ("dispatch_starved_pct.decode" in names) == (cell in SERVE)


def test_a_pause_takes_its_gap_from_the_scheduler_span_around_it():
    # the device ran dry for 100 ms while the scheduler sat in a
    # dispatch of 115 ms; the watch found 101 ms of them lost
    idle = [(105 * MS, 205 * MS)]
    spans = [("serving.decode.dispatch", 100 * MS, 215 * MS),
             ("host.pause", 104.5 * MS, 205.5 * MS),
             ("serving.decode_step", 90 * MS, 400 * MS)]
    got = tr.attribute_gaps(idle, spans)
    assert got == pytest.approx({"host.pause": 0.100})
    assert "serving.decode.dispatch" not in got
    # without the pause span the same gap reads as a slow dispatch
    assert tr.attribute_gaps(idle, [spans[0], spans[2]]) == pytest.approx(
        {"serving.decode.dispatch": 0.100})


def test_a_marker_of_no_length_takes_no_gap():
    idle = [(105 * MS, 205 * MS)]
    spans = [("serving.prefill.dispatch", 100 * MS, 215 * MS),
             ("serving.starved.prefill", 150 * MS, 150 * MS),
             ("serving.starved.decode", 160 * MS, 160 * MS)]
    assert tr.attribute_gaps(idle, spans) == pytest.approx(
        {"serving.prefill.dispatch": 0.100})


def test_a_pause_that_straddles_the_gap_leaves_the_rest_where_it_was():
    # the pause ends 40 ms into a gap of 100 ms: what follows is the
    # dispatch's own
    idle = [(105 * MS, 205 * MS)]
    spans = [("serving.decode.dispatch", 100 * MS, 215 * MS),
             ("host.pause", 50 * MS, 145 * MS)]
    assert tr.attribute_gaps(idle, spans) == pytest.approx(
        {"host.pause": 0.040, "serving.decode.dispatch": 0.060})


def test_a_tiny_serve_cell_feeds_the_reader_with_real_spans():
    """The program's real markers, from a tiny closed-loop cell on the
    CPU with the tracer on from before the engine exists: every dry
    dispatch the engine counted left one marker, the watch ran while the
    tracer did, and both metrics read a number."""
    sys.path.insert(0, HERE)
    import chipbench_tiny as tiny
    from paddle_tpu.observability import pause_watch, tracing
    from paddle_tpu.serving import metrics as sm
    tracer = tracing.default_tracer()
    tracer.reset()

    def counted():
        return {view: sm.DISPATCH_STARVED.labels(model="lm", view=view).value
                for view in ("decode", "prefill")}
    before = counted()
    tracer.start()
    try:
        assert pause_watch.running()
        run, obs = tiny.run_cell(tiny.serve_config(),
                                 tiny.serve_traffic("closed_decode"), 5)
    finally:
        tracer.stop()
    spans = [(s.name, s.start_s * 1e9, s.end_s * 1e9)
             for s in tracer.spans()]
    tracer.reset()
    assert obs["correct"] and obs["units"]["decode_steps"] > 0
    delta = {v: counted()[v] - before[v] for v in before}
    for view, n in delta.items():
        assert n == sum(name == "serving.starved." + view
                        for name, _a, _b in spans)
    # the first dispatch after the engine was empty is dry by construction
    assert delta["prefill"] + delta["decode"] >= 1
    lo = min(a for _n, a, _b in spans)
    hi = max(b for _n, _a, b in spans)
    obs["reduced"] = _obs(spans, lo, hi)["reduced"]
    obs["units"] = {"decode_steps": sum(
        n == "serving.decode.fetch" for n, _a, _b in spans)}
    pause = host_gaps.read(obs, "pause_pct")
    starved = host_gaps.read(obs, "starved_pct")
    assert 0.0 <= pause < 100.0
    assert starved == pytest.approx(
        100.0 * delta["decode"] / obs["units"]["decode_steps"])
