"""The harness: which metrics a cell reports, the result line's shape,
the readers on hand-made observations, the process clock."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.layer_metrics import (compiles, device_idle_pct,  # noqa: E402
                                     histogram_mean_ms, kv_pages_held_pct,
                                     lateness_ms, mfu_pct, peak_hbm_gb,
                                     phase_seconds, runtime_start_s,
                                     slot_occupancy)

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


class FakeRun:
    def __init__(self, cell, trace):
        self.bench, self.cell, self.trace = BENCH, {"name": cell}, trace
        self.setup_s = 61.5
        import jax
        self.devices = jax.devices()[:1]


def fake_obs(cell):
    e2e = {m["name"]: 123.456 for m in
           harness.metrics_of(BENCH, "end_to_end", cell)}
    return {"correct": True, "attempted": 10, "failed": 0,
            "end_to_end": e2e, "notes": {}}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line_carries_the_cells_end_to_end_metrics(cell):
    line = json.loads(harness.result_line(FakeRun(cell, False),
                                          fake_obs(cell)))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert line["metrics"]["setup_s"] == {"value": 61.5, "unit": "s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(isinstance(v["value"], float) and v["unit"]
               for v in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_some_per_layer_metric(cell):
    names = [m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)]
    assert {"setup_build_s", "setup_warm_s", "runtime_start_s"} <= set(names)
    assert any(n.startswith("device_idle_pct.") for n in names)
    assert any(n.startswith("compiles_in_window.") for n in names)


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    obs = {"phases": {p: 1.0 for p in harness.PHASES},
           "compiles_in_window": 0, "peak_bytes": 0, "counters":
           {"queue_wait_sum": 0.0, "queue_wait_count": 0},
           "slot_occupancy": None, "lateness_s": None,
           "kv_pages_held": None}
    bench = {"per_layer": [
        {"name": "setup_build_s", "unit": "s"},
        {"name": "kv_pages_held_pct.decode", "unit": "%"},
        {"name": "runtime_start_s", "unit": "s"},
        {"name": "peak_hbm_gb.train", "unit": "GB"},
        {"name": "queue_wait_mean_ms", "unit": "ms"},
        {"name": "slot_occupancy_mean", "unit": "%"},
        {"name": "gen_lateness_p95_ms", "unit": "ms"}]}
    got = harness.read_layer_metrics(bench, "any", obs)
    assert got == {"setup_build_s": {"value": 2.0, "unit": "s"}}


@pytest.mark.parametrize("reader,obs,args,want", [
    (phase_seconds, {"phases": {"warm": 2.0, "prime": 0.5}},
     {"phases": ["warm", "prime"]}, 2.5),
    (compiles, {"compiles_in_window": 3}, {}, 3),
    (device_idle_pct, {"reduced": {"busy_s": 1.0, "window_s": 4.0}}, {},
     75.0),
    (peak_hbm_gb, {"peak_bytes": 5_000_000_000}, {}, 5.0),
    (mfu_pct, {"model_flops": 197e12, "window_s": 2.0, "chips": 1,
               "peaks": {"bf16_flops": 197e12}}, {}, 50.0),
    (mfu_pct, {"model_flops": 197e12 * 4, "window_s": 2.0, "chips": 4,
               "peaks": {"bf16_flops": 197e12}}, {}, 50.0),
    (histogram_mean_ms, {"counters": {"inter_token_sum": 4.0,
                                      "inter_token_count": 10}},
     {"family": "inter_token"}, 400.0),
    (slot_occupancy, {"slot_occupancy": 0.975}, {}, 97.5),
    (kv_pages_held_pct, {"kv_pages_held": 0.5}, {}, 50.0),
    (runtime_start_s, {"runtime_start_s": 12.25}, {}, 12.25),
    (lateness_ms, {"lateness_s": [0.001] * 99 + [0.5]},
     {"percentile": 50}, 1.0)])
def test_readers_on_hand_made_observations(reader, obs, args, want):
    assert reader.read(obs, **args) == pytest.approx(want)


@pytest.mark.parametrize("cell", [c for c in CELLS if c.startswith("serve_")])
def test_serve_cells_report_how_much_of_the_pool_is_held(cell):
    names = [m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)]
    assert sum(n.startswith("kv_pages_held_pct.") for n in names) == 1


def test_compile_cache_cap_is_lifted_whatever_the_machine_sets(monkeypatch):
    """A cap makes JAX keep ``-atime`` files, and in a directory with
    entries written without one every write then fails (seen on the
    chip, PR 23): the harness lifts the cap and says so."""
    import jax
    from paddle_tpu.utils import chip
    monkeypatch.setattr(chip, "compile_cache_dir", lambda: "somewhere")
    before = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", 192 << 20)
    try:
        assert harness.place_compile_cache() == "somewhere"
        assert jax.config.jax_compilation_cache_max_size == -1
    finally:
        jax.config.update("jax_compilation_cache_max_size", before)


def test_process_start_is_before_now_and_recent():
    t = harness.process_start_unix()
    assert t <= time.time() and time.time() - t < 7200


def test_unknown_cell_and_missing_files_are_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell(BENCH, "no_such_cell")
    with pytest.raises(harness.Refused):
        harness.load_json("traffic", "no_such_mix.json")


def test_attach_refuses_a_cpu_unless_a_unit_test_asks():
    with pytest.raises(harness.Refused, match="no accelerator"):
        harness.attach(1)
    assert len(harness.attach(1, allow_cpu=True)) == 1
    with pytest.raises(harness.Refused, match="needs"):
        harness.attach(10_000, allow_cpu=True)
