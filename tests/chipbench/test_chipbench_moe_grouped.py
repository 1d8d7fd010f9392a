"""The two per-layer metrics of the expert layers' grouped way
(``layer_metrics/moe_grouped.py``): how ``BENCHMARK.json`` declares
them, the scope reader on hand-laid observations of a window with
prefills in it, and the counter reader on the program's registry."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import moe_grouped, scope_ms  # noqa: E402
from chipbench.runners import serve  # noqa: E402

CELL = "serve_granite_sessions_closed"
METRICS = {"experts_ms_per_prefill": ("experts_ms", "ms", "device_trace"),
           "moe_grouped_held_rows_pct.prefill":
               ("held_rows_pct", "%", "program_counter")}
MS = 1e6
DECODE = "jit_lm_decode_paged_s8ff8"
PREFILLS = {"jit_lm_prefill_paged_1024": 1.0, "jit_lm_prefill_paged_2048": 2.0}
# (scope, ms) of a 1 024-token prefill; a 2 048-token one takes twice
FILL = [("expert_ffn_held/route", 1.0), ("ssd_prefill/scan", 4.0),
        ("expert_ffn_held/up", 5.0), ("expert_ffn_held/down", 9.0),
        ("expert_ffn_held/shared", 2.0), ("kv_attention_prefill_paged", 3.0)]
STEP = [("ssd_decode/state", 12.0), ("expert_ffn_held/up", 3.0)]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_declared_for_the_cell_alone(name):
    what, unit, source = METRICS[name]
    bench = harness.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert (entry["unit"], entry["source"]) == (unit, source)
    assert entry["layer"] == "kernels"
    # not a ``scope_ms`` entry: those are step metrics, one module each
    # (tests/chipbench/test_chipbench_scope_ms.py)
    assert harness.load_json("layer_metrics", name + ".json") \
        == {"reader": "moe_grouped", "args": {"what": what}}
    # at the ends of the list, after everything PR 42 left there
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "experts_ms_per_prefill", "moe_grouped_held_rows_pct.prefill"]


def observations(monkeypatch, scopes="map"):
    """Three decode steps, then a prefill of each bucket, 1 ms apart,
    with the program's map of them."""
    events, modules, table = [], [], {}
    at, number = 1.0, 0
    runs = [(DECODE, STEP, 1.0)] * 3 + [
        (module, FILL, times) for module, times in PREFILLS.items()]
    for module, ops, times in runs:
        start = at
        for scope, ms in ops:
            name = f"fusion.{number}"
            number += 1
            table.setdefault(module, {})[name] = scope
            events.append([f"{name} fusion ", at * MS, ms * times * MS])
            at += ms * times
        modules.append([f"{module}(7)", start * MS, (at - start) * MS])
        at += 1.0
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    program = {"map": (table, {"seconds": 0.1}), "none": (None, None)}
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: program[scopes])
    return {"reduced": tr.reduce_window(trace, 0.0, (at + 1.0) * MS, []),
            "units": {"decode_steps": 3, "prefills": 2},
            "config": {"name": "-", "build": {}}, "traffic": {}}


def test_the_experts_time_is_the_prefill_views_alone(monkeypatch):
    """The four phases of ``expert_ffn_held`` in both prefill buckets'
    executions, over the program's count of prefills: 17 ms in the
    1 024-token one and 34 in the other — and nothing of the decode
    steps' own experts."""
    obs = observations(monkeypatch)
    assert moe_grouped.read(obs, "experts_ms") == pytest.approx(
        (17.0 + 34.0) / 2)
    with pytest.raises(ValueError, match="cannot read"):
        moe_grouped.read(obs, "anything_else")


def test_nothing_to_read_is_none_and_never_a_raise(monkeypatch):
    """No trace, no map of the program's scopes, no prefill in the
    window: the line leaves the metric out."""
    obs = observations(monkeypatch)
    assert moe_grouped.read({k: v for k, v in obs.items()
                             if k != "reduced"}, "experts_ms") is None
    assert moe_grouped.read(observations(monkeypatch, scopes="none"),
                            "experts_ms") is None
    obs = observations(monkeypatch)
    obs["units"]["prefills"] = 0
    assert moe_grouped.read(obs, "experts_ms") is None


def test_the_held_share_is_the_registrys(monkeypatch):
    """held over given of the served model's children of
    ``paddle_moe_grouped_rows_total``; another model's rows stay out;
    None before any row was given, and from a program without the
    family (a parent of PR 44)."""
    from paddle_tpu.observability import metrics
    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "default_registry", lambda: registry)
    assert moe_grouped.read({}, "held_rows_pct") is None
    fam = registry.counter(moe_grouped.FAMILY, "rows",
                           labelnames=("model", "rows"))
    assert moe_grouped.read({}, "held_rows_pct") is None
    fam.labels(model=serve.MODEL, rows="given").inc(138 * 15360 * 10)
    assert moe_grouped.read({}, "held_rows_pct") == 0.0
    fam.labels(model=serve.MODEL, rows="held").inc(138 * 2765 * 10)
    fam.labels(model="another", rows="held").inc(10 ** 9)
    assert moe_grouped.read({}, "held_rows_pct") == pytest.approx(
        100 * 2765 / 15360)


def test_the_family_the_reader_names_is_the_programs():
    from paddle_tpu.serving import metrics as sm
    assert sm.MOE_GROUPED_ROWS.name == moe_grouped.FAMILY
    assert tuple(sm.MOE_GROUPED_ROWS.labelnames) == ("model", "rows")
