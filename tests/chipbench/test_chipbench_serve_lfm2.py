"""``lfm2_8b_a1b_d12`` and its cell: the configuration's file against
the catalog row it was drawn from, key by key; the traffic file's lease
of the pool; the runner at a tiny size on the CPU (contract of the
observations, two seeds dispatch the same work); the new reader on
hand-laid observations; the operations function against a hand count."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_shortconv, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import (  # noqa: E402
    moe_counts, moe_grouped, scope_ms, shortconv_ops, ssd_ops)

NAME = "lfm2_8b_a1b_d12"
CELL = "serve_lfm2_extract_closed"
NEW = ["shortconv_ms_per_prefill", "shortconv_ms_per_step.decode",
       "shortconv_roofline.prefill", "attn_ms_per_prefill"]
WHAT = ["prefill_ms", "decode_ms", "prefill_roofline", "attn_prefill_ms"]

# the numbers of the catalog row ``LFM2-8B-A1B``
# (model-configs/architectures.jsonl, ``config``), key by key
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
LAYER_TYPES = ["conv", "conv", "full_attention"] \
    + ["conv", "conv", "conv", "full_attention"] * 4 \
    + ["conv", "conv", "full_attention", "conv", "conv"]


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        n_layer=4, d_model=64, d_inner=96, n_head=4, vocab=96,
        prompt_len=32, max_new=16, prompt_buckets=[16, 32], n_slots=4,
        page_size=4, n_kv_head=2, head_dim=16, n_routed_experts=8,
        n_experts_held=8, d_expert=24, dtype="float32")
    # float32 against float32 on the CPU: see tests/test_lfm2_serve.py
    cfg["check"].update(
        prompt_lens=[21, 9, 13, 2], max_new=[6, 4, 6, 3],
        window_dtype="float32",
        limits={"logit_err_median": 2e-5, "logit_err_max": 2e-5,
                "window_err_max": 2e-5, "picks_gap_max": 0.0,
                "picks_gap_layer_mean_max": 0.0,
                "margin_max_sd": 0.0})
    return cfg


def tiny_traffic():
    tr_ = tiny._load("traffic", "closed_extract_longprompt")
    tr_.update(clients=4, prompt_len={"dist": "log_uniform", "lo": 2,
                                      "hi": 32},
               max_new={"dist": "uniform", "lo": 10, "hi": 16},
               first_round_min=4, prime_decode_steps=2)
    return tr_


# ------------------------------------------------- the configuration file

@pytest.mark.parametrize("key", sorted(CATALOG) + ["layer_types"])
def test_the_file_holds_the_catalog_rows_key(key):
    """Every number of the catalog row's ``config`` is in the file under
    the same key, at the top level and under ``published.config``."""
    cfg = committed()
    want = LAYER_TYPES if key == "layer_types" else CATALOG[key]
    assert cfg[key] == want
    assert cfg["published"]["config"][key] == want


def test_every_width_is_the_sources():
    """No width differs from the source's config; what is cut is depth
    alone, and ``reduced`` says so."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    assert set(src) == set(CATALOG) | {"layer_types"}
    assert len(src["layer_types"]) == 24
    assert src["layer_types"].count("full_attention") == 6
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["hidden_size"] // src["num_attention_heads"]),
            ("conv_taps", src["conv_L_cache"]),
            ("gqa_rope_theta", src["rope_theta"]),
            ("first_k_dense", src["num_dense_layers"]),
            ("d_expert", src["moe_intermediate_size"]),
            ("n_routed_experts", src["num_experts"]),
            ("n_experts_held", src["num_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("norm_topk_prob", src["norm_topk_prob"]),
            ("routed_scaling_factor", src["routed_scaling_factor"]),
            ("router_bias", src["use_expert_bias"]),
            ("vocab", src["vocab_size"]),
            ("rms_eps", src["norm_eps"])):
        assert build[ours] == theirs, ours
    assert build["n_shared_experts"] == 0 and build["tie_embeddings"]
    assert build["qk_norm"] and not build["gqa_gate"]
    assert "moe_picks" not in build    # the programs are a deployment's
    assert "window" not in build and "rope_theta" not in build
    # the stage is the source's first twelve layers, whole periods
    kind = {"conv": "conv", "full_attention": "gqa"}
    period = build["layer_kinds"]
    assert [period[i % len(period)] for i in range(build["n_layer"])] \
        == [kind[t] for t in src["layer_types"][:12]]
    assert build["n_layer"] == 12
    # the one cut, within the floors: whole periods, >= 4 expert layers
    assert cfg["reduced"] == ["n_layer"]
    assert cfg["published"]["n_layer"] == src["num_hidden_layers"] == 24
    assert build["n_layer"] - build["first_k_dense"] >= 4
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    assert "FIRST of two pipeline stages" in cfg["stands_for"]
    assert set(cfg["assumed"]) >= {"head", "conv", "attention", "router"}
    assert any("1e-6" in d for d in cfg["departures"])
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_files_byte_count_is_the_models():
    """3.93 B parameters in bfloat16, 1.69 GB of pages, 4.7 MB of conv
    windows: what ``reduced_why`` and ``assumed`` state, from the
    build."""
    b = committed()["build"]
    m = b["d_model"]
    conv = m * 3 * m + m * m + b["conv_taps"] * m
    attn = 2 * m * b["n_head"] * b["head_dim"] \
        + 2 * m * b["n_kv_head"] * b["head_dim"] + 2 * b["head_dim"]
    dense = 3 * m * b["d_inner"]
    moe = m * b["n_routed_experts"] + b["n_routed_experts"] \
        + 3 * b["n_experts_held"] * m * b["d_expert"]
    assert conv == 16_783_360 and attn == 10_485_888
    params = 9 * conv + 3 * attn + 2 * dense + 10 * moe \
        + 12 * 2 * m + m + b["vocab"] * m
    assert params == pytest.approx(3.93e9, rel=2e-3)
    rows = b["n_slots"] * (b["prompt_len"] + b["max_new"])
    pages = rows * 2 * b["n_kv_head"] * b["head_dim"] * 2 * 3
    assert pages == pytest.approx(1.686e9, rel=1e-3)
    windows = b["n_slots"] * 9 * (b["conv_taps"] - 1) * m * 2
    assert windows == pytest.approx(4.7e6, rel=1e-2)
    assert 2 * params + pages + windows == pytest.approx(9.55e9, rel=2e-3)


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert config["runner"] == "serve_lfm2"
    assert traffic["clients"] == config["build"]["n_slots"] == 64
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per_layer = [m["name"] for m in bench["per_layer"]]
    assert per_layer[-4:] == NEW                 # appended, at the end
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME
    mine = {m["name"]: m for m in harness.metrics_of(bench, "per_layer",
                                                     CELL)}
    for name, what in zip(NEW, WHAT):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_tokens_per_s"
        assert mine[name]["layer"] == "kernels"
        assert mine[name]["source"] == "device_trace"
        assert harness.load_json("layer_metrics", name + ".json") \
            == {"reader": "shortconv_ops", "args": {"what": what}}
    assert mine["shortconv_roofline.prefill"]["unit"] == "%"
    assert {"slot_occupancy_mean", "decode_step_device_ms",
            "peak_hbm_gb.decode", "attn_ms_per_step.decode",
            "experts_ms_per_step.decode", "experts_ms_per_prefill",
            "moe_experts_hit_pct.decode", "moe_load_max_over_mean.decode",
            "unscoped_pct.decode", "device_idle_pct.decode",
            # the step's gather of every row of its three full layers'
            # tables, by the kernel's name and its result's shape
            "gqa_gather_ms_per_step", "gqa_gather_roofline"} <= set(mine)
    # readers that name another mixer's scope, shapes or sizes: not here
    # (``prefill_window_share_pct.decode``'s reader asks for ``ssd``
    # layers before it reads anything; ``moe_up_*``'s counts ``n_layer``
    # expert layers where two of the twelve are dense, as at GLM-5's
    # cell: its share of the roofline would read 1.2 x too high —
    # PERF.md section 7)
    assert not {"state_ms_per_step.decode", "ssd_ms_per_step.decode",
                "moe_up_ms_per_step", "moe_up_roofline",
                "prefill_window_share_pct.decode",
                "moe_grouped_held_rows_pct.prefill",
                "kv_gather_roofline"} & set(mine)
    # the cell's step module is the one tests/conftest.py gives the
    # scope metrics' contract test
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "root_conftest", os.path.join(tiny.ROOT, "tests", "conftest.py"))
    table = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(table)
    from paddle_tpu.observability import device_scopes
    assert table.STEP_MODULES_SINCE_PR37[CELL] == "jit_" \
        + device_scopes.module_name(
            "lm_decode_paged",
            ["shortconv_decode", "kv_attention_decode_paged",
             "expert_ffn_held"])
    for scope in ("shortconv_prefill", "shortconv_decode"):
        assert device_scopes.PHASES[scope] == ("project", "conv", "out")


def test_the_traffic_is_the_issues_and_leases_the_pool():
    """Closed loop, 64 callers, prompts log-uniform 1025-4096 in buckets
    2048 and 4096 (half each), 64-192 new tokens: every request fits its
    slot's 4288 rows."""
    cfg = committed()
    build = cfg["build"]
    traffic = harness.load_json("traffic", "closed_extract_longprompt.json")
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 1025,
                                     "hi": 4096}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 64, "hi": 192}
    assert (traffic["clients"], traffic["rounds"],
            traffic["first_round_min"], traffic["prime_decode_steps"],
            traffic["schedule_seed"], traffic["trace_seconds"]) \
        == (64, 8, 8, 4, 23, 10)
    assert build["prompt_buckets"] == [2048, 4096]
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 4288 and rows % build["page_size"] == 0
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 64
    buckets, leased = [], []
    for requests in plan["clients"]:
        for j, (prompt, budget) in enumerate(requests):
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 1025 <= len(prompt) <= 4096
            assert (8 if j == 0 else 64) <= budget <= 192
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
            buckets.append(bucket)
            if j:
                leased.append((bucket + budget) / rows)
    assert abs(buckets.count(2048) - buckets.count(4096)) <= 2
    assert 0.49 < min(leased) and max(leased) <= 1.0
    # the check's prompts: no bucket's length, both buckets, one shorter
    # than the conv's taps, budgets that differ
    chk = cfg["check"]
    assert chk["prompt_lens"] == [3000, 1500, 1100, 2]
    assert not set(chk["prompt_lens"]) & set(build["prompt_buckets"])
    assert min(chk["prompt_lens"]) < build["conv_taps"]
    assert all(48 <= n <= 96 for n in chk["max_new"])
    assert len(set(chk["max_new"])) == 4
    assert set(chk["limits"]) == {"logit_err_median", "window_err_max",
                                  "picks_gap_max",
                                  "picks_gap_layer_mean_max"}
    assert chk["window_dtype"] == "bfloat16"


# ------------------------------------------------------ the runner, tiny

def logged_run(monkeypatch, seed, seconds=0.3):
    from paddle_tpu.serving import engine as eng
    log, opened = [], []
    real_run = eng.GenerativeModel._run
    real_open = harness.Run.open_window

    def spy(self, cb, aot_key, feeds):
        log.append((aot_key, tuple(sorted(
            (k, tuple(np.shape(v))) for k, v in feeds.items()))))
        return real_run(self, cb, aot_key, feeds)

    def open_window(self):
        opened.append(len(log))
        return real_open(self)

    monkeypatch.setattr(eng.SlotGenerativeModel, "_run", spy)
    monkeypatch.setattr(harness.Run, "open_window", open_window)
    run, obs = tiny.run_cell(tiny_config(), tiny_traffic(), seed, seconds)
    return run, obs, log[:opened[0]]


def admissions(setup_log):
    return [e for e in setup_log if e[0][0].startswith("prefill")]


@pytest.fixture(scope="module")
def two_runs():
    mp = pytest.MonkeyPatch()
    try:
        yield [logged_run(mp, seed) for seed in (3, 2 ** 31 + 5)]
    finally:
        mp.undo()


def test_tiny_lfm2_cell_agrees_with_the_reference(two_runs):
    _run, obs, setup = two_runs[0]
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5 and seen["window_err_max"] <= 2e-5
    assert seen["tokens_compared"] == 19 and seen["same_through_server"]
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    assert obs["units"]["decode_steps"] > 0
    assert obs["units"]["prefills"] > 0          # prefills INSIDE the window
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.3
    # three conv layers: true tokens at the prefills, slots at the steps
    tokens = obs["shortconv_tokens"]
    assert tokens["prefill"] > 0 and tokens["prefill"] % 3 == 0
    assert tokens["prefill"] <= 3 * 32 * obs["units"]["prefills"]
    # counted when a step is LAUNCHED: up to three steps of four slots
    # run ahead of what the scheduler has committed at the window's edge
    assert 0 < tokens["decode"] \
        <= 3 * (obs["counters"]["sched_slot_steps"] + 3 * 4)
    # an untraced run has no device time to read
    for what in WHAT:
        assert shortconv_ops.read(obs, what) is None
    # [expert layers, (tokens, steps hit), held experts], window deltas
    assert obs["moe_counts"].shape == (2, 2, 8)
    assert 0 < moe_counts.read(obs, "hit_pct") <= 100
    # warm-up's 2 buckets, the 4 compared requests (stepped together,
    # then once more through the server), one admission per client
    assert len(admissions(setup)) >= 2 + 2 * 4 + 4
    assert all("state_slot" in dict(e[1]) for e in admissions(setup))


def test_setup_dispatches_the_same_work_for_two_seeds(two_runs):
    (_r1, _o1, setup1), (_r2, o2, setup2) = two_runs
    assert o2["correct"]
    n = 2 + 2 * 4 + 4
    assert admissions(setup1)[:n] == admissions(setup2)[:n]
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    # warm-up's step, the longest compared budget's 5 steps twice, the
    # priming's 2: what comes on top is the scheduler's own timing
    assert min(steps) >= 1 + 2 * 5 + 2


# ----------------------------------------------------------- the readers

MS = 1e6       # nanoseconds
DECODE, PREFILL = "jit_lm_decode_paged_se045", \
    "jit_lm_prefill_paged_4096_sfa9e"
BUILD = dict(n_layer=12, d_model=2048,
             layer_kinds=["conv", "conv", "gqa", "conv"])
# (scope, ms): the ops of one decode step and of one prefill
STEP = [("shortconv_decode/project", 1.5), ("shortconv_decode/conv", 0.25),
        ("shortconv_decode/out", 0.5), ("expert_ffn_held/up", 6.0),
        ("kv_attention_decode_paged/gather", 2.0), ("", 0.5)]
FILL = [("shortconv_prefill/project", 8.0), ("shortconv_prefill/conv", 1.0),
        ("shortconv_prefill/out", 3.0), ("shortconv_prefill", 0.5),
        ("expert_ffn_held/up", 90.0), ("kv_attention_prefill_paged", 24.0)]


def observations(monkeypatch, scopes="map", steps=3, prefills=2):
    """``steps`` decode executions and ``prefills`` prefill executions
    back to back, 1 ms apart, with the program's map of them."""
    events, modules, table = [], [], {DECODE: {}, PREFILL: {}}
    at, number = 1.0, 0
    for module, ops in [(DECODE, STEP)] * steps + [(PREFILL, FILL)] * prefills:
        start = at
        for scope, ms in ops:
            name = f"fusion.{number}"
            number += 1
            table[module][name] = scope
            events.append([f"{name} fusion ", at * MS, ms * MS])
            at += ms
        modules.append([f"{module}(7)", start * MS, (at - start) * MS])
        at += 1.0
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    program = {"map": (table, {"seconds": 0.1}), "none": (None, None)}
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: program[scopes])
    window_ms = at + 1.0
    return {"reduced": tr.reduce_window(trace, 0.0, window_ms * MS, []),
            "units": {"decode_steps": steps, "prefills": prefills},
            "config": {"name": "-", "build": BUILD}, "traffic": {},
            "shortconv_tokens": {"prefill": 9 * (3000 + 2500),
                                 "decode": 9 * 60 * steps},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}, \
        window_ms


def test_readers_on_hand_laid_observations(monkeypatch):
    obs, _window_ms = observations(monkeypatch)
    assert shortconv_ops.read(obs, "prefill_ms") == pytest.approx(12.5)
    assert shortconv_ops.read(obs, "decode_ms") == pytest.approx(2.25)
    assert shortconv_ops.read(obs, "attn_prefill_ms") == pytest.approx(24.0)
    ops = 9 * 5500 * 2 * (2048 * 6144 + 2048 * 2048)
    assert shortconv_ops.read(obs, "prefill_roofline") == pytest.approx(
        100 * ops / 197e12 / (2 * 12.5e-3))
    assert 0 < shortconv_ops.read(obs, "prefill_roofline") < 100
    # the accepted readers the cell lists read the same observations
    assert moe_grouped.read(obs, "experts_ms") == pytest.approx(90.0)
    assert scope_ms.read(obs, "ms", ssd_ops.DECODE, ["expert_ffn_held"],
                         "decode_steps") == pytest.approx(6.0)
    with pytest.raises(ValueError, match="cannot read"):
        shortconv_ops.read(obs, "anything_else")


def test_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without device scopes (no map), a model without conv
    layers, a run without the counter: None, never a raise — the line
    leaves the metric out."""
    obs, _w = observations(monkeypatch, scopes="none")
    assert all(shortconv_ops.read(obs, what) is None for what in WHAT)
    obs, _w = observations(monkeypatch)
    assert shortconv_ops.read({**obs, "shortconv_tokens": {}},
                              "prefill_roofline") is None
    assert shortconv_ops.read({**obs, "shortconv_tokens": None},
                              "prefill_roofline") is None
    plain = {**obs, "config": {"build": {"n_layer": 4, "layer_kinds": [
        "gqa", "kda", "kda", "kda"]}}}
    assert all(shortconv_ops.read(plain, what) is None for what in WHAT)
    assert all(shortconv_ops.read(
        {**obs, "config": {"build": {"n_layer": 12}}}, what) is None
        for what in WHAT)
    # the accepted reader of the prefills' share of the window asks for
    # ``ssd`` layers first: nothing to read here (PERF.md section 7)
    assert ssd_ops.read(obs, "prefill_window_share_pct") is None


def test_operations_against_a_hand_count():
    # one token, one conv layer: W_in [2048, 6144] and W_out [2048, 2048]
    assert flops_shortconv.mixer_flops(1, 2048) \
        == 2 * (2048 * 6144 + 2048 * 2048) == 33_554_432
    # a 3072-token prompt through nine layers: 0.93 TFLOP, 4.7 ms at peak
    assert flops_shortconv.mixer_flops(9 * 3072, 2048) \
        == pytest.approx(0.928e12, rel=1e-3)
