"""``jamba2_3b`` and its cell: the configuration's file against the
catalog row it was drawn from, key by key — and that it cuts NOTHING; the
traffic file's lease of the pool; the runner at a tiny size on the CPU
(contract of the observations, two seeds dispatch the same work); the
new reader on hand-laid observations; the operations-and-bytes functions
against hand counts."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_s6, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import s6_ops, scope_ms  # noqa: E402

NAME = "jamba2_3b"
CELL = "serve_jamba2_reasoning_closed"
NEW = ["s6_scan_ms_per_prefill", "s6_scan_roofline.prefill",
       "s6_scan_padding_pct.prefill", "s6_state_ms_per_step.decode",
       "s6_state_roofline.decode"]
WHAT = ["scan_ms", "scan_roofline", "scan_padding_pct", "state_ms",
        "state_roofline"]
JOINED = ["slot_occupancy_mean", "itl_mean_ms", "kv_pages_held_pct.decode",
          "compiles_in_window.decode", "decode_step_device_ms",
          "device_idle_pct.decode", "peak_hbm_gb.decode",
          "sched_host_ms_per_step", "fetch_lag_ms.decode",
          "decode_busy_ms_per_step", "attn_ms_per_step.decode",
          "sample_ms_per_step.decode", "unscoped_pct.decode",
          "host_pause_pct.decode", "dispatch_starved_pct.decode"]

# the numbers of the catalog row ``AI21-Jamba2-3B``
# (model-configs/architectures.jsonl, ``config``), key by key
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        n_layer=14, d_model=32, d_inner=48, n_head=4, vocab=96,
        prompt_len=32, max_new=16, prompt_buckets=[16, 32], n_slots=4,
        page_size=4, first_k_dense=14, n_kv_head=1, head_dim=8,
        s6_d_inner=64, s6_d_state=4, s6_dt_rank=6, s6_chunk=8,
        dtype="float32")
    # float32 against float32 on the CPU: see tests/test_jamba2_serve.py
    cfg["check"].update(
        prompt_lens=[3, 21, 13], max_new=[6, 4, 6], state_layers=[0, 6, 12],
        limits={"logit_err_median": 2e-5, "logit_err_max": 2e-5,
                "state_err_max": 2e-5, "margin_max_sd": 0.0})
    return cfg


def tiny_traffic():
    tr_ = tiny._load("traffic", "closed_reasoning_wide")
    tr_.update(clients=4, prompt_len={"dist": "log_uniform", "lo": 2,
                                      "hi": 32},
               max_new={"dist": "uniform", "lo": 10, "hi": 16},
               first_round_min=4, prime_decode_steps=2)
    return tr_


# ------------------------------------------------- the configuration file

@pytest.mark.parametrize("key", sorted(CATALOG))
def test_the_file_holds_the_catalog_rows_key(key):
    """Every number of the catalog row's ``config`` is in the file under
    the same key, at the top level and under ``published.config``."""
    cfg = committed()
    assert cfg[key] == CATALOG[key]
    assert cfg["published"]["config"][key] == CATALOG[key]


def test_nothing_is_cut():
    """No width, no depth and no vocabulary differs from the source's
    config: ``reduced`` is empty, and the build is the whole model."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    assert set(src) == set(CATALOG)
    for ours, theirs in (
            ("n_layer", src["num_hidden_layers"]),
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("vocab", src["vocab_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["hidden_size"] // src["num_attention_heads"]),
            ("s6_d_inner", src["mamba_expand"] * src["hidden_size"]),
            ("s6_d_state", src["mamba_d_state"]),
            ("s6_dt_rank", src["mamba_dt_rank"]),
            ("s6_conv_taps", src["mamba_d_conv"]),
            ("tie_embeddings", src["tie_word_embeddings"]),
            ("rms_eps", src["rms_norm_eps"])):
        assert build[ours] == theirs, ours
    assert (build["n_layer"], build["d_model"], build["s6_d_inner"],
            build["s6_d_state"], build["s6_dt_rank"], build["n_head"],
            build["n_kv_head"], build["head_dim"], build["d_inner"],
            build["vocab"]) == (28, 2560, 5120, 16, 160, 20, 1, 128, 8192,
                                65536)
    assert cfg["reduced"] == [] and cfg["published"]["n_layer"] == 28
    # the order of the layer kinds: attention where i % 14 == 7
    kinds = build["layer_kinds"]
    assert len(kinds) == src["attn_layer_period"] == 14
    assert [i for i, k in enumerate(kinds) if k == "gqa"] \
        == [src["attn_layer_offset"]]
    assert set(kinds) == {"s6", "gqa"}
    # no positions, no gate, no expert layer anywhere
    assert "gqa_rope_theta" not in build and not build["gqa_gate"]
    assert src["num_experts"] == 1
    assert build["first_k_dense"] == build["n_layer"]
    assert not [k for k in build if "expert" in k or k == "d_expert"]
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    assert (build["n_slots"], build["page_size"], build["prompt_buckets"],
            build["prompt_len"], build["max_new"]) \
        == (256, 16, [512, 1024], 1024, 3072)
    assert "WHOLE" in cfg["stands_for"] and "nothing is cut" \
        in cfg["stands_for"]
    assert {"head_dim", "layer_order", "positions", "mamba_layer",
            "decay_start", "state_dtype", "weights"} <= set(cfg["assumed"])
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == []
    assert entry["source"] == cfg["source"] and entry["file"].endswith(
        NAME + ".json")


def test_the_files_byte_count_is_the_models():
    """3.029 B parameters = 6.06 GB in bfloat16, 2.39 GB of state, 1.07
    GB of pages: what ``reduced_why`` and ``stands_for`` state, from the
    build."""
    cfg = committed()
    b = cfg["build"]
    m, f, c = b["d_model"], b["d_inner"], b["s6_d_inner"]
    n, r, taps = b["s6_d_state"], b["s6_dt_rank"], b["s6_conv_taps"]
    mamba = m * 2 * c + c * (r + 2 * n) + r * c + c + c * m + n * c \
        + taps * c + c + c + r + 2 * n
    d = b["head_dim"]
    attn = 2 * m * b["n_head"] * d + 2 * m * b["n_kv_head"] * d
    ffn = 3 * m * f
    params = 26 * (mamba + ffn + 2 * m) + 2 * (attn + ffn + 2 * m) \
        + b["vocab"] * m + m
    assert mamba == pytest.approx(41.24e6, rel=1e-3)
    assert attn == pytest.approx(13.76e6, rel=1e-3)
    assert ffn == pytest.approx(62.91e6, rel=1e-3)
    assert params == pytest.approx(3.029e9, rel=1e-3)
    assert "3.029 B parameters = 6.06 GB" in cfg["reduced_why"]
    rows = b["n_slots"] * (b["prompt_len"] + b["max_new"])
    pages = rows * 2 * 2 * b["n_kv_head"] * d * 2
    assert pages == pytest.approx(1.074e9, rel=1e-3)
    state = b["n_slots"] * 26 * (n * c * 4 + (taps - 1) * c * 2)
    assert state == pytest.approx(2.385e9, rel=1e-3)
    assert 2 * params + pages + state == pytest.approx(9.52e9, rel=2e-3)


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert config["runner"] == "serve_jamba2"
    assert traffic["clients"] == config["build"]["n_slots"] == 256
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in harness.metrics_of(bench, "per_layer",
                                                     CELL)}
    for name, what in zip(NEW, WHAT):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_tokens_per_s"
        assert mine[name]["layer"] == "kernels"
        assert harness.load_json("layer_metrics", name + ".json") \
            == {"reader": "s6_ops", "args": {"what": what}}
    assert set(JOINED) | {"setup_build_s", "runtime_start_s"} <= set(mine)
    # readers that name another mixer's scope, shapes or layer kinds, and
    # the expert layers' (the model has none): not here
    assert not {"state_ms_per_step.decode", "kda_state_roofline",
                "attn_ms_per_prefill", "prefill_window_share_pct.decode",
                "experts_ms_per_step.decode", "moe_experts_hit_pct.decode",
                "ssd_state_roofline.decode", "gdn_state_roofline.decode"} \
        & set(mine)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "root_conftest", os.path.join(tiny.ROOT, "tests", "conftest.py"))
    table = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(table)
    from paddle_tpu.observability import device_scopes
    assert table.STEP_MODULES_SINCE_PR37[CELL] == "jit_" \
        + device_scopes.module_name(
            "lm_decode_paged", ["s6_decode", "kv_attention_decode_paged",
                                "swiglu_ffn", "token_sample"])


def test_the_traffic_is_the_issues_and_leases_the_pool():
    """Closed loop, 256 callers, prompts log-uniform 257-1024 in buckets
    512 and 1024 (half each), 1024-3072 new tokens: every request fits
    its slot's 4096 rows and leases 37-100 % of them — about half the
    pool on average."""
    cfg = committed()
    build = cfg["build"]
    traffic = harness.load_json("traffic", "closed_reasoning_wide.json")
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 257,
                                     "hi": 1024}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 1024, "hi": 3072}
    assert (traffic["clients"], traffic["rounds"],
            traffic["first_round_min"], traffic["prime_decode_steps"],
            traffic["schedule_seed"], traffic["trace_seconds"]) \
        == (256, 8, 8, 4, 23, 10)
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 4096 and rows % build["page_size"] == 0
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 256
    buckets, leased = [], []
    for requests in plan["clients"]:
        assert len(requests) == traffic["rounds"]
        for j, (prompt, budget) in enumerate(requests):
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 257 <= len(prompt) <= 1024
            assert (8 if j == 0 else 1024) <= budget <= 3072
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
            buckets.append(bucket)
            if j:
                leased.append((bucket + budget) / rows)
    assert abs(buckets.count(512) - buckets.count(1024)) <= 2
    assert 0.37 < min(leased) and max(leased) <= 1.0
    # a slot in mid-request holds its bucket and half its budget so far:
    # about half of the pool is leased over a window
    mid = [(bucket + budget / 2) / rows
           for requests in plan["clients"]
           for j, (prompt, budget) in enumerate(requests) if j
           for bucket in [min(b for b in build["prompt_buckets"]
                              if b >= len(prompt))]]
    assert 0.4 < np.mean(mid) < 0.6
    # the check's prompts: no bucket's length, no multiple of the chunk,
    # both buckets, one shorter than a chunk, budgets that differ
    chk = cfg["check"]
    assert len(chk["prompt_lens"]) >= 4
    assert not [n for n in chk["prompt_lens"]
                if n % build["s6_chunk"] == 0 or n in
                build["prompt_buckets"]]
    assert {min(b for b in build["prompt_buckets"] if b >= n)
            for n in chk["prompt_lens"]} == {512, 1024}
    assert len(set(chk["max_new"])) >= 3
    assert set(chk["limits"]) == {"logit_err_median", "state_err_median",
                                  "state_bf16_share"}
    assert chk["state_dtype"] == "float32"
    assert chk["state_layers"] == [0, 12, 25]     # first, middle, last


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


def test_tiny_jamba2_cell_agrees_with_the_reference(two_runs):
    _run, obs, setup = two_runs[0]
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5 and seen["state_err_max"] <= 2e-5
    assert seen["tokens_compared"] == 16 and seen["same_through_server"]
    assert seen["state_bf16_share"] < 0.01
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    assert obs["units"]["decode_steps"] > 0
    assert obs["units"]["prefills"] > 0          # prefills INSIDE the window
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.3
    # thirteen Mamba layers: true tokens scanned, whole chunks of 8 walked
    assert 0 < obs["s6_tokens"] <= obs["s6_rows"]
    assert obs["s6_rows"] % (13 * 8) == 0 and obs["s6_tokens"] % 13 == 0
    assert obs["slot_steps"] == obs["counters"]["sched_slot_steps"] > 0
    pad = s6_ops.read(obs, "scan_padding_pct")
    assert pad == pytest.approx(
        100 * (1 - obs["s6_tokens"] / obs["s6_rows"])) and 0 <= pad < 90
    # an untraced run has no device time to read
    for what in ("scan_ms", "scan_roofline", "state_ms", "state_roofline"):
        assert s6_ops.read(obs, what) is None
    assert "moe_counts" not in obs               # no expert layer to count
    # warm-up's 2 buckets, the 3 compared requests (stepped together,
    # then once more through the server), one admission per client
    assert len(admissions(setup)) >= 2 + 2 * 3 + 4
    assert all("state_slot" in dict(e[1]) for e in admissions(setup))


def test_setup_dispatches_the_same_work_for_two_seeds(two_runs):
    (_r1, _o1, setup1), (_r2, o2, setup2) = two_runs
    assert o2["correct"]
    # warm-up's and the compared requests' admissions in order; the four
    # clients' first ones as a multiset (their threads race to the queue,
    # and under a loaded machine the order is the operating system's)
    n = 2 + 2 * 3
    assert admissions(setup1)[:n] == admissions(setup2)[:n]
    assert sorted(admissions(setup1)[n:n + 4]) \
        == sorted(admissions(setup2)[n:n + 4])
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    # warm-up's step, the longest compared budget's 5 steps twice, the
    # priming's 2: what comes on top is the scheduler's own timing
    assert min(steps) >= 1 + 2 * 5 + 2


def test_the_result_line_carries_the_cells_metrics(two_runs):
    """The untraced line: the two end-to-end metrics the cell reports,
    under the names BENCHMARK.json gives them."""
    run, obs, _setup = two_runs[0]
    run.bench, run.cell = harness.load_benchmark(), {
        **run.cell, "name": CELL}
    line = json.loads(harness.result_line(run, obs))
    assert line["correct"] and set(line["metrics"]) == {
        "serve_tokens_per_s", "setup_s"}
    assert line["notes"]["reference"]["same_through_server"]


# ----------------------------------------------------------- the readers

MS = 1e6       # nanoseconds
DECODE, PREFILL = "jit_lm_decode_paged_s6ffc", \
    "jit_lm_prefill_paged_1024_s617b"
BUILD = dict(n_layer=28, layer_kinds=["s6"] * 7 + ["gqa"] + ["s6"] * 6,
             s6_d_inner=5120, s6_d_state=16, s6_dt_rank=160, s6_chunk=64)
# (scope, ms): the ops of one decode step and of one prefill
STEP = [("s6_decode", 3.0), ("s6_decode/conv", 0.4),
        ("s6_decode/project", 0.6), ("s6_decode/state", 7.0),
        ("swiglu_ffn", 5.0), ("kv_attention_decode_paged/attend", 0.5),
        ("", 0.5)]
FILL = [("s6_prefill", 8.0), ("s6_prefill/conv", 1.0),
        ("s6_prefill/project", 2.0), ("s6_prefill/scan", 6.0),
        ("swiglu_ffn", 12.0), ("kv_attention_prefill_paged", 1.0)]


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
            "slot_steps": 250 * steps, "s6_tokens": 26 * 1200,
            "s6_rows": 26 * 1280,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}, \
        window_ms


def test_readers_on_hand_laid_observations(monkeypatch):
    obs, _window_ms = observations(monkeypatch)
    assert s6_ops.read(obs, "scan_ms") == pytest.approx(6.0)
    assert s6_ops.read(obs, "state_ms") == pytest.approx(7.0)
    state = 250 * 26 * 16 * 5120 * 4 * 2              # bytes a step
    assert s6_ops.read(obs, "state_roofline") == pytest.approx(
        100 * state / 819e9 / 7.0e-3)
    assert 0 < s6_ops.read(obs, "state_roofline") < 100
    # the scan's least time is its bytes': float32 x, dt, y a token
    moved = flops_s6.scan_bytes(26 * 1200, 5120, 16)
    ops = flops_s6.scan_flops(26 * 1200, 5120, 16)
    assert moved / 819e9 > ops / 197e12
    assert s6_ops.read(obs, "scan_roofline") == pytest.approx(
        100 * moved / 819e9 / (2 * 6e-3))
    assert 0 < s6_ops.read(obs, "scan_roofline") < 100
    assert s6_ops.read(obs, "scan_padding_pct") == pytest.approx(
        100 * (1 - 1200 / 1280))
    with pytest.raises(ValueError, match="cannot read"):
        s6_ops.read(obs, "anything_else")


def test_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without device scopes (no map), a model without S6
    layers, a run without the counters (a parent of PR 65): None, never a
    raise — the line leaves the metric out."""
    obs, _w = observations(monkeypatch, scopes="none")
    for what in ("scan_ms", "scan_roofline", "state_ms", "state_roofline"):
        assert s6_ops.read(obs, what) is None
    obs, _w = observations(monkeypatch)
    obs["s6_rows"] = obs["s6_tokens"] = None
    assert s6_ops.read(obs, "scan_padding_pct") is None
    assert s6_ops.read(obs, "scan_roofline") is None
    obs["slot_steps"] = 0
    assert s6_ops.read(obs, "state_roofline") is None
    plain = {**obs, "config": {"build": {"n_layer": 4, "layer_kinds": [
        "gqa", "ssd", "ssd", "ssd"]}}}
    assert all(s6_ops.read(plain, what) is None for what in WHAT)
    assert all(s6_ops.read({**obs, "config": {"build": {"n_layer": 12}}},
                           what) is None for what in WHAT)


def test_operations_and_bytes_against_hand_counts():
    # one live slot, one layer, one step: the state read and written
    assert flops_s6.state_bytes(1, 1, 5120, 16) == 2 * 4 * 81920
    # 256 slots, 26 layers: 4.36 GB a step, 5.3 ms at 819 GB/s
    assert flops_s6.state_bytes(256, 26, 5120, 16) \
        == pytest.approx(4.362e9, rel=1e-3)
    assert flops_s6.state_flops(1, 1, 5120, 16) == 6 * 81920
    # a token of 3 channels and a state of 2: dt A, the update and h C,
    # a multiply-accumulate each an element: 2 x 3 x 6 FLOPs
    assert flops_s6.scan_flops(1, 3, 2) == 2 * 3 * 3 * 2
    # x, dt in and y out (3 each), B and C in (2 each), float32
    assert flops_s6.scan_bytes(1, 3, 2) == 4 * (3 * 3 + 2 * 2)
    # a token a layer: 61.6 KB against 0.49 MFLOP: the bytes bind by 30 x
    per_token = flops_s6.scan_bytes(1, 5120, 16)
    assert per_token == 4 * (15360 + 32) == 61568
    assert per_token / 819e9 > 25 * flops_s6.scan_flops(1, 5120, 16) / 197e12
