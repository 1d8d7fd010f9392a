"""``granite4_h_small_ep4_d10`` and its cell: the configuration's file
against the catalog row it was drawn from, key by key; the traffic
file's lease of the pool; the runner at a tiny size on the CPU (contract
of the observations, two seeds dispatch the same work); the new reader
on hand-laid observations; the operations-and-bytes functions against
hand counts."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_ssd, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import moe_counts, scope_ms, ssd_ops  # noqa

NAME = "granite4_h_small_ep4_d10"
CELL = "serve_granite_sessions_closed"
NEW = ["ssd_ms_per_step.decode", "ssd_state_roofline.decode",
       "ssd_scan_ms_per_prefill", "ssd_scan_roofline.prefill",
       "ssd_scan_padding_pct.prefill", "prefill_window_share_pct.decode"]
WHAT = ["decode_ms", "state_roofline", "scan_ms", "scan_roofline",
        "scan_padding_pct", "prefill_window_share_pct"]

# the numbers of the catalog row ``granite-4.0-h-small``
# (model-configs/architectures.jsonl, ``config``), key by key
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        n_layer=4, d_model=64, n_head=4, vocab=96, prompt_len=32,
        max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
        layer_kinds=["ssd", "gqa", "ssd", "ssd"], n_kv_head=2, head_dim=16,
        ssd_heads=8, ssd_head_dim=8, ssd_d_state=16, ssd_chunk=8,
        n_routed_experts=12, n_experts_held=3, n_experts_per_tok=4,
        d_expert=24, d_shared=40, dtype="float32")
    # float32 against float32 on the CPU: see tests/test_ssd_lm.py
    cfg["check"].update(prompt_lens=[3, 21, 13], max_new=[6, 4, 6], limits={
        "logit_err_median": 2e-5, "logit_err_max": 2e-5,
        "state_err_max": 2e-5, "margin_max_sd": 0.0})
    return cfg


def tiny_traffic():
    tr_ = tiny._load("traffic", "closed_decode_sessions")
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
    """No width differs from the source's config; what is cut is depth,
    the experts held and the vocabulary, and ``reduced`` says so."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    assert set(src) == set(CATALOG) | {"layer_types"}
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["hidden_size"] // src["num_attention_heads"]),
            ("ssd_heads", src["mamba_n_heads"]),
            ("ssd_head_dim", src["mamba_d_head"]),
            ("ssd_d_state", src["mamba_d_state"]),
            ("ssd_groups", src["mamba_n_groups"]),
            ("ssd_conv_taps", src["mamba_d_conv"]),
            ("ssd_chunk", src["mamba_chunk_size"]),
            ("d_expert", src["intermediate_size"]),
            ("d_shared", src["shared_intermediate_size"]),
            ("n_routed_experts", src["num_local_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("embed_scale", src["embedding_multiplier"]),
            ("residual_scale", src["residual_multiplier"]),
            ("attn_scale", src["attention_multiplier"]),
            ("logits_scale", src["logits_scaling"]),
            ("tie_embeddings", src["tie_word_embeddings"]),
            ("rms_eps", src["rms_norm_eps"])):
        assert build[ours] == theirs, ours
    assert build["ssd_heads"] * build["ssd_head_dim"] \
        == src["mamba_expand"] * src["hidden_size"]
    assert build["scoring"] == "softmax_topk" and not build["gqa_gate"]
    # the period is the source's first ten layers, whole
    kind = {"mamba": "ssd", "attention": "gqa"}
    assert build["layer_kinds"] == [kind[t] for t in src["layer_types"][:10]]
    assert src["layer_types"] == src["layer_types"][:10] * 4
    assert build["n_layer"] == 10
    # the cuts, within the floors: a whole period, >= 8 experts, >= 1/8
    assert cfg["reduced"] == ["n_layer", "n_experts_held", "vocab"]
    assert cfg["published"]["n_layer"] == src["num_hidden_layers"] == 40
    assert build["n_experts_held"] * 4 == build["n_routed_experts"]
    assert build["vocab"] * 4 == src["vocab_size"]
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    assert "v5e-16" in cfg["stands_for"] and "four" in cfg["stands_for"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


def test_the_files_byte_count_is_the_models():
    """2.96 B parameters in bfloat16, 4.89 GB of state, 1.48 GB of
    pages: what ``reduced_why`` and ``assumed`` state, from the build."""
    b = committed()["build"]
    m, inner = b["d_model"], b["ssd_heads"] * b["ssd_head_dim"]
    wide = inner + 2 * b["ssd_groups"] * b["ssd_d_state"]
    ssd_layer = m * (inner + wide + b["ssd_heads"]) + inner * m
    gqa_layer = 2 * m * b["n_head"] * b["head_dim"] \
        + 2 * m * b["n_kv_head"] * b["head_dim"]
    moe = m * b["n_routed_experts"] \
        + 3 * b["n_experts_held"] * m * b["d_expert"] + 3 * m * b["d_shared"]
    params = 9 * ssd_layer + gqa_layer + 10 * moe + b["vocab"] * m
    assert params == pytest.approx(2.96e9, rel=5e-3)
    state = b["n_slots"] * 9 * (b["ssd_d_state"] * inner * 4 + 3 * wide * 2)
    assert state == pytest.approx(4.89e9, rel=5e-3)
    rows = b["n_slots"] * (b["prompt_len"] + b["max_new"])
    pages = rows * 2 * b["n_kv_head"] * b["head_dim"] * 2
    assert pages == pytest.approx(1.48e9, rel=5e-3)
    assert 2 * params + state + pages == pytest.approx(12.28e9, rel=5e-3)


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert config["runner"] == "serve_granite"
    assert traffic["clients"] == config["build"]["n_slots"] == 128
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in harness.metrics_of(bench, "per_layer",
                                                     CELL)}
    assert set(NEW) <= set(mine)
    for name, what in zip(NEW, WHAT):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_tokens_per_s"
        assert harness.load_json("layer_metrics", name + ".json") \
            == {"reader": "ssd_ops", "args": {"what": what}}
    assert {"slot_occupancy_mean", "decode_step_device_ms",
            "peak_hbm_gb.decode", "attn_ms_per_step.decode",
            "experts_ms_per_step.decode", "moe_experts_hit_pct.decode",
            "unscoped_pct.decode"} <= set(mine)
    # readers that name another mixer's scope or shapes: not here
    assert not {"state_ms_per_step.decode", "kda_state_ms_per_step",
                "kda_state_roofline", "kv_gather_roofline"} & set(mine)
    # the cell's step module is the one tests/conftest.py gives the
    # scope metrics' contract test
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "root_conftest", os.path.join(tiny.ROOT, "tests", "conftest.py"))
    table = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(table)
    from paddle_tpu.observability import device_scopes
    # what the chip's listing names the step (my chip runs, PR 42)
    assert table.STEP_MODULES_SINCE_PR37[CELL] == "jit_lm_decode_paged_s8ff8"
    assert table.STEP_MODULES_SINCE_PR37[CELL] == "jit_" \
        + device_scopes.module_name(
            "lm_decode_paged",
            ["ssd_decode", "kv_attention_decode_paged", "expert_ffn_held"])


def test_the_traffic_is_the_issues_and_leases_the_pool():
    """Closed loop, 128 callers, prompts log-uniform 513-2048 in buckets
    1024 and 2048 (half each), 256-768 new tokens: every request fits
    its slot's 2816 rows and leases 28-100 % of them."""
    cfg = committed()
    build = cfg["build"]
    traffic = harness.load_json("traffic", "closed_decode_sessions.json")
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 513,
                                     "hi": 2048}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 256, "hi": 768}
    assert (traffic["clients"], traffic["rounds"],
            traffic["first_round_min"], traffic["schedule_seed"],
            traffic["trace_seconds"]) == (128, 8, 8, 23, 10)
    assert build["prompt_buckets"] == [1024, 2048]
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 2816 and rows % build["page_size"] == 0
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 128
    buckets, leased = [], []
    for requests in plan["clients"]:
        for j, (prompt, budget) in enumerate(requests):
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 513 <= len(prompt) <= 2048
            assert (8 if j == 0 else 256) <= budget <= 768
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
            buckets.append(bucket)
            if j:
                leased.append((bucket + budget) / rows)
    assert abs(buckets.count(1024) - buckets.count(2048)) <= 2
    assert 0.4 < min(leased) and max(leased) <= 1.0
    assert 0.6 < np.mean(leased) < 0.8
    # the check's prompts: no bucket's length, no multiple of the chunk,
    # both buckets, budgets that differ
    chk = cfg["check"]
    assert len(chk["prompt_lens"]) >= 4
    assert not [n for n in chk["prompt_lens"]
                if n % build["ssd_chunk"] == 0 or n in
                build["prompt_buckets"]]
    assert {min(b for b in build["prompt_buckets"] if b >= n)
            for n in chk["prompt_lens"]} == {1024, 2048}
    assert len(set(chk["max_new"])) >= 3
    assert set(chk["limits"]) == {"logit_err_median", "state_err_median",
                                  "state_bf16_share"}


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


def test_tiny_granite_cell_agrees_with_the_reference(two_runs):
    _run, obs, setup = two_runs[0]
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5 and seen["state_err_max"] <= 2e-5
    assert seen["tokens_compared"] == 16 and seen["same_through_server"]
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    assert obs["units"]["decode_steps"] > 0
    assert obs["units"]["prefills"] > 0          # prefills INSIDE the window
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.3
    # three SSD layers: true tokens scanned, whole chunks of 8 computed
    assert 0 < obs["ssd_tokens"] <= obs["ssd_rows"]
    assert obs["ssd_rows"] % (3 * 8) == 0
    assert obs["ssd_tokens"] % 3 == 0
    assert obs["slot_steps"] == obs["counters"]["sched_slot_steps"] > 0
    pad = ssd_ops.read(obs, "scan_padding_pct")
    assert pad == pytest.approx(
        100 * (1 - obs["ssd_tokens"] / obs["ssd_rows"])) and 0 <= pad < 90
    # an untraced run has no device time to read
    for what in ("decode_ms", "state_roofline", "scan_ms", "scan_roofline",
                 "prefill_window_share_pct"):
        assert ssd_ops.read(obs, what) is None
    # [expert layers, (tokens, steps hit), held experts], window deltas
    assert obs["moe_counts"].shape == (4, 2, 3)
    assert 0 < moe_counts.read(obs, "hit_pct") <= 100
    # warm-up's 2 buckets, the 3 compared requests (stepped together,
    # then once more through the server), one admission per client
    assert len(admissions(setup)) >= 2 + 2 * 3 + 4
    assert all("state_slot" in dict(e[1]) for e in admissions(setup))


def test_setup_dispatches_the_same_work_for_two_seeds(two_runs):
    (_r1, _o1, setup1), (_r2, o2, setup2) = two_runs
    assert o2["correct"]
    n = 2 + 2 * 3 + 4
    assert admissions(setup1)[:n] == admissions(setup2)[:n]
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    # warm-up's step, the longest compared budget's 5 steps twice, the
    # priming's 2: what comes on top is the scheduler's own timing
    assert min(steps) >= 1 + 2 * 5 + 2


# ----------------------------------------------------------- the readers

MS = 1e6       # nanoseconds
DECODE, PREFILL = "jit_lm_decode_paged_s8ff8", \
    "jit_lm_prefill_paged_2048_s0b8b"
BUILD = dict(n_layer=10, layer_kinds=["ssd"] * 5 + ["gqa"] + ["ssd"] * 4,
             ssd_heads=128, ssd_head_dim=64, ssd_d_state=128, ssd_groups=1,
             ssd_chunk=256)
# (scope, ms): the ops of one decode step and of one prefill
STEP = [("ssd_decode", 3.0), ("ssd_decode/conv", 0.5),
        ("ssd_decode/state", 12.0), ("expert_ffn_held/up", 6.0),
        ("kv_attention_decode_paged/gather", 2.0), ("", 0.5)]
FILL = [("ssd_prefill", 20.0), ("ssd_prefill/conv", 1.0),
        ("ssd_prefill/scan", 8.0), ("expert_ffn_held/up", 15.0),
        ("kv_attention_prefill_paged", 4.0)]


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
            "slot_steps": 120 * steps, "ssd_tokens": 9 * 2600,
            "ssd_rows": 9 * 3072,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}, \
        window_ms


def test_readers_on_hand_laid_observations(monkeypatch):
    obs, window_ms = observations(monkeypatch)
    assert ssd_ops.read(obs, "decode_ms") == pytest.approx(15.5)
    assert ssd_ops.read(obs, "scan_ms") == pytest.approx(29.0)
    state = 120 * 9 * 128 * 64 * 128 * 4 * 2          # bytes a step
    assert ssd_ops.read(obs, "state_roofline") == pytest.approx(
        100 * state / 819e9 / 12e-3)
    assert 0 < ssd_ops.read(obs, "state_roofline") < 100
    ops = flops_ssd.scan_flops(9 * 3072, 256, 128, 64, 128, 1)
    assert ssd_ops.read(obs, "scan_roofline") == pytest.approx(
        100 * ops / 197e12 / (2 * 8e-3))
    assert 0 < ssd_ops.read(obs, "scan_roofline") < 100
    assert ssd_ops.read(obs, "scan_padding_pct") == pytest.approx(
        100 * (1 - 2600 / 3072))
    assert ssd_ops.read(obs, "prefill_window_share_pct") == pytest.approx(
        100 * 2 * 48.0 / window_ms)
    with pytest.raises(ValueError, match="cannot read"):
        ssd_ops.read(obs, "anything_else")


def test_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without device scopes (no map), a model without SSD
    layers, a run without the counters: None, never a raise — the line
    leaves the metric out."""
    obs, _w = observations(monkeypatch, scopes="none")
    for what in ("decode_ms", "state_roofline", "scan_ms", "scan_roofline"):
        assert ssd_ops.read(obs, what) is None
    obs, _w = observations(monkeypatch)
    obs["ssd_rows"] = obs["ssd_tokens"] = None
    assert ssd_ops.read(obs, "scan_padding_pct") is None
    assert ssd_ops.read(obs, "scan_roofline") is None
    obs["slot_steps"] = 0
    assert ssd_ops.read(obs, "state_roofline") is None
    plain = {**obs, "config": {"build": {"n_layer": 4, "layer_kinds": [
        "gqa", "kda", "kda", "kda"]}}}
    assert all(ssd_ops.read(plain, what) is None for what in WHAT)
    assert all(ssd_ops.read({**obs, "config": {"build": {"n_layer": 12}}},
                            what) is None for what in WHAT)


def test_operations_and_bytes_against_hand_counts():
    # one live slot, one layer, one step: the state read and written
    assert flops_ssd.state_bytes(1, 1, 128, 64, 128) == 2 * 4 * 1048576
    assert flops_ssd.state_bytes(128, 9, 128, 64, 128) \
        == pytest.approx(9.66e9, rel=1e-3)      # 11.8 ms at 819 GB/s
    assert flops_ssd.state_flops(1, 1, 128, 64, 128) == 6 * 1048576
    # a chunk of 2 rows, one head of 3 channels, a state of 5, one group:
    # rows see 1 and 2 rows (1.5 on average): C.B 5 and L(dt x) 3 a row
    # seen; C.S and the update 15 each; 2 FLOPs a multiply-accumulate
    assert flops_ssd.scan_flops(2, 2, 1, 3, 5, 1) \
        == 2 * 2 * (1.5 * (5 + 3) + 2 * 15)
    per_row = flops_ssd.scan_flops(1, 256, 128, 64, 128, 1)
    assert per_row == pytest.approx(2 * (128.5 * (128 + 8192) + 2 * 1048576))
