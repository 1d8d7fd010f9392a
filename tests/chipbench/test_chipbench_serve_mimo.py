"""``mimo_v2_flash_ep16_d7`` and its cell: the configuration's file
against the catalog's row key by key, the cut against ``build``, the
traffic's schedule against the buckets, a slot's rows and the full
group's pool, the runner at a tiny size on the CPU (ONE run for the
module: the contract of the observations), the new readers, and the
roofline's functions against a hand count."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops, flops_grouped_kv, harness  # noqa: E402
from chipbench.generators import _multiset as ms  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import grouped_kv_attn, scope_ms  # noqa: E402

NAME = "mimo_v2_flash_ep16_d7"
CELL = "serve_mimo_decode_deepctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("full_kv_attn_ms_per_step.decode",
       "sink_window_attn_ms_per_step.decode",
       "full_kv_attn_roofline.decode", "sink_window_attn_roofline.decode",
       "full_kv_live_rows_pct.decode")


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def committed_traffic():
    with open(os.path.join(tiny.ROOT, "chipbench", "traffic",
                           "closed_decode_deepctx.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        d_model=64, d_inner=96, n_head=8, vocab=96, prompt_len=32,
        max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
        n_pages=48, n_kv_head=2, swa_n_kv_head=4, head_dim=24,
        gqa_v_head_dim=16, rotary_dim=8, window=8, n_routed_experts=16,
        n_experts_held=4, held_start=4, n_experts_per_tok=4, d_expert=24,
        dtype="float32")
    # float32 against float32 on the CPU: see tests/test_mimo_serve.py
    cfg["check"].update(prompt_lens=[21, 11, 6, 2], max_new=[6, 8, 7, 4],
                        limits={"logit_err_median": 2e-5,
                                "window_rows_wrong_share": 0.0})
    return cfg


def tiny_traffic():
    tr_ = committed_traffic()
    tr_.update(clients=4, prompt_len={"dist": "log_uniform", "lo": 9,
                                      "hi": 32},
               max_new={"dist": "uniform", "lo": 14, "hi": 16},
               first_round_min=14, prime_decode_steps=2)
    return tr_


# ------------------------------------------------- the configuration file

def test_every_width_is_the_catalog_rows():
    """The file's top level holds the catalog row's ``config`` key by
    key; no width of ``build`` differs from it; what is cut is depth,
    the experts held and the vocabulary, and ``reduced`` says so."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiMo-V2-Flash")
        assert src == row["config"] and cfg["source"] == row["source_url"]
        assert cfg["published"]["described_as"] == row["described_as"]
    for key, value in src.items():
        assert cfg[key] == value, key
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_head", src["swa_num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("swa_n_kv_head", src["swa_num_key_value_heads"]),
            ("head_dim", src["head_dim"]),
            ("head_dim", src["swa_head_dim"]),
            ("gqa_v_head_dim", src["v_head_dim"]),
            ("gqa_v_head_dim", src["swa_v_head_dim"]),
            ("rotary_dim", int(src["head_dim"]
                               * src["partial_rotary_factor"])),
            ("gqa_rope_theta", src["rope_theta"]),
            ("rope_theta", src["swa_rope_theta"]),
            ("window", src["sliding_window"]),
            ("value_scale", src["attention_value_scale"]),
            ("swa_sink", src["add_swa_attention_sink_bias"]),
            ("d_expert", src["moe_intermediate_size"]),
            ("n_routed_experts", src["n_routed_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("norm_topk_prob", src["norm_topk_prob"]),
            ("rms_eps", src["layernorm_epsilon"])):
        assert build[ours] == theirs, ours
    assert (build["n_head"], build["n_kv_head"], build["swa_n_kv_head"],
            build["head_dim"], build["gqa_v_head_dim"], build["rotary_dim"],
            build["window"]) == (64, 4, 8, 192, 128, 64, 128)
    assert not src["add_full_attention_sink_bias"]
    assert not src["attention_bias"] and not src["tie_word_embeddings"]
    assert src["n_shared_experts"] is None \
        and build["n_shared_experts"] == 0
    assert src["routed_scaling_factor"] is None \
        and build["routed_scaling_factor"] == 1.0
    assert src["scoring_func"] == "sigmoid" and build["router_bias"]
    assert src["n_group"] == src["topk_group"] == 1
    assert not build["gqa_gate"] and not build["qk_norm"]
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    # the cuts: the chip's share of sixteen, an eighth of the vocabulary
    assert build["n_experts_held"] * 16 == src["n_routed_experts"]
    assert build["vocab"] * 8 == src["vocab_size"]
    assert build["n_experts_held"] >= 8 and build["held_start"] == 0


def test_the_cut_is_depth_experts_and_vocabulary_and_says_so():
    cfg = committed()
    build, pub = cfg["build"], cfg["published"]
    src = pub["config"]
    assert cfg["reduced"] == ["n_layer", "n_experts_held", "vocab"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert pub["n_layer"] == src["num_hidden_layers"] == 48
    assert pub["n_routed_experts"] == 256 and pub["vocab"] == 152576
    pattern = src["hybrid_layer_pattern"]
    assert (pub["full_layers"], pub["window_layers"]) \
        == (pattern.count(0), pattern.count(1)) == (9, 39)
    kinds = {0: "gqa", 1: "swa"}
    served = [build["layer_kinds"][i % len(build["layer_kinds"])]
              for i in range(build["n_layer"])]
    # the published layers 0-6: the leading dense layer and one whole
    # period (full, 5 window) of expert layers — 5 window : 1 full
    assert served == [kinds[t] for t in pattern[:7]]
    assert pattern[5:11] == [0, 1, 1, 1, 1, 1] == pattern[11:17]
    assert src["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert build["first_k_dense"] == src["moe_layer_freq"].count(0) == 1
    after = served[build["first_k_dense"]:]
    assert len(after) == 6 >= 4 and after.count("gqa") * 6 == len(after)
    for word in ("sixteen", "16 of each layer's 256", "19072", "41 layers"):
        assert word in cfg["stands_for"], word
    for word in ("7 of 48", "3.43 B", "6.86 GB", "89.1 M", "94.4 M"):
        assert word in cfg["reduced_why"], word
    # the parameters the file reckons, from build
    m, h = build["d_model"], build["n_head"]
    dk, dv = build["head_dim"], build["gqa_v_head_dim"]
    attn = lambda kv: m * (h * dk + kv * dk + kv * dv) + h * dv * m  # noqa
    full, win = attn(build["n_kv_head"]), attn(build["swa_n_kv_head"]) + h
    assert full / 1e6 == pytest.approx(89.1, abs=0.05)
    assert win / 1e6 == pytest.approx(94.4, abs=0.05)
    experts = build["n_experts_held"] * 3 * m * build["d_expert"] \
        + m * build["n_routed_experts"]
    total = full + 3 * m * build["d_inner"] + 5 * win + full \
        + 6 * experts + 2 * build["vocab"] * m
    assert total / 1e9 == pytest.approx(3.43, abs=0.01)
    for key in ("stands_for", "reduced_why", "assumed", "departures"):
        assert cfg[key] and "TO BE WRITTEN" not in json.dumps(cfg[key])
    assert any("MTP" in d for d in cfg["departures"])
    chk = cfg["check"]
    assert "TO BE WRITTEN" not in chk["why"]
    for word in ("low_precision", "sink", "rotary_dim", "value_scale",
                 "127"):
        assert word in chk["why"], word


def test_the_reference_is_importable_and_plain():
    import importlib
    ref = importlib.import_module("chipbench.reference." + NAME)
    names = ref.param_names(committed()["build"])
    assert "lm_l1_attn.sink" in names and "lm_l0_attn.sink" not in names
    assert "lm_l0_ffn.w_gate" in names and "lm_l1_moe.router_bias" in names
    with open(ref.__file__) as f:
        text = f.read()
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "paddle_tpu" not in text.split('"""')[2]


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert traffic["clients"] == config["build"]["n_slots"] == 24
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][-1]["name"] == CELL
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert {"slot_occupancy_mean", "itl_mean_ms",
            "kv_pages_held_pct.decode", "compiles_in_window.decode",
            "decode_step_device_ms", "decode_busy_ms_per_step",
            "device_idle_pct.decode", "peak_hbm_gb.decode",
            "sched_host_ms_per_step", "fetch_lag_ms.decode",
            "moe_experts_hit_pct.decode", "moe_load_max_over_mean.decode",
            "attn_ms_per_step.decode", "experts_ms_per_step.decode",
            "sample_ms_per_step.decode", "unscoped_pct.decode",
            "host_pause_pct.decode", "dispatch_starved_pct.decode",
            "kv_window_pages_held_pct.decode",
            "kv_window_pages_recycled_per_step.decode", *NEW} <= mine
    # flops_window.py reckons 2 * n_kv * head_dim a row: not this cell's
    assert not {m for m in mine if m.startswith(
        ("window_attn_", "moe_up_", "kv_gather_", "gqa_gather_",
         "kda_state_", "dsa_", "mla_", "ssd_", "shortconv_"))}
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.isfile(os.path.join(
            harness.HERE, "layer_metrics", spec["reader"] + ".py"))
    for words in [c["why"] for c in bench["configs"]] \
            + [w["why"] for w in bench["workloads"]]:
        assert 1 <= len(words) <= 200


def test_the_schedule_fits_its_buckets_its_slots_and_the_pool():
    """24 callers, six a bucket in the FIRST round; every request asks
    for at least 3584 tokens and fits its slot; the full group's pool
    holds what the first round leases (bucket + budget a slot) and is
    NOT every slot at full length; the check's prompts are as the issue
    names them."""
    cfg, traffic = committed(), committed_traffic()
    build = cfg["build"]
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 2049,
                                     "hi": 32768}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 3584,
                                  "hi": 4096}
    assert (traffic["clients"], traffic["rounds"],
            traffic["first_round_min"], traffic["prime_decode_steps"],
            traffic["schedule_seed"], traffic["trace_seconds"]) \
        == (24, 2, 3584, 4, 18, 10)
    for name in os.listdir(os.path.join(tiny.ROOT, "chipbench", "traffic")):
        if name != "closed_decode_deepctx.json":
            with open(os.path.join(tiny.ROOT, "chipbench", "traffic",
                                   name)) as f:
                assert json.load(f).get("schedule_seed") != 18, name
    buckets = build["prompt_buckets"]
    assert buckets == [4096, 8192, 16384, 32768]
    rows = build["prompt_len"] + build["max_new"]
    ps = build["page_size"]
    assert rows == 36864 and rows % ps == 0
    for seed in (3, 2 ** 31 + 17, 3000000019):
        plan = closed_loop.make(traffic, cfg, seed, 30.0)
        assert len(plan["clients"]) == 24
        firsts, leased = [], 0
        for requests in plan["clients"]:
            for prompt, budget in requests:
                assert 2049 <= len(prompt) <= 32768
                assert 3584 <= budget <= 4096
                assert ms.bucket_of(len(prompt), buckets) + budget <= rows
                assert prompt.max() < build["vocab"] and prompt.min() >= 1
            prompt, budget = requests[0]
            bucket = ms.bucket_of(len(prompt), buckets)
            firsts.append(bucket)
            leased += -(-(bucket + budget) // ps)
        assert [firsts.count(b) for b in buckets] == [6, 6, 6, 6]
        assert leased <= build["n_pages"]
    assert build["n_pages"] * ps == 6 * sum(buckets) + 24 * 4096 == 466944
    assert build["n_pages"] < build["n_slots"] * rows // ps
    assert "466944 rows" in cfg["assumed"]["cache"]
    # a window of 128 over pages of 16: a ring of 9, 144 rows a slot
    assert -(-build["window"] // ps) + 1 == 9
    chk = cfg["check"]
    assert chk["prompt_lens"] == [5000, 2500, 120, 60]
    assert all(64 <= m <= 96 for m in chk["max_new"])
    w = build["window"]
    lens, news = chk["prompt_lens"], chk["max_new"]
    assert lens[2] + 9 - 1 == w            # crosses at its 9th token
    assert lens[2] < w < lens[2] + news[2] and lens[3] + news[3] < w
    assert chk["min_released"] >= 1
    assert set(chk["limits"]) == {"logit_err_median",
                                  "window_rows_wrong_share"}


# ------------------------------------------------------ the runner, tiny

@pytest.fixture(scope="module")
def tiny_run():
    return tiny.run_cell(tiny_config(), tiny_traffic(), 3, 0.3)


def test_tiny_mimo_cell_agrees_with_the_reference(tiny_run):
    _run, obs = tiny_run
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5
    assert seen["window_rows_wrong_share"] == 0.0
    assert seen["window_pages_released"] >= 1
    assert seen["tokens_compared"] == 25 and seen["same_through_server"]
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    steps = obs["units"]["decode_steps"]
    assert steps > 0
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.3
    assert 0 < obs["kv_window_pages_held"] <= 1
    assert obs["window_pages_released"] > 0
    # five window layers of a window of 8, two full layers of at most 48
    # live rows a slot; every step gathers 4 slots x 48 rows a full layer
    assert 0 < obs["window_rows"] <= 5 * 8 * 4 * (steps + 2)
    assert 0 < obs["full_rows"] <= 2 * 48 * 4 * (steps + 2)
    # (the two counters are read one after the other at a window's edge:
    # under load a step ends between the readings, either way)
    assert 2 * 4 * 48 * (steps - 2) <= obs["full_rows_gathered"] \
        <= 2 * 4 * 48 * (steps + 2)
    assert obs["notes"]["kv_row_bytes"] == {
        "full": 2 * 2 * (24 + 16) * 4, "window": 5 * 4 * (24 + 16) * 4}
    # six expert layers of seven; [layers, (tokens, steps hit), held]
    assert obs["moe_counts"].shape == (6, 2, 4)
    live = grouped_kv_attn.read(obs, "live_rows_pct")
    assert live == pytest.approx(
        100.0 * obs["full_rows"] / obs["full_rows_gathered"])
    assert 10 < live < 100


# ----------------------------------------------------------- the readers

def test_the_rooflines_read_rows_bytes_and_scope_time(monkeypatch):
    """Live rows x ``kv_bytes`` over the scope's time against the HBM
    peak, each kind at its OWN KV head count; nothing to read gives
    None, never an error."""
    build = committed()["build"]
    asked = []

    def fake_ms(obs, what, module, scopes, unit):
        asked.append(tuple(scopes))
        return {tuple(grouped_kv_attn.FULL): 20.0,
                tuple(grouped_kv_attn.WINDOW): 1.0}[tuple(scopes)]
    monkeypatch.setattr(scope_ms, "read", fake_ms)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    steps = 100
    obs = {"config": {"build": build}, "units": {"decode_steps": steps},
           "peaks": peaks,
           "full_rows": 2 * 24 * 12000 * steps,
           "window_rows": 5 * 24 * 128 * steps,
           "full_rows_gathered": 2 * 24 * 36864 * steps}
    full = grouped_kv_attn.read(obs, "full_roofline")
    want = 100.0 * (2 * 24 * 12000 * 4 * 320 * 2 / 819e9) / 20e-3
    assert full == pytest.approx(want) and 0 < full < 100
    win = grouped_kv_attn.read(obs, "window_roofline")
    want = 100.0 * (5 * 24 * 128 * 8 * 320 * 2 / 819e9) / 1e-3
    assert win == pytest.approx(want) and 0 < win < 100
    assert asked == [tuple(grouped_kv_attn.FULL),
                     tuple(grouped_kv_attn.WINDOW)]
    assert grouped_kv_attn.read(obs, "live_rows_pct") \
        == pytest.approx(100 * 12000 / 36864)
    # a parent's observations: no such counters, nothing to read
    bare = {"config": {"build": build}, "units": {"decode_steps": steps},
            "peaks": peaks}
    for what in ("full_roofline", "window_roofline", "live_rows_pct"):
        assert grouped_kv_attn.read(bare, what) is None
    assert grouped_kv_attn.read({**bare, "units": {}}, "full_ms") is None
    with pytest.raises(ValueError):
        grouped_kv_attn.read(obs, "other")
    # the two scope times come through the same reader (scope_ms's
    # contract test pins ITS metrics to PR 35's ten), by the program's
    # names: a window layer's phases lie under .../window/... and not in
    # the full layers' three
    assert grouped_kv_attn.read(obs, "full_ms") == 20.0
    assert grouped_kv_attn.read(obs, "window_ms") == 1.0
    for metric, what in (
            ("full_kv_attn_ms_per_step.decode", "full_ms"),
            ("sink_window_attn_ms_per_step.decode", "window_ms"),
            ("full_kv_attn_roofline.decode", "full_roofline"),
            ("sink_window_attn_roofline.decode", "window_roofline"),
            ("full_kv_live_rows_pct.decode", "live_rows_pct")):
        spec = harness.load_json("layer_metrics", metric + ".json")
        assert spec == {"reader": "grouped_kv_attn",
                        "args": {"what": what}}
    from paddle_tpu.observability import device_scopes
    for scope in grouped_kv_attn.FULL + grouped_kv_attn.WINDOW:
        op, *phases = scope.split("/")
        assert all(p in device_scopes.PHASES[op] or
                   f"{op}/{p}" in device_scopes.PHASES for p in phases)
    assert not scope_ms.in_scope(
        "kv_attention_decode_paged/window/gather", grouped_kv_attn.FULL)
    assert scope_ms.in_scope("kv_attention_decode_paged/gather",
                             grouped_kv_attn.FULL)
    assert scope_ms.in_scope("kv_attention_decode_paged/window/attend",
                             grouped_kv_attn.WINDOW)


def test_bytes_and_operations_against_a_hand_count():
    # one full layer, one slot of 1000 live rows: 4 KV heads of 192 +
    # 128 values in bfloat16; 64 query heads over each row's key and value
    assert flops_grouped_kv.kv_bytes(1000, 4, 192, 128, 2) \
        == 1000 * (4 * 192 + 4 * 128) * 2 == 2_560_000
    assert flops_grouped_kv.kv_bytes(1000, 8, 192, 128, 2) == 5_120_000
    assert flops_grouped_kv.attn_flops(1000, 64, 192, 128) \
        == 2 * 1000 * 64 * (192 + 128)
    # equal heads: flops_window's count; unequal: a fifth fewer bytes
    from chipbench import flops_window
    assert flops_grouped_kv.kv_bytes(7, 4, 128, 128, 2) \
        == flops_window.window_bytes(7, 4, 128, 2)
    assert flops_grouped_kv.attn_flops(7, 32, 128, 128) \
        == flops_window.window_flops(7, 32, 128)
    assert flops_window.window_bytes(7, 8, 192, 2) \
        == pytest.approx(1.2 * flops_grouped_kv.kv_bytes(7, 8, 192, 128, 2))
    # memory-bound on a v5e: 16 FLOP a byte against a ridge of 240
    peaks = harness.load_json("peaks.json")["device_kinds"]["TPU v5 lite"]
    ops = flops_grouped_kv.attn_flops(1e6, 64, 192, 128)
    bytes_ = flops_grouped_kv.kv_bytes(1e6, 4, 192, 128, 2)
    assert ops / peaks["bf16_flops"] < bytes_ / peaks["hbm_bytes_per_s"]
    assert flops.roofline_pct(ops, bytes_, bytes_ / 819e9, peaks) \
        == pytest.approx(100.0)
