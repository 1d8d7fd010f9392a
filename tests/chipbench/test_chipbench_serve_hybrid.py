"""``solar_open2_250b_ep8_d4`` and its cell: the configuration's file
against its own source, the hybrid runner at a tiny size on the CPU
(contract of the observations, two seeds dispatch the same work), the
new readers on a small hand-recorded trace, and the operations-and-bytes
functions against hand counts."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_hybrid, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.layer_metrics import hybrid_ops, moe_counts  # noqa: E402

NAME = "solar_open2_250b_ep8_d4"
CELL = "serve_solar_decode_closed"


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        n_layer=4, d_model=64, n_head=4, vocab=96, prompt_len=16,
        max_new=16, prompt_buckets=[8, 16], n_slots=4, page_size=4,
        n_kv_head=2, head_dim=16, kda_heads=4, kda_head_dim=16,
        kda_gate_rank=8, n_routed_experts=16, n_experts_held=4,
        n_experts_per_tok=4, d_expert=24, dtype="float32")
    # float32 against float32 on the CPU: see tests/test_hybrid_lm.py
    cfg["check"].update(prompt_lens=[3, 12, 7], max_new=[6, 4, 6], limits={
        "logit_err_median": 2e-5, "logit_err_max": 2e-5,
        "state_err_max": 2e-5, "margin_max_sd": 0.0})
    return cfg


def tiny_traffic():
    with open(os.path.join(tiny.ROOT, "chipbench", "traffic",
                           "closed_decode_reasoning.json")) as f:
        tr_ = json.load(f)
    tr_.update(clients=4, prompt_len={"dist": "log_uniform", "lo": 2,
                                      "hi": 16},
               max_new={"dist": "uniform", "lo": 14, "hi": 16},
               first_round_min=14, prime_decode_steps=2)
    return tr_


def logged_run(monkeypatch, seed, seconds=0.4):
    return tiny.logged_run(monkeypatch, tiny_config(), tiny_traffic(), seed,
                           seconds)


admissions = tiny.admissions
BEFORE = 2 + 2 * 3     # warm-up's 2 buckets, the 3 compared requests twice


def primed(setup, seed, seconds):
    """(head, first, later, steps) of the set-up, split at the
    scheduler's own events (``chipbench_tiny.priming``)."""
    return tiny.priming(setup, tiny_config(), tiny_traffic(), seed, seconds,
                        BEFORE)


# ------------------------------------------------- the configuration file

def test_every_width_is_the_sources():
    """No width differs from the source's config: hidden, heads, KV
    heads, head sizes, expert width, the router's width, the experts per
    token, the conv's taps; what is cut is depth, the experts held and
    the vocabulary, and ``reduced`` says so."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    lin = src["linear_attn_config"]
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["head_dim"]),
            ("kda_heads", lin["num_heads"]),
            ("kda_head_dim", lin["head_dim"]),
            ("kda_conv_taps", lin["short_conv_kernel_size"]),
            ("d_expert", src["moe_intermediate_size"]),
            ("n_routed_experts", src["n_routed_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("n_shared_experts", src["n_shared_experts"]),
            ("rms_eps", src["rms_norm_eps"]),
            ("norm_topk_prob", src["norm_topk_prob"]),
            ("routed_scaling_factor", src["routed_scaling_factor"]),
            ("gqa_gate", src["use_gqa_gate"])):
        assert build[ours] == theirs, ours
    # the period of layer kinds is the source's: layer i is softmax
    # attention iff i is in gqa_layers
    period = src["gqa_interval"] + 1
    assert build["layer_kinds"] == [
        "gqa" if i in src["gqa_layers"] else "kda" for i in range(period)]
    assert build["n_layer"] % period == 0 and build["n_layer"] >= 4
    assert not src["use_rope"] and src["first_k_dense_replace"] == 0
    # the cuts, within the floors: a whole period, >= 8 experts, >= 1/8
    assert cfg["reduced"] == ["n_layer", "n_experts_held", "vocab"]
    assert cfg["published"]["n_layer"] == src["num_hidden_layers"] == 48
    assert 8 <= build["n_experts_held"] < build["n_routed_experts"]
    assert build["vocab"] * 8 >= src["vocab_size"] > build["vocab"]
    # the file's top level carries the source's config verbatim
    for key, value in src.items():
        assert cfg[key] == value, key
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert traffic["clients"] == config["build"]["n_slots"] == 128
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert {"kda_state_ms_per_step", "kda_state_roofline",
            "moe_up_ms_per_step", "moe_up_roofline",
            "gqa_gather_ms_per_step", "gqa_gather_roofline",
            "moe_experts_hit_pct.decode", "moe_load_max_over_mean.decode",
            "decode_step_device_ms", "peak_hbm_gb.decode"} <= mine
    # the gather's readers take every custom call of the step: not here
    assert not {"kv_gather_ms_per_step", "kv_gather_roofline"} & mine
    # a slot of the cell's traffic holds 256-2048 of its 2048 rows
    b = config["build"]
    assert traffic["prompt_len"]["hi"] <= b["prompt_len"]
    assert traffic["max_new"]["hi"] <= b["max_new"]


# ------------------------------------------------------ the runner, tiny

def test_tiny_hybrid_cell_agrees_with_the_reference(monkeypatch):
    run, obs, setup = logged_run(monkeypatch, 21)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5 and seen["state_err_max"] <= 2e-5
    assert seen["tokens_compared"] == 16 and seen["same_through_server"]
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    assert obs["units"]["decode_steps"] > 0
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.5
    # [expert layers, (tokens, steps hit), held experts], window deltas
    counts = obs["moe_counts"]
    assert counts.shape == (4, 2, 4) and counts.min() >= 0
    steps = obs["moe_steps"]
    assert 0 < steps <= obs["units"]["decode_steps"] + 64
    assert (counts[:, 1] <= steps).all() and counts[:, 0].sum() > 0
    assert 0 < moe_counts.read(obs, "hit_pct") <= 100
    assert moe_counts.read(obs, "max_over_mean") >= 1.0
    # warm-up's 2 buckets, the 3 compared requests (stepped together,
    # then once more through the server), then exactly one admission
    # of each client's first request before the window opens (a
    # client's next one is counted apart: the scheduler admits it when
    # the runner, under load, sees the count late)
    head, first, later, steps = primed(setup, 21, 0.4)
    assert len(admissions(head)) == BEFORE and len(first) == 4 >= len(later)
    assert steps >= 2
    assert all("state_slot" in dict(e[1]) for e in admissions(setup))


def test_setup_dispatches_the_same_work_for_two_seeds(monkeypatch):
    seeds = (3, 2 ** 31 + 5)
    _r1, _o1, setup1 = logged_run(monkeypatch, seeds[0], 0.2)
    _r2, _o2, setup2 = logged_run(monkeypatch, seeds[1], 0.2)
    # counted by the scheduler's own events, not by the clock: up to the
    # first client's admission the two seeds dispatch one list of work,
    # entry for entry; the clients' first requests are the same
    # programs and shapes (the clients are threads: the order they come
    # in is the host's); and never fewer decode steps than asked for —
    # how many more is the host's load at this size, not the seed's
    (head1, first1, _l1, after1), (head2, first2, _l2, after2) = (
        primed(s, seed, 0.2) for s, seed in zip((setup1, setup2), seeds))
    assert head1 == head2 and len(admissions(head1)) == BEFORE
    assert sorted(first1) == sorted(first2) and len(first1) == 4
    # warm-up's 2, the longest compared budget's 5 twice, the priming's 2
    assert len(head1) - BEFORE >= 2 + 2 * 5
    assert min(after1, after2) >= 2
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    assert min(steps) >= 2 + 2 * 5 + 2


# ----------------------------------------------------------- the readers

BUILD = dict(n_slots=4, n_layer=4, layer_kinds=["gqa", "kda", "kda", "kda"],
             kda_heads=4, kda_head_dim=16, kda_conv_taps=4, d_model=64,
             d_expert=24, n_experts_held=8, dtype="bfloat16",
             prompt_len=32, max_new=32, n_kv_head=2, head_dim=16)
MS = 1e6       # nanoseconds


def recorded(ops):
    """A hand-recorded window: two decode executions of 10 ms each, each
    caused by a ``serving.decode_step`` span, holding ``ops`` (name,
    offset ms, duration ms); a prefill execution between them holds the
    same ops and must not be counted."""
    events, modules, spans = [], [], []
    for start, span in ((10, SPAN), (30, "serving.prefill@8"), (50, SPAN)):
        modules.append([("jit_lm_decode_paged_s1a2b(1)" if span == SPAN
                         else "jit_lm_prefill_paged_8_s5dba(2)"),
                        start * MS, 10 * MS])
        spans.append((span, (start - 1) * MS, (start + 10) * MS))
        events += [[name, (start + off) * MS, dur * MS]
                   for name, off, dur in ops]
    # the engine's snapshot of the expert counters, under the
    # first step's span: an execution, and no step
    modules.append(["jit_copy(5)", 9.5 * MS, 0.001 * MS])
    events.append(["copy.1 copy s32[2,4] ", 9.5 * MS, 0.001 * MS])
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    return tr.reduce_window(trace, 0.0, 70 * MS, spans)


SPAN = hybrid_ops.SPAN
OPS = [("add_select_fusion fusion f32[4,4,16,16] ", 0.0, 0.5),
       ("fusion.14 fusion f32[4,4,2,16] ", 1.0, 0.25),
       ("select_fusion.2 fusion bf16[4,3,192] ", 2.0, 0.25),
       ("fusion.99 fusion f32[4,64] ", 3.0, 4.0),
       ("gather_pages.2 custom-call bf16[256,32] tpu_custom_call ", 7.0, 1.0),
       ("fusion.153 fusion f32[4,8,24] ", 8.0, 0.1),
       ("fusion.154 fusion bf16[4,8,24] ", 8.5, 0.4),
       ("convolution_add_fusion.7 fusion f32[4,64] ", 9.0, 0.3)]


def observations(**extra):
    counts = np.zeros((4, 2, 4), np.int64)
    counts[:, 0] = [[6, 2, 0, 4]] * 4          # tokens over 2 steps
    counts[:, 1] = [[2, 1, 0, 2]] * 4          # steps hit
    return {"reduced": recorded(OPS),
            "config": {"build": dict(BUILD), "kv_codec": "bf16"},
            "units": {"decode_steps": 2}, "moe_counts": counts,
            "moe_steps": 2,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            **extra}


def test_readers_select_by_shape():
    obs = observations()
    assert hybrid_ops.kda_shapes(BUILD) == (
        "f32[4,4,16,16]", "f32[4,4,2,16]", "bf16[4,3,192]")
    assert hybrid_ops.expert_shapes(BUILD) == ("f32[4,8,24]", "bf16[4,8,24]")
    # the three state ops of a DECODE execution: 0.5 + 0.25 + 0.25 ms
    assert hybrid_ops.read(obs, "kda_state", "ms") == pytest.approx(1.0)
    # the gate product and the hidden rows; neither the page gather nor
    # the down projection, whose result looks like any [4, 64]
    assert hybrid_ops.read(obs, "expert_up", "ms") == pytest.approx(0.5)
    kda_bytes = flops_hybrid.kda_state_bytes_per_step(4, 3, 4, 16, 4, 2)
    assert hybrid_ops.read(obs, "kda_state", "roofline") == pytest.approx(
        100 * kda_bytes / 819e9 / 1e-3)
    up_bytes = flops_hybrid.expert_up_bytes_per_step(4, 4, 8, 64, 24, 2)
    assert hybrid_ops.read(obs, "expert_up", "roofline") == pytest.approx(
        100 * up_bytes / 819e9 / 0.5e-3)
    # the kernel by its name AND its result: 4 slots x 64 rows of 2 x 16
    assert hybrid_ops.gather_shape(BUILD, "bf16") == "bf16[256,32]"
    assert hybrid_ops.read(obs, "page_gather", "ms") == pytest.approx(1.0)
    gather_bytes = flops_hybrid.page_gather_bytes_per_step(4, 64, 1, 32, 2)
    assert hybrid_ops.read(obs, "page_gather", "roofline") == \
        pytest.approx(100 * gather_bytes / 819e9 / 1e-3)
    renamed = observations()
    renamed["reduced"] = recorded(
        [("fusion.7 fusion bf16[256,32] ", 7.0, 1.0)])
    assert hybrid_ops.read(renamed, "page_gather", "ms") is None
    assert moe_counts.read(obs, "hit_pct") == pytest.approx(
        100 * 20 / (16 * 2))
    assert moe_counts.read(obs, "max_over_mean") == pytest.approx(2.0)


def test_readers_find_nothing_where_there_is_nothing():
    """A configuration without KDA layers, a program without the
    counters, a window without decode steps: None, never an error."""
    obs = observations()
    del obs["config"]["build"]["kda_heads"]
    assert hybrid_ops.read(obs, "kda_state", "ms") is None
    del obs["config"]["build"]["n_experts_held"]
    assert hybrid_ops.read(obs, "expert_up", "roofline") is None
    del obs["config"]["build"]["n_kv_head"]
    assert hybrid_ops.read(obs, "page_gather", "roofline") is None
    obs = observations(moe_counts=None)
    assert moe_counts.read(obs, "hit_pct") is None
    obs = observations()
    obs["reduced"] = recorded([])
    assert hybrid_ops.read(obs, "kda_state", "ms") is None
    obs["reduced"]["host_spans"] = []
    assert hybrid_ops.read(obs, "expert_up", "ms") is None


def test_every_new_metric_has_its_reader_file():
    bench = harness.load_benchmark()
    for m in harness.metrics_of(bench, "per_layer", CELL):
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.isfile(os.path.join(
            harness.HERE, "layer_metrics", spec["reader"] + ".py"))


# ------------------------------------------------- operations and bytes

def test_bytes_and_operations_against_hand_counts():
    # the cell's sizes: 128 slots, 3 KDA layers, 64 heads of 128
    state = 128 * 64 * 128 * 128 * 4                  # 536 870 912
    conv = 128 * 3 * 24576 * 2                        # 18 874 368
    assert flops_hybrid.kda_state_bytes_per_step(
        128, 3, 64, 128, 4, 2) == 2 * 3 * (state + conv) == 3334471680
    assert flops_hybrid.kda_state_flops_per_step(128, 3, 64, 128) \
        == 6 * 128 * 64 * 128 * 128 * 3
    # the cell's decode step: 128 tokens through the gate and up matrices
    # of 40 experts in each of 4 layers, bf16
    weights = 2 * 40 * 4096 * 1280 * 2                 # 838 860 800
    tokens = 2 * 128 * 4096 * 2
    assert flops_hybrid.expert_up_bytes_per_step(
        128, 4, 40, 4096, 1280, 2) == 4 * (weights + tokens) \
        == 3363831808
    assert flops_hybrid.expert_up_flops_per_step(128, 4, 40, 4096, 1280) \
        == 4 * 128 * 40 * 4096 * 1280 * 4
    # both gathers of the one softmax layer: 128 slots x 2048 rows of
    # 8 x 128 bf16, K and V, read once and written once
    assert flops_hybrid.page_gather_bytes_per_step(
        128, 2048, 1, 1024, 2) == 4 * 128 * 2048 * 1024 * 2 == 2147483648
