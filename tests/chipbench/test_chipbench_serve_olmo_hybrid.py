"""``olmo_hybrid_7b_pp2_d16`` and its cell: the configuration's file
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

from chipbench import flops_gdn, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import gdn_ops, scope_ms  # noqa: E402

NAME = "olmo_hybrid_7b_pp2_d16"
CELL = "serve_olmo_hybrid_docqa_closed"
NEW = ["gdn_scan_ms_per_prefill", "gdn_scan_roofline.prefill",
       "gdn_scan_padding_pct.prefill", "gdn_state_ms_per_step.decode",
       "gdn_state_roofline.decode"]
WHAT = ["scan_ms", "scan_roofline", "scan_padding_pct", "state_ms",
        "state_roofline"]

# the numbers of the catalog row ``Olmo-Hybrid-7B``
# (model-configs/architectures.jsonl, ``config``), key by key
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
LAYER_TYPES = (["linear_attention"] * 3 + ["full_attention"]) * 8


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        n_layer=4, d_model=48, d_inner=80, n_head=3, vocab=96,
        prompt_len=32, max_new=16, prompt_buckets=[16, 32], n_slots=4,
        page_size=4, first_k_dense=4, n_kv_head=3, head_dim=16,
        gdn_heads=3, gdn_key_dim=8, gdn_value_dim=16, gdn_chunk=4,
        dtype="float32")
    # float32 against float32 on the CPU: see tests/test_olmo_hybrid_serve.py
    cfg["check"].update(prompt_lens=[3, 21, 13], max_new=[6, 4, 6], limits={
        "logit_err_median": 2e-5, "logit_err_max": 2e-5,
        "state_err_max": 2e-5, "margin_max_sd": 0.0})
    return cfg


def tiny_traffic():
    tr_ = tiny._load("traffic", "closed_docqa_longprompt")
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
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("vocab", src["vocab_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["hidden_size"] // src["num_attention_heads"]),
            ("gdn_heads", src["linear_num_key_heads"]),
            ("gdn_heads", src["linear_num_value_heads"]),
            ("gdn_key_dim", src["linear_key_head_dim"]),
            ("gdn_value_dim", src["linear_value_head_dim"]),
            ("gdn_conv_taps", src["linear_conv_kernel_dim"]),
            ("rms_eps", src["rms_norm_eps"])):
        assert build[ours] == theirs, ours
    assert not src["tie_word_embeddings"] and "tie_embeddings" not in build
    assert src["linear_allow_neg_eigval"]      # beta = 2 sigmoid(.)
    assert src["rope_parameters"] == {"rope_theta": None}
    assert "gqa_rope_theta" not in build       # no rotation is built
    assert build["qk_norm"] == "projection" and not build["gqa_gate"]
    assert build["post_norms"] and not build["pre_norms"]
    # no expert layer anywhere: every layer's feed-forward is dense
    assert build["first_k_dense"] == build["n_layer"] == 16
    assert not [k for k in build if "expert" in k or k == "d_expert"]
    kind = {"linear_attention": "gdn", "full_attention": "gqa"}
    assert build["layer_kinds"] == [kind[t] for t in src["layer_types"][:4]]
    assert src["layer_types"] == src["layer_types"][:4] * 8
    # the cut, within the floors: whole periods, the whole vocabulary
    assert cfg["reduced"] == ["n_layer"]
    assert cfg["published"]["n_layer"] == src["num_hidden_layers"] == 32
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    assert "TWO-STAGE PIPELINE" in cfg["stands_for"]
    assert "larger than deployed" in cfg["stands_for"]
    assert {"linear_layer", "full_layer", "block", "decay_start", "weights"} \
        <= set(cfg["assumed"])
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and entry["file"].endswith(
        NAME + ".json")


def test_the_files_byte_count_is_the_models():
    """4.10 B parameters = 8.20 GB in bfloat16, 4.15 GB of pages,
    0.22 GB of state: what ``reduced_why`` and ``assumed`` state, from
    the build."""
    cfg = committed()
    b = cfg["build"]
    m, f, h = b["d_model"], b["d_inner"], b["gdn_heads"]
    dk, dv = b["gdn_key_dim"], b["gdn_value_dim"]
    wide = 2 * h * dk + h * dv
    linear = 2 * m * h * dk + 3 * m * h * dv + 2 * m * h \
        + b["gdn_conv_taps"] * wide + 2 * h + dv
    full = 4 * m * m + 2 * m
    ffn = 3 * m * f
    period = 3 * (linear + ffn + 2 * m) + full + ffn + 2 * m
    params = 4 * period + 2 * b["vocab"] * m + m
    assert linear == pytest.approx(88.7e6, rel=2e-3)
    assert full == pytest.approx(59.0e6, rel=2e-3)
    assert ffn == pytest.approx(126.8e6, rel=2e-3)
    assert params == pytest.approx(4.10e9, rel=2e-3)
    assert "4.101 B parameters = 8.20 GB" in cfg["reduced_why"]
    rows = b["n_slots"] * (b["prompt_len"] + b["max_new"])
    pages = rows * 4 * 2 * b["n_kv_head"] * b["head_dim"] * 2
    assert pages == pytest.approx(4.15e9, rel=2e-3)
    state = b["n_slots"] * 12 * (h * dk * dv * 4 + 3 * wide * 2)
    assert state == pytest.approx(0.219e9, rel=5e-3)
    assert 2 * params + pages + state == pytest.approx(12.57e9, rel=2e-3)


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert cell == bench["workloads"][-1]              # appended, not put in
    assert config["runner"] == "serve_olmo_hybrid"
    assert traffic["clients"] == config["build"]["n_slots"] == 8
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"]: m for m in harness.metrics_of(bench, "per_layer",
                                                     CELL)}
    assert [m["name"] for m in bench["per_layer"][-5:]] == NEW
    for name, what in zip(NEW, WHAT):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_tokens_per_s"
        assert mine[name]["layer"] == "kernels"
        assert harness.load_json("layer_metrics", name + ".json") \
            == {"reader": "gdn_ops", "args": {"what": what}}
    assert {"slot_occupancy_mean", "itl_mean_ms", "decode_step_device_ms",
            "decode_busy_ms_per_step", "peak_hbm_gb.decode",
            "attn_ms_per_step.decode", "device_idle_pct.decode",
            "host_pause_pct.decode", "dispatch_starved_pct.decode",
            "kv_pages_held_pct.decode", "compiles_in_window.decode",
            "unscoped_pct.decode", "setup_build_s", "runtime_start_s"} \
        <= set(mine)
    # readers that name another mixer's scope, shapes or layer kinds, and
    # the expert layers' (the model has none): not here
    assert not {"state_ms_per_step.decode", "kda_state_roofline",
                "attn_ms_per_prefill", "prefill_window_share_pct.decode",
                "experts_ms_per_step.decode", "moe_experts_hit_pct.decode",
                "ssd_state_roofline.decode"} & set(mine)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "root_conftest", os.path.join(tiny.ROOT, "tests", "conftest.py"))
    table = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(table)
    from paddle_tpu.observability import device_scopes
    assert table.STEP_MODULES_SINCE_PR37[CELL] == "jit_" \
        + device_scopes.module_name(
            "lm_decode_paged", ["gdn_decode", "kv_attention_decode_paged",
                                "swiglu_ffn", "token_sample"])


def test_the_traffic_is_the_issues_and_leases_the_pool():
    """Closed loop, 8 callers, prompts log-uniform 2049-8192 in buckets
    4096 and 8192 (half each), 64-256 new tokens: every request fits its
    slot's 8448 rows and leases 49-100 % of them."""
    cfg = committed()
    build = cfg["build"]
    traffic = harness.load_json("traffic", "closed_docqa_longprompt.json")
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 2049,
                                     "hi": 8192}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert (traffic["clients"], traffic["first_round_min"],
            traffic["schedule_seed"], traffic["trace_seconds"]) \
        == (8, 8, 23, 10)
    assert build["prompt_buckets"] == [4096, 8192]
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 8448 and rows % build["page_size"] == 0
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 8
    buckets, leased = [], []
    for requests in plan["clients"]:
        assert len(requests) == traffic["rounds"]
        for j, (prompt, budget) in enumerate(requests):
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 2049 <= len(prompt) <= 8192
            assert (8 if j == 0 else 64) <= budget <= 256
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
            buckets.append(bucket)
            if j:
                leased.append((bucket + budget) / rows)
    assert abs(buckets.count(4096) - buckets.count(8192)) <= 2
    assert 0.49 < min(leased) and max(leased) <= 1.0
    # the check's prompts: no bucket's length, no multiple of the chunk,
    # both buckets, one shorter than a block of the scan, budgets that
    # differ
    chk = cfg["check"]
    assert len(chk["prompt_lens"]) >= 4
    assert not [n for n in chk["prompt_lens"]
                if n % build["gdn_chunk"] == 0 or n in
                build["prompt_buckets"]]
    assert {min(b for b in build["prompt_buckets"] if b >= n)
            for n in chk["prompt_lens"]} == {4096, 8192}
    assert min(chk["prompt_lens"]) < 1024 and len(set(chk["max_new"])) >= 3
    assert set(chk["limits"]) == {"logit_err_median", "state_err_median",
                                  "state_bf16_share"}
    assert chk["state_dtype"] == "float32"


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


def test_tiny_olmo_hybrid_cell_agrees_with_the_reference(two_runs):
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
    # three linear layers: true tokens scanned, whole blocks computed
    # (a bucket of 16 is one block of 4 chunks, of 32 one of 8)
    assert 0 < obs["gdn_tokens"] <= obs["gdn_rows"]
    assert obs["gdn_rows"] % (3 * 16) == 0 and obs["gdn_tokens"] % 3 == 0
    assert obs["slot_steps"] == obs["counters"]["sched_slot_steps"] > 0
    pad = gdn_ops.read(obs, "scan_padding_pct")
    assert pad == pytest.approx(
        100 * (1 - obs["gdn_tokens"] / obs["gdn_rows"])) and 0 <= pad < 95
    # an untraced run has no device time to read
    for what in ("scan_ms", "scan_roofline", "state_ms", "state_roofline"):
        assert gdn_ops.read(obs, what) is None
    assert "moe_counts" not in obs               # no expert layer to count
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
DECODE, PREFILL = "jit_lm_decode_paged_scfb5", \
    "jit_lm_prefill_paged_8192_sba48"
BUILD = dict(n_layer=16, layer_kinds=["gdn", "gdn", "gdn", "gqa"],
             gdn_heads=30, gdn_key_dim=96, gdn_value_dim=192, gdn_chunk=64)
# (scope, ms): the ops of one decode step and of one prefill
STEP = [("gdn_decode", 2.0), ("gdn_decode/conv", 0.3),
        ("gdn_decode/state", 0.9), ("gdn_decode/gate", 0.4),
        ("swiglu_ffn", 6.0), ("kv_attention_decode_paged/gather", 2.0),
        ("", 0.5)]
FILL = [("gdn_prefill", 120.0), ("gdn_prefill/conv", 30.0),
        ("gdn_prefill/scan", 90.0), ("gdn_prefill/gate", 25.0),
        ("swiglu_ffn", 200.0), ("kv_attention_prefill_paged", 60.0)]


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
            "slot_steps": 7 * steps, "gdn_tokens": 12 * 9000,
            "gdn_rows": 12 * 10240,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}, \
        window_ms


def test_readers_on_hand_laid_observations(monkeypatch):
    obs, _window_ms = observations(monkeypatch)
    assert gdn_ops.read(obs, "scan_ms") == pytest.approx(90.0)
    assert gdn_ops.read(obs, "state_ms") == pytest.approx(0.9)
    state = 7 * 12 * 30 * 96 * 192 * 4 * 2            # bytes a step
    assert gdn_ops.read(obs, "state_roofline") == pytest.approx(
        100 * state / 819e9 / 0.9e-3)
    assert 0 < gdn_ops.read(obs, "state_roofline") < 100
    # the scan's least time is its bytes': float32 q, k, v, o a token
    moved = flops_gdn.scan_bytes(12 * 9000, 30, 96, 192)
    ops = flops_gdn.scan_flops(12 * 9000, 64, 30, 96, 192)
    assert moved / 819e9 > ops / 197e12
    assert gdn_ops.read(obs, "scan_roofline") == pytest.approx(
        100 * moved / 819e9 / (2 * 90e-3))
    assert 0 < gdn_ops.read(obs, "scan_roofline") < 100
    assert gdn_ops.read(obs, "scan_padding_pct") == pytest.approx(
        100 * (1 - 9000 / 10240))
    with pytest.raises(ValueError, match="cannot read"):
        gdn_ops.read(obs, "anything_else")


def test_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without device scopes (no map), a model without GDN
    layers, a run without the counters (a parent of PR 59): None, never a
    raise — the line leaves the metric out."""
    obs, _w = observations(monkeypatch, scopes="none")
    for what in ("scan_ms", "scan_roofline", "state_ms", "state_roofline"):
        assert gdn_ops.read(obs, what) is None
    obs, _w = observations(monkeypatch)
    obs["gdn_rows"] = obs["gdn_tokens"] = None
    assert gdn_ops.read(obs, "scan_padding_pct") is None
    assert gdn_ops.read(obs, "scan_roofline") is None
    obs["slot_steps"] = 0
    assert gdn_ops.read(obs, "state_roofline") is None
    plain = {**obs, "config": {"build": {"n_layer": 4, "layer_kinds": [
        "gqa", "kda", "kda", "kda"]}}}
    assert all(gdn_ops.read(plain, what) is None for what in WHAT)
    assert all(gdn_ops.read({**obs, "config": {"build": {"n_layer": 12}}},
                            what) is None for what in WHAT)


def test_operations_and_bytes_against_hand_counts():
    # one live slot, one layer, one step: the state read and written
    assert flops_gdn.state_bytes(1, 1, 30, 96, 192) == 2 * 4 * 552960
    assert flops_gdn.state_bytes(8, 12, 30, 96, 192) \
        == pytest.approx(0.4247e9, rel=1e-3)    # 0.52 ms at 819 GB/s
    assert flops_gdn.state_flops(1, 1, 30, 96, 192) == 6 * 552960
    # a chunk of 2 rows, one head with keys of 3 and values of 5: rows see
    # 1 and 2 rows (1.5 on average: k.k and q.k 3 each, the chunk's own
    # attention 5) and have 0 and 1 rows before them (0.5: a row of the
    # triangular system, 3 + 5); w S, q S and the update 15 each; 2 FLOPs
    # a multiply-accumulate
    assert flops_gdn.scan_flops(2, 2, 1, 3, 5) \
        == 2 * 2 * (1.5 * (3 + 3 + 5) + 0.5 * (3 + 5) + 3 * 15)
    per_token = flops_gdn.scan_flops(1, 64, 30, 96, 192)
    assert per_token == pytest.approx(
        2 * 30 * (32.5 * 384 + 31.5 * 288 + 3 * 18432))
    # 12 layers: 55 MFLOP a token, 0.8 % of the model's 6.66 GFLOP
    assert 12 * per_token == pytest.approx(55.3e6, rel=5e-3)
    assert flops_gdn.scan_bytes(1, 30, 96, 192) == 4 * 30 * (192 + 384 + 2)
