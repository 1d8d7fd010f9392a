"""``trinity_mini_26b_d5`` and its cell: the configuration's file against
the catalog's row key by key, the cut against ``build``, the traffic's
lengths against the buckets and a slot's rows, the runner at a tiny size
on the CPU (contract of the observations, two seeds dispatch the same
work), the window group's readers on a decode step as the chip ran it,
and the roofline's byte function against a hand count."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops, flops_window, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import scope_ms, window_attn  # noqa: E402

NAME = "trinity_mini_26b_d5"
CELL = "serve_trinity_decode_mixedctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("window_attn_ms_per_step.decode", "window_attn_roofline.decode",
       "kv_window_pages_held_pct.decode",
       "kv_window_pages_recycled_per_step.decode")


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def committed_traffic():
    with open(os.path.join(tiny.ROOT, "chipbench", "traffic",
                           "closed_decode_mixedctx.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    cfg["build"].update(
        d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=32,
        max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
        n_kv_head=2, head_dim=16, window=8, embed_scale=8.0,
        n_routed_experts=8, n_experts_held=8, n_experts_per_tok=2,
        d_expert=24, dtype="float32")
    # float32 against float32 on the CPU: see tests/test_swa_lm.py
    cfg["check"].update(prompt_lens=[21, 11, 6, 2], max_new=[6, 8, 7, 4],
                        limits={"logit_err_median": 2e-5,
                                "window_rows_wrong_share": 0.0})
    return cfg


def tiny_traffic():
    tr_ = committed_traffic()
    tr_.update(clients=4, prompt_len={"dist": "log_uniform", "lo": 3,
                                      "hi": 32},
               max_new={"dist": "uniform", "lo": 14, "hi": 16},
               first_round_min=14, prime_decode_steps=2)
    return tr_


# ------------------------------------------------- the configuration file

def test_every_width_is_the_catalog_rows():
    """The file's top level holds the catalog row's ``config`` key by
    key; no width of ``build`` differs from it; what is cut is depth
    alone, and ``reduced`` says so."""
    cfg = committed()
    build, src = cfg["build"], cfg["published"]["config"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert src == row["config"] and cfg["source"] == row["source_url"]
    for key, value in src.items():
        assert cfg[key] == value, key
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("n_head", src["num_attention_heads"]),
            ("n_kv_head", src["num_key_value_heads"]),
            ("head_dim", src["head_dim"]),
            ("window", src["sliding_window"]),
            ("rope_theta", src["rope_theta"]),
            ("vocab", src["vocab_size"]),
            ("d_expert", src["moe_intermediate_size"]),
            ("n_routed_experts", src["num_experts"]),
            ("n_experts_held", src["num_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("n_shared_experts", src["num_shared_experts"]),
            ("rms_eps", src["rms_norm_eps"]),
            ("norm_topk_prob", src["route_norm"]),
            ("routed_scaling_factor", src["route_scale"])):
        assert build[ours] == theirs, ours
    assert build["embed_scale"] == pytest.approx(src["hidden_size"] ** 0.5)
    assert src["mup_enabled"] and src["score_func"] == "sigmoid"
    assert src["rope_scaling"] is None and not src["tie_word_embeddings"]
    assert src["n_group"] == src["topk_group"] == 1
    assert build["held_start"] == 0 and build["router_bias"]
    assert build["qk_norm"] and build["post_norms"] and build["gqa_gate"]
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"


def test_the_cut_is_depth_alone_and_says_so():
    """``reduced`` names the depth and nothing else; the five layers
    are one leading dense layer and one whole period in the published
    3:1 ratio; ``stands_for`` and ``reduced_why`` agree with ``build``."""
    cfg = committed()
    build, pub = cfg["build"], cfg["published"]
    src = pub["config"]
    assert cfg["reduced"] == ["n_layer"]
    assert pub["n_layer"] == src["num_hidden_layers"] == 32
    period = src["global_attn_every_n_layers"]
    kinds = {"sliding_attention": "swa", "full_attention": "gqa"}
    assert build["layer_kinds"] == [kinds[t]
                                    for t in src["layer_types"][:period]]
    assert src["layer_types"] == src["layer_types"][:period] * (32 // period)
    # leading dense layers count once; then a whole period
    assert 1 == build["first_k_dense"] <= src["num_dense_layers"]
    assert build["n_layer"] == build["first_k_dense"] + period == 5
    served = [build["layer_kinds"][i % period]
              for i in range(build["n_layer"])]
    assert served == ["swa", "swa", "swa", "gqa", "swa"]
    assert served[build["first_k_dense"]:].count("gqa") * period \
        == len(served[build["first_k_dense"]:])
    for word in ("128", "200192", "five layers", "27 layers"):
        assert word in cfg["stands_for"], word
    for word in ("5 of 32", "1, 5, 6, 7, 8", "4.24 B", "8.48 GB"):
        assert word in cfg["reduced_why"], word
    # the bytes the file reckons, from build
    m, h, kv, d = (build[k] for k in ("d_model", "n_head", "n_kv_head",
                                      "head_dim"))
    attn = m * (2 * h * d + 2 * kv * d) + h * d * m
    experts = build["n_routed_experts"] * 3 * m * build["d_expert"]
    expert_layer = attn + experts + 3 * m * build["d_expert"] \
        + m * build["n_routed_experts"]
    total = attn + 3 * m * build["d_inner"] + 4 * expert_layer \
        + 2 * build["vocab"] * m
    assert total / 1e9 == pytest.approx(4.24, abs=0.01)
    for key in ("stands_for", "reduced_why", "assumed", "departures"):
        assert cfg[key] and "TO BE WRITTEN" not in json.dumps(cfg[key])
    chk = cfg["check"]
    assert "TO BE WRITTEN" not in chk["why"]
    for word in ("2047", "2049", "low_precision", "rope_full"):
        assert word in chk["why"], word


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert traffic["clients"] == config["build"]["n_slots"] == 32
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
            *NEW} <= mine
    # the shape readers count by other configurations' shapes
    assert not {m for m in mine if m.startswith(
        ("moe_up_", "kv_gather_", "gqa_gather_", "kda_state_", "dsa_",
         "mla_"))}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            spec = harness.load_json("layer_metrics", m["name"] + ".json")
            assert os.path.isfile(os.path.join(
                harness.HERE, "layer_metrics", spec["reader"] + ".py"))
    for words in [c["why"] for c in bench["configs"]] \
            + [w["why"] for w in bench["workloads"]]:
        assert 1 <= len(words) <= 200


def test_every_length_fits_its_bucket_and_its_slot():
    """Every request of the plan, the staggered first round too, asks
    for at least 3584 tokens — more decode steps than a 30 s window
    holds at the 10 ms the weights alone cost — and fits its slot:
    bucket + budget <= 20480 rows; a quarter of the callers in each
    bucket's octave."""
    cfg, traffic = committed(), committed_traffic()
    build = cfg["build"]
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 1025,
                                     "hi": 16384}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 3584,
                                  "hi": 4096}
    assert (traffic["rounds"], traffic["first_round_min"],
            traffic["prime_decode_steps"], traffic["schedule_seed"],
            traffic["trace_seconds"]) == (2, 3584, 4, 31, 10)
    assert build["prompt_buckets"] == [2048, 4096, 8192, 16384]
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 20480 and rows % build["page_size"] == 0
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 32
    firsts = []
    for requests in plan["clients"]:
        firsts.append(len(requests[0][0]))
        for prompt, budget in requests:
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 1025 <= len(prompt) <= 16384
            assert 3000 < 3584 <= budget <= 4096
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
    # short and long in one batch: every bucket has callers
    per_bucket = [sum(lo < n <= hi for n in firsts)
                  for lo, hi in ((1024, 2048), (2048, 4096), (4096, 8192),
                                 (8192, 16384))]
    assert min(per_bucket) >= 2 and sum(per_bucket) == 32
    # the check's prompts: none a bucket's length; two past the window
    # before decoding, one that crosses it while decoding, one that
    # never reaches it; at least one releases a window page
    chk, w = cfg["check"], build["window"]
    lens, news = chk["prompt_lens"], chk["max_new"]
    assert len(lens) >= 4 and all(48 <= m <= 96 for m in news)
    assert not set(lens) & set(build["prompt_buckets"])
    assert sum(n > w for n in lens) >= 2
    assert any(n < w < n + m for n, m in zip(lens, news))
    assert any(n + m < w for n, m in zip(lens, news))
    ps = build["page_size"]
    assert any(n > w and (n + m) // ps > n // ps
               for n, m in zip(lens, news)) and chk["min_released"] >= 1


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


def test_tiny_trinity_cell_agrees_with_the_reference(two_runs):
    _run, obs, setup = two_runs[0]
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
    # the window group: every slot's ring is leased in part or whole,
    # pages come back while the requests live, and the rows the window
    # layers attended are at most a window's a slot, layer and step
    assert 0 < obs["kv_window_pages_held"] <= 1
    assert obs["window_pages_released"] > 0
    assert 0 < obs["window_rows"] <= 4 * 8 * 4 * (steps + 2)
    assert window_attn.read(obs, "held_pct") \
        == pytest.approx(100 * obs["kv_window_pages_held"])
    assert window_attn.read(obs, "recycled_per_step") \
        == pytest.approx(obs["window_pages_released"] / steps)
    # four expert layers of five; [layers, (tokens, steps hit), held]
    assert obs["moe_counts"].shape == (4, 2, 8)
    # warm-up's 2 buckets, the 4 compared requests (stepped together,
    # then once more through the server), one admission per client
    assert len(admissions(setup)) == 2 + 2 * 4 + 4
    assert all({"page_rows", "page_rows_w"} <= set(dict(e[1]))
               for e in admissions(setup))
    decodes = [e for e in setup if not e[0][0].startswith("prefill")]
    assert decodes and all("page_table_w" in dict(e[1]) for e in decodes)


def test_setup_dispatches_the_same_work_for_two_seeds(two_runs):
    (_r1, _o1, setup1), (_r2, o2, setup2) = two_runs
    assert o2["correct"]
    n = 2 + 2 * 4 + 4
    assert admissions(setup1) == admissions(setup2)
    assert len(admissions(setup1)) == n
    # the warm-up's step, the longest compared budget's 7 steps twice,
    # the priming's 2: what comes on top is the scheduler's own timing
    steps = [len(s) - n for s in (setup1, setup2)]
    assert min(steps) >= 1 + 2 * 7 + 2


# ----------------------------------------------------------- the readers

MS = 1e6       # nanoseconds
SPAN = "serving.decode_step"


def recorded():
    with open(os.path.join(HERE, "data", "trinity_decode_trace.json")) as f:
        return json.load(f)


def observations(rec, monkeypatch, executions=2, scopes="map", **extra):
    """The recorded step laid out as ``executions`` decode executions
    back to back under their spans, with the program's map of them."""
    module = rec["module"]
    events, modules, spans, table = [], [], [], {module: {}}
    at = 1.0
    for _ in range(executions):
        start, number = at, 0
        for scope, stem, what, ms, count in rec["groups"]:
            n = max(int(round(count)), 1)
            for i in range(n):
                name = f"{stem}.{number + i}"
                table[module][name] = scope
                events.append([f"{name} {what} ", at * MS, ms / n * MS])
                at += ms / n
            number += n
        modules.append([f"{module}(17)", start * MS, (at - start) * MS])
        spans.append((SPAN, (start - 0.2) * MS, at * MS))
        at += 0.5
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    program = {"map": (table, {"seconds": 0.1}), "none": (None, None)}
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: program[scopes])
    return {"reduced": tr.reduce_window(trace, 0.0, (at + 1.0) * MS, spans),
            "units": {"decode_steps": executions, "prefills": 0},
            "config": committed(), "traffic": {},
            "window_rows": executions * rec["window_rows_per_step"],
            "window_pages_released":
                executions * rec["window_pages_released_per_step"],
            "kv_window_pages_held": rec["kv_window_pages_held_share"],
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            **extra}


def test_readers_on_the_recorded_step(monkeypatch):
    """One decode step of the cell as the chip ran it (my chip run,
    PR 37): the window layers' scope holds their write, gather and
    attend; the share of the roofline is the live rows' K and V over
    the scope's time, between 0 and 100; the full layer is what is left
    of ``attn_ms_per_step.decode``."""
    rec = recorded()
    obs = observations(rec, monkeypatch)
    want = rec["readings"]
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_conftest", os.path.join(HERE, "conftest.py"))
    table = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(table)
    assert table.STEP_MODULES[CELL] == rec["module"]
    for name, what in zip(NEW, ("ms", "roofline", "held_pct",
                                "recycled_per_step")):
        assert harness.load_json("layer_metrics", name + ".json") \
            == {"reader": "window_attn", "args": {"what": what}}
    window_ms = window_attn.read(obs, "ms")
    by_hand = sum(ms for scope, _s, _w, ms, _n in rec["groups"]
                  if "/kv_attention_decode_paged/window/" in f"/{scope}/")
    assert window_ms == pytest.approx(by_hand)
    assert window_ms == pytest.approx(want[NEW[0]], rel=5e-3)
    attn = harness.load_json("layer_metrics", "attn_ms_per_step.decode.json")
    whole = scope_ms.read(obs, **attn["args"])
    assert whole == pytest.approx(want["attn_ms_per_step.decode"], rel=5e-3)
    # a window layer (four of them) against the full layer (one)
    assert 0 < window_ms / 4 < (whole - window_ms) / 5
    share = window_attn.read(obs, "roofline")
    bytes_ = flops_window.window_bytes(rec["window_rows_per_step"], 4, 128, 2)
    assert share == pytest.approx(100 * bytes_ / 819e9 / (window_ms / 1e3))
    assert share == pytest.approx(want[NEW[1]], rel=5e-3)
    assert 0 < share < 100
    assert window_attn.read(obs, "held_pct") == pytest.approx(want[NEW[2]])
    assert window_attn.read(obs, "recycled_per_step") \
        == pytest.approx(want[NEW[3]], rel=5e-3)


def test_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """A program without a window group (the parent): no counters, no
    gauges, no scope — None, not an error."""
    rec = recorded()
    obs = observations(rec, monkeypatch, window_rows=None,
                       window_pages_released=None,
                       kv_window_pages_held=None)
    for what in ("held_pct", "recycled_per_step", "roofline"):
        assert window_attn.read(obs, what) is None
    assert window_attn.read(obs, "ms") > 0      # the scope alone is there
    obs = observations(rec, monkeypatch)
    del obs["config"]["build"]["window"]        # no window layers at all
    assert window_attn.read(obs, "ms") is None
    obs = observations(rec, monkeypatch)
    del obs["window_rows"], obs["window_pages_released"]
    del obs["kv_window_pages_held"]
    for what in ("held_pct", "recycled_per_step", "roofline"):
        assert window_attn.read(obs, what) is None
    obs = observations(rec, monkeypatch, scopes="none")
    assert window_attn.read(obs, "roofline") is None
    assert window_attn.read(obs, "ms") is None
    obs = observations(rec, monkeypatch)
    obs["units"]["decode_steps"] = 0
    assert window_attn.read(obs, "recycled_per_step") is None
    assert window_attn.read(obs, "roofline") is None
    with pytest.raises(ValueError):
        window_attn.read(obs, "something")


# ------------------------------------------------- operations and bytes

def test_bytes_and_operations_against_hand_counts():
    # one step of 32 slots: 8 past the window (2048 rows each), 24 at
    # 1000 positions, four window layers
    rows = (8 * 2048 + 24 * 1000) * 4
    assert flops_window.window_bytes(rows, 4, 128, 2) == rows * 2048
    assert flops_window.window_flops(rows, 32, 128) == rows * 32 * 512
    # the bound is the bytes: 2048 B a row against 16384 operations
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t = rows * 2048 / 819e9
    assert flops.roofline_pct(rows * 16384, rows * 2048, 2 * t, peaks) \
        == pytest.approx(50.0)
    # the cache a position and layer, and both groups at the cell's sizes
    build = committed()["build"]
    row = 2 * build["n_kv_head"] * build["head_dim"] * 2
    assert row == 2048
    slots, ps = build["n_slots"], build["page_size"]
    full = slots * (build["prompt_len"] + build["max_new"]) * row
    ring = -(-build["window"] // ps) + 1
    window = slots * ring * ps * row * 4
    assert (full, window) == (1342177280, 541065216)
    assert ring * ps == 2064
