"""``glm5_744b_ep16_d5`` and its cell: the configuration's file against
the catalog's row key by key, the traffic's lengths against a slot's
rows, the runner at a tiny size on the CPU (contract of the
observations), the new readers on a small hand-recorded trace, and the
operations-and-bytes functions against hand counts."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_latent, harness  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.generators import closed_loop  # noqa: E402
from chipbench.layer_metrics import latent_ops, moe_counts, scope_ms  # noqa: E402

NAME = "glm5_744b_ep16_d5"
CELL = "serve_glm5_decode_longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def committed():
    with open(os.path.join(tiny.ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def committed_traffic():
    with open(os.path.join(tiny.ROOT, "chipbench", "traffic",
                           "closed_decode_longctx.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = committed()
    cfg["kv_codec"] = "none"
    # no bucket at this size takes the prefill's grouped way, whose
    # counters the balancing reads: ``balanced_config`` has one
    del cfg["router_balance"]
    cfg["build"].update(
        n_layer=3, d_model=64, d_inner=96, n_head=4, vocab=96,
        prompt_len=32, max_new=16, prompt_buckets=[16, 32], n_slots=4,
        page_size=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
        index_head_dim=16, index_topk=8, n_routed_experts=16,
        n_experts_held=4, n_experts_per_tok=4, d_expert=24,
        dtype="float32")
    # float32 against float32 on the CPU: see tests/test_mla_lm.py
    cfg["check"].update(prompt_lens=[9, 27, 14], max_new=[6, 4, 6],
                        limits={"logit_err_median": 2e-5,
                                "select_overlap_min": 1.0})
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
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert src == row["config"] and cfg["source"] == row["source_url"]
    for key, value in src.items():
        assert cfg[key] == value, key
    for ours, theirs in (
            ("d_model", src["hidden_size"]),
            ("d_inner", src["intermediate_size"]),
            ("n_head", src["num_attention_heads"]),
            ("q_lora_rank", src["q_lora_rank"]),
            ("kv_lora_rank", src["kv_lora_rank"]),
            ("qk_nope_head_dim", src["qk_nope_head_dim"]),
            ("qk_rope_head_dim", src["qk_rope_head_dim"]),
            ("v_head_dim", src["v_head_dim"]),
            ("rope_theta", src["rope_parameters"]["rope_theta"]),
            ("index_n_heads", src["index_n_heads"]),
            ("index_head_dim", src["index_head_dim"]),
            ("index_topk", src["index_topk"]),
            ("d_expert", src["moe_intermediate_size"]),
            ("n_routed_experts", src["n_routed_experts"]),
            ("n_experts_per_tok", src["num_experts_per_tok"]),
            ("n_shared_experts", src["n_shared_experts"]),
            ("rms_eps", src["rms_norm_eps"]),
            ("norm_topk_prob", src["norm_topk_prob"]),
            ("routed_scaling_factor", src["routed_scaling_factor"])):
        assert build[ours] == theirs, ours
    assert src["qk_head_dim"] == build["qk_nope_head_dim"] \
        + build["qk_rope_head_dim"]
    assert src["topk_method"] == "noaux_tc" and build["router_bias"]
    assert src["n_group"] == src["topk_group"] == 1
    assert not src["tie_word_embeddings"]
    # the cuts, within the floors: one leading dense layer and at least
    # four of the layers that follow, >= 8 experts, >= 1/8 vocabulary
    assert cfg["reduced"] == ["n_layer", "n_experts_held", "vocab"]
    assert build["layer_kinds"] == ["mla"]
    assert 1 == build["first_k_dense"] <= src["first_k_dense_replace"]
    assert build["n_layer"] - build["first_k_dense"] >= 4
    pub = cfg["published"]
    assert pub["n_layer"] == src["num_hidden_layers"] == 78
    assert pub["n_routed_experts"] == 256 and pub["vocab"] == 154880
    assert 8 <= build["n_experts_held"] < build["n_routed_experts"]
    assert build["n_experts_held"] * 16 == build["n_routed_experts"]
    assert build["vocab"] * 8 == src["vocab_size"]
    assert cfg["kv_codec"] == "bf16" and build["dtype"] == "bfloat16"
    for key in ("stands_for", "reduced_why", "assumed", "departures"):
        assert cfg[key] and "TO BE WRITTEN" not in json.dumps(cfg[key])
    assert "TO BE WRITTEN" not in cfg["check"]["why"]


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == NAME
    assert traffic["clients"] == config["build"]["n_slots"] == 32
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    mine = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert {"dsa_index_ms_per_step", "dsa_index_roofline",
            "mla_sparse_ms_per_step", "mla_sparse_roofline",
            "dsa_selected_pct.decode", "slot_occupancy_mean", "itl_mean_ms",
            "kv_pages_held_pct.decode", "compiles_in_window.decode",
            "decode_step_device_ms", "device_idle_pct.decode",
            "peak_hbm_gb.decode", "sched_host_ms_per_step",
            "fetch_lag_ms.decode", "moe_experts_hit_pct.decode",
            "moe_load_max_over_mean.decode"} <= mine
    # hybrid_ops counts n_layer expert layers (one here is dense) and
    # the other gathers' readers other planes: not this cell's
    assert not {m for m in mine if m.startswith(
        ("moe_up_", "kv_gather_", "gqa_gather_", "kda_state_"))}
    for m in bench["per_layer"]:
        if m["name"].startswith(("dsa_", "mla_")):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"


def test_no_request_can_end_inside_a_window():
    """Every request of the plan, the staggered first round too, asks
    for at least 3584 tokens — more decode steps than a 30 s window
    holds at the 9.3 ms the weights alone cost (3225) — and fits its
    slot: bucket + budget <= 12288 rows."""
    cfg, traffic = committed(), committed_traffic()
    build = cfg["build"]
    assert traffic["generator"] == "closed_loop"
    assert traffic["prompt_len"] == {"dist": "log_uniform", "lo": 4097,
                                     "hi": 8192}
    assert traffic["max_new"] == {"dist": "uniform", "lo": 3584,
                                  "hi": 4096}
    assert traffic["rounds"] == 2 and traffic["prime_decode_steps"] == 4
    plan = closed_loop.make(traffic, cfg, 2 ** 31 + 17, 30.0)
    assert len(plan["clients"]) == 32
    rows = build["prompt_len"] + build["max_new"]
    assert rows == 12288 and rows % build["page_size"] == 0
    for requests in plan["clients"]:
        for prompt, budget in requests:
            bucket = min(b for b in build["prompt_buckets"]
                         if b >= len(prompt))
            assert 4097 <= len(prompt) <= 8192
            assert 3225 < 3584 <= budget <= 4096
            assert bucket + budget <= rows
            assert prompt.max() < build["vocab"] and prompt.min() >= 1
    # the check's prompts: none a bucket's length, all past index_topk
    chk = cfg["check"]
    assert all(n > build["index_topk"] and n not in build["prompt_buckets"]
               and n + m <= rows
               for n, m in zip(chk["prompt_lens"], chk["max_new"]))


# ------------------------------------------------------ the runner, tiny

def test_tiny_glm5_cell_agrees_with_the_reference():
    run, obs = tiny.run_cell(tiny_config(), tiny_traffic(), 2 ** 31 + 9, 0.4)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["logit_err_max"] <= 2e-5
    assert seen["select_overlap_min"] == 1.0
    assert seen["tokens_compared"] == 16 and seen["same_through_server"]
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    assert obs["units"]["decode_steps"] > 0
    assert 0 < obs["slot_occupancy"] <= 1 and obs["kv_pages_held"] > 0.5
    # two expert layers of three; [layers, (tokens, steps hit), held]
    assert obs["moe_counts"].shape == (2, 2, 4)
    assert 0 < moe_counts.read(obs, "hit_pct") <= 100
    # rows scored and attended over the window's steps, three layers
    rows, steps = obs["dsa_rows"], obs["units"]["decode_steps"]
    assert rows["scored"] > rows["selected"] > 0
    assert rows["selected"] <= 3 * 8 * 4 * (steps + 2)
    assert 0 < latent_ops.read(obs, "selected_pct") < 100
    # what the per-layer metrics read is in an untraced run's notes too
    notes = obs["notes"]
    assert notes["dsa_rows"] == rows
    assert notes["moe_steps"] == obs["moe_steps"] > 0
    assert notes["moe_counts"] == obs["moe_counts"].tolist()
    # this tiny size has no bucket the balancing could run on
    assert notes["reference"]["router_balance"] is None
    # the committed traffic samples its tokens: every request of the
    # plan has a seed of its own, and the same --seed draws the same
    plan = closed_loop.make(tiny_traffic(), tiny_config(), 2 ** 31 + 9, 0.4)
    again = closed_loop.make(tiny_traffic(), tiny_config(), 2 ** 31 + 9, 0.4)
    assert plan["sampling"] == again["sampling"]
    assert plan["sampling"]["temperature"] == 1.0
    assert np.asarray(plan["sampling"]["seeds"]).shape == (4, 2)
    assert len(set(np.asarray(plan["sampling"]["seeds"]).ravel())) == 8
    greedy = {k: v for k, v in tiny_traffic().items() if k != "sampling"}
    same = closed_loop.make(greedy, tiny_config(), 2 ** 31 + 9, 0.4)
    assert "sampling" not in same
    for mine, theirs in zip(plan["clients"], same["clients"]):
        for (p1, b1), (p2, b2) in zip(mine, theirs):
            assert b1 == b2 and (p1 == p2).all()


# ------------------------------------------------- the router's balancing

BALANCE = dict(bucket=768, rounds=14, gamma=0.1, decay=0.8)


def balanced_config():
    """The tiny configuration with a bucket the prefill's grouped way
    takes (more than 512 tokens: its per-expert counters are what the
    balancing reads) and a schedule for widths at which one step of the
    bias moves a load much less than at the cell's."""
    cfg = tiny_config()
    cfg["build"].update(prompt_len=768, prompt_buckets=[16, 768],
                        page_size=16)
    cfg["router_balance"] = dict(BALANCE)
    return cfg


def test_the_committed_schedule_fits_the_cell():
    """The cell's own schedule: a bucket the cell serves and whose
    prefill counts per expert (the grouped way: more than 512 tokens);
    steps that can travel as far as a held expert's entry had to on the
    chip (0.038 at most over eight seeds, PERF.md PR 60) and end finer
    than a tenth of the draw's spread; the traffic samples."""
    cfg = committed()
    rb, build = cfg["router_balance"], cfg["build"]
    assert rb["bucket"] in build["prompt_buckets"] and rb["bucket"] > 512
    assert 0 < rb["decay"] < 1 and rb["rounds"] >= 6
    steps = [rb["gamma"] * rb["decay"] ** i for i in range(rb["rounds"])]
    assert sum(steps) >= 0.05 and steps[-1] <= 0.002
    assert build["router_bias"] and "router_balance" in cfg["assumed"]["router"]
    assert committed_traffic()["sampling"] == {"temperature": 1.0,
                                               "top_k": 0}


def held_loads(engine, cfg, tokens):
    """[expert layers, held]: the held experts' tokens of one dispatch
    of the served prefill view over ``tokens``, nothing written."""
    p_len = len(tokens)
    cb = engine._cb_prefill[p_len]
    names = [op.inputs["Counts"][0]
             for op in cb._program_desc.global_block.ops
             if op.type == "expert_ffn_held" and op.inputs.get("Counts")]

    def counts():
        return np.stack([np.asarray(engine.scope.find_var(n))[0]
                         for n in names]).astype(np.int64)
    feeds = engine._prefill_feeds(p_len)
    feeds["ids"][0, :, 0] = tokens
    feeds["seq_len"][:] = p_len
    before = counts()
    engine._run(cb, (engine.PREFILL, p_len), feeds)
    return counts() - before


def router_biases(engine):
    return {n: np.array(engine.scope.find_var(n))
            for n in sorted(engine._cb_decode.sig.const_names)
            if n.endswith(".router_bias")}


@pytest.fixture(scope="module")
def skewed_engines():
    """Two seeds' engines whose drawn bias is made a bad draw (one held
    expert favoured, one starved: the busiest held expert far over
    twice the mean), each balanced; (engine, bias before, bias after,
    what the balancing saw) by seed."""
    import jax
    from chipbench.runners import serve_glm5
    cfg, dev, out = balanced_config(), jax.devices()[0], {}
    for seed in (3, 2 ** 31 + 11):
        engine = serve_glm5.build_engine(cfg, seed, dev)
        engine.warmup()
        for name, b in router_biases(engine).items():
            b[0, 0] += 0.3
            b[0, 1] -= 0.3
            engine.scope.set_var(name, jax.device_put(b, dev))
        before = router_biases(engine)
        seen = serve_glm5.balance_router_bias(cfg, engine, seed, dev)
        out[seed] = (engine, before, router_biases(engine), seen)
    return cfg, out


def test_balancing_evens_the_held_experts_load(skewed_engines):
    """From a draw that leaves the busiest held expert over twice the
    mean, the rule ends under 1.4 on tokens it has not seen, with the
    held experts' mean load at the known mean tokens x top_k / experts
    — so a step of T tokens hits the share 1 - (1 - k/E)^T of them that
    a uniform router hits, within 3 points."""
    cfg, engines = skewed_engines
    build = cfg["build"]
    k, n_e = build["n_experts_per_tok"], build["n_routed_experts"]
    for seed, (engine, _before, _after, seen) in engines.items():
        assert seen["max_over_mean_by_round"][0] > 2.0, seed
        fresh = np.random.RandomState(seed % 1000 + 77).randint(
            1, build["vocab"], 768)
        load = held_loads(engine, cfg, fresh).astype(float)
        assert (load.max(axis=1) / load.mean(axis=1)).mean() < 1.4, load
        mean = 768 * k / n_e
        assert abs(load.mean() / mean - 1) < 0.1, load
        # a decode step's tokens (the cell's 32) spread by these loads
        p = load / 768
        hit = (1 - (1 - p) ** 32).mean()
        assert abs(hit - (1 - (1 - k / n_e) ** 32)) < 0.03, hit


def test_balancing_is_the_seeds_and_leaves_the_absent_experts(skewed_engines):
    import jax
    from chipbench.runners import serve_glm5
    cfg, engines = skewed_engines
    held = cfg["build"]["n_experts_held"]
    for seed, (engine, before, after, seen) in engines.items():
        assert seen["absent_bias_moved"] == 0.0
        for name in before:
            assert (before[name][0, held:] == after[name][0, held:]).all()
            assert (before[name][0, :held] != after[name][0, :held]).any()
        # the same seed from the same start: the same bias to the bit
        for name, b in before.items():
            engine.scope.set_var(name, jax.device_put(b, jax.devices()[0]))
        again = serve_glm5.balance_router_bias(cfg, engine, seed,
                                               jax.devices()[0])
        assert again == seen
        for name, b in router_biases(engine).items():
            assert (b == after[name]).all(), name


def test_check_reads_the_balanced_bias_on_a_fresh_engine(skewed_engines):
    """After the balancing the engine is as warm-up left it — no slot
    live, every page free, the decode view's expert counters at zero —
    and the check passes: program and reference read the one bias (the
    reference takes it from the scope the window is served from)."""
    from chipbench.runners import serve_glm5
    cfg, engines = skewed_engines
    seed, (engine, _before, after, _seen) = next(iter(engines.items()))
    assert engine.active_count() == 0
    assert engine.free_pages() == engine.n_pages
    counted = engine.expert_token_counts(sync=True)
    assert counted["steps"] == 0 and not counted["counts"].any()
    cfg = dict(cfg, check={**cfg["check"], "prompt_lens": [9, 700, 14]})
    correct, seen, _served = serve_glm5.compare_with_reference(
        cfg, engine, np.random.RandomState(seed + 1))
    assert correct, seen
    for name, b in router_biases(engine).items():
        assert (b == after[name]).all(), name
    # and a reference that read the DRAWN bias would not agree: the
    # skewed entries pick other experts
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    assert any(n.endswith(".router_bias")
               for n in ref.param_names(cfg["build"], serve_glm5.MODEL))


# ----------------------------------------------------------- the readers

BUILD = dict(n_slots=4, n_layer=1, layer_kinds=["mla"], n_head=4,
             dtype="bfloat16",
             prompt_len=128, max_new=128, kv_lora_rank=16,
             qk_rope_head_dim=8, index_n_heads=2, index_head_dim=16,
             index_topk=8)
MS = 1e6       # nanoseconds
MODULE = "jit_lm_decode_paged_sf6d7"
# (scope, instruction stem, opcode and shape, ms a step, instances)
GROUPS = [
    ("mla_decode_paged/index", "gather_pages",
     "custom-call bf16[1024,16] tpu_custom_call", 0.5, 1),
    ("mla_decode_paged/index", "fusion", "fusion f32[4,2,128]", 0.25, 1),
    ("mla_decode_paged/select", "sort", "sort f32[4,256]", 1.25, 1),
    ("mla_decode_paged/project", "fusion", "fusion f32[4,256]", 0.5, 1),
    # another plane's gather, by the same kernel: the attention's
    ("mla_decode_paged/attend", "gather_pages",
     "custom-call bf16[1024,128] tpu_custom_call", 0.5, 1),
    ("mla_decode_paged/attend", "fusion", "fusion bf16[32,128]", 0.5, 1),
    ("mla_decode_paged/attend/scores", "fusion", "fusion f32[4,4,8]",
     0.25, 2),
    ("expert_ffn_held/up", "fusion", "fusion f32[4,64]", 1.0, 1),
    ("", "copy", "copy s32[2,4]", 0.125, 1)]


def observations(monkeypatch, groups=GROUPS, executions=2, counted=None,
                 module=MODULE, scopes="map", **extra):
    """``groups`` laid out as ``executions`` decode executions back to
    back, each followed by a ``jit_copy`` execution (the engine's
    snapshot of the expert counters: no step) and one prefill execution
    holding the same ops; the program's map of the module is the
    groups' own scopes (``scopes="map"``) or none at all. The window
    counted ``counted`` steps with ``rows`` a step."""
    events, modules, table, at = [], [], {module: {}}, 1.0
    for run in range(executions + 1):
        start, number = at, 0
        prefill = run == executions
        for scope, stem, what, ms, count in groups:
            for i in range(count):
                name = f"{stem}.{number}"
                if not prefill:
                    table[module][name] = scope
                events.append([f"{name} {what} ", at * MS, ms / count * MS])
                at += ms / count
                number += 1
        modules.append([("jit_lm_prefill_paged_32_s5dba(9)" if prefill
                         else f"{module}(17)"), start * MS,
                        (at - start) * MS])
        events.append(["copy.1 copy s32[2,4] ", at * MS, 0.001 * MS])
        modules.append(["jit_copy(5)", at * MS, 0.001 * MS])
        at += 0.5
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    counted = counted or executions
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: {
        "map": (table, {"seconds": 0.1}), "none": (None, None)}[scopes])
    return {"reduced": tr.reduce_window(trace, 0.0, (at + 1.0) * MS, []),
            "config": {"name": "not-a-cell", "build": dict(BUILD)},
            "traffic": {}, "units": {"decode_steps": counted},
            "dsa_rows": {"scored": counted * 400.0,
                         "selected": counted * 32.0},
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            **extra}


def test_readers_select_by_scope(monkeypatch):
    obs = observations(monkeypatch)
    assert latent_ops.latent_width(BUILD) == 128
    # whatever lies under the index scope: the plane's gather and the
    # scoring — not the selection, not the other plane's gather
    assert latent_ops.read(obs, "index", "ms") == pytest.approx(0.75)
    # by result shape, as since PR 33: the selected rows' gather and the
    # scores — not the other ops of the attend scope (the page table's
    # copy, the attention's own plane's gather)
    assert latent_ops.read(obs, "sparse", "ms") == pytest.approx(0.75)
    assert latent_ops.read(obs, "selected_pct") == pytest.approx(8.0)
    # the window's rows over the window's steps' time
    bytes_ = flops_latent.index_bytes(2 * 400.0, 16, 2)
    assert latent_ops.read(obs, "index", "roofline") == pytest.approx(
        100 * bytes_ / 819e9 / 1.5e-3)
    bytes_ = flops_latent.sparse_bytes(2 * 32.0, 16, 8, 2)
    assert latent_ops.read(obs, "sparse", "roofline") == pytest.approx(
        100 * bytes_ / 819e9 / 1.5e-3)
    # two of the window's four counted steps lie whole in the trace: a
    # step is an execution, and half the counters' rows are theirs
    half = observations(monkeypatch, counted=4)
    assert half["dsa_rows"]["scored"] == 4 * 400.0
    for group, what in (("index", "ms"), ("index", "roofline"),
                        ("sparse", "ms"), ("sparse", "roofline")):
        assert latent_ops.read(half, group, what) == pytest.approx(
            latent_ops.read(obs, group, what)), (group, what)
    # the same work whatever implements it: a program that scores the
    # plane in place (no ``gather_pages`` kernel, other shapes) is read
    # as before, and its share is the same bytes over the shorter time
    in_place = [("mla_decode_paged/index", "score_pages",
                 "custom-call f32[4,256] tpu_custom_call", 0.25, 1)] \
        + [g for g in GROUPS if g[0] != "mla_decode_paged/index"]
    obs = observations(monkeypatch, in_place)
    assert latent_ops.read(obs, "index", "ms") == pytest.approx(0.25)
    assert latent_ops.read(obs, "index", "roofline") == pytest.approx(
        100 * flops_latent.index_bytes(800.0, 16, 2) / 819e9 / 0.5e-3)
    assert latent_ops.read(obs, "sparse", "ms") == pytest.approx(0.75)


def test_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """A configuration without latent layers, a program without the
    counters (the parent) or without device scopes, a window without
    decode steps, a scope under which nothing ran: None, never 0."""
    obs = observations(monkeypatch)
    del obs["config"]["build"]["index_topk"]
    assert latent_ops.read(obs, "index", "ms") is None
    assert latent_ops.read(obs, "selected_pct") is None
    for rows in (None, {}, {"scored": 0, "selected": 0}):
        obs = observations(monkeypatch, dsa_rows=rows)
        assert latent_ops.read(obs, "sparse", "roofline") is None
        assert latent_ops.read(obs, "selected_pct") is None
    obs = observations(monkeypatch)
    del obs["dsa_rows"]
    assert latent_ops.read(obs, "index", "roofline") is None
    obs = observations(monkeypatch, units={"decode_steps": 0})
    assert latent_ops.read(obs, "index", "ms") is None
    obs = observations(monkeypatch, [g for g in GROUPS if "index" not in g[0]])
    assert latent_ops.read(obs, "index", "ms") is None
    assert latent_ops.read(obs, "index", "roofline") is None
    assert latent_ops.read(obs, "sparse", "ms") == pytest.approx(0.75)
    obs = observations(monkeypatch, [g for g in GROUPS
                                     if "attend" not in g[0]])
    assert latent_ops.read(obs, "sparse", "ms") is None
    assert latent_ops.read(obs, "sparse", "roofline") is None
    # no device scopes: nothing by scope; the shapes need no map
    obs = observations(monkeypatch, scopes="none")
    assert latent_ops.read(obs, "index", "ms") is None
    assert latent_ops.read(obs, "index", "roofline") is None
    assert latent_ops.read(obs, "sparse", "ms") == pytest.approx(0.75)
    obs = observations(monkeypatch, module="jit_something_else")
    assert latent_ops.read(obs, "sparse", "ms") is None


def recorded_step():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "glm5_decode_scopes.json")) as f:
        return json.load(f)


def test_readers_on_the_recorded_step(monkeypatch):
    """The op groups of one decode step of the cell as the chip ran it
    (my chip run, PR 60), under the scopes the program gave them: the
    indexer's reader finds by scope what the run itself read, within a
    fifth of what the reader of PRs 33-59 found by kernel name and
    result shape; the sparse attention's reader finds by shape what that
    reader found; both shares of the roofline are under 100."""
    rec = recorded_step()
    obs = observations(
        monkeypatch, [tuple(g[:4]) + (max(int(round(g[4])), 1),)
                      for g in rec["groups"]], module=rec["module"],
        dsa_rows={k: 2 * v for k, v in rec["rows_per_step"].items()})
    obs["config"] = committed()
    by_scope, by_shape = rec["readings"], rec["by_shape"]
    for group, what, name, want in (
            ("index", "ms", "dsa_index_ms_per_step", by_scope),
            ("index", "roofline", "dsa_index_roofline", by_scope),
            ("sparse", "ms", "mla_sparse_ms_per_step", by_shape),
            ("sparse", "roofline", "mla_sparse_roofline", by_shape)):
        assert latent_ops.read(obs, group, what) == pytest.approx(
            want[name], rel=2e-3), name
    for group in ("index", "sparse"):
        assert 0 < latent_ops.read(obs, group, "roofline") < 100
    assert latent_ops.read(obs, "selected_pct") == pytest.approx(
        by_scope["dsa_selected_pct.decode"], rel=1e-3)
    # by hand from the file, as the older reader chose them: the index
    # plane's gather and the scoring
    ms = {(g[1], g[2]): g[3] for g in rec["groups"]}
    assert by_shape["dsa_index_ms_per_step"] == pytest.approx(
        ms["gather_pages", "custom-call bf16[393216,128] tpu_custom_call"]
        + ms["fusion", "fusion f32[32,96,128]"], rel=1e-3)
    for name in ("dsa_index_ms_per_step", "dsa_index_roofline"):
        assert abs(by_scope[name] / by_shape[name] - 1) < 0.2, name


def test_every_new_metric_has_its_reader_file():
    bench = harness.load_benchmark()
    for m in harness.metrics_of(bench, "per_layer", CELL):
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert os.path.isfile(os.path.join(
            harness.HERE, "layer_metrics", spec["reader"] + ".py"))


# ------------------------------------------------- operations and bytes

def test_bytes_and_operations_against_hand_counts():
    # one step of 32 slots at 8000 live rows, five layers
    scored = 32 * 8000 * 5
    assert flops_latent.index_bytes(scored, 128, 2) == scored * 256
    assert flops_latent.index_flops(scored, 32, 128) == scored * 8192
    selected = 32 * 2048 * 5
    assert flops_latent.sparse_bytes(selected, 512, 64, 2) \
        == selected * 1152
    assert flops_latent.sparse_flops(selected, 64, 512, 64) \
        == selected * 2 * 64 * (576 + 512)
    # the cache a position: 5 layers x (640 + 128) values x 2 bytes
    build = committed()["build"]
    assert latent_ops.latent_width(build) == 640
    rows = build["n_slots"] * (build["prompt_len"] + build["max_new"])
    assert rows * build["n_layer"] * (640 + 128) * 2 == 3019898880
