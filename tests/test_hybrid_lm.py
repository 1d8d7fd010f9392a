"""The hybrid sparse block of the slot views (``decoder_lm(...,
layer_kinds=...)``: gated grouped-KV attention through the paged pool,
Kimi Delta Attention with a recurrent state per slot, an expert layer of
which the program holds a share) against the plain reference of
``chipbench/reference/solar_open2_250b_ep8_d4.py``, at a tiny size on
the CPU in float32: two periods of (gqa, kda, kda, kda), width 64, 16
experts of which 4 are held.

The tolerance of every comparison is ``TOL``: system and reference both
compute in float32 from the same weights (the CPU multiplies float32 in
float32), so what separates them is the order of the sums — a few 1e-6
here. Each fault the ISSUE names moves a result by 1e-2 or more, and
``test_a_fault_fails_the_comparison`` shows each one failing ``TOL``.
"""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.reference import solar_open2_250b_ep8_d4 as ref  # noqa: E402
from chipbench.runners import serve_hybrid  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.analysis import contracts  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import expert_ffn, kda  # noqa: E402

TOL = 2e-5
BUILD = dict(
    n_layer=8, d_model=64, n_head=4, vocab=96, prompt_len=16, max_new=16,
    prompt_buckets=[8, 16], n_slots=4, page_size=4,
    layer_kinds=["gqa", "kda", "kda", "kda"], n_kv_head=2, head_dim=16,
    kda_heads=4, kda_head_dim=16, kda_conv_taps=4, kda_gate_rank=8,
    n_routed_experts=16, n_experts_held=4, n_experts_per_tok=4,
    d_expert=24, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_eps=1e-5, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="solar_open2_250b_ep8_d4")


FAMILY = families.Family(serve_hybrid, CFG, ref, serve_hybrid.LogitProbe)


@pytest.fixture(params=["dense", "grouped"])
def way(request, monkeypatch):
    """Both ways the expert layer has to the same sum: at this size
    every call is under ``DENSE_MAX_TOKENS`` and would take the dense
    one; 0 sends them through the grouped product."""
    if request.param == "grouped":
        monkeypatch.setattr(expert_ffn, "DENSE_MAX_TOKENS", 0)
    return request.param


@pytest.fixture(scope="module", params=["dense", "grouped"])
def engine(request):
    return FAMILY.shared(
        patches=families.GROUPED if request.param == "grouped" else ())


def worst(engine, prompt_len, max_new=10, seed=1, build=BUILD, params=None):
    """The largest relative error of the served logits and of the slot's
    recurrent state against the reference, over one request."""
    prompt, toks, logits, states = FAMILY.request(engine, prompt_len,
                                                  max_new, seed)
    logit_err, state_err, margin, _slow = ref.compare(
        params or FAMILY.params_of(engine, build), prompt, toks, logits,
        states, build)
    return max(logit_err.max(), state_err.max()), margin.max()


# prompt lengths that are not bucket multiples (buckets 8 and 16), one
# shorter than the conv's four taps
@pytest.mark.parametrize("prompt_len", [2, 5, 11, 13, 16])
def test_prefill_then_decode_matches_the_full_forward(engine, prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through pages AND recurrent state, and the state left in
    the slot, against one full causal forward with no cache."""
    err, margin = worst(engine, prompt_len)
    assert err <= TOL
    assert margin == 0.0            # every served token the argmax


def _negated_decay(orig):
    def terms(x, w, h, d):
        u, g, beta, gate = orig(x, w, h, d)
        return u, -g, beta, gate
    return terms


def _beta_without_its_two(orig):
    def terms(x, w, h, d):
        u, g, beta, gate = orig(x, w, h, d)
        return u, g, beta / 2.0, gate
    return terms


def _bf16_state(orig):
    def step(s, q, k, v, g, beta):
        s, o = orig(s, q, k, v, g, beta)
        return s.astype(jax.numpy.bfloat16).astype(s.dtype), o
    return step


def _unnormalised(orig):
    return lambda x, w, k, norm, scaling: orig(x, w, k, False, scaling)


FAULTS = {
    "flipped_decay_sign": (kda, "_token_terms", _negated_decay),
    "beta_without_factor_2": (kda, "_token_terms", _beta_without_its_two),
    "bf16_recurrent_state": (kda, "_delta_step", _bf16_state),
    "top_k_weights_unnormalised": (expert_ffn, "route", _unnormalised),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["dropped_conv_tap"])
def test_a_fault_fails_the_comparison(monkeypatch, fault):
    """The tolerance bites: the system with one fault in it (or, for the
    conv tap, the reference given conv weights with a tap zeroed) misses
    ``TOL`` by orders of magnitude."""
    # one period of (gqa, kda, kda, kda) and the one bucket the prompt
    # of 11 takes show the same miss as two periods and both buckets
    # do; the reference is told the same
    depth = dict(n_layer=len(BUILD["layer_kinds"]), prompt_buckets=[16])
    build = FAMILY.build(**depth)
    if fault in FAULTS:
        module, attr, wrap = FAULTS[fault]
        monkeypatch.setattr(module, attr, wrap(getattr(module, attr)))
        eng = FAMILY.fresh(**depth)
    else:
        eng = FAMILY.shared(**depth)
    params = FAMILY.params_of(eng, build)
    if fault == "dropped_conv_tap":
        for name in [n for n in params if n.endswith("kda.conv")]:
            params[name] = params[name].at[0].set(0.0)
    err, _margin = worst(eng, 11, max_new=8, seed=3, build=build,
                         params=params)
    assert err > 100 * TOL


# ------------------------------------------------------ the expert layer

def _expert_weights(rng, n, m=64, f=24):
    def w(*shape):
        return (rng.randn(*shape) * (2.0 / sum(shape[-2:])) ** 0.5
                ).astype(np.float32)
    return {"router": w(m, 16), "w_gate": w(n, m, f), "w_up": w(n, m, f),
            "w_down": w(n, f, m), "s_gate": w(m, f), "s_up": w(m, f),
            "s_down": w(f, m)}


def _system_layer(x, w, held_start, n_held):
    ins = {"X": [x[None]], "RouterW": [w["router"]],
           "WGate": [w["w_gate"][held_start:held_start + n_held]],
           "WUp": [w["w_up"][held_start:held_start + n_held]],
           "WDown": [w["w_down"][held_start:held_start + n_held]],
           "SGate": [w["s_gate"]], "SUp": [w["s_up"]],
           "SDown": [w["s_down"]]}
    out = expert_ffn._expert_ffn_held(
        None, {k: [jax.numpy.asarray(v[0])] for k, v in ins.items()},
        {"top_k": 4, "held_start": held_start})
    return np.asarray(out["Out"][0][0])


def test_the_shares_add_up_to_the_uncut_layer(way):
    """Four members holding four of sixteen experts each: their partial
    results, the shared expert counted once, add up to what the
    reference gives for the whole layer with every expert held."""
    rng = np.random.RandomState(0)
    w = _expert_weights(rng, 16)
    x = rng.randn(24, 64).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.expert_layer(
            lambda t: w[t], jax.numpy.asarray(x), BUILD, (0, 16)))
        shared = np.asarray(ref.ffn(jax.numpy.asarray(x), w["s_gate"],
                                     w["s_up"], w["s_down"]))
    parts = sum(_system_layer(x, w, start, 4) for start in (0, 4, 8, 12))
    np.testing.assert_allclose(parts - 3 * shared, whole,
                               atol=TOL * np.abs(whole).max())


def test_the_sixteen_shares_of_a_bias_corrected_router_add_up(way):
    """GLM-5's routing at a small size: 32 experts picked by score +
    bias, weighed by score, renormalised, scaled by 2.5; sixteen members
    holding two experts each. Their routed parts, the shared expert
    counted once, add up to the uncut reference layer — and the bias
    does change the picks (without it the whole layer differs)."""
    from chipbench.reference import glm5_744b_ep16_d5 as glm
    rng = np.random.RandomState(1)
    w = _expert_weights(rng, 32)
    w["router"] = (rng.randn(64, 32) * 0.1).astype(np.float32)
    w["router_bias"] = (rng.randn(1, 32) * 0.05).astype(np.float32)
    x = rng.randn(24, 64).astype(np.float32)
    cfg = dict(n_experts_per_tok=4, norm_topk_prob=True, router_bias=True,
               routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(glm.expert_layer(
            lambda t: w[t], jax.numpy.asarray(x), cfg, (0, 32)))
        unbiased = np.asarray(glm.expert_layer(
            lambda t: w[t], jax.numpy.asarray(x),
            {**cfg, "router_bias": False}, (0, 32)))
        shared = np.asarray(ref.ffn(jax.numpy.asarray(x), w["s_gate"],
                                     w["s_up"], w["s_down"]))

    def share(start):
        ins = {"X": x[None], "RouterW": w["router"],
               "RouterBias": w["router_bias"],
               "WGate": w["w_gate"][start:start + 2],
               "WUp": w["w_up"][start:start + 2],
               "WDown": w["w_down"][start:start + 2],
               "SGate": w["s_gate"], "SUp": w["s_up"], "SDown": w["s_down"]}
        out = expert_ffn._expert_ffn_held(
            None, {k: [jax.numpy.asarray(v)] for k, v in ins.items()},
            {"top_k": 4, "held_start": start, "norm_topk": True,
             "scaling": 2.5})
        return np.asarray(out["Out"][0][0])

    parts = sum(share(start) for start in range(0, 32, 2))
    np.testing.assert_allclose(parts - 15 * shared, whole,
                               atol=TOL * np.abs(whole).max())
    assert np.abs(whole - unbiased).max() > 100 * TOL * np.abs(whole).max()


def test_dropless_under_a_skewed_router(way):
    """Every token on ONE held expert (a router that scores expert 1
    highest for every token): no token is dropped — the held part equals
    the reference's, and the expert's count is the number of tokens."""
    rng = np.random.RandomState(1)
    w = _expert_weights(rng, 16)
    w["router"][:] = 0.0
    x = np.abs(rng.randn(32, 64)).astype(np.float32)
    w["router"][:, 1] = 1.0         # x > 0: expert 1 wins everywhere
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.expert_layer(
            lambda t: w[t][:4] if t.startswith("w_") else w[t],
            jax.numpy.asarray(x), BUILD, (0, 4)))
    np.testing.assert_allclose(_system_layer(x, w, 0, 4), whole,
                               atol=TOL * np.abs(whole).max())
    combine, idx = expert_ffn.route(jax.numpy.asarray(x), w["router"], 4,
                                    True, 1.0)
    _y, sizes = expert_ffn.held_experts_part(
        jax.numpy.asarray(x), combine, idx, w["w_gate"][:4],
        w["w_up"][:4], w["w_down"][:4], 0)
    assert int(sizes[1]) == 32 and int(sizes.sum()) <= 32 * 4


def _poisoned(monkeypatch):
    """What the chip does now and then (PERF.md, PR 37): the grouped
    product leaves the rows past its groups as it found them — NaN, here
    always. A combine that WEIGHS such a row, by 0 even, returns NaN."""
    plain = expert_ffn._grouped

    def grouped(rows, w, sizes):
        out = plain(rows, w, sizes)
        past = np.arange(rows.shape[0])[:, None] >= sizes.sum()
        return jax.numpy.where(past, np.nan, out)
    monkeypatch.setattr(expert_ffn, "_grouped", grouped)


# (router 16 wide, 4 picks a token, 192 tokens: 768 assignments)
# case -> (experts held, what the router is made to do, padded tail)
GROUPED_CASES = {
    "a_quarter_held": (4, None, 0),
    "every_pick_held": (16, None, 0),
    "no_pick_held": (4, "elsewhere", 0),
    "every_token_on_one_held_expert": (4, "one", 0),
    "a_padded_tail": (4, None, 70),
    "every_pick_of_every_token_on_a_quarter": (4, "all_four", 0),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_the_grouped_way_gives_the_dense_ways_sum(case, monkeypatch):
    """Whatever the draw, the grouped way — buffers sized to the held
    share (256 rows for 4 of 16 experts), as many turns as the held
    assignments fill — returns the dense way's sum and the same counts,
    with the rows no product computed poisoned: a pick held elsewhere, a
    padded token's, or another turn's contributes a selected zero."""
    n_held, skew, padded = GROUPED_CASES[case]
    n, k, n_experts = 192, 4, 16
    rng = np.random.RandomState(3)
    w = _expert_weights(rng, n_experts)
    x = np.abs(rng.randn(n, 64)).astype(np.float32)
    if skew == "elsewhere":            # x > 0: experts 8-11 win everywhere
        w["router"][:, 8:12] += 1.0
    elif skew == "one":                # expert 1 is every token's first
        w["router"][:, 1] += 1.0
    elif skew == "all_four":           # the four held are every token's
        w["router"][:, :4] += 1.0
    valid = jax.numpy.arange(n) < n - padded if padded else None
    combine, idx = expert_ffn.route(jax.numpy.asarray(x), w["router"], k,
                                    True, 1.0)

    def part():
        return expert_ffn.held_experts_part(
            jax.numpy.asarray(x), combine, idx, w["w_gate"][:n_held],
            w["w_up"][:n_held], w["w_down"][:n_held], 0, valid, n_experts)
    with jax.default_matmul_precision("highest"):
        dense, dense_sizes = part()
        monkeypatch.setattr(expert_ffn, "DENSE_MAX_TOKENS", 0)
        _poisoned(monkeypatch)
        grouped, sizes = part()
    rows = expert_ffn.grouped_rows(n, k, n_held, n_experts)
    held = int(sizes.sum())
    assert rows == (n * k if n_held == n_experts else 256)
    assert {"every_pick_held": held == n * k, "no_pick_held": held == 0,
            "every_token_on_one_held_expert": int(sizes[1]) == n,
            "every_pick_of_every_token_on_a_quarter":
                held == n * k and -(-held // rows) == 3}.get(case, held > 0)
    if padded:
        assert held < (n - padded) * k and not np.asarray(grouped)[-padded:].any()
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(dense_sizes))
    assert np.isfinite(np.asarray(grouped)).all()
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(dense),
        atol=TOL * max(np.abs(np.asarray(dense)).max(), 1e-3))


def test_the_grouped_rows_counter_reads_all_held_where_all_are(monkeypatch):
    """``paddle_moe_grouped_rows_total`` through the engine: a member
    that holds every expert, prompts that fill their buckets — every row
    the grouped way was given it held (100 %); with a quarter of the
    experts held, a share between none and all. Both rows come from ONE
    snapshot (``expert_token_counts``), the decode steps' counters
    beside them untouched by the prefills."""
    from paddle_tpu.serving import metrics as sm
    monkeypatch.setattr(expert_ffn, "DENSE_MAX_TOKENS", 0)
    rng = np.random.RandomState(5)

    def rows(engine):
        engine.expert_token_counts(sync=True)
        return np.asarray([sm.MOE_GROUPED_ROWS.labels(
            model=engine.name, rows=r).value for r in ("given", "held")])

    for n_held, full in ((16, True), (4, False)):
        # a quarter held and the grouped way is the module's own engine
        engine = FAMILY.shared(patches=families.GROUPED,
                               n_experts_held=n_held)
        engine.reset()
        assert len(engine._grouped_vars) == BUILD["n_layer"]
        before = rows(engine)
        steps0 = engine.expert_token_counts()["counts"].sum()
        try:
            for length in (8, 16, 16):
                engine.admit(rng.randint(1, 96, length), max_new=2)
            given, held = rows(engine) - before
            steps = engine.expert_token_counts()["counts"].sum()
        finally:
            engine.reset()
        assert given == (8 + 16 + 16) * 4 * BUILD["n_layer"]
        assert held == given if full else 0 < held < given
        assert steps == steps0


# ------------------------------------------------------------ the engine

def _states(engine, slot):
    return [np.asarray(engine.scope.find_var(n)[slot])
            for n in engine.state_vars]


def test_a_slots_state_is_overwritten_on_readmission(engine):
    """No leak from the previous request: the state a request leaves in
    slot 0 after another used it equals the state it leaves in a slot
    nothing has used."""
    engine.reset()
    rng = np.random.RandomState(7)
    first, second = rng.randint(1, 96, 13), rng.randint(1, 96, 6)
    engine.generate([first], max_new=9)              # dirties slot 0
    slot, _tok, _done = engine.admit(second, max_new=4)
    assert slot == 0
    after_reuse = _states(engine, 0)
    engine.release(0)
    fresh = FAMILY.fresh()
    slot, _tok, _done = fresh.admit(second, max_new=4)
    # to TOL and not to the bit: ``fresh`` takes the dense way through
    # the expert layer whichever way ``engine`` takes
    for a, b in zip(after_reuse, _states(fresh, slot)):
        np.testing.assert_allclose(a, b, atol=TOL * np.abs(b).max())


def test_an_inactive_slots_state_is_untouched(engine):
    engine.reset()
    rng = np.random.RandomState(8)
    slot_a, _t, _d = engine.admit(rng.randint(1, 96, 9), max_new=12)
    slot_b, _t, _d = engine.admit(rng.randint(1, 96, 5), max_new=12)
    engine.step()
    engine.release(slot_a)
    before = _states(engine, slot_a)
    for _ in range(3):
        engine.step()                                # slot_b alone decodes
    for a, b in zip(before, _states(engine, slot_a)):
        np.testing.assert_array_equal(a, b)
    engine.release(slot_b)


def test_a_prefix_shared_admission_yields_the_same_state(engine):
    """The page radix cache stays sound beside the recurrent state: the
    prefill recomputes the whole prompt (sentinel rows skip only the
    page WRITE), so a request admitted onto shared prefix pages leaves
    the state it leaves when nothing is shared."""
    engine.reset()
    rng = np.random.RandomState(9)
    prompt = rng.randint(1, 96, 14)                  # 3 full pages of 4
    slot, tok_a, _d = engine.admit(prompt, max_new=4)
    alone = _states(engine, slot)
    slot2, tok_b, _d = engine.admit(prompt, max_new=4)
    assert engine.pool.shared_count() > 0
    assert tok_a == tok_b
    for a, b in zip(alone, _states(engine, slot2)):
        np.testing.assert_array_equal(a, b)
    engine.release(slot)
    engine.release(slot2)


def test_warmup_leaves_every_slots_state_as_startup_left_it():
    eng = FAMILY.fresh()
    for n in eng.state_vars:
        assert not np.asarray(eng.scope.find_var(n)).any(), n
    assert eng.free_count() == eng.n_slots


def test_the_multi_head_familys_fingerprint_is_the_parents():
    """``gpt2_medium_d12``'s programs are what they were before the
    hybrid block existed: the hash the AOT files are keyed by, pinned
    from the parent commit (4e4bd0a) at that configuration's geometry."""
    import json
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gpt2_medium_d12.json")) as f:
        cfg = json.load(f)
    build = cfg["build"]
    # temporaries are numbered by a process-wide generator, and the hash
    # covers their names: start it afresh, as a new process does
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        programs = T.build_decoder_lm_programs(
            name="lm", modes=T.slot_modes("paged"),
            kv_codec=cfg["kv_codec"],
            **{**build, "prompt_buckets": tuple(build["prompt_buckets"])})
    eng = serving.make_slot_model("lm", programs, init=False)
    assert eng._fingerprint == ("3f519956615718d32d5d7e0822a926c07d74e174"
                                "407e54c34fc62960cfc82447")
    assert eng.state_vars == []


def test_the_hybrid_familys_fingerprint_is_the_parents():
    """``solar_open2_250b_ep8_d4``'s programs are what they were before
    the latent-attention kind, the dense layers and the router's bias
    existed: the hash the AOT files are keyed by, pinned from the
    parent commit (5f15896) at that configuration's geometry, taken
    before any edit."""
    import json
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar_open2_250b_ep8_d4.json")) as f:
        cfg = json.load(f)
    build = cfg["build"]
    from paddle_tpu.fluid import unique_name
    with unique_name.guard():
        programs = T.build_decoder_lm_programs(
            name="lm", modes=T.slot_modes("paged"),
            kv_codec=cfg["kv_codec"],
            **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
               "layer_kinds": tuple(build["layer_kinds"])})
    eng = serving.make_slot_model("lm", programs, init=False)
    assert eng._fingerprint == ("166b61c4f9411249b1e6b06f5e1b84901d51c6ab"
                                "60f619c502f61ba49008e1b3")
    assert eng._dsa_layers == 0


def _family(**changes):
    build = {**BUILD, **changes}
    return T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes(), kv_codec="none",
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})


def test_the_family_verifier_knows_the_state():
    """One family passes every cross-view contract; views whose state
    shapes disagree (a decode view built for other KDA heads) are
    rejected as drift of the shared persistable."""
    family = _family()
    assert [d for d in contracts.verify_family(family)
            if d.severity >= contracts.Severity.ERROR] == []
    family["decode_paged"] = _family(kda_heads=2)["decode_paged"]
    drift = [d for d in contracts.verify_family(family)
             if d.rule == "ctr-view-var-drift"]
    assert any("_kda_state_" in (d.var or "") for d in drift)
    # and a state variable that does not hold one row per slot
    odd = _family()
    odd["decode_paged"][0].desc.global_block.vars[
        "lm_kda_state_1"].shape[0] = 3
    assert any(d.rule == "ctr-geometry-drift" and d.var == "lm_kda_state_1"
               for d in contracts.verify_family(odd))
    with pytest.raises(ValueError, match="state"):
        serving.make_slot_model("lm", {
            **_family(layer_kinds=["gqa"]),
            "decode_paged": family["decode_paged"]}, init=False)


def test_the_hybrid_block_is_served_by_the_slot_views_alone():
    with pytest.raises(ValueError, match="prefill_paged and decode_paged"):
        T.build_decoder_lm_programs(
            modes=("full",), **{k: BUILD[k] for k in (
                "layer_kinds", "n_kv_head", "head_dim", "kda_heads",
                "kda_head_dim", "kda_gate_rank", "n_routed_experts",
                "n_experts_held", "n_experts_per_tok", "d_expert")})
    with pytest.raises(TypeError, match="unknown argument"):
        T.build_decoder_lm_programs(layer_kind=("gqa",))


def test_expert_counters_follow_the_decode_steps(engine):
    """The device-side counters: between two reads, every active slot's
    token lands on ``n_experts_per_tok`` experts of the router's 16, and
    the held four get their share; an expert's steps-hit never exceeds
    the steps. Without ``sync`` a read is of the last snapshot."""
    engine.reset()
    rng = np.random.RandomState(11)
    before = engine.expert_token_counts(sync=True)
    engine.admit(rng.randint(1, 96, 7), max_new=16)
    engine.admit(rng.randint(1, 96, 12), max_new=16)
    for _ in range(5):
        engine.step()
    stale = engine.expert_token_counts()
    assert stale["steps"] <= before["steps"] + 5
    after = engine.expert_token_counts(sync=True)
    assert after["steps"] == before["steps"] + 5
    delta = after["counts"] - before["counts"]
    assert delta.shape == (8, 2, 4)
    tokens, hit = delta[:, 0], delta[:, 1]
    assert (tokens.sum(axis=1) <= 5 * 2 * 4).all() and tokens.sum() > 0
    assert (hit <= 5).all() and (hit <= tokens).all()
    engine.reset()


def test_steps_ahead_leave_the_same_tokens_and_state(engine):
    """The scheduler's ``step(ahead=True)`` (the next step queued on the
    device before this one's tokens are fetched, its token feed this
    step's output) against one step at a time: the same tokens, and the
    same recurrent state in each slot, to the bit."""
    rng = np.random.RandomState(12)
    prompts = [rng.randint(1, 96, n) for n in (11, 4, 7)]
    budgets = (9, 5, 12)
    seen = {}
    for ahead in (False, True):
        engine.reset()
        toks = {}
        for prompt, budget in zip(prompts, budgets):
            slot, tok, _d = engine.admit(prompt, max_new=budget)
            toks[slot] = [tok]
        while engine.active_count():
            for slot, tok, _d in engine.step(ahead=ahead):
                toks[slot].append(tok)
        seen[ahead] = (toks, {s: _states(engine, s) for s in toks})
    assert seen[True][0] == seen[False][0]
    assert [len(t) for t in seen[True][0].values()] == list(budgets)
    for slot, states in seen[False][1].items():
        for a, b in zip(states, seen[True][1][slot]):
            np.testing.assert_array_equal(a, b)
    engine.reset()


def test_the_grouped_way_starts_above_every_served_bucket():
    """One way through the expert layer for every size a cell serves:
    the dense way up to ``DENSE_MAX_TOKENS`` tokens (the measured
    crossover is near 700: PERF.md, PR 31), the grouped product from the
    next token on."""
    import json
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "solar_open2_250b_ep8_d4.json")) as f:
        build = json.load(f)["build"]
    assert max(build["prompt_buckets"] + [build["n_slots"]]) \
        <= expert_ffn.DENSE_MAX_TOKENS
    rng = np.random.RandomState(13)
    w = _expert_weights(rng, 16, m=8, f=4)
    held = {k: w[k][:4] for k in ("w_gate", "w_up", "w_down")}

    def lowered(n):
        def layer(x):
            combine, idx = expert_ffn.route(x, w["router"], 4, True, 1.0)
            return expert_ffn.held_experts_part(
                x, combine, idx, held["w_gate"], held["w_up"],
                held["w_down"], 0)[0]
        return str(jax.make_jaxpr(layer)(
            np.zeros((n, 8), np.float32)))
    assert "ragged_dot" not in lowered(expert_ffn.DENSE_MAX_TOKENS)
    assert "ragged_dot" in lowered(expert_ffn.DENSE_MAX_TOKENS + 1)
