"""LFM2's block through the slot server (``decoder_lm(...,
layer_kinds=["conv", "conv", "gqa", "conv"])``: gated short convolutions
with a window per slot beside ROTARY grouped-KV layers through the paged
pool, two leading dense layers, bias-corrected experts with NO shared
expert, a tied head) against the plain reference of
``chipbench/reference/lfm2_8b_a1b_d12.py``, at a tiny size on the CPU in
float32.

The tolerance of every comparison is ``TOL``: system and reference both
compute in float32 from the same weights, so what separates them is the
order of the sums and the reference's 1e-6 under the picks' sum (a few
1e-6 here). A fault moves a result by 1e-2 or more, and
``test_a_fault_fails_the_comparison`` shows each one failing it.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.reference import lfm2_8b_a1b_d12 as ref  # noqa: E402
from chipbench.runners import serve_hybrid, serve_lfm2  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.analysis import contracts  # noqa: E402
from paddle_tpu.core.registry import slot_state_vars  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import shortconv  # noqa: E402

TOL = 2e-5
# two periods of (conv, conv, gqa, conv), the first two layers dense;
# buckets 16 and 32; 8 experts, all held, 4 picks by score + bias, no
# shared expert; rotary positions on the gqa layers; a tied table
BUILD = dict(
    n_layer=8, d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=32,
    max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["conv", "conv", "gqa", "conv"], first_k_dense=2,
    n_kv_head=2, head_dim=16, qk_norm=True, gqa_gate=False,
    gqa_rope_theta=1e6, conv_taps=3,
    n_routed_experts=8, n_experts_held=8, n_experts_per_tok=4, d_expert=24,
    n_shared_experts=0, router_bias=True, norm_topk_prob=True,
    routed_scaling_factor=1.0, tie_embeddings=True,
    rms_eps=1e-5, dtype="float32")
CHECK = dict(prompt_lens=[21, 9, 13, 2], max_new=[9, 5, 7, 4],
             window_dtype="float32",
             limits=dict(logit_err_median=TOL, window_err_max=TOL,
                         picks_gap_max=0.0,
                         picks_gap_layer_mean_max=0.0))
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="lfm2_8b_a1b_d12", check=CHECK)
CONV_LAYERS = (0, 1, 3, 4, 5, 7)


def _thirty_times_the_bias(engine, _build, _seed):
    # among 8 experts a bias of 0.01 seldom changes a pick: thirty times
    # the drawn one, so that picking by score + bias and by score differ
    for n in engine.scope.local_var_names():
        if n.endswith(".router_bias"):
            engine.scope.set_var(n, 30.0 * engine.scope.find_var(n))


FAMILY = families.Family(serve_lfm2, CFG, ref, serve_lfm2.PicksProbe,
                         prepare=_thirty_times_the_bias)
params_of = FAMILY.params_of


@pytest.fixture(scope="module", params=["dense", "grouped"])
def engine(request):
    return FAMILY.shared(
        patches=families.GROUPED if request.param == "grouped" else ())


def worst(engine, prompt_len, max_new=10, seed=1, build=BUILD, forced=True,
          **ref_kwargs):
    prompt, toks, logits, windows, picks = FAMILY.request(
        engine, prompt_len, max_new, seed, build)
    assert picks.shape == (sum(i >= build["first_k_dense"] for i in range(
        build["n_layer"])), prompt_len + max_new - 1, 4)
    logit_err, window_err, margin, gaps = ref.compare(
        params_of(engine, build), prompt, toks, logits, windows, build,
        served_picks=picks if forced else None, **ref_kwargs)
    return max(logit_err.max(), window_err.max(), gaps.max()), \
        margin.max()


# prompt lengths: two shorter than the conv's three taps, one that is no
# bucket's (16, 32), one a whole bucket
@pytest.mark.parametrize("prompt_len", [1, 2, 13, 32])
def test_prefill_then_decode_matches_the_full_forward(engine, prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through pages AND conv windows, and the window left in
    the slot, against one full causal forward with no cache."""
    err, margin = worst(engine, prompt_len)
    assert err <= TOL               # every pick the reference's own, too
    assert margin == 0.0            # every served token the argmax
    # and against the reference's OWN picks: the same, in float32
    if prompt_len == 13:
        assert worst(engine, prompt_len, forced=False)[0] <= TOL


@pytest.fixture(scope="module")
def flash_engine():
    """Buckets of 128 and 256 rows with the prefill's flash kernel
    forced on (interpreted; ``GQA_QUERY_BLOCK`` lowered under the
    buckets, tiles of 64 x 128 so that a bucket is several): the
    programs are traced inside ``warmup()``, under the patches."""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    build = {**BUILD, "prompt_buckets": [128, 256], "prompt_len": 256}
    before = kv.GQA_PREFILL_ATTEND_LOWERED.labels(path="flash").value
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
        patch.setattr(kv, "GQA_QUERY_BLOCK", 64)
        patch.setattr(pk, "causal_blocks", lambda t, d, dv: (64, 128))
        engine = FAMILY.fresh(prompt_buckets=build["prompt_buckets"],
                              prompt_len=build["prompt_len"])
    lowered = kv.GQA_PREFILL_ATTEND_LOWERED.labels(path="flash").value
    return engine, build, lowered - before


@pytest.mark.parametrize("prompt_len", [70, 129, 256])
def test_the_prefill_through_the_flash_kernel_matches_the_full_forward(
        flash_engine, prompt_len):
    """ISSUE 52: the prefill view attending through the causal flash
    forward kernel (grouped key heads, rotated and normalised q and k,
    the bucket's padded rows attended as before) against one full causal
    forward with no cache, to the float32 tolerance of every other
    comparison here; every lowering of a bucket's program counted its
    two attention layers under ``flash``."""
    engine, build, lowered = flash_engine
    assert lowered and lowered % (2 * len(build["prompt_buckets"])) == 0
    err, margin = worst(engine, prompt_len, max_new=4, build=build)
    assert err <= TOL
    assert margin == 0.0


@pytest.mark.parametrize("prompt_len", [1, 2, 11])
def test_the_window_after_a_prefill_is_the_prompts_true_end(engine,
                                                            prompt_len):
    """A request of ONE token is its prefill alone: the slot's windows
    are the reference's ``B * x`` at the prompt's last two TRUE
    positions, zeros where the prompt is shorter — never the bucket's
    padded end."""
    prompt = np.random.RandomState(prompt_len).randint(1, BUILD["vocab"],
                                                       prompt_len)
    _toks, _logits, windows, _p = serve_lfm2.serve_one(engine, prompt, 1)
    _ref_logits, want, _gaps = ref.forward(params_of(engine), prompt,
                                    [prompt_len - 1], BUILD)
    assert len(windows) == len(want) == len(CONV_LAYERS)
    for got, w in zip(windows, want):
        np.testing.assert_allclose(got, np.asarray(w), atol=TOL)
        if prompt_len == 1:
            assert not got[0].any() and got[1].any()


def test_requests_live_together_leave_each_other_alone(engine):
    """Four requests admitted together and stepped together, leaving at
    different steps, under the limits a configuration's ``check``
    states: each slot's logits and final windows are its own request's
    (a released slot's windows sit through the others' steps)."""
    rng = np.random.RandomState(3)
    prompts, served = serve_lfm2.serve_check(CFG, engine, rng)
    correct, seen = serve_lfm2.judge(CFG, engine, prompts, served)
    assert correct, seen
    assert seen["margin_max_sd"] == 0.0


def test_one_layer_picking_without_its_bias_fails_by_the_gap_alone(engine):
    """The check's own control, a fault IN THE PROGRAM: one expert layer
    of six serves with its correction bias zeroed (the reference is
    given the honest bias back). The logits and the windows still agree
    — the reference weighs the served picks — and the picks' certificate
    alone refuses the run: a served pick lies under the reference's k-th
    best by what the bias moved, not by a rounding."""
    name = "lm_l4_moe.router_bias"
    honest = engine.scope.find_var(name)
    engine.scope.set_var(name, 0.0 * honest)
    try:
        prompts, served = serve_lfm2.serve_check(
            CFG, engine, np.random.RandomState(4))
    finally:
        engine.scope.set_var(name, honest)
    correct, seen = serve_lfm2.judge(CFG, engine, prompts, served)
    assert not correct
    assert seen["logit_err_median"] <= TOL and seen["window_err_max"] <= TOL
    assert seen["picks_gap_max"] > 0.01 and seen["picks_not_own_share"] > 0
    # a sixth of the pairs are that layer's: its mean is most of the sum
    assert seen["picks_gap_layer_mean_max"] > 1e-3


def test_two_tokens_exchanging_their_picks_fail_by_the_largest_gap(engine):
    """No compared logit can see a wrong pick of a mid-prompt token in
    the LAST expert layer (the reference adopts the served picks, and
    that layer's result feeds no later token): the certificate of each
    pick does."""
    prompts, served = serve_lfm2.serve_check(CFG, engine,
                                             np.random.RandomState(6))
    picks = np.array(served[0][3])
    last = np.sort(picks[-1, :len(prompts[0]) - 1], -1)
    i, j = next((i, j) for i in range(len(last)) for j in range(i)
                if (last[i] != last[j]).any())
    picks[-1, [i, j]] = picks[-1, [j, i]]
    served[0] = (*served[0][:3], picks)
    correct, seen = serve_lfm2.judge(CFG, engine, prompts, served)
    assert not correct and seen["picks_gap_max"] > 1e-3
    assert seen["logit_err_median"] <= TOL and seen["window_err_max"] <= TOL


def test_picks_that_name_an_expert_twice_are_no_picks():
    """``route`` holds served picks to its own scores: the router's own
    read 0, a swap for the fifth best reads the distance between the
    fourth and the fifth, a doubled expert reads inf."""
    rng = np.random.RandomState(0)
    w = {"router": rng.randn(8, 6).astype(np.float32),
         "router_bias": 0.1 * rng.randn(1, 6).astype(np.float32)}
    x = rng.randn(5, 8).astype(np.float32)
    cfg = dict(router_bias=True, n_experts_per_tok=2)
    biased = np.asarray(jax.nn.sigmoid(x @ w["router"])) + w["router_bias"]
    order = np.argsort(-biased, -1)
    own, third, twice = order[:, :2], order[:, [0, 2]], order[:, [0, 0]]
    for picks, want in ((own, np.zeros(5)), (twice, np.full(5, np.inf)), (
            third, np.take_along_axis(biased, order[:, 1:2], -1)[:, 0]
            - np.take_along_axis(biased, order[:, 2:3], -1)[:, 0])):
        _c, gap = ref.route(w.__getitem__, x, cfg, served_picks=picks)
        np.testing.assert_allclose(np.asarray(gap), want, atol=1e-6)


def test_the_served_programs_name_no_picks(engine):
    """No builder declares the expert op's ``Picks``: the served
    programs are a deployment's, and the probe's copies alone name it."""
    for cb in [engine._cb_decode, *engine._cb_prefill.values()]:
        ops = [op for op in cb._program_desc.global_block.ops
               if op.type == "expert_ffn_held"]
        assert ops and not any("Picks" in op.outputs for op in ops)
        assert not any("picks" in op.attrs for op in ops)
        copy, names = serve_lfm2.with_picks(cb._program_desc)
        assert len(names) == len(ops) == 6
        assert all(n in copy.global_block.vars for n in names)
        assert not any(n in cb._program_desc.global_block.vars
                       for n in names)


def _windows_of(engine, slot):
    return [np.asarray(engine.scope.find_var(n)[slot])
            for n in engine.state_vars]


def test_windows_do_not_leak_across_release_and_reuse():
    """A slot's windows after a request are what a FRESH engine leaves
    for that request: admission overwrites them (a shorter prompt keeps
    nothing of the slot's last tenant), inactive slots keep theirs bit
    for bit through another slot's admission and steps."""
    rng = np.random.RandomState(8)
    first, second = (rng.randint(1, BUILD["vocab"], n) for n in (23, 1))
    # the worker's honest engine with every slot free: what this test
    # puts in it is released again, and a window is overwritten at the
    # next admission (which is the claim)
    used, fresh = FAMILY.shared(), FAMILY.fresh()
    used.reset()
    slot, _t, _d = used.admit(first, max_new=5)
    while any(not done for _s, _t, done in used.step()):
        pass
    assert all(np.abs(w).max() > 0 for w in _windows_of(used, slot))
    other = (slot + 1) % BUILD["n_slots"]
    untouched = _windows_of(used, other)
    again, _t, _d = used.admit(second, max_new=4)
    assert again == slot
    while any(not done for _s, _t, done in used.step()):
        pass
    clean, _t, _d = fresh.admit(second, max_new=4)
    while any(not done for _s, _t, done in fresh.step()):
        pass
    for a, b in zip(_windows_of(used, slot), _windows_of(fresh, clean)):
        assert np.array_equal(a, b)
    for a, b in zip(_windows_of(used, other), untouched):
        assert np.array_equal(a, b)


def test_the_server_returns_the_compared_tokens(engine):
    """The same requests through ``ModelServer.submit_generate`` (the
    scheduler's loop, its steps dispatched ahead): the tokens whose
    logits were compared."""
    rng = np.random.RandomState(11)
    prompts, served = serve_lfm2.serve_check(CFG, engine, rng)
    server = serving.ModelServer()
    try:
        server.add_model(engine)
        assert serve_hybrid.same_through_server(
            server, CFG, prompts, [s[0] for s in served])
    finally:
        server.stop()


# ------------------------------------------------------ the program's part

def test_the_third_kind_of_state_is_found_by_its_declared_role(engine):
    """The engine finds the conv windows by what the ops DECLARE, with
    no name matched and no edit to ``_discover_state``; admission names
    the slot; pages are leased for the two attention layers alone."""
    block = engine._cb_decode._program_desc.global_block
    names = [f"lm_conv_state_{i}" for i in CONV_LAYERS]
    assert slot_state_vars(block) == {"shortconv": {"ConvOut": names}}
    assert engine.state_kinds == {"shortconv": names}
    assert engine.state_vars == names
    assert serve_lfm2.window_vars(engine) == names
    assert "state_slot" in engine._cb_prefill[16].sig.feed_names
    gvars = block.vars
    assert sorted(n for n in gvars if "_page_k_" in n) == [
        "lm_page_k_2", "lm_page_k_6"]
    for n in names:
        assert list(gvars[n].shape) == [4, 2, 64]
    programs = T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes("paged"), kv_codec="none",
        **{**BUILD, "prompt_buckets": tuple(BUILD["prompt_buckets"]),
           "layer_kinds": tuple(BUILD["layer_kinds"])})
    assert not [d for d in contracts.verify_family(programs)
                if d.severity.name == "ERROR"]


def test_no_shared_expert_means_no_parameter_and_no_branch(engine):
    """``n_shared_experts`` 0: no zero-width ``s_gate`` / ``s_up`` /
    ``s_down`` is made and the op is handed none."""
    block = engine._cb_decode._program_desc.global_block
    assert not [n for n in block.vars if ".s_gate" in n or ".s_up" in n
                or ".s_down" in n]
    experts = [op for op in block.ops if op.type == "expert_ffn_held"]
    assert len(experts) == 6
    for op in experts:
        assert not op.inputs.get("SGate") and op.input("RouterBias")
    # ... and lowers no shared branch
    from paddle_tpu.core.registry import OPS, EmitContext
    rng = np.random.RandomState(0)
    f = lambda *s: jax.numpy.asarray(                          # noqa: E731
        rng.standard_normal(s).astype(np.float32))
    ins = {"X": [f(1, 5, 8)], "RouterW": [f(8, 4)], "WGate": [f(4, 8, 6)],
           "WUp": [f(4, 8, 6)], "WDown": [f(4, 6, 8)]}
    text = jax.jit(lambda i: OPS["expert_ffn_held"].emit(
        EmitContext(base_key=jax.random.PRNGKey(0)), i,
        {"top_k": 2})["Out"][0]).lower(ins).as_text()
    assert "shared" not in text


def test_the_gqa_layers_rotate_and_the_others_programs_do_not_change(engine):
    block = engine._cb_decode._program_desc.global_block
    attn = [op for op in block.ops
            if op.type == "kv_attention_decode_paged"]
    assert [op.attrs["rope_theta"] for op in attn] == [1e6, 1e6]
    assert all(op.attrs["qk_norm"] and "window" not in op.attrs
               for op in attn)
    # a gqa layer of a family that asks for no positions carries no
    # such attribute (its programs are what they were)
    plain = T.build_decoder_lm_programs(
        name="lm", modes=("decode_paged",), kv_codec="none",
        **{**BUILD, "gqa_rope_theta": None,
           "prompt_buckets": tuple(BUILD["prompt_buckets"]),
           "layer_kinds": tuple(BUILD["layer_kinds"])})
    assert not [op for op in plain["decode_paged"][0].desc.global_block.ops
                if "rope_theta" in op.attrs]


def test_convolved_tokens_are_counted_and_the_span_names_the_kind():
    """``paddle_shortconv_tokens_total`` counts a prompt's TRUE tokens
    at its prefill and the running slots of a step, both times the conv
    layers; ``serving.admit.state`` says which kind of state the slot
    was named for."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import metrics as sm
    engine = FAMILY.fresh(n_layer=4)                     # 3 conv layers
    count = {v: sm.SHORTCONV_TOKENS.labels(model="lm", view=v)
             for v in ("prefill", "decode")}
    p0, d0 = count["prefill"].value, count["decode"].value
    tracer = tracing.default_tracer()
    tracer.reset()
    tracer.start()
    try:
        engine.admit(np.arange(1, 22), max_new=3)
        engine.admit(np.arange(1, 3), max_new=2)
        engine.step()                   # two slots run, one finishes
        engine.step()                   # one slot runs
    finally:
        tracer.stop()
    assert count["prefill"].value - p0 == 3 * (21 + 2)
    assert count["decode"].value - d0 == 3 * (2 + 1)
    spans = [s for s in tracer.spans() if s.name == "serving.admit.state"]
    assert len(spans) == 2
    assert all(s.args["kinds"] == "shortconv" for s in spans)
    assert sm.RECURRENT_STATE_BYTES.labels(
        model="lm", kind="shortconv").value == 3 * 4 * 2 * 64 * 4


def test_the_new_family_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_shortconv_tokens_total" \
        in obs_metrics.default_registry().snapshot()


def test_a_conv_layer_is_refused_without_its_taps():
    arch = {k: v for k, v in BUILD.items() if k in T._HYBRID_KEYS}
    with pytest.raises(ValueError, match="conv_taps"):
        T.hybrid_arch({k: v for k, v in arch.items() if k != "conv_taps"},
                      "decode_paged", 8)
    # a family without the kind need not give them
    T.hybrid_arch({**{k: v for k, v in arch.items() if k != "conv_taps"},
                   "layer_kinds": ["gqa"]}, "decode_paged", 8)


# ----------------------------------- the older families' programs stand

# the hash the AOT files are keyed by, of every hybrid configuration the
# benchmark serves, pinned from the parent commit (f05f2d6) at that
# configuration's geometry before any edit (tests/test_hybrid_lm.py
# pins gpt2_medium_d12's and solar_open2_250b_ep8_d4's the same way)
PARENTS = {
    "glm5_744b_ep16_d5": "3abb8e18bcce9b66e2b0401f40bc31676eb29510"
                         "3da211698262738819348093",
    "trinity_mini_26b_d5": "dc43d068f616d89617d1f657b2464b1bd50ff290"
                           "89f8014366369a53b0ea659b",
    "granite4_h_small_ep4_d10": "3632cf1aac1af0b7909e026b1da076fc5285"
                                "144eb5f3bdf3c9ea152663176f11",
}


@pytest.mark.parametrize("config", sorted(PARENTS))
def test_an_older_hybrid_familys_fingerprint_is_the_parents(config):
    """A sixth kind, rotary ``gqa`` layers and an expert layer without a
    shared expert are attributes set only where they differ: the served
    hybrid configurations' programs are what they were."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
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
    assert eng._fingerprint == PARENTS[config]
    assert "shortconv" not in eng.state_kinds


# --------------------------------------------------------------- faults

@pytest.mark.parametrize("fault", [
    "window_at_buckets_end", "no_rotation", "rotation_on_one_side",
    "picks_without_bias", "reference_without_rotation",
    "reference_without_bias", "reference_window_at_buckets_end"])
def test_a_fault_fails_the_comparison(monkeypatch, fault):
    """The tolerance bites: the system with one fault in it (the
    reference is fed the honest configuration), or the honest system
    against a reference control, lies far outside it."""
    # one period, and the one bucket the prompt of 13 takes
    changes, ref_kwargs = {"n_layer": 4, "prompt_buckets": [16],
                           "prompt_len": 16}, {}
    honest = FAMILY.build(**changes)      # what the reference is told
    if fault == "window_at_buckets_end":
        real = jax.lax.dynamic_slice
        monkeypatch.setattr(
            shortconv.jax.lax, "dynamic_slice",
            lambda operand, start, sizes:
            real(operand, (operand.shape[0] - sizes[0], 0), sizes)
            if len(sizes) == 2 and sizes[0] == 2
            else real(operand, start, sizes))
    elif fault == "no_rotation":
        changes["gqa_rope_theta"] = None
    elif fault == "rotation_on_one_side":
        from paddle_tpu.ops import kv_attention
        real_rope = kv_attention.rope_half
        monkeypatch.setattr(
            kv_attention, "rope_half", lambda x, pos, theta, rotary=None:
            real_rope(x, pos, theta) if x.shape[2] == 4 else x)
    elif fault == "picks_without_bias":
        changes["router_bias"] = False
    elif fault == "reference_without_rotation":
        ref_kwargs["rotary"] = False
    elif fault == "reference_without_bias":
        ref_kwargs["use_bias"] = False
    else:
        ref_kwargs["window_end"] = 16
    try:
        # a control that faults the reference alone takes the worker's
        # honest engine of that depth
        engine = (FAMILY.shared if ref_kwargs else FAMILY.fresh)(
            seed=9, **changes)
        if fault == "picks_without_bias":
            # the faulty family has no bias to hand the reference: it is
            # given one of the size the honest family draws
            bias = np.random.RandomState(2).randn(1, 8).astype(np.float32)
            real = engine.scope.find_var
            monkeypatch.setattr(
                engine.scope, "find_var", lambda n:
                0.3 * bias if n.endswith(".router_bias") else real(n))
        err, _margin = worst(engine, 13, max_new=8, build=honest,
                             **ref_kwargs)
    finally:
        monkeypatch.undo()
    assert err > 100 * TOL
