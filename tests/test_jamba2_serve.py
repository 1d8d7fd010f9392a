"""Jamba2's block through the slot server at a small size on the CPU,
float32 against the float32 reference
(``chipbench/reference/jamba2_3b.py``): prefill (the selective scan)
and then decode through pages AND state against the reference's full
forward, by LOGITS and by each slot's state; the period of fourteen,
multi-query attention (ONE KV head), the tied table and the stack
without an expert layer; the state's declared role, its bytes and the
scan's counters; a released slot's state; and that the lowered prefill
holds no array of a bucket's ``[T, N, C]``.

``TOL`` is ``tests/test_hybrid_lm.py``'s: float32 on both sides, so what
separates them is the order of the sums."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.reference import jamba2_3b as ref  # noqa: E402
from chipbench.runners import serve_jamba2  # noqa: E402
from paddle_tpu.core.registry import get_op  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

TOL = 2e-5
KINDS = ["s6"] * 7 + ["gqa"] + ["s6"] * 6
BUILD = dict(
    n_layer=14, d_model=32, n_head=4, vocab=96, d_inner=48, prompt_len=32,
    max_new=16, prompt_buckets=[16, 32], n_slots=6, page_size=4,
    layer_kinds=KINDS, first_k_dense=14, n_kv_head=1, head_dim=8,
    gqa_gate=False, tie_embeddings=True, s6_d_inner=64, s6_d_state=4,
    s6_dt_rank=6, s6_conv_taps=4, s6_chunk=8, rms_eps=1e-6,
    dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="jamba2_3b",
           check=dict(prompt_lens=[21, 11, 6, 2, 32, 16],
                      max_new=[6, 8, 7, 4, 3, 5], state_dtype="float32",
                      state_layers=[0, 6, 12],
                      limits={"logit_err_max": TOL, "state_err_max": TOL,
                              "state_bf16_share": 0.01,
                              "margin_max_sd": 0.0}))
S6_LAYERS = [i for i, k in enumerate(KINDS) if k == "s6"]


@functools.lru_cache(maxsize=None)
def programs():
    """The tiny family's programs, built once a module (read only)."""
    build = BUILD
    return T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes("paged"), kv_codec="none",
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})


FAMILY = families.Family(serve_jamba2, CFG, ref)


@pytest.fixture(scope="module")
def engine():
    return FAMILY.shared()


@pytest.fixture(scope="module")
def judged(engine):
    """Six requests — prompts of a bucket's length, of a multiple of the
    chunk of 8 and not, shorter than the conv's taps — admitted together
    and stepped together, against the reference's full forward."""
    prompts, served = serve_jamba2.serve_check(
        CFG, engine, np.random.RandomState(1))
    ok, seen = serve_jamba2.judge(CFG, engine, prompts, served)
    return ok, seen, prompts, served


@pytest.mark.parametrize("reading", ["logit_err_max", "state_err_max",
                                     "state_bf16_share", "margin_max_sd"])
def test_prefill_then_decode_is_the_references_forward(judged, reading):
    """Logits of every token the served path chose (the prefill view's
    row, then the decode view's through pages and state), the state of a
    first, a middle and the last Mamba layer after each request, and the
    served tokens the reference's own best: six requests live together,
    the largest reading."""
    ok, seen, _prompts, served = judged
    assert ok and seen["tokens_compared"] == 33
    assert seen[reading] <= CFG["check"]["limits"][reading], seen
    assert all(len(states) == 3 and states[0].shape == (64, 4)
               for _t, _l, states in served)


@pytest.mark.parametrize("control", [True, "state", "recurrence"])
def test_a_lower_precision_is_outside_the_tolerance(judged, engine, control):
    """The same served readings against the reference one precision
    down, against the reference with ONLY its state in bfloat16, and
    with the recurrence's factors in bfloat16 too: not correct."""
    _ok, _seen, prompts, served = judged
    # two of the six requests: each length is a compile of the control
    ok, seen = serve_jamba2.judge(CFG, engine, prompts[:2], served[:2],
                                  low_precision=control)
    assert not ok and seen["state_err_max"] > 50 * TOL, seen


def test_a_state_kept_in_bfloat16_reads_as_one(judged, engine):
    """What ``state_bf16_share`` is for: the served states rounded to
    bfloat16 — what a state variable kept in bfloat16 would hold — read
    1.0 where the float32 ones read ~1 / 65536, and are not correct by
    that limit, whatever the other two say."""
    _ok, _seen, prompts, served = judged
    rounded = [(toks, logits, [np.asarray(jnp.asarray(s).astype(
        jnp.bfloat16).astype(jnp.float32)) for s in states])
        for toks, logits, states in served]
    loose = {**CFG, "check": {**CFG["check"], "limits": {
        "logit_err_max": 1.0, "state_err_max": 1.0,
        "state_bf16_share": 0.01}}}
    ok, seen = serve_jamba2.judge(loose, engine, prompts, rounded)
    assert not ok and seen["state_bf16_share"] == 1.0
    assert seen["state_err_max"] < 0.01        # the limit that sees it


def test_a_released_slots_state_is_kept_bit_for_bit(engine):
    """Two requests; the first leaves after 2 tokens and its slot sits
    released while the second decodes on: its state and conv window are
    what they were when it left."""
    engine.reset()
    rng = np.random.RandomState(3)
    a, _t, _d = engine.admit(rng.randint(1, 96, 9).astype(np.int64),
                             max_new=2)
    b, _t, _d = engine.admit(rng.randint(1, 96, 20).astype(np.int64),
                             max_new=9)
    engine.step()                       # a's second token: a leaves
    names = engine.state_vars
    left = {n: np.asarray(engine.scope.find_var(n)[a]) for n in names}
    moved = {n: np.asarray(engine.scope.find_var(n)[b]) for n in names}
    for _ in range(4):
        engine.step()
    for n in names:
        assert np.array_equal(np.asarray(engine.scope.find_var(n)[a]),
                              left[n]), n
    assert any(not np.array_equal(
        np.asarray(engine.scope.find_var(n)[b]), moved[n]) for n in names)
    engine.reset()


# ------------------------------------------- the state, by its declared role

def test_the_state_is_found_by_role_and_sized(engine):
    from paddle_tpu.serving import metrics as sm
    assert sorted(engine.state_kinds) == ["s6"]
    assert engine.state_kinds["s6"] == sorted(
        [f"lm_s6_state_{i}" for i in S6_LAYERS]
        + [f"lm_s6_conv_{i}" for i in S6_LAYERS])
    assert serve_jamba2.state_vars(engine) == [
        f"lm_s6_state_{i}" for i in S6_LAYERS]
    state = engine.scope.find_var("lm_s6_state_0")
    assert state.shape == (6, 4, 64) and str(state.dtype) == "float32"
    assert engine.scope.find_var("lm_s6_conv_13").shape == (6, 3, 64)
    want = 13 * 6 * (4 * 64 * 4 + 3 * 64 * 4)        # float32 windows here
    # the gauge is what the LAST engine of this name was built with: one
    # built here, not the worker's shared one
    FAMILY.fresh(warm=False)
    assert sm.RECURRENT_STATE_BYTES.labels(
        model=engine.name, kind="s6").value == want


@pytest.mark.parametrize("length,rows", [(5, 8), (8, 8), (9, 16), (16, 16),
                                         (17, 24), (32, 32)])
def test_the_scans_counters(engine, length, rows):
    """True tokens and the rows the scan walked (the whole chunks of 8 a
    prompt's true length fills), summed over the thirteen Mamba layers,
    at every admission."""
    from paddle_tpu.serving import metrics as sm
    engine.reset()
    tokens = sm.S6_TOKENS_SCANNED.labels(model=engine.name)
    walked = sm.S6_CHUNK_ROWS.labels(model=engine.name)
    t0, r0 = tokens.value, walked.value
    engine.admit(np.arange(1, length + 1).astype(np.int64), max_new=1)
    assert tokens.value - t0 == 13 * length
    assert walked.value - r0 == 13 * rows
    engine.reset()


def test_the_families_are_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    snap = obs_metrics.default_registry().snapshot()
    assert {"paddle_s6_tokens_scanned_total", "paddle_s6_chunk_rows_total",
            "paddle_s6_scan_lowered_total", "paddle_s6_state_lowered_total",
            "paddle_recurrent_state_bytes"} <= set(snap)


def test_the_admission_span_names_the_kind(engine):
    from paddle_tpu.observability import tracing
    tracer = tracing.default_tracer()
    engine.reset()
    tracer.reset()
    tracer.start()
    try:
        engine.admit(np.arange(1, 8).astype(np.int64), max_new=1)
    finally:
        tracer.stop()
        engine.reset()
    spans = [s for s in tracer.spans() if s.name == "serving.admit.state"]
    assert spans and spans[-1].args["kinds"] == "s6"


# ------------------------------------------------------ the block's shape

def params_of(progs):
    main = progs["decode_paged"][0]
    return {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}


def test_the_period_is_fourteen_with_one_attention_layer():
    """Layer ``i`` is attention where ``i % 14 == 7``: at 28 layers,
    layers 7 and 21, a Mamba layer otherwise."""
    arch = {k: v for k, v in {**BUILD, "first_k_dense": 28}.items()
            if k in T._HYBRID_KEYS}
    hy = T.hybrid_arch(arch, "decode_paged", 28, 4)
    assert [i for i, k in enumerate(hy["kinds"]) if k == "gqa"] == [7, 21]
    assert hy["kinds"].count("s6") == 26
    types_ = [op.type for op in
              programs()["decode_paged"][0].desc.global_block.ops]
    assert types_.count("s6_decode") == 13
    assert types_.count("kv_attention_decode_paged") == 1
    assert types_.count("swiglu_ffn") == 14
    assert "expert_ffn_held" not in types_


def test_attention_is_multi_query_over_a_tied_table():
    names = params_of(programs())
    # 4 query heads of 8 over ONE KV head: K and V project to 8 columns
    assert names["lm_l7_attn.wq"] == (32, 32)
    assert names["lm_l7_attn.wk"] == (32, 8) == names["lm_l7_attn.wv"]
    assert not [n for n in names if "gate" in n and "attn" in n]
    assert "lm_head_w" not in names and names["lm_emb"] == (96, 32)
    gvars = programs()["decode_paged"][0].desc.global_block.vars
    assert tuple(gvars["lm_page_k_7"].shape)[-1] == 8      # a row: 1 x 8
    assert not [n for n in gvars if n.startswith("lm_page_k_")
                and n != "lm_page_k_7"]


def test_a_mamba_layers_weights():
    names = params_of(programs())
    want = {"w_in": (32, 128), "w_out": (64, 32), "conv": (4, 64),
            "conv_bias": (1, 64), "w_x": (64, 6 + 2 * 4), "w_dt": (6, 64),
            "dt_norm": (6,), "b_norm": (4,), "c_norm": (4,),
            "dt_bias": (64,), "a_log": (4 * 64,), "d": (64,)}
    assert {k: names[f"lm_l0_s6.{k}"] for k in want} == want
    assert sorted(n.split(".")[1] for n in names
                  if n.startswith("lm_l0_s6.")) == sorted(want)


def test_the_start_up_values_are_mamba_ones(engine):
    """A_log = log(1..N) along the state index in every channel (flat,
    the state index first), D = 1, softplus(dt_bias) log-evenly over
    [0.001, 0.1] across the channels, the three gains 1: what the
    drawer leaves (it draws matrices alone)."""
    get = lambda tag: np.asarray(                            # noqa: E731
        engine.scope.find_var(f"lm_l3_s6.{tag}"))
    assert np.allclose(np.exp(get("a_log")).reshape(4, 64),
                       np.arange(1, 5)[:, None])
    assert np.array_equal(get("d"), np.ones(64, np.float32))
    dt0 = np.log1p(np.exp(get("dt_bias")))
    assert np.allclose(dt0, np.exp(np.linspace(np.log(1e-3), np.log(0.1),
                                               64)), rtol=1e-4)
    for tag in ("dt_norm", "b_norm", "c_norm"):
        assert np.array_equal(get(tag), np.ones_like(get(tag)))


@pytest.mark.parametrize("over,error", [
    (dict(s6_d_state=None), "needs .*s6_d_state"),
    (dict(s6_dt_rank=None), "needs .*s6_dt_rank"),
    (dict(first_k_dense=3), "needs .*n_experts_held"),
    (dict(layer_kinds=["s6", "mamba"]), "a layer is one of"),
    (dict(n_kv_head=3), "does not divide n_head"),
])
def test_what_the_block_refuses(over, error):
    arch = {k: v for k, v in {**BUILD, **over}.items()
            if k in T._HYBRID_KEYS}
    with pytest.raises(ValueError, match=error):
        T.hybrid_arch(arch, "decode_paged", 14, 4)


# ------------------------ no array of a bucket's [T, N, C] in the prefill

def _shapes(jaxpr, found, prims):
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue                  # its body's values live in VMEM
        for v in eqn.outvars:
            found.add(tuple(getattr(v.aval, "shape", ())))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found, prims)
    return found


@pytest.mark.parametrize("tier", ["loop", "kernel"])
def test_the_prefill_holds_no_array_of_the_buckets_states(tier, monkeypatch):
    """``s6_prefill`` at the cell's sizes (abstractly), through either
    tier: no value of ``[1024, 16, 5120]`` anywhere in it — the state
    ``[16, 5120]`` is a loop's carry or a kernel's resident tile — and
    the loop's largest values are the bucket's ``[1024, 5120]`` rows."""
    from paddle_tpu.ops import pallas as plk
    if tier == "kernel":
        monkeypatch.setattr(plk, "on_tpu", lambda: True)
    t, m, c, n, r = 1024, 64, 5120, 16, 160

    def z(*shape, dt=jnp.bfloat16):
        return [jax.ShapeDtypeStruct(shape, dt)]
    f32 = jnp.float32
    ins = {"X": z(1, t, m), "WIn": z(m, 2 * c), "WOut": z(c, m),
           "ConvW": z(4, c), "ConvB": z(1, c), "WX": z(c, r + 2 * n),
           "WDt": z(r, c), "DtNorm": z(r, dt=f32), "BNorm": z(n, dt=f32),
           "CNorm": z(n, dt=f32), "DtBias": z(c, dt=f32),
           "ALog": z(n * c, dt=f32), "D": z(c, dt=f32),
           "State": z(256, n, c, dt=f32), "Conv": z(256, 3, c),
           "SeqLen": z(1, 1, dt=jnp.int32), "Slot": z(1, 1, dt=jnp.int32)}
    jaxpr = jax.make_jaxpr(lambda i: get_op("s6_prefill").emit(
        types.SimpleNamespace(mesh=None), i,
        {"chunk": 64, "epsilon": 1e-6}))(ins)
    prims = set()
    shapes = _shapes(jaxpr.jaxpr, set(), prims)
    assert (t, c) in shapes and (256, n, c) in shapes
    big = [s for s in shapes if len(s) >= 2 and int(np.prod(s)) > t * 2 * c
           and s != (256, n, c)]
    assert not big, big
    assert ("pallas_call" in prims) == (tier == "kernel")
