"""Olmo-Hybrid's block through the slot server at a small size on the
CPU, float32 against the float32 reference
(``chipbench/reference/olmo_hybrid_7b_pp2_d16.py``): prefill (the
chunked scan) and then decode through pages AND state against the
reference's full forward, by LOGITS; the block's switches (no norm
before a sub-layer, a norm over the whole q and k projection, a stack
without an expert layer); the state's declared role, its bytes and the
scan's counters; a released slot's state; and that no loop over tokens
is left in the lowered prefill of a linear layer.

``TOL`` is ``tests/test_hybrid_lm.py``'s: float32 on both sides, so what
separates them is the order of the sums."""

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
from chipbench.reference import olmo_hybrid_7b_pp2_d16 as ref  # noqa: E402
from chipbench.runners import serve_hybrid, serve_olmo_hybrid  # noqa: E402
from paddle_tpu.core.registry import get_op  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402

TOL = 2e-5
BUILD = dict(
    n_layer=4, d_model=48, n_head=3, vocab=96, d_inner=80, prompt_len=32,
    max_new=16, prompt_buckets=[16, 32], n_slots=6, page_size=4,
    layer_kinds=["gdn", "gdn", "gdn", "gqa"], first_k_dense=4, n_kv_head=3,
    head_dim=16, gqa_gate=False, qk_norm="projection", pre_norms=False,
    post_norms=True, gdn_heads=3, gdn_key_dim=8, gdn_value_dim=16,
    gdn_conv_taps=4, gdn_chunk=4, rms_eps=1e-6, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="olmo_hybrid_7b_pp2_d16",
           check=dict(prompt_lens=[21, 11, 6, 2, 32, 16],
                      max_new=[6, 8, 7, 4, 3, 5], state_dtype="float32",
                      limits={"logit_err_max": TOL, "state_err_max": TOL,
                              "state_bf16_share": 0.01,
                              "margin_max_sd": 0.0}))


def programs(**over):
    build = {**BUILD, **over}
    return T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes("paged"), kv_codec="none",
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})


FAMILY = families.Family(serve_hybrid, CFG, ref)


@pytest.fixture(scope="module")
def engine():
    return FAMILY.shared()


@pytest.fixture(scope="module")
def judged(engine):
    """Six requests — prompts of a bucket's length, of a multiple of the
    chunk of 4 and not, shorter than the conv's taps — admitted together
    and stepped together, against the reference's full forward."""
    prompts, served = serve_olmo_hybrid.serve_check(
        CFG, engine, np.random.RandomState(1))
    ok, seen = serve_olmo_hybrid.judge(CFG, engine, prompts, served)
    return ok, seen, prompts, served


@pytest.mark.parametrize("reading", ["logit_err_max", "state_err_max",
                                     "state_bf16_share", "margin_max_sd"])
def test_prefill_then_decode_is_the_references_forward(judged, reading):
    """Logits of every token the served path chose (the prefill view's
    row, then the decode view's through pages and state), each slot's
    state after its request, and the served tokens the reference's own
    best: six requests live together, the largest reading."""
    ok, seen, _prompts, _served = judged
    assert ok and seen["tokens_compared"] == 33
    assert seen[reading] <= CFG["check"]["limits"][reading], seen


@pytest.mark.parametrize("control", [True, "state"])
def test_a_lower_precision_is_outside_the_tolerance(judged, engine, control):
    """The same served readings against the reference one precision
    down, and against the reference with ONLY its state in bfloat16: not
    correct."""
    _ok, _seen, prompts, served = judged
    ok, seen = serve_olmo_hybrid.judge(CFG, engine, prompts, served,
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
    ok, seen = serve_olmo_hybrid.judge(loose, engine, prompts, rounded)
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
    assert sorted(engine.state_kinds) == ["gdn"]
    assert engine.state_kinds["gdn"] == sorted(
        [f"lm_gdn_state_{i}" for i in range(3)]
        + [f"lm_gdn_conv_{i}" for i in range(3)])
    assert serve_olmo_hybrid.state_vars(engine) == [
        f"lm_gdn_state_{i}" for i in range(3)]
    state = engine.scope.find_var("lm_gdn_state_0")
    assert state.shape == (6, 3, 8, 16) and str(state.dtype) == "float32"
    assert engine.scope.find_var("lm_gdn_conv_0").shape == (6, 3, 96)
    want = 3 * 6 * (3 * 8 * 16 * 4 + 3 * 96 * 4)      # float32 windows here
    # the gauge is what the LAST engine of this name was built with: one
    # built here, not the worker's shared one
    FAMILY.fresh(warm=False)
    assert sm.RECURRENT_STATE_BYTES.labels(
        model=engine.name, kind="gdn").value == want


@pytest.mark.parametrize("length,rows", [(5, 16), (16, 16), (17, 32),
                                         (32, 32)])
def test_the_scans_counters(engine, length, rows):
    """True tokens and the rows the scan computed (whole blocks: a
    bucket of 16 is one block of 4 chunks, of 32 one of 8), summed over
    the three linear layers, at every admission."""
    from paddle_tpu.serving import metrics as sm
    engine.reset()
    tokens = sm.GDN_TOKENS_SCANNED.labels(model=engine.name)
    computed = sm.GDN_CHUNK_ROWS.labels(model=engine.name)
    t0, r0 = tokens.value, computed.value
    engine.admit(np.arange(1, length + 1).astype(np.int64), max_new=1)
    assert tokens.value - t0 == 3 * length
    assert computed.value - r0 == 3 * rows
    engine.reset()


def test_the_families_are_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    snap = obs_metrics.default_registry().snapshot()
    assert {"paddle_gdn_tokens_scanned_total", "paddle_gdn_chunk_rows_total",
            "paddle_kda_decode_lowered_total",
            "paddle_recurrent_state_bytes"} <= set(snap)


# ------------------------------------------------------ the block's switches

def params_of(progs):
    main = progs["decode_paged"][0]
    return {p.name: tuple(p.shape)
            for p in main.global_block().all_parameters()}


def test_the_block_has_the_norm_after_and_none_before():
    names = params_of(programs())
    assert "lm_l0_ln1_post_scale" in names and "lm_l3_ln2_post_scale" in names
    assert not [n for n in names if n.endswith(("_ln1_scale", "_ln2_scale"))]
    both = params_of(programs(pre_norms=True))
    assert "lm_l0_ln1_scale" in both and "lm_l0_ln1_post_scale" in both


def test_the_qk_norm_is_over_the_whole_projection():
    names = params_of(programs())
    assert names["lm_l3_attn.q_norm"] == (48,) == names["lm_l3_attn.k_norm"]
    per_head = params_of(programs(qk_norm=True))
    assert per_head["lm_l3_attn.q_norm"] == (16,)
    ops = {op.type: op for op in
           programs()["decode_paged"][0].desc.global_block.ops}
    assert ops["kv_attention_decode_paged"].attrs["qk_norm_whole"] is True
    ops = {op.type: op for op in programs(qk_norm=True)[
        "decode_paged"][0].desc.global_block.ops}
    assert "qk_norm_whole" not in ops["kv_attention_decode_paged"].attrs


def test_a_norm_over_the_projection_is_not_a_norm_a_head():
    """The two norms differ by the heads' relative sizes: the op with
    ``qk_norm_whole`` against numpy, and against the per-head norm."""
    from paddle_tpu.ops import kv_attention as kva
    r = np.random.RandomState(0)
    x = r.randn(1, 5, 12).astype(np.float32)
    wq, wk, wv = (r.randn(12, 8).astype(np.float32) for _ in range(3))
    gq, gk = (1 + 0.1 * r.randn(8).astype(np.float32) for _ in range(2))
    ins = {"QNorm": [jnp.asarray(gq)], "KNorm": [jnp.asarray(gk)]}
    attrs = {"qk_norm": True, "qk_norm_whole": True, "rms_eps": 1e-6}
    q, k, _v = kva._gqa_qkv(jnp.asarray(x), wq, wk, wv, ins, attrs,
                            (2, 2, 4, 4), None)
    y = x @ wq
    want = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * gq
    assert np.abs(np.asarray(q).reshape(1, 5, 8) - want).max() < 1e-5
    per_head = {"QNorm": [jnp.asarray(gq[:4])], "KNorm": [jnp.asarray(gk[:4])]}
    q2, _k, _v = kva._gqa_qkv(jnp.asarray(x), wq, wk, wv, per_head,
                              {"qk_norm": True, "rms_eps": 1e-6},
                              (2, 2, 4, 4), None)
    assert np.abs(np.asarray(q2) - np.asarray(q)).max() > 1e-2
    assert k.shape == (1, 5, 2, 4)


@pytest.mark.parametrize("over,error", [
    (dict(pre_norms=False, post_norms=False), "pre_norms False without"),
    (dict(first_k_dense=3), "needs .*n_experts_held"),
    (dict(gdn_key_dim=None), "needs .*gdn_key_dim"),
    (dict(layer_kinds=["gdn", "gru"]), "a layer is one of"),
])
def test_what_the_block_refuses(over, error):
    arch = {k: v for k, v in {**BUILD, **over}.items()
            if k in T._HYBRID_KEYS}
    with pytest.raises(ValueError, match=error):
        T.hybrid_arch(arch, "decode_paged", 4, 3)


def test_a_dense_stack_holds_without_a_routers_sizes():
    arch = {k: v for k, v in BUILD.items() if k in T._HYBRID_KEYS}
    hy = T.hybrid_arch(arch, "decode_paged", 4, 3)
    assert hy["kinds"] == ("gdn", "gdn", "gdn", "gqa")
    assert hy["n_routed_experts"] is None and hy["d_expert"] is None
    types_ = [op.type for op in
              programs()["decode_paged"][0].desc.global_block.ops]
    assert "expert_ffn_held" not in types_
    assert types_.count("swiglu_ffn") == 4 and types_.count("gdn_decode") == 3


# -------------------------------- no loop over tokens in the lowered prefill

def _loops(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(("scan", eqn.params["length"]))
        elif eqn.primitive.name == "while":
            found.append(("while", None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _loops(sub, found)
    return found


def test_the_prefill_of_a_linear_layer_has_no_loop_over_tokens():
    """``gdn_prefill`` at the cell's sizes (abstractly): ONE loop of
    unknown length, over the blocks a prompt fills; inside it a scan over
    the block's 16 chunks and the 15 turns of the substitution inside a
    chunk's four diagonal blocks of 16 rows (63 over the whole chunk
    until PR 67). No loop runs a token a turn (``kda_prefill``'s does, by
    design: S9)."""
    t, m, h, dk, dv = 8192, 64, 30, 96, 192
    wide = 2 * h * dk + h * dv

    def z(*shape, dt=jnp.bfloat16):
        return [jax.ShapeDtypeStruct(shape, dt)]
    ins = {"X": z(1, t, m), "Wq": z(m, h * dk), "Wk": z(m, h * dk),
           "Wv": z(m, h * dv), "Wz": z(m, h * dv), "Wo": z(h * dv, m),
           "ConvW": z(4, wide), "ALog": z(h, dt=jnp.float32),
           "DtBias": z(h, dt=jnp.float32), "Wa": z(m, h), "Wb": z(m, h),
           "ONorm": z(dv, dt=jnp.float32),
           "State": z(8, h, dk, dv, dt=jnp.float32), "Conv": z(8, 3, wide),
           "SeqLen": z(1, 1, dt=jnp.int32), "Slot": z(1, 1, dt=jnp.int32)}
    attrs = {"n_head": h, "key_dim": dk, "value_dim": dv, "chunk": 64,
             "epsilon": 1e-6}
    jaxpr = jax.make_jaxpr(lambda i: get_op("gdn_prefill").emit(
        types.SimpleNamespace(mesh=None), i, attrs))(ins)
    loops = _loops(jaxpr.jaxpr, [])
    assert sorted(loops, key=str) == sorted(
        [("while", None), ("scan", 16), ("scan", 15)], key=str), loops
    # Solar's keeps its loop over the prompt: one while, a token a turn
    kda_ins = {"X": z(1, 64, m), "Wq": z(m, 256), "Wk": z(m, 256),
               "Wv": z(m, 256), "Wo": z(256, m), "ConvW": z(4, 768),
               "ALog": z(2, dt=jnp.float32), "DtBias": z(256, dt=jnp.float32),
               "WaDown": z(m, 8), "WaUp": z(8, 256), "WBeta": z(m, 2),
               "WgDown": z(m, 8), "WgUp": z(8, 256),
               "ONorm": z(128, dt=jnp.float32),
               "State": z(2, 2, 128, 128, dt=jnp.float32),
               "Conv": z(2, 3, 768), "SeqLen": z(1, 1, dt=jnp.int32),
               "Slot": z(1, 1, dt=jnp.int32)}
    kda = jax.make_jaxpr(lambda i: get_op("kda_prefill").emit(
        types.SimpleNamespace(mesh=None), i,
        {"n_head": 2, "head_dim": 128, "epsilon": 1e-5}))(kda_ins)
    assert _loops(kda.jaxpr, []) == [("while", None)]
