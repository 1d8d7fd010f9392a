"""The state-space (``ssd``) kind of the hybrid sparse block
(``decoder_lm(..., layer_kinds=[..., "ssd", ...])``: Mamba-2 layers with
a fixed-size state per slot beside a NoPE grouped-KV layer through the
paged pool, softmax-over-the-picks experts with a shared expert of its
own width, a tied and scaled table) against the plain reference of
``chipbench/reference/granite4_h_small_ep4_d10.py``, at a tiny size on
the CPU in float32.

The tolerance of every comparison is ``TOL``: system and reference both
compute in float32 from the same weights, so what separates them is the
order of the sums (the chunked scan against the token-by-token
recurrence: a few 1e-7 here). A fault moves a result by 1e-2 or more,
and ``test_a_fault_fails_the_comparison`` shows each one failing it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.reference import granite4_h_small_ep4_d10 as ref  # noqa: E402
from chipbench.runners import serve_granite  # noqa: E402
from paddle_tpu.analysis import contracts  # noqa: E402
from paddle_tpu.core.registry import slot_state_vars  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import expert_ffn, ssd  # noqa: E402

TOL = 2e-5
# two periods of (ssd, gqa, ssd, ssd); buckets 16 and 32 over chunks of
# 8 rows; 12 experts of which 3 are held, 4 picks; a shared expert wider
# than an expert; the four multipliers; a tied table
BUILD = dict(
    n_layer=8, d_model=64, n_head=4, vocab=96, prompt_len=32, max_new=16,
    prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["ssd", "gqa", "ssd", "ssd"], n_kv_head=2, head_dim=16,
    gqa_gate=False, attn_scale=0.1, embed_scale=3.0, residual_scale=0.22,
    logits_scale=4.0, tie_embeddings=True,
    ssd_heads=8, ssd_head_dim=8, ssd_d_state=16, ssd_groups=1,
    ssd_conv_taps=4, ssd_chunk=8,
    n_routed_experts=12, n_experts_held=3, held_start=0,
    n_experts_per_tok=4, d_expert=24, d_shared=40, scoring="softmax_topk",
    rms_eps=1e-5, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="granite4_h_small_ep4_d10")


FAMILY = families.Family(serve_granite, CFG, ref,
                         serve_granite.serve_hybrid.LogitProbe)
params_of = FAMILY.params_of


@pytest.fixture(scope="module", params=["dense", "grouped"])
def engine(request):
    return FAMILY.shared(
        patches=families.GROUPED if request.param == "grouped" else ())


def worst(engine, prompt_len, max_new=10, seed=1, build=BUILD):
    prompt, toks, logits, states = FAMILY.request(engine, prompt_len,
                                                  max_new, seed, build)
    logit_err, state_err, margin, _slow = ref.compare(
        params_of(engine, build), prompt, toks, logits, states, build)
    return max(logit_err.max(), state_err.max()), margin.max()


# prompt lengths that are no multiple of the chunk (8) and no bucket's
# (16, 32), one shorter than the conv's four taps, one a whole bucket
@pytest.mark.parametrize("prompt_len", [2, 7, 13, 21, 27, 32])
def test_prefill_then_decode_matches_the_full_forward(engine, prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through pages AND state, and the state left in the slot,
    against one full causal forward with no cache and no chunks."""
    err, margin = worst(engine, prompt_len)
    assert err <= TOL
    assert margin == 0.0            # every served token the argmax


def test_requests_live_together_leave_each_other_alone(engine):
    """Three requests admitted together and stepped together, leaving at
    different steps: each slot's logits and final state are its own
    request's (an inactive slot's state and conv window are untouched
    by the others' steps)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, BUILD["vocab"], n) for n in (5, 19, 11)]
    budgets = [9, 3, 6]
    served = serve_granite.serve_together(
        engine, FAMILY.probe(engine), prompts, budgets)
    for prompt, (toks, logits, states) in zip(prompts, served):
        logit_err, state_err, _m, _s = ref.compare(
            params_of(engine), prompt, toks, logits, states, BUILD)
        assert max(logit_err.max(), state_err.max()) <= TOL


def _state_of(engine, slot):
    return [np.asarray(engine.scope.find_var(n)[slot])
            for n in engine.state_vars]


def test_state_does_not_leak_across_release_and_reuse():
    """A slot's state and conv window after a request are what a FRESH
    engine leaves for that request: admission overwrites both (nothing
    of the slot's last tenant is left), inactive slots keep theirs bit
    for bit through another slot's admission and steps, and release
    leaves the state where it is."""
    rng = np.random.RandomState(8)
    first, second = (rng.randint(1, BUILD["vocab"], n) for n in (23, 6))
    # the worker's honest engine with every slot free: what this test
    # puts in it is released again, and a slot's state is overwritten at
    # the next admission (which is the claim)
    used, fresh = FAMILY.shared(), FAMILY.fresh()
    used.reset()
    slot, _t, _d = used.admit(first, max_new=5)
    while any(not done for _s, _t, done in used.step()):
        pass
    left = _state_of(used, slot)
    assert all(np.abs(s).max() > 0 for s in left)
    other = (slot + 1) % BUILD["n_slots"]
    untouched = _state_of(used, other)
    # the same slot again (the lowest free one), a shorter prompt
    again, _t, _d = used.admit(second, max_new=4)
    assert again == slot
    for a, b in zip(_state_of(used, other), untouched):
        assert np.array_equal(a, b)
    while any(not done for _s, _t, done in used.step()):
        pass
    clean, _t, _d = fresh.admit(second, max_new=4)
    while any(not done for _s, _t, done in fresh.step()):
        pass
    for a, b in zip(_state_of(used, slot), _state_of(fresh, clean)):
        assert np.array_equal(a, b)
    for a, b in zip(_state_of(used, other), untouched):
        assert np.array_equal(a, b)


# ------------------------------------------------------------- the scan

def _layer_inputs(t, seed, h=8, p=8, n=16, g=1):
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    dt = np.log1p(np.exp(f(t, h) - 2.0))                 # softplus
    log_a = -np.linspace(1.0, 16.0, h, dtype=np.float32) * dt
    return f(t, h, p), f(t, g, n), f(t, g, n), dt, log_a


def _token_by_token(x, b, c, dt, log_a):
    h, g = x.shape[1], b.shape[1]
    s = np.zeros((h, x.shape[2], b.shape[2]), np.float64)
    ys = []
    for t in range(x.shape[0]):
        bt, ct = (np.repeat(v[t], h // g, axis=0) for v in (b, c))
        s = np.exp(log_a[t])[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", s, ct))
    return np.stack(ys), s


@pytest.mark.parametrize("bucket, true_len, chunk, groups", [
    (32, 21, 8, 1),      # a padded bucket, the true length inside a chunk
    (32, 32, 8, 1),      # a whole bucket
    (32, 3, 8, 1),       # one chunk, mostly padding
    (16, 9, 16, 1),      # the chunk is the bucket
    (32, 19, 8, 2),      # two groups of B and C
])
def test_chunked_scan_is_the_token_by_token_recurrence(bucket, true_len,
                                                       chunk, groups):
    """``ssd.chunk_scan`` over a padded bucket (rows past the true
    length have dt = 0, x = 0: they change nothing) against the
    recurrence one token at a time, at ``TOL``: the outputs of the true
    rows and the state after the last of them."""
    x, b, c, dt, log_a = _layer_inputs(bucket, bucket + true_len, g=groups)
    real = np.arange(bucket)[:, None] < true_len
    xm, dtm, lam = (np.where(real[..., None], x, 0), np.where(real, dt, 0),
                    np.where(real, log_a, 0))
    y, s = jax.jit(ssd.chunk_scan, static_argnums=(6,))(
        *(jnp.asarray(v) for v in (xm, b, c, dtm, lam)),
        -(-true_len // chunk), chunk)
    want_y, want_s = _token_by_token(x[:true_len], b[:true_len],
                                     c[:true_len], dt[:true_len],
                                     log_a[:true_len])
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(y)[:true_len] - want_y).max() <= TOL * scale
    assert np.abs(np.asarray(s) - want_s).max() \
        <= TOL * np.abs(want_s).max()
    # chunks past the true length are never computed
    done = -(-true_len // chunk) * chunk
    assert not np.asarray(y)[done:].any()


def test_an_inactive_slots_state_is_kept_bit_for_bit():
    """``ssd_decode``: a slot with ``Active`` 0 keeps its state and its
    conv window bit for bit; an active slot's state is ``state_step``'s."""
    from paddle_tpu.core.registry import OPS, EmitContext
    rng = np.random.RandomState(1)
    f = lambda *s: jnp.asarray(                                # noqa: E731
        rng.standard_normal(s).astype(np.float32))
    ins = {"X": [f(3, 1, 8)], "WIn": [f(8, 34)], "WOut": [f(8, 8)],
           "ConvW": [f(4, 24)], "ConvB": [f(1, 24)], "ALog": [f(2)],
           "DtBias": [f(2)], "D": [f(2)], "Norm": [f(8)],
           "State": [f(3, 8, 8)], "Conv": [f(3, 3, 24)],
           "Active": [jnp.asarray([[1], [0], [1]])]}
    out = OPS["ssd_decode"].emit(
        EmitContext(base_key=jax.random.PRNGKey(0)), ins,
        {"n_head": 2, "head_dim": 4, "d_state": 8, "n_groups": 1})
    state, conv = np.asarray(out["StateOut"][0]), np.asarray(out["ConvOut"][0])
    assert np.array_equal(state[1], np.asarray(ins["State"][0])[1])
    assert np.array_equal(conv[1], np.asarray(ins["Conv"][0])[1])
    for live in (0, 2):
        assert not np.array_equal(state[live],
                                  np.asarray(ins["State"][0])[live])
        assert np.array_equal(conv[live, :2],
                              np.asarray(ins["Conv"][0])[live, 1:])
    assert np.isfinite(np.asarray(out["Out"][0])).all()


# ------------------------------------------------- router, shares, table

def test_softmax_topk_is_granites_gating():
    """``route(..., scoring="softmax_topk")`` against the definition of
    ``GraniteMoeTopKGating``: the ``top_k`` largest LOGITS, gates a
    softmax over those alone (they sum to 1; an expert outside the picks
    weighs nothing, whatever its logit)."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((9, 16)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((16, 12)).astype(np.float32))
    gates, idx = expert_ffn.route(x, w, 4, True, 1.0,
                                  scoring="softmax_topk")
    logits = np.asarray(x) @ np.asarray(w)
    for row, (g, i) in enumerate(zip(np.asarray(gates), np.asarray(idx))):
        order = np.argsort(-logits[row])[:4]
        assert sorted(i) == sorted(order)
        picked = logits[row][i]
        want = np.exp(picked - picked.max())
        np.testing.assert_allclose(g, want / want.sum(), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    dense = np.asarray(ref.topk_gating(jnp.asarray(logits), 4))
    assert ((dense > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(idx), -1), np.asarray(gates),
        rtol=1e-5)
    with pytest.raises(ValueError, match="softmax_topk"):
        expert_ffn.route(x, w, 4, True, 1.0, scoring="softmax")


def _whole_layer(x, p, cfg):
    """The UNCUT expert layer, by hand: every one of the router's
    experts, weighed by the gating, plus the shared expert once."""
    gates = np.asarray(ref.topk_gating(
        jnp.asarray(x @ p["router"]), cfg["n_experts_per_tok"]))
    silu = lambda v: v / (1.0 + np.exp(-v))                   # noqa: E731
    out = (silu(x @ p["s_gate"]) * (x @ p["s_up"])) @ p["s_down"]
    for e in range(gates.shape[1]):
        y = (silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]
        out = out + gates[:, e:e + 1] * y
    return out


@pytest.mark.parametrize("way", ["dense", "grouped"])
def test_the_four_shares_add_up_to_the_uncut_layer(monkeypatch, way):
    """12 experts over 4 members of 3: the routed parts the four shares
    give (the op, told which experts it holds) plus the shared expert
    counted ONCE equal the uncut reference's layer."""
    from paddle_tpu.core.registry import OPS, EmitContext
    if way == "grouped":
        monkeypatch.setattr(expert_ffn, "DENSE_MAX_TOKENS", 0)
    rng = np.random.RandomState(4)
    f = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.3  # noqa
    m, e, w, ws, n = 16, 12, 10, 14, 11
    p = dict(router=f(m, e), w_gate=f(e, m, w), w_up=f(e, m, w),
             w_down=f(e, w, m), s_gate=f(m, ws), s_up=f(m, ws),
             s_down=f(ws, m))
    x = f(n, m)
    cfg = dict(n_experts_per_tok=4)
    want = _whole_layer(x, p, cfg)

    def share(first, count):
        held = slice(first, first + count)
        ins = {"X": [jnp.asarray(x[None])], "RouterW": [p["router"]],
               "WGate": [p["w_gate"][held]], "WUp": [p["w_up"][held]],
               "WDown": [p["w_down"][held]], "SGate": [p["s_gate"]],
               "SUp": [p["s_up"]], "SDown": [p["s_down"]]}
        ins = {k: [jnp.asarray(v[0])] for k, v in ins.items()}
        out = OPS["expert_ffn_held"].emit(
            EmitContext(base_key=jax.random.PRNGKey(0)), ins,
            {"top_k": 4, "held_start": first,
             "scoring": "softmax_topk"})
        return np.asarray(out["Out"][0])[0]

    shared = np.asarray(ref.ffn(jnp.asarray(x), p["s_gate"], p["s_up"],
                                p["s_down"]))
    parts = [share(first, 3) - shared for first in (0, 3, 6, 9)]
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-4,
                               atol=2e-5)
    # and the reference's own shares say the same
    g = lambda tag: p[tag]                                    # noqa: E731
    ref_parts = [np.asarray(ref.expert_layer(
        lambda tag, first=first: p[tag][first:first + 3]
        if tag in ("w_gate", "w_up", "w_down") else p[tag],
        jnp.asarray(x), cfg, (first, 3), shared=False))
        for first in (0, 3, 6, 9)]
    np.testing.assert_allclose(sum(ref_parts) + shared, want, rtol=2e-4,
                               atol=2e-5)
    assert g("router").shape == (m, e)


def test_the_table_is_tied_and_scaled(engine):
    """One table: the family has no ``head_w``, the logits are
    ``RMSNorm(x) E^T / logits_scale`` over the embedding's own rows, and
    a row of the table that changes moves both the embedding and the
    logits of that token."""
    gvars = engine._cb_decode._program_desc.global_block.vars
    assert "lm_emb" in gvars and "lm_head_w" not in gvars
    heads = [op for op in engine._cb_decode._program_desc.global_block.ops
             if op.type == "dense" and op.attrs.get("transpose_w")]
    assert len(heads) == 1 and heads[0].input("W") == ["lm_emb"]
    assert heads[0].attrs["scale"] == pytest.approx(1 / 4.0)
    # untied, unscaled families keep the op's attrs as they were
    plain = FAMILY.shared(warm=False, tie_embeddings=False,
                          logits_scale=1.0, n_layer=4)
    gvars = plain._cb_decode._program_desc.global_block.vars
    assert "lm_head_w" in gvars
    assert not [op for op in plain._cb_decode._program_desc.global_block.ops
                if op.type == "dense" and set(op.attrs) - {"out_dtype"}]


# ------------------------------------------------------ the engine's part

def test_recurrent_state_is_found_by_its_declared_role(engine):
    """The engine finds per-slot state by what the ops DECLARE
    (``slot_state``), not by a mixer's variable names: six SSD layers'
    state and conv window, counted under their kind."""
    block = engine._cb_decode._program_desc.global_block
    found = slot_state_vars(block)
    assert list(found) == ["ssd"]
    assert len(found["ssd"]["StateOut"]) == len(found["ssd"]["ConvOut"]) == 6
    assert engine.state_kinds == {"ssd": sorted(
        found["ssd"]["StateOut"] + found["ssd"]["ConvOut"])}
    assert engine.state_vars == engine.state_kinds["ssd"]
    assert serve_granite.state_vars(engine) == [
        f"lm_ssd_state_{i}" for i in (0, 2, 3, 4, 6, 7)]
    # both views are one family: the prefill names the slot
    assert "state_slot" in engine._cb_prefill[16].sig.feed_names
    report = contracts.verify_family(engine.family) \
        if hasattr(engine, "family") else None
    assert report is None or not [d for d in report if d.severity.name
                                  == "ERROR"]


def test_scanned_tokens_and_chunk_rows_are_counted():
    """``paddle_ssd_tokens_scanned_total`` counts a prompt's TRUE tokens
    and ``paddle_ssd_chunk_rows_total`` the whole chunks the scan
    computed, both summed over the SSD layers, at admission."""
    from paddle_tpu.serving import metrics as sm
    engine = FAMILY.fresh(n_layer=4)
    tokens = sm.SSD_TOKENS_SCANNED.labels(model="lm")
    rows = sm.SSD_CHUNK_ROWS.labels(model="lm")
    t0, r0 = tokens.value, rows.value
    engine.admit(np.arange(1, 22), max_new=2)           # 21 -> 3 chunks
    engine.admit(np.arange(1, 9), max_new=2)            # 8 -> 1 chunk
    assert tokens.value - t0 == 3 * (21 + 8)
    assert rows.value - r0 == 3 * (24 + 8)
    # ... and the state the model keeps, under its kind (float32 here:
    # the state, and the conv window's 3 rows of 64 + 2 x 16 channels)
    inner = BUILD["ssd_heads"] * BUILD["ssd_head_dim"]
    per_layer = BUILD["n_slots"] * (
        BUILD["ssd_d_state"] * inner * 4
        + 3 * (inner + 2 * BUILD["ssd_d_state"]) * 4)
    assert sm.RECURRENT_STATE_BYTES.labels(
        model="lm", kind="ssd").value == 3 * per_layer


def test_the_new_families_are_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    families = obs_metrics.default_registry().snapshot()
    for name in ("paddle_ssd_tokens_scanned_total",
                 "paddle_ssd_chunk_rows_total",
                 "paddle_recurrent_state_bytes"):
        assert name in families, name


def test_a_kind_is_refused_without_its_sizes():
    with pytest.raises(ValueError, match="ssd_chunk"):
        T.hybrid_arch({k: v for k, v in BUILD.items()
                       if k in T._HYBRID_KEYS and k != "ssd_chunk"},
                      "decode_paged", 8)
    with pytest.raises(ValueError, match="one of"):
        T.hybrid_arch({**{k: v for k, v in BUILD.items()
                          if k in T._HYBRID_KEYS},
                       "layer_kinds": ["ssm"]}, "decode_paged", 8)
    with pytest.raises(ValueError, match="attn_scale"):
        T.hybrid_arch({**{k: v for k, v in BUILD.items()
                          if k in T._HYBRID_KEYS},
                       "layer_kinds": ["swa", "ssd"], "window": 8,
                       "rope_theta": 1e4}, "decode_paged", 8)


# --------------------------------------------------------------- faults

def _no_conv_bias(orig):
    def split(c, sizes):
        return orig(c - 0.05, sizes)
    return split


def _bf16_state(orig):
    def step(state, a, u, b, c):
        new, y = orig(state, a, u, b, c)
        return new.astype(jnp.bfloat16).astype(new.dtype), y
    return step


def _decay_not_negated(orig):
    def decay(dt_raw, w):
        dt, log_a = orig(dt_raw, w)
        return dt, log_a * 0.5
    return decay


def _gate_after_norm(orig):
    def output(y, z, w, eps, dt):
        return orig(y, jnp.zeros_like(z) + 1.278, w, eps, dt)
    return output


def _sigmoid_router(orig):
    def route(x, w, k, norm, scaling, *bias, scoring="sigmoid"):
        return orig(x, w, k, norm, scaling, *bias)
    return route


FAULTS = {
    "conv_bias_off": (ssd, "_split", _no_conv_bias),
    "bf16_state": (ssd, "state_step", _bf16_state),
    "decay_halved": (ssd, "_decay", _decay_not_negated),
    "gate_lost": (ssd, "_output", _gate_after_norm),
    "sigmoid_router": (expert_ffn, "route", _sigmoid_router),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + [
    "attn_scale_default", "residual_unscaled", "scan_ignores_length"])
def test_a_fault_fails_the_comparison(monkeypatch, fault):
    """The tolerance bites: the system with one fault in it (the
    reference is fed the honest configuration) lies far outside it."""
    # one period, and the one bucket the prompt of 13 takes
    changes = {"n_layer": 4, "prompt_buckets": [16], "prompt_len": 16}
    honest = FAMILY.build(**changes)      # what the reference is told
    if fault in FAULTS:
        module, name, wrap = FAULTS[fault]
        monkeypatch.setattr(module, name, wrap(getattr(module, name)))
    elif fault == "attn_scale_default":
        changes["attn_scale"] = None
    elif fault == "residual_unscaled":
        changes["residual_scale"] = 1.0
    else:
        real = ssd.chunk_scan

        def whole_bucket(x, b, c, dt, log_a, n_chunks, chunk):
            # every row decays and feeds the state, padding too
            return real(x + 1.0, b, c, dt + 0.05, log_a - 0.05,
                        x.shape[0] // chunk, chunk)
        monkeypatch.setattr(ssd, "chunk_scan", whole_bucket)
    try:
        engine = FAMILY.fresh(seed=9, **changes)
        err, _margin = worst(engine, 13, max_new=8, build=honest)
    finally:
        monkeypatch.undo()
    assert err > 100 * TOL
