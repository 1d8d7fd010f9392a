"""Grouped attention whose geometry differs by layer kind
(``decoder_lm(..., layer_kinds=("gqa", "swa", "swa", "swa", "swa"),
swa_n_kv_head=..., gqa_v_head_dim=..., rotary_dim=..., swa_sink=True,
value_scale=...)``: MiMo-V2-Flash's block) against the plain reference of
``chipbench/reference/mimo_v2_flash_ep16_d7.py``, at a tiny size on the
CPU in float32: width 64, 8 query heads over 2 KV heads in the full
layers and over 4 in the window layers, keys of 24 beside values of 16,
the first 8 values of a head rotated (base 5e6 full, 1e4 window), a
window of 8 positions behind a sink over pages of 4 (a ring of 3 pages a
slot), 4 of 16 experts held from the fourth on, the published period of
seven layers (full + dense, window x4, full, window).

ONE engine is built for the module. The tolerance of every comparison is
``TOL``: system and reference both compute in float32 from the same
weights, so what separates them is the order of the sums — under 1e-6
here. Each fault moves a result by 1e-2 or more.
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
from chipbench.reference import mimo_v2_flash_ep16_d7 as ref  # noqa: E402
from chipbench.runners import serve_mimo, serve_trinity  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import expert_ffn, kv_attention  # noqa: E402
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

TOL = 2e-5
BUILD = dict(
    n_layer=7, d_model=64, d_inner=96, n_head=8, vocab=96, prompt_len=32,
    max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["gqa", "swa", "swa", "swa", "swa"], first_k_dense=1,
    n_kv_head=2, swa_n_kv_head=4, head_dim=24, gqa_v_head_dim=16,
    rotary_dim=8, gqa_rope_theta=5e6, rope_theta=1e4, window=8,
    swa_sink=True, value_scale=0.707, gqa_gate=False, qk_norm=False,
    n_routed_experts=16, n_experts_held=4, held_start=4,
    n_experts_per_tok=4, d_expert=24, n_shared_experts=0,
    norm_topk_prob=True, router_bias=True, routed_scaling_factor=1.0,
    rms_eps=1e-5, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="mimo_v2_flash_ep16_d7")


def _drawn_gains(engine, build, seed):
    rng = np.random.RandomState(seed)
    for name in ref.param_names(build):
        if name.endswith("_scale"):
            shape = np.shape(engine.scope.find_var(name))
            engine.scope.set_var(name, jax.device_put(
                rng.uniform(0.5, 1.5, shape).astype(np.float32)))


FAMILY = families.Family(serve_mimo, CFG, ref, serve_trinity.AttendedProbe,
                         prepare=_drawn_gains)
params_of = FAMILY.params_of


@pytest.fixture(scope="module")
def engine():
    """The engine with the weights (and sinks) of seed 5 and every norm's
    gain drawn from 0.5-1.5."""
    return FAMILY.shared()


@pytest.fixture(scope="module")
def served(engine):
    """{prompt length: (prompt, tokens, logits, attended)}: one request
    of 14 tokens per length, served once for the module. Shorter than,
    as long as and longer than the window of 8, all but one SHORTER than
    their bucket (16 or 32), every one crossing the window or past it."""
    out = {}
    for n in (3, 7, 8, 9, 16, 21, 30):
        prompt = np.random.RandomState(n).randint(1, BUILD["vocab"], n)
        out[n] = (prompt,) + tuple(serve_trinity.serve_one(
            engine, prompt, 14, probe=FAMILY.probe(engine)))
    return out


def worst(engine, served, n, **control):
    prompt, toks, logits, seen = served[n]
    err, margin, positions = ref.compare(params_of(engine), prompt, toks,
                                         logits, BUILD, **control)
    want = ref.attended(BUILD, positions, control.get("window"))
    return err.max(), int((seen != want[:, None, :]).any(-1).sum()), \
        margin.max()


@pytest.mark.parametrize("prompt_len", [3, 7, 8, 9, 16, 21, 30])
def test_prefill_then_decode_matches_the_full_forward(engine, served,
                                                      prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through both page groups (K rows of n_kv * 24, V rows of
    n_kv * 16, n_kv the kind's own), and WHAT each window layer
    attended, against one full causal forward with no cache."""
    err, wrong, margin = worst(engine, served, prompt_len)
    assert err <= TOL
    assert wrong == 0
    assert margin == 0.0            # every served token the argmax
    assert engine.pool.stats()["window_pages_free"] \
        == engine.n_window_pages


@pytest.mark.parametrize("control", [
    dict(sink=False), dict(rotary_dim=24), dict(value_scale=1.0),
    dict(swa_n_kv_head=None), dict(window=7), dict(window=9),
    dict(low_precision=True)], ids=lambda c: "-".join(
        f"{k}={v}" for k, v in c.items()))
def test_a_fault_fails_the_comparison(engine, served, control):
    """The sink left out of the window layers' softmax, every value of a
    head rotated, V unscaled, the window layers grouped over the FULL
    layers' KV head count, a window one key short or long, a forward one
    precision down: each is far outside the tolerance (and the window's
    faults in every reading of what was attended)."""
    err, wrong, _ = worst(engine, served, 21, **control)
    assert err > 500 * TOL
    if "window" in control:
        assert wrong > 0


def test_each_kinds_planes_have_its_own_row_width(engine):
    """K rows are n_kv * head_dim wide and V rows n_kv * v_head_dim,
    n_kv the kind's own; the bytes a position costs are reckoned per
    group, from the planes themselves."""
    shape = lambda n: tuple(np.shape(engine.scope.find_var(n)))  # noqa
    full, ring = engine.n_pages, engine.n_window_pages
    assert ring == BUILD["n_slots"] * 3
    for i in (0, 5):
        assert shape(f"lm_page_k_{i}") == (full, 4, 2 * 24)
        assert shape(f"lm_page_v_{i}") == (full, 4, 2 * 16)
    for i in (1, 2, 3, 4, 6):
        assert shape(f"lm_page_wk_{i}") == (ring, 4, 4 * 24)
        assert shape(f"lm_page_wv_{i}") == (ring, 4, 4 * 16)
        assert shape(f"lm_l{i}_attn.sink") == (8,)
        assert str(engine.scope.find_var(f"lm_l{i}_attn.sink").dtype) \
            == "float32"
    assert shape("lm_l0_attn.wv") == (64, 2 * 16)
    assert shape("lm_l1_attn.wv") == (64, 4 * 16)
    assert shape("lm_l1_attn.wo") == (8 * 16, 64)
    assert engine.row_bytes == {"full": 2 * 2 * (24 + 16) * 4,
                                "window": 5 * 4 * (24 + 16) * 4}
    # the gauge is what the LAST engine of this name was built with: one
    # built here, not the worker's shared one
    assert FAMILY.fresh(warm=False).row_bytes == engine.row_bytes
    for group, value in engine.row_bytes.items():
        assert smetrics.KV_ROW_BYTES.labels(
            model="lm", group=group).value == value


def test_the_sinks_follow_the_seed(engine):
    """``weights_chunked`` leaves rank-1 parameters as start-up drew
    them; the runner draws the sinks Normal(0, 1) from the seed (here
    into scopes of their own, under the engine's names)."""
    import types
    import paddle_tpu.fluid as fluid
    names = [n for n in engine._cb_decode.sig.const_names
             if n.endswith(".sink")]
    assert len(names) == 5

    def drawn(seed):
        scope = fluid.Scope()
        for n in names:
            scope.set_var(n, jnp.zeros((8,), jnp.float32))
        serve_mimo.draw_sinks(types.SimpleNamespace(
            scope=scope, _cb_decode=engine._cb_decode), seed,
            jax.devices()[0])
        return np.stack([np.asarray(scope.find_var(n)) for n in names])
    a, b = drawn(5), drawn(6)
    assert np.array_equal(a, drawn(5)) and not np.array_equal(a, b)
    assert np.array_equal(a[0], np.asarray(
        engine.scope.find_var(names[0])))       # the engine's own draw
    assert len(np.unique(a)) == a.size
    assert 0.5 < a.std() < 1.5 and abs(a.mean()) < 0.5


def test_the_full_layers_rows_are_counted(engine):
    """Per step, slot and full layer: the LIVE rows attended and the
    whole table's rows gathered — this engine's layers take the copy
    path (the CPU; ``tests/test_kv_attend_in_place.py`` has the other)."""
    def read():
        return (smetrics.KV_FULL_ROWS_ATTENDED.labels(model="lm").value,
                smetrics.KV_FULL_ROWS_GATHERED.labels(model="lm").value,
                smetrics.KV_WINDOW_ROWS_ATTENDED.labels(model="lm").value)
    a0, g0, w0 = read()
    prompt = np.arange(1, 12)
    serve_trinity.serve_one(engine, prompt, 5)
    a1, g1, w1 = read()
    # four decode steps behind the prefill's token: the query's own row
    # is live, 11 + 1 ... 11 + 4 rows; two full layers, five window
    lives = [len(prompt) + 1 + i for i in range(4)]
    assert a1 - a0 == 2 * sum(lives)
    assert g1 - g0 == 2 * 4 * BUILD["n_slots"] * 48
    assert w1 - w0 == 5 * sum(min(n, 8) for n in lives)
    assert (engine._full_layers, engine._in_place_blocks) == (2, {})


# --------------------------------------------------------- the op's parts

def test_rope_half_turns_a_leading_share_alone():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 5, 3, 24).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, 50, (2, 5)))
    got = np.asarray(kv_attention.rope_half(x, pos, 1e4, 8))
    inv = 1e4 ** (-np.arange(0, 8, 2) / 8)
    ang = np.asarray(pos)[:, :, None, None] * inv
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    np.testing.assert_allclose(got[..., :4], a * np.cos(ang)
                               - b * np.sin(ang), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[..., 4:8], b * np.cos(ang)
                               + a * np.sin(ang), rtol=1e-5, atol=1e-6)
    assert np.array_equal(got[..., 8:], np.asarray(x[..., 8:]))
    whole = kv_attention.rope_half(x, pos, 1e4)
    assert np.array_equal(np.asarray(kv_attention.rope_half(
        x, pos, 1e4, 24)), np.asarray(whole))


def _plain_decode(q, k, v, valid, n_kv, sink=None):
    """Per-head float64 attention of q [B,1,H,Dk] over k [B,S,n_kv*Dk],
    v [B,S,n_kv*Dv] with an optional sink logit a head."""
    b, _, h, dk = q.shape
    dv = v.shape[2] // n_kv
    out = np.zeros((b, 1, h, dv))
    for i in range(b):
        for head in range(h):
            kv = head // (h // n_kv)
            s = k[i, :, kv * dk:(kv + 1) * dk] @ q[i, 0, head] / dk ** 0.5
            s = np.where(valid[i], s, -np.inf)
            m = max(s.max(), -np.inf if sink is None else sink[head])
            e = np.exp(s - m)
            den = e.sum() + (0.0 if sink is None else np.exp(sink[head] - m))
            out[i, 0, head] = (e / den) @ v[i, :, kv * dv:(kv + 1) * dv]
    return out


@pytest.mark.parametrize("with_sink", [False, True])
def test_decode_contract_with_values_of_another_size(with_sink):
    """The block-diagonal query spans the K lanes alone; p . v runs over
    the V lanes (n_kv * Dv) and a sink joins the denominator."""
    rng = np.random.RandomState(1)
    b, s, h, n_kv, dk, dv = 3, 20, 8, 4, 24, 16
    q = rng.randn(b, 1, h, dk).astype(np.float32)
    k = rng.randn(b, s, n_kv * dk).astype(np.float32)
    v = rng.randn(b, s, n_kv * dv).astype(np.float32)
    valid = rng.rand(b, s) < 0.6
    valid[:, 0] = True
    sink = rng.randn(h).astype(np.float32) if with_sink else None
    got = np.asarray(kv_attention._decode_contract(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(valid)[:, None], jnp.float32, n_kv,
        sink=None if sink is None else jnp.asarray(sink)))
    assert got.shape == (b, 1, h, dv)
    np.testing.assert_allclose(got, _plain_decode(q, k, v, valid, n_kv,
                                                  sink), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("t,window", [(16, 8), (1024, 128), (1024, None)],
                         ids=["whole", "blocked-band", "blocked-full"])
def test_gqa_attend_with_a_sink_and_values_of_another_size(t, window):
    """The whole square and the blocked band take the same softmax: the
    sink's column in both, context heads of Dv."""
    rng = np.random.RandomState(2)
    n_kv, g, dk, dv = 2, 2, 24, 16
    q = jnp.asarray(rng.randn(1, t, n_kv, g, dk).astype(np.float32))
    k = jnp.asarray(rng.randn(1, t, n_kv, dk).astype(np.float32))
    v = jnp.asarray(rng.randn(1, t, n_kv, dv).astype(np.float32))
    sink = None if window is None else jnp.asarray(
        rng.randn(n_kv * g).astype(np.float32))
    got, seen = kv_attention._gqa_attend(q, k, v, window, sink=sink)
    assert got.shape == (1, t, n_kv, g, dv)
    s = np.einsum("tkgd,skd->kgts", np.asarray(q[0], np.float64),
                  np.asarray(k[0], np.float64)) / dk ** 0.5
    ahead = np.arange(t)[:, None] - np.arange(t)[None, :]
    keep = (ahead >= 0) & (True if window is None else ahead < window)
    s = np.where(keep, s, -np.inf)
    extra = -np.inf if sink is None else np.asarray(
        sink, np.float64).reshape(n_kv, g, 1, 1)
    m = np.maximum(s.max(-1, keepdims=True), extra)
    e = np.exp(s - m)
    p = e / (e.sum(-1, keepdims=True) + np.exp(extra - m))
    want = np.einsum("kgts,skd->tkgd", p, np.asarray(v[0], np.float64))
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4,
                               atol=2e-5)
    if window is not None:
        np.testing.assert_array_equal(
            np.asarray(seen), ref.attended({"window": window},
                                           np.arange(t)))


def test_long_calls_run_in_blocks_to_the_same_result(monkeypatch):
    """Three places keep a long prefill's float32 intermediates a block
    at a time (a 32768-token prefill does not fit otherwise): the dense
    SwiGLU, the rotated projections, the grouped way's combine. Forced at
    a small size, each gives what the whole call gives."""
    rng = np.random.RandomState(3)
    w = lambda *shape: jnp.asarray(                           # noqa: E731
        (rng.randn(*shape) * 0.2).astype(np.float32))
    x, wg, wu, wd = w(64, 16), w(16, 24), w(16, 24), w(24, 16)
    whole = expert_ffn.swiglu(x, wg, wu, wd)
    monkeypatch.setattr(expert_ffn, "SWIGLU_WHOLE_MAX", 64)
    monkeypatch.setattr(expert_ffn, "SWIGLU_ROW_BLOCK", 16)
    np.testing.assert_allclose(np.asarray(expert_ffn.swiglu(x, wg, wu, wd)),
                               np.asarray(whole), rtol=1e-6, atol=1e-7)

    xs, wq, wk, wv = w(2, 32, 16), w(16, 4 * 8), w(16, 2 * 8), w(16, 2 * 6)
    attrs = {"rope_theta": 1e4, "rotary_dim": 4, "value_scale": 0.5}
    pos = lambda: jnp.broadcast_to(jnp.arange(32), (2, 32))   # noqa: E731
    args = (xs, wq, wk, wv, {}, attrs, (4, 2, 8, 6), pos)
    whole = kv_attention._gqa_qkv(*args)
    monkeypatch.setattr(kv_attention, "GQA_PROJECT_WHOLE_MAX", 64)
    monkeypatch.setattr(kv_attention, "GQA_PROJECT_ROW_BLOCK", 8)
    for got, want in zip(kv_attention._gqa_qkv(*args), whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)

    n, k, e = 1024, 2, 4
    xt = w(n, 16)
    combine = jnp.asarray(rng.rand(n, k).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 8, (n, k)))
    ws = (w(e, 16, 12), w(e, 16, 12), w(e, 12, 16))
    whole, sizes = expert_ffn.held_experts_part(xt, combine, idx, *ws, 2,
                                                n_experts=8)
    monkeypatch.setattr(expert_ffn, "COMBINE_UNROLLED_MAX", 64)
    looped, sizes2 = expert_ffn.held_experts_part(xt, combine, idx, *ws, 2,
                                                  n_experts=8)
    np.testing.assert_allclose(np.asarray(looped), np.asarray(whole),
                               rtol=1e-6, atol=1e-7)
    assert np.array_equal(np.asarray(sizes), np.asarray(sizes2))


# ------------------------------------------------------ the expert layer

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """MiMo-V2-Flash's routing at a small size: 32 experts picked by
    score + bias, weighed by score, normalised, no routed scale and NO
    shared expert; sixteen members holding two experts each. Their parts
    add up to what the reference gives with every expert held — and a
    member's own part is what the reference gives for its share."""
    rng = np.random.RandomState(1)

    def w(*shape):
        return (rng.randn(*shape) * (2.0 / sum(shape[-2:])) ** 0.5
                ).astype(np.float32)
    weights = {"router": (rng.randn(64, 32) * 0.1).astype(np.float32),
               "router_bias": (rng.randn(1, 32) * 0.05).astype(np.float32),
               "w_gate": w(32, 64, 24), "w_up": w(32, 64, 24),
               "w_down": w(32, 24, 64)}
    x = rng.randn(24, 64).astype(np.float32)
    cfg = dict(n_experts_per_tok=4, norm_topk_prob=True, router_bias=True,
               routed_scaling_factor=1.0, n_experts_held=32, held_start=0)

    def reference(start, count):
        held = {**weights, **{t: weights[t][start:start + count]
                              for t in ("w_gate", "w_up", "w_down")}}
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.expert_layer(
                lambda t: held[t], jnp.asarray(x),
                {**cfg, "held_start": start, "n_experts_held": count}))

    def share(start):
        ins = {"X": x[None], "RouterW": weights["router"],
               "RouterBias": weights["router_bias"],
               "WGate": weights["w_gate"][start:start + 2],
               "WUp": weights["w_up"][start:start + 2],
               "WDown": weights["w_down"][start:start + 2]}
        out = expert_ffn._expert_ffn_held(
            None, {k: [jnp.asarray(v)] for k, v in ins.items()},
            {"top_k": 4, "held_start": start, "norm_topk": True,
             "scaling": 1.0})
        return np.asarray(out["Out"][0][0])

    whole = reference(0, 32)
    parts = [share(start) for start in range(0, 32, 2)]
    np.testing.assert_allclose(sum(parts), whole,
                               atol=TOL * np.abs(whole).max())
    np.testing.assert_allclose(parts[3], reference(6, 2),
                               atol=TOL * np.abs(whole).max())
    unbiased = {**weights, "router_bias": np.zeros((1, 32), np.float32)}
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.expert_layer(
            lambda t: unbiased[t], jnp.asarray(x), cfg))
    assert np.abs(whole - other).max() > 100 * TOL * np.abs(whole).max()


# ---------------------------------------------------------- hybrid_arch

ARCH = {k: v for k, v in BUILD.items() if k in T._HYBRID_KEYS}


@pytest.mark.parametrize("changes,words", [
    (dict(rotary_dim=7), "rotary_dim 7"),
    (dict(rotary_dim=26), "rotary_dim 26"),
    (dict(swa_n_kv_head=3), "swa_n_kv_head 3 does not divide"),
    (dict(n_kv_head=3), "n_kv_head 3 does not divide"),
    (dict(layer_kinds=["gqa"], swa_sink=True, swa_n_kv_head=None,
          window=None, rope_theta=None), "a window layer's"),
    (dict(layer_kinds=["gqa"], swa_sink=False, swa_n_kv_head=4,
          window=None, rope_theta=None), "a window layer's"),
    (dict(layer_kinds=["gqa"], swa_sink=False, swa_n_kv_head=None,
          window=None, rope_theta=None, gqa_rope_theta=None),
     "rotary_dim without a rotation"),
], ids=["odd", "over-head_dim", "swa-heads", "gqa-heads", "sink-no-window",
        "heads-no-window", "no-rotation"])
def test_hybrid_arch_refuses(changes, words):
    with pytest.raises(ValueError, match=words):
        T.hybrid_arch({**ARCH, **changes}, "decode_paged", 7, 8)


def test_hybrid_arch_refuses_the_grouped_keys_without_a_grouped_kind():
    arch = dict(layer_kinds=["conv"], conv_taps=3, n_routed_experts=8,
                n_experts_held=8, n_experts_per_tok=2, d_expert=8)
    T.hybrid_arch(arch, "decode_paged", 2, 8)                # accepted
    with pytest.raises(ValueError, match="'gqa' and 'swa' layers alone"):
        T.hybrid_arch({**arch, "value_scale": 0.5}, "decode_paged", 2, 8)
    hy = T.hybrid_arch(ARCH, "decode_paged", 7, 8)
    assert hy["kinds"] == ("gqa", "swa", "swa", "swa", "swa", "gqa", "swa")
