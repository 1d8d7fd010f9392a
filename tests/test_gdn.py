"""``ops/gdn.py``: Gated DeltaNet's chunked prefill and its decode step
against the token-by-token recurrence — ``ops/kda.py:_delta_step`` under
``lax.scan`` for the scan alone, the plain reference's layer
(``chipbench/reference/olmo_hybrid_7b_pp2_d16.py:gdn_layer``, written
from the equations) for the two ops — at a small size on the CPU, all in
float32.

``TOL`` is ``tests/test_hybrid_lm.py``'s: both sides compute in float32,
so what separates them is the order of the sums; the mutants of
``test_a_mutant_fails`` (a bfloat16 inverse of a chunk's diagonal
blocks, a bfloat16 solution of its triangular system, a bfloat16 state
between chunks) move a result by a hundred times that."""

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

from chipbench.reference import olmo_hybrid_7b_pp2_d16 as ref  # noqa: E402
from paddle_tpu.core.registry import get_op  # noqa: E402
from paddle_tpu.ops import gdn, kda  # noqa: E402
from paddle_tpu.ops import pallas as plk  # noqa: E402

TOL = 2e-5
F32 = np.float32
H, DK, DV = 3, 8, 16


def rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / np.abs(np.asarray(b)).max())


def tokens(t, seed=0, beta="random", decay="random", h=H, dk=DK, dv=DV):
    """q, k, v, g, beta of ``t`` tokens as ``_qkv`` and ``_token_terms``
    give them: q, k normalised, g <= 0, beta in (0, 2). ``beta="two"``:
    every beta within 1e-3 of 2 (the eigenvalue at -1), ``"zero"``: every
    beta within 1e-3 of 0 (a token that writes next to nothing);
    ``decay``:
    ``"none"`` g = -1e-6 (a head that never forgets), ``"fast"`` g in
    [-60, -20] (exp(g) underflows within a chunk), ``"mixed"`` a head of
    each."""
    r = np.random.RandomState(seed)
    q = r.randn(t, h, dk).astype(F32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k = r.randn(t, h, dk).astype(F32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(t, h, dv).astype(F32)
    g = {"random": -np.abs(r.randn(t, h)) * 0.3,
         "none": np.full((t, h), -1e-6),
         "fast": -20.0 - 40.0 * r.rand(t, h),
         "mixed": np.stack([np.full(t, -1e-6), -20.0 - 40.0 * r.rand(t),
                            -np.abs(r.randn(t))] * h, 1)[:, :h],
         }[decay].astype(F32)
    b = {"random": 2.0 / (1.0 + np.exp(-r.randn(t, h))),
         "two": 2.0 - 1e-3 * r.rand(t, h),
         "zero": 1e-3 * r.rand(t, h)}[beta].astype(F32)
    return q, k, v, g, b


def token_loop(q, k, v, g, beta, n):
    """The recurrence a token at a time over the first ``n`` tokens:
    (o [n, H, Dv], the state after them)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        return kda._delta_step(s, q_t, k_t, v_t, g_t[:, None], b_t)
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    s, o = jax.lax.scan(step, s0, tuple(a[:n] for a in (q, k, v, g, beta)))
    return o, s


def chunked(q, k, v, g, beta, n, chunk, scan=gdn.chunk_scan):
    """``chunk_scan`` over a bucket of ``len(q)`` rows of which ``n`` are
    true, as ``gdn_prefill`` calls it."""
    t = q.shape[0]
    real = (np.arange(t) < n)[:, None]
    chunk = min(chunk, t)
    rows = gdn.block_rows(t, chunk)
    o, s = scan(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
                -(-n // rows), chunk, rows)
    return np.asarray(o), np.asarray(s)


def scan_matches_token_loop(bucket, chunk, n, beta, decay):
    """The true rows' outputs and the state after them, chunked against
    token by token, to ``TOL``; nothing NaN or inf."""
    q, k, v, g, b = tokens(bucket, seed=n, beta=beta, decay=decay)
    o, s = chunked(q, k, v, g, b, n, chunk)
    want_o, want_s = token_loop(q, k, v, g, b, n)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    scale = np.abs(np.asarray(want_o)).max()
    assert np.abs(o[:n] - np.asarray(want_o)).max() <= TOL * scale
    assert np.abs(s - np.asarray(want_s)).max() \
        <= TOL * max(np.abs(np.asarray(want_s)).max(), 1.0)


# bucket, chunk, true length: whole chunks and not, one token, a bucket
# that is one block and several, a length that ends a block
LENGTHS = [(32, 4, 32), (32, 4, 17), (32, 4, 1), (32, 4, 4), (64, 8, 50),
           (256, 4, 130), (256, 4, 64), (128, 64, 100), (16, 64, 9)]


@pytest.mark.parametrize("beta,decay", [
    ("random", "random"), ("two", "none"), ("two", "fast"),
    ("two", "mixed"), ("random", "fast")])
@pytest.mark.parametrize("bucket,chunk,n", LENGTHS)
def test_the_chunked_scan_is_the_token_loop(bucket, chunk, n, beta, decay):
    """For true lengths that are, and are not, multiples of the chunk,
    with beta near 2 and decays near 0 and near 1: the outputs of the
    true rows and the state after them, to ``TOL``; nothing is NaN or
    inf however fast a head forgets."""
    scan_matches_token_loop(bucket, chunk, n, beta, decay)


# a chunk of 64 is four diagonal blocks of 16 rows (the cell's), of 32
# two, of 48 three, of 20 two of 10, of 6 one: true lengths that end
# inside a diagonal block, at its edge, in a chunk's first and last one,
# and in a bucket's second block of 16 chunks
BLOCKED = [(128, 64, 70), (128, 64, 17), (128, 64, 16), (128, 64, 113),
           (256, 64, 255), (2048, 64, 1030), (64, 32, 40), (96, 48, 70),
           (40, 20, 33), (12, 6, 7)]


@pytest.mark.parametrize("beta,decay", [
    ("two", "none"), ("two", "mixed"), ("zero", "none"), ("zero", "fast"),
    ("random", "mixed")])
@pytest.mark.parametrize("bucket,chunk,n", BLOCKED)
def test_the_blocked_solve_is_the_token_loop(bucket, chunk, n, beta, decay):
    """A chunk's triangular system solved in diagonal blocks of
    ``gdn.solve_rows(chunk)`` rows: with beta at both its ends (near 2
    the powers of ``A`` would grow before they vanish: none is formed),
    a head that never forgets beside one that forgets within a token,
    to ``TOL``."""
    assert chunk % gdn.solve_rows(chunk) == 0 < gdn.solve_rows(chunk) <= 16
    scan_matches_token_loop(bucket, chunk, n, beta, decay)


def _scan_lengths(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scan_lengths(sub, found)
    return found


@pytest.mark.parametrize("chunk,sub", [(64, 16), (32, 16), (48, 16),
                                       (20, 10), (8, 8), (4, 4)])
def test_the_substitution_has_a_turn_a_row_of_a_diagonal_block(chunk, sub):
    """The one loop that goes a ROW a turn runs over a diagonal block's
    rows (``sub - 1`` turns, all of a chunk's blocks at once), not over
    the chunk's (``chunk - 1``); the other scan is the block's chunks."""
    assert gdn.solve_rows(chunk) == sub
    t = 4 * chunk
    rows = gdn.block_rows(t, chunk)
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *a: gdn.chunk_scan(*a, chunk, rows))(
        S((t, H, DK), F32), S((t, H, DK), F32), S((t, H, DV), F32),
        S((t, H), F32), S((t, H), F32), S((), np.int32))
    assert sorted(_scan_lengths(jaxpr.jaxpr, [])) \
        == sorted([sub - 1, rows // chunk])


@pytest.mark.parametrize("bucket,chunk,n", [(64, 4, 17), (256, 4, 130)])
def test_rows_past_the_true_length_touch_nothing(bucket, chunk, n):
    """Whatever lies in the bucket past the prompt's end — other tokens,
    huge values — the state and the true rows' outputs are BIT FOR BIT
    the same: padded rows have beta = g = 0 and blocks past the length
    are not computed."""
    q, k, v, g, b = tokens(bucket, seed=3)
    o1, s1 = chunked(q, k, v, g, b, n, chunk)
    q2, k2, v2 = q.copy(), k.copy(), v.copy()
    q2[n:], k2[n:], v2[n:] = 7.0, -3.0, 1e6
    o2, s2 = chunked(q2, k2, v2, g, b, n, chunk)
    assert np.array_equal(s1, s2) and np.array_equal(o1[:n], o2[:n])
    rows = gdn.scan_rows(n, bucket, chunk)
    assert not o1[rows:].any()          # blocks past the length: not run


@pytest.mark.parametrize("length,bucket,chunk,want", [
    (1, 8192, 64, 1024), (1024, 8192, 64, 1024), (1025, 8192, 64, 2048),
    (8192, 8192, 64, 8192), (2049, 4096, 64, 3072), (5, 32, 4, 32),
    (5, 256, 4, 64),
    (17, 32, 4, 32), (9, 16, 64, 16), (100, 192, 64, 192)])
def test_rows_the_scan_computes(length, bucket, chunk, want):
    """Whole blocks of ``BLOCK_CHUNKS`` chunks (as many as divide the
    bucket's) up to the true length: what the engine counts."""
    assert gdn.scan_rows(length, bucket, chunk) == want
    assert want % gdn.block_rows(bucket, chunk) == 0


def test_a_bucket_is_whole_chunks():
    with pytest.raises(ValueError, match="whole number of chunks"):
        gdn.block_rows(100, 64)


def _bf16(x):
    return jax.lax.reduce_precision(x, 8, 7)


def _bf16_inverse(monkeypatch):
    real = gdn._unit_lower_inverse
    monkeypatch.setattr(gdn, "_unit_lower_inverse",
                        lambda a: _bf16(real(a)))


def _bf16_solve(monkeypatch):
    real = gdn._unit_lower_solve
    monkeypatch.setattr(gdn, "_unit_lower_solve",
                        lambda *a: _bf16(real(*a)))


def _bf16_state(monkeypatch):
    real = jax.lax.scan

    def scan(f, init, xs, **kw):
        def g(s, x):
            s, o = f(s, x)
            return _bf16(s), o
        return real(g, init, xs, **kw)
    monkeypatch.setattr(gdn.jax.lax, "scan", scan)


@pytest.mark.parametrize("bucket,chunk,n", [(64, 8, 50), (128, 64, 100)])
@pytest.mark.parametrize("mutant", [None, "bf16_inverse", "bf16_solve",
                                    "bf16_state"])
def test_a_mutant_fails(monkeypatch, mutant, bucket, chunk, n):
    """``TOL`` bites, in chunks of one diagonal block (8 rows) and of
    four (64: the block turns' products lie between the two roundings):
    the scan with the inverses of a chunk's diagonal blocks rounded to
    bfloat16, with the solution of its triangular system rounded so, or
    with the state rounded to bfloat16 between chunks, is a hundred
    times outside it; the scan as written inside."""
    if mutant:
        {"bf16_inverse": _bf16_inverse, "bf16_solve": _bf16_solve,
         "bf16_state": _bf16_state}[mutant](monkeypatch)
    q, k, v, g, b = tokens(bucket, seed=1, decay="none")
    o, s = chunked(q, k, v, g, b, n, chunk)
    want_o, want_s = token_loop(q, k, v, g, b, n)
    err = max(rel(o[:n], want_o), rel(s, want_s))
    if mutant:
        assert err > 100 * TOL, err
    else:
        assert err <= TOL, err


# ------------------------------------------------------------ the two ops

M, TAPS = 24, 4


def layer_weights(seed=0, dtype=F32):
    """One layer's weights under the op's slot names, A and dt_bias
    spread as the layer's start-up spreads them."""
    r = np.random.RandomState(seed)
    wide = 2 * H * DK + H * DV

    def mat(*shape):
        return (r.randn(*shape) * (2.0 / sum(shape[-2:])) ** 0.5) \
            .astype(dtype)
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), H))
    return {"Wq": mat(M, H * DK), "Wk": mat(M, H * DK),
            "Wv": mat(M, H * DV), "Wz": mat(M, H * DV),
            "Wo": mat(H * DV, M),
            "ConvW": (r.randn(TAPS, wide) * TAPS ** -0.5).astype(dtype),
            "ALog": np.log(np.linspace(1.0, 16.0, H)).astype(F32),
            "DtBias": (dt0 + np.log(-np.expm1(-dt0))).astype(F32),
            "Wa": mat(M, H), "Wb": mat(M, H),
            "ONorm": (1.0 + 0.1 * r.randn(DV)).astype(F32)}


_TAGS = {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wz": "wz", "Wo": "wo",
         "ConvW": "conv", "ALog": "a_log", "DtBias": "dt_bias", "Wa": "wa",
         "Wb": "wb", "ONorm": "onorm"}
CFG = {"gdn_heads": H, "gdn_key_dim": DK, "gdn_value_dim": DV,
       "gdn_conv_taps": TAPS, "rms_eps": 1e-6}
ATTRS = {"n_head": H, "key_dim": DK, "value_dim": DV, "epsilon": 1e-6,
         "chunk": 4}


def reference_layer(w, x):
    return ref.gdn_layer(lambda tag: w[{v: k for k, v in _TAGS.items()}[tag]],
                         jnp.asarray(x), CFG)


def run_op(name, w, **ins):
    out = get_op(name).emit(
        types.SimpleNamespace(mesh=None),
        {**{k: [jnp.asarray(v)] for k, v in w.items()},
         **{k: [jnp.asarray(v)] for k, v in ins.items()}}, ATTRS)
    return {k: np.asarray(v[0]) for k, v in out.items()}


@pytest.mark.parametrize("n", [1, 2, 3, 4, 13, 16, 29, 32])
def test_gdn_prefill_is_the_references_layer(n):
    """The op over a bucket of 32 rows of which ``n`` are true (fewer
    than the conv's taps, a multiple of the chunk of 4 or not), into
    slot 2 of 4: the true rows' outputs, the slot's state and its conv
    window are the reference's; the other slots are untouched."""
    w, bucket, slots = layer_weights(), 32, 4
    r = np.random.RandomState(n)
    x = r.randn(1, bucket, M).astype(F32)
    wide = 2 * H * DK + H * DV
    state = r.randn(slots, H, DK, DV).astype(F32)
    conv = r.randn(slots, TAPS - 1, wide).astype(F32)
    with jax.default_matmul_precision("highest"):
        out = run_op("gdn_prefill", w, X=x, State=state, Conv=conv,
                     SeqLen=np.array([[n]]), Slot=np.array([[2]]))
        want_y, want_s, _ = reference_layer(w, x[0, :n])
        u = np.concatenate([x[0, :n] @ w[t] for t in ("Wq", "Wk", "Wv")],
                           -1)
    assert rel(out["Out"][0, :n], want_y) <= TOL
    assert rel(out["StateOut"][2], want_s) <= TOL
    window = np.concatenate([np.zeros((TAPS - 1, wide), F32), u])[-3:]
    assert np.abs(out["ConvOut"][2] - window).max() <= TOL
    others = [0, 1, 3]
    assert np.array_equal(out["StateOut"][others], state[others])
    assert np.array_equal(out["ConvOut"][others], conv[others])


def test_a_slot_past_the_pool_writes_nothing():
    """The warm-up's dispatch names slot ``n_slots``: no state changes."""
    w = layer_weights()
    r = np.random.RandomState(0)
    state = r.randn(2, H, DK, DV).astype(F32)
    conv = r.randn(2, TAPS - 1, 2 * H * DK + H * DV).astype(F32)
    out = run_op("gdn_prefill", w, X=r.randn(1, 16, M).astype(F32),
                 State=state, Conv=conv, SeqLen=np.array([[9]]),
                 Slot=np.array([[2]]))
    assert np.array_equal(out["StateOut"], state)
    assert np.array_equal(out["ConvOut"], conv)


@pytest.mark.parametrize("active", [[1, 1, 1], [1, 0, 1], [0, 0, 0]])
def test_gdn_decode_continues_the_prefill(active):
    """Each slot prefilled with a prompt of its own, then one decode
    step of every slot: an ACTIVE slot's output and state are the
    reference's over the prompt and the new token; an inactive slot
    keeps its state and its conv window BIT FOR BIT."""
    w, bucket = layer_weights(seed=1), 16
    r = np.random.RandomState(7)
    lens = [5, 16, 11]
    xs = [r.randn(n + 1, M).astype(F32) for n in lens]
    wide = 2 * H * DK + H * DV
    state = np.zeros((3, H, DK, DV), F32)
    conv = np.zeros((3, TAPS - 1, wide), F32)
    with jax.default_matmul_precision("highest"):
        for slot, (n, x) in enumerate(zip(lens, xs)):
            padded = np.zeros((1, bucket, M), F32)
            padded[0, :n] = x[:n]
            out = run_op("gdn_prefill", w, X=padded, State=state,
                         Conv=conv, SeqLen=np.array([[n]]),
                         Slot=np.array([[slot]]))
            state, conv = out["StateOut"], out["ConvOut"]
        step = run_op("gdn_decode", w,
                      X=np.stack([x[-1:] for x in xs]), State=state,
                      Conv=conv, Active=np.array(active)[:, None])
        for slot, x in enumerate(xs):
            if active[slot]:
                want_y, want_s, _ = reference_layer(w, x)
                assert rel(step["Out"][slot, 0], want_y[-1]) <= TOL
                assert rel(step["StateOut"][slot], want_s) <= TOL
            else:
                assert np.array_equal(step["StateOut"][slot], state[slot])
                assert np.array_equal(step["ConvOut"][slot], conv[slot])


# --------------------------------------------- which tier, and the counter

def _decode_inputs(b=8, h=30, dk=96, dv=192, m=32, taps=4,
                   dtype=jnp.bfloat16):
    def z(*shape, dt=dtype):
        return [jax.ShapeDtypeStruct(shape, dt)]
    wide = 2 * h * dk + h * dv
    ins = {"X": z(b, 1, m), "Wq": z(m, h * dk), "Wk": z(m, h * dk),
           "Wv": z(m, h * dv), "Wz": z(m, h * dv), "Wo": z(h * dv, m),
           "ConvW": z(taps, wide), "ALog": z(h, dt=jnp.float32),
           "DtBias": z(h, dt=jnp.float32), "Wa": z(m, h), "Wb": z(m, h),
           "ONorm": z(dv, dt=jnp.float32),
           "State": z(b, h, dk, dv, dt=jnp.float32),
           "Conv": z(b, taps - 1, wide), "Active": z(b, 1, dt=jnp.int32)}
    return ins, {"n_head": h, "key_dim": dk, "value_dim": dv,
                 "epsilon": 1e-6}


@pytest.mark.parametrize("case,want", [
    ("cpu", "refer"),               # no kernel tier off the chip
    ("chip", "kernel"),             # the cell: 30 heads of [96, 192]
    ("chip-mesh2", "refer"),        # XLA cannot partition a Mosaic call
    ("chip-small", "refer"),        # [8, 16]: tests' sizes, mostly padding
    ("cpu-forced", "kernel"),       # the tests' way in: interpreted
])
def test_the_decode_tier_is_chosen_by_what_the_lowering_sees(case, want,
                                                             monkeypatch):
    """``gdn_decode`` takes ``kda_decode``'s tiers by ``kda_decode``'s
    rule and counts in its counter: one increment a layer."""
    words = case.split("-")
    if words[0] == "chip":
        monkeypatch.setattr(plk, "on_tpu", lambda: True)
        monkeypatch.setattr(plk, "interpret_mode", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                       "1" if "forced" in words else "0")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",)) \
        if "mesh2" in words else None
    ins, attrs = _decode_inputs(
        **(dict(h=H, dk=DK, dv=DV) if "small" in words else {}))
    fam = kda.KDA_DECODE_LOWERED
    before = {p: fam.labels(path=p).value for p in ("kernel", "refer")}
    jax.eval_shape(lambda i: get_op("gdn_decode").emit(
        types.SimpleNamespace(mesh=mesh), i, attrs), ins)
    grew = {p: fam.labels(path=p).value - before[p] for p in before}
    assert grew == {p: (1 if p == want else 0) for p in grew}


@pytest.mark.parametrize("active", [[1, 0], [1, 1]])
def test_both_tiers_of_the_decode_step_agree(monkeypatch, active):
    """The whole op through the kernel (interpreted, at a tile the
    kernel takes: keys of 8, values of 128) and through ``_delta_step``:
    the same step; an inactive slot bit for bit either way."""
    h, dk, dv, m = 3, 8, 128, 16
    r = np.random.RandomState(2)
    wide = 2 * h * dk + h * dv
    shapes = {"Wq": (m, h * dk), "Wk": (m, h * dk), "Wv": (m, h * dv),
              "Wz": (m, h * dv), "Wo": (h * dv, m), "ConvW": (4, wide),
              "Wa": (m, h), "Wb": (m, h)}
    ins = {k: [jnp.asarray(r.randn(*s).astype(F32) * 0.3)]
           for k, s in shapes.items()}
    ins.update(ALog=[jnp.zeros(h)], DtBias=[jnp.zeros(h)],
               ONorm=[jnp.ones(dv)],
               X=[jnp.asarray(r.randn(2, 1, m).astype(F32))],
               State=[jnp.asarray(r.randn(2, h, dk, dv).astype(F32))],
               Conv=[jnp.asarray(r.randn(2, 3, wide).astype(F32))],
               Active=[jnp.asarray(np.array(active)[:, None])])
    attrs = {"n_head": h, "key_dim": dk, "value_dim": dv, "epsilon": 1e-6}
    outs = {}
    for forced in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)
        outs[forced] = get_op("gdn_decode").emit(
            types.SimpleNamespace(mesh=None), ins, attrs)
    on = np.array(active) > 0
    for key in ("Out", "StateOut"):
        a, b = (np.asarray(outs[f][key][0]) for f in ("0", "1"))
        assert rel(a[on], b[on]) <= TOL
    for f in ("0", "1"):
        assert np.array_equal(np.asarray(outs[f]["StateOut"][0])[~on],
                              np.asarray(ins["State"][0])[~on])
