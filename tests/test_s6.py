"""``ops/s6.py``: Mamba-1's selective scan — the prefill through both of
its tiers (the Pallas kernel interpreted, the row loop) and the decode
step — against the recurrence written out token by token in float64, and
the two ops against the plain reference's layer
(``chipbench/reference/jamba2_3b.py:s6_layer``, written from the
equations), at a small size on the CPU, all in float32.

``TOL`` is ``tests/test_hybrid_lm.py``'s: both sides compute in float32,
so what separates them is the order of the sums; a state dropped at a
chunk's edge (``test_a_state_dropped_at_a_chunks_edge_fails``) moves a
result by thousands of times that."""

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

from chipbench.reference import jamba2_3b as ref  # noqa: E402
from paddle_tpu.core.registry import get_op, slot_state_vars  # noqa: E402
from paddle_tpu.ops import pallas as plk  # noqa: E402
from paddle_tpu.ops import s6  # noqa: E402
from paddle_tpu.ops.pallas import s6_scan as kernel  # noqa: E402
from paddle_tpu.ops.pallas import s6_state as state_kernel  # noqa: E402

TOL = 2e-5
F32 = np.float32
C, N, R, M, TAPS = 128, 4, 6, 24, 4


def rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / np.abs(np.asarray(b)).max())


def rows(t, seed=0, c=C, n=N, slow=True):
    """x, dt, B, C of ``t`` rows and A [N, C] as ``s6_prefill`` hands them
    to its scan: dt > 0 spread log-evenly over the channels (``slow``:
    [0.001, 0.1], the start-up's — a state that remembers hundreds of
    tokens), A = -(1..N) along the state index."""
    r = np.random.RandomState(seed)
    lo, hi = (1e-3, 0.1) if slow else (0.5, 5.0)
    dt = np.exp(np.linspace(np.log(lo), np.log(hi), c))[None] \
        * np.exp(0.3 * r.randn(t, c))
    a = -np.repeat(np.arange(1.0, n + 1.0)[:, None], c, 1)
    return (r.randn(t, c).astype(F32), dt.astype(F32),
            r.randn(t, n).astype(F32), r.randn(t, n).astype(F32),
            a.astype(F32))


def token_loop(x, dt, b, c, a, n_true):
    """The recurrence a token at a time in float64 over the first
    ``n_true`` rows: (y [n_true, C], the state [N, C] after them)."""
    x, dt, b, c, a = (np.asarray(v, np.float64) for v in (x, dt, b, c, a))
    h = np.zeros(a.shape)
    y = np.zeros((n_true, x.shape[1]))
    for t in range(n_true):
        h = np.exp(dt[t][None] * a) * h + (dt[t] * x[t])[None] * b[t][:, None]
        y[t] = (h * c[t][:, None]).sum(0)
    return y, h


def scanned(path, x, dt, b, c, a, n_true, chunk, tile=0):
    """The scan of ``path`` over a bucket of ``len(x)`` rows of which
    ``n_true`` are true, as ``s6_prefill`` calls it."""
    real = (np.arange(x.shape[0]) < n_true)[:, None]
    xm, dtm = np.where(real, x, 0).astype(F32), np.where(real, dt, 0) \
        .astype(F32)
    n_chunks = -(-n_true // chunk)
    if path == "kernel":
        y, s = kernel.s6_scan(xm, dtm, b, c, a, n_chunks, chunk=chunk,
                              tile=tile, interpret=True)
    else:
        y, s = s6.loop_scan(*(jnp.asarray(v) for v in (xm, dtm, b, c, a)),
                            n_chunks, chunk)
    return np.asarray(y), np.asarray(s)


# bucket, chunk, true length: whole chunks and not, one token, a length
# that ends a chunk, that ends the bucket, one past a chunk's edge
LENGTHS = [(32, 8, 32), (32, 8, 17), (32, 8, 1), (32, 8, 8), (32, 8, 9),
           (64, 16, 50), (48, 16, 33), (16, 16, 9)]


@pytest.mark.parametrize("path", ["kernel", "loop"])
@pytest.mark.parametrize("bucket,chunk,n", LENGTHS)
def test_the_scan_is_the_token_loop(path, bucket, chunk, n):
    """For true lengths that are, and are not, multiples of the chunk,
    across chunk edges: the outputs of the true rows and the state after
    them, to ``TOL``."""
    x, dt, b, c, a = rows(bucket, seed=n)
    y, s = scanned(path, x, dt, b, c, a, n, chunk)
    want_y, want_s = token_loop(x, dt, b, c, a, n)
    assert np.isfinite(y[:n]).all() and np.isfinite(s).all()
    assert rel(y[:n], want_y) <= TOL and rel(s, want_s) <= TOL


@pytest.mark.parametrize("path", ["kernel", "loop"])
def test_rows_past_the_true_length_touch_nothing(path):
    """Whatever lies in the bucket past the prompt's end, the state and
    the true rows' outputs are BIT FOR BIT the same: padded rows come in
    with dt = x = 0 and chunks past the length are not walked."""
    x, dt, b, c, a = rows(48, seed=3)
    y1, s1 = scanned(path, x, dt, b, c, a, 19, 8)
    x2, b2, c2 = x.copy(), b.copy(), c.copy()
    x2[19:], b2[19:], c2[19:] = 1e6, -3.0, 7.0
    y2, s2 = scanned(path, x2, dt, b2, c2, a, 19, 8)
    assert np.array_equal(s1, s2) and np.array_equal(y1[:19], y2[:19])


def test_the_kernels_tiles_follow_the_channels():
    """Several channel tiles and several chunks give what one of each
    gives; the tile is the widest whole number of lane tiles at or under
    ``CHANNEL_TILE`` that divides the channels (Jamba's 5120: 1280)."""
    x, dt, b, c, a = rows(32, seed=5, c=384)
    one = scanned("kernel", x, dt, b, c, a, 27, 32, tile=384)
    many = scanned("kernel", x, dt, b, c, a, 27, 8, tile=128)
    assert rel(many[0][:27], one[0][:27]) <= TOL
    assert rel(many[1], one[1]) <= TOL
    assert kernel.channel_tile(5120) == 1280
    assert kernel.channel_tile(384) == 384 and kernel.channel_tile(256) == 256
    assert kernel.channel_tile(1536) == 768
    assert kernel.channel_tile(100) == 0 and kernel.channel_tile(64) == 0
    with pytest.raises(ValueError, match="no whole tiles"):
        kernel.s6_scan(x, dt, b, c, a, 1, chunk=12, interpret=True)


def _drop_state_in_the_loop(monkeypatch):
    real = jax.lax.scan
    monkeypatch.setattr(
        s6.jax.lax, "scan",
        lambda f, h, xs, **kw: real(f, jnp.zeros_like(h), xs, **kw))


def _drop_state_in_the_kernel(monkeypatch):
    real = kernel.pl.when

    def when(cond):
        # the branch that zeroes the state at the first chunk: at every one
        if getattr(when, "first", True):
            when.first = False
            return real(cond | True)
        return real(cond)
    monkeypatch.setattr(kernel.pl, "when", when)


@pytest.mark.parametrize("path,mutant", [
    ("loop", None), ("kernel", None), ("loop", _drop_state_in_the_loop),
    ("kernel", _drop_state_in_the_kernel)])
def test_a_state_dropped_at_a_chunks_edge_fails(monkeypatch, path, mutant):
    """``TOL`` bites: with the start-up's decays a state remembers
    hundreds of tokens, so a scan that starts every chunk from a zero
    state is thousands of times outside the tolerance, in the rows after
    the first chunk and in the state it leaves; the scan as written is
    inside."""
    if mutant is not None:
        mutant(monkeypatch)
        kernel.s6_scan.clear_cache()
    x, dt, b, c, a = rows(32, seed=1)
    try:
        y, s = scanned(path, x, dt, b, c, a, 29, 8)
    finally:
        kernel.s6_scan.clear_cache()
    want_y, want_s = token_loop(x, dt, b, c, a, 29)
    err = max(rel(y[:29], want_y), rel(s, want_s))
    if mutant is not None:
        assert err > 1000 * TOL, err
        assert rel(y[:8], want_y[:8]) <= TOL      # the first chunk is right
    else:
        assert err <= TOL, err


# ------------------------------------------------------------ the two ops

def layer_weights(seed=0, dtype=F32, c=C):
    """One layer's weights under the op's slot names, A_log, dt_bias and D
    as the layer's start-up sets them, the three norms' gains off 1."""
    r = np.random.RandomState(seed)

    def mat(*shape):
        return (r.randn(*shape) * (2.0 / sum(shape[-2:])) ** 0.5) \
            .astype(dtype)
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), c))
    return {"WIn": mat(M, 2 * c), "WOut": mat(c, M),
            "ConvW": (r.randn(TAPS, c) * TAPS ** -0.5).astype(dtype),
            "ConvB": (0.1 * r.randn(1, c)).astype(dtype),
            "WX": mat(c, R + 2 * N), "WDt": mat(R, c),
            "DtNorm": (1.0 + 0.1 * r.randn(R)).astype(F32),
            "BNorm": (1.0 + 0.1 * r.randn(N)).astype(F32),
            "CNorm": (1.0 + 0.1 * r.randn(N)).astype(F32),
            "DtBias": (dt0 + np.log(-np.expm1(-dt0))).astype(F32),
            "ALog": np.repeat(np.log(np.arange(1.0, N + 1.0)), c)
            .astype(F32),
            "D": (1.0 + 0.1 * r.randn(c)).astype(F32)}


_TAGS = {"WIn": "w_in", "WOut": "w_out", "ConvW": "conv",
         "ConvB": "conv_bias", "WX": "w_x", "WDt": "w_dt",
         "DtNorm": "dt_norm", "BNorm": "b_norm", "CNorm": "c_norm",
         "DtBias": "dt_bias", "ALog": "a_log", "D": "d"}
CFG = {"s6_d_inner": C, "s6_d_state": N, "s6_dt_rank": R,
       "s6_conv_taps": TAPS, "rms_eps": 1e-6}
ATTRS = {"epsilon": 1e-6, "chunk": 8}


def reference_layer(w, x):
    """(out, the state [N, C], _) of the reference's layer."""
    y, h, decay = ref.s6_layer(
        lambda tag: w[{v: k for k, v in _TAGS.items()}[tag]],
        jnp.asarray(x), CFG)
    return y, np.asarray(h).T, decay


def run_op(name, w, **ins):
    out = get_op(name).emit(
        types.SimpleNamespace(mesh=None),
        {**{k: [jnp.asarray(v)] for k, v in w.items()},
         **{k: [jnp.asarray(v)] for k, v in ins.items()}}, ATTRS)
    return {k: np.asarray(v[0]) for k, v in out.items()}


@pytest.fixture(params=["loop", "kernel"])
def path(request, monkeypatch):
    """Both tiers of the prefill through the whole op: the kernel is the
    tests' way in (interpreted)."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                       "1" if request.param == "kernel" else "0")
    return request.param


@pytest.mark.parametrize("n", [1, 2, 3, 8, 13, 16, 29, 32])
def test_s6_prefill_is_the_references_layer(path, n):
    """The op over a bucket of 32 rows of which ``n`` are true (fewer
    than the conv's taps, a multiple of the chunk of 8 or not, the whole
    bucket), into slot 2 of 4: the true rows' outputs, the slot's state
    and its conv window are the reference's; the other slots are
    untouched; the lowering counted the tier it took."""
    w, bucket, slots = layer_weights(), 32, 4
    r = np.random.RandomState(n)
    x = r.randn(1, bucket, M).astype(F32)
    state = r.randn(slots, N, C).astype(F32)
    conv = r.randn(slots, TAPS - 1, C).astype(F32)
    before = s6.S6_SCAN_LOWERED.labels(path=path).value
    with jax.default_matmul_precision("highest"):
        out = run_op("s6_prefill", w, X=x, State=state, Conv=conv,
                     SeqLen=np.array([[n]]), Slot=np.array([[2]]))
        want_y, want_s, _ = reference_layer(w, x[0, :n])
        u = (x[0, :n] @ w["WIn"])[:, :C]
    assert s6.S6_SCAN_LOWERED.labels(path=path).value == before + 1
    assert rel(out["Out"][0, :n], want_y) <= TOL
    assert rel(out["StateOut"][2], want_s) <= TOL
    window = np.concatenate([np.zeros((TAPS - 1, C), F32), u])[-3:]
    assert np.abs(out["ConvOut"][2] - window).max() <= TOL
    others = [0, 1, 3]
    assert np.array_equal(out["StateOut"][others], state[others])
    assert np.array_equal(out["ConvOut"][others], conv[others])


def test_a_slot_past_the_pool_writes_nothing(path):
    """The warm-up's dispatch names slot ``n_slots``: no state changes."""
    w = layer_weights()
    r = np.random.RandomState(0)
    state = r.randn(2, N, C).astype(F32)
    conv = r.randn(2, TAPS - 1, C).astype(F32)
    out = run_op("s6_prefill", w, X=r.randn(1, 16, M).astype(F32),
                 State=state, Conv=conv, SeqLen=np.array([[9]]),
                 Slot=np.array([[2]]))
    assert np.array_equal(out["StateOut"], state)
    assert np.array_equal(out["ConvOut"], conv)


@pytest.mark.parametrize("active", [[1, 1, 1], [1, 0, 1], [0, 0, 0]])
def test_s6_decode_continues_the_prefill(path, active):
    """Each slot prefilled with a prompt of its own (across a chunk's
    edge, a whole bucket, short of a chunk), then one decode step of
    every slot: an ACTIVE slot's output and state are the reference's
    over the prompt and the new token — the state was carried from the
    prefill into the step; an inactive slot keeps its state and its conv
    window BIT FOR BIT."""
    w, bucket = layer_weights(seed=1), 16
    r = np.random.RandomState(7)
    lens = [5, 16, 11]
    xs = [r.randn(n + 1, M).astype(F32) for n in lens]
    state = np.zeros((3, N, C), F32)
    conv = np.zeros((3, TAPS - 1, C), F32)
    with jax.default_matmul_precision("highest"):
        for slot, (n, x) in enumerate(zip(lens, xs)):
            padded = np.zeros((1, bucket, M), F32)
            padded[0, :n] = x[:n]
            out = run_op("s6_prefill", w, X=padded, State=state,
                         Conv=conv, SeqLen=np.array([[n]]),
                         Slot=np.array([[slot]]))
            state, conv = out["StateOut"], out["ConvOut"]
        step = run_op("s6_decode", w, X=np.stack([x[-1:] for x in xs]),
                      State=state, Conv=conv,
                      Active=np.array(active)[:, None])
        for slot, x in enumerate(xs):
            if active[slot]:
                want_y, want_s, _ = reference_layer(w, x)
                assert rel(step["Out"][slot, 0], want_y[-1]) <= TOL
                assert rel(step["StateOut"][slot], want_s) <= TOL
            else:
                assert np.array_equal(step["StateOut"][slot], state[slot])
                assert np.array_equal(step["ConvOut"][slot], conv[slot])


# ------------------------------------------------------------ which tier

@pytest.mark.parametrize("case,want", [
    ("cpu", "loop"),                # no kernel tier off the chip
    ("chip", "kernel"),             # the cell: 5120 channels, chunks of 64
    ("chip-mesh2", "loop"),         # XLA cannot partition a Mosaic call
    ("chip-narrow", "loop"),        # 96 channels: no whole lane tile
    ("chip-oddchunk", "loop"),      # a chunk of 12 rows: no sublane tiles
    ("cpu-forced", "kernel"),       # the tests' way in: interpreted
])
def test_the_scans_tier_is_chosen_from_the_shapes(case, want, monkeypatch):
    words = case.split("-")
    if words[0] == "chip":
        monkeypatch.setattr(plk, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                       "1" if "forced" in words else "0")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",)) \
        if "mesh2" in words else None
    channels = 96 if "narrow" in words else 5120
    chunk = 12 if "oddchunk" in words else 64
    rows_ = 1020 if "oddchunk" in words else 1024
    assert s6.scan_path(rows_, channels, chunk, mesh) == want


@pytest.mark.parametrize("case,want", [
    ("cpu", "fused"), ("chip", "kernel"), ("chip-mesh2", "fused"),
    ("chip-narrow", "fused"),       # 96 channels: no whole lane tile
    ("chip-fewslots", "fused"),     # 6 slots: no whole block of eight
    ("cpu-forced", "kernel")])
def test_the_decode_tier_is_chosen_from_the_shapes(case, want, monkeypatch):
    words = case.split("-")
    if words[0] == "chip":
        monkeypatch.setattr(plk, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                       "1" if "forced" in words else "0")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",)) \
        if "mesh2" in words else None
    assert s6.state_path(6 if "fewslots" in words else 256,
                         96 if "narrow" in words else 5120, mesh) == want
    assert state_kernel.tiles(256, 5120) == 2560
    assert state_kernel.tiles(8, 384) == 384


@pytest.mark.parametrize("tile", [128, 384])
def test_the_decode_kernel_is_the_fused_step(tile):
    """``s6_state_update`` (interpreted) against ``state_step``: the new
    state and ``y`` of the active slots to float32 rounding, an inactive
    slot's state BIT FOR BIT what it was."""
    r = np.random.RandomState(2)
    slots = 16
    state = r.randn(slots, N, 384).astype(F32)
    x, dt, b, c, a = rows(slots, seed=4, c=384)
    active = (r.rand(slots) > 0.4).astype(np.int32)
    new, y = state_kernel.s6_state_update(state, dt, x, b, c, a, active,
                                          tile=tile, interpret=True)
    want, want_y = s6.state_step(jnp.asarray(state), jnp.asarray(a), dt, x,
                                 b, c)
    on = active > 0
    assert on.any() and not on.all()
    assert rel(np.asarray(new)[on], np.asarray(want)[on]) <= 1e-6
    assert rel(np.asarray(y)[on], np.asarray(want_y)[on]) <= 1e-6
    assert np.array_equal(np.asarray(new)[~on], state[~on])
    with pytest.raises(ValueError, match="no whole tiles"):
        state_kernel.s6_state_update(state[:6], dt[:6], x[:6], b[:6], c[:6],
                                     a, active[:6], interpret=True)


@pytest.mark.parametrize("active", [[1, 0, 1, 1, 0, 1, 1, 1], [1] * 8])
def test_both_tiers_of_the_decode_step_agree(monkeypatch, active):
    """The whole op through the kernel (interpreted: eight slots of 128
    channels) and through the fused step: the same outputs, states and
    windows; an inactive slot bit for bit either way; each lowering
    counted under its tier."""
    w = layer_weights(seed=3)
    r = np.random.RandomState(5)
    ins = dict(X=r.randn(8, 1, M).astype(F32),
               State=r.randn(8, N, C).astype(F32),
               Conv=r.randn(8, TAPS - 1, C).astype(F32),
               Active=np.array(active)[:, None])
    outs = {}
    for tier in ("fused", "kernel"):
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                           "1" if tier == "kernel" else "0")
        before = s6.S6_STATE_LOWERED.labels(path=tier).value
        outs[tier] = run_op("s6_decode", w, **ins)
        assert s6.S6_STATE_LOWERED.labels(path=tier).value == before + 1
    on = np.array(active) > 0
    for key in ("Out", "StateOut", "ConvOut"):
        assert rel(outs["kernel"][key][on], outs["fused"][key][on]) <= 1e-6
    for tier in outs:
        assert np.array_equal(outs[tier]["StateOut"][~on],
                              ins["State"][~on])
        assert np.array_equal(outs[tier]["ConvOut"][~on], ins["Conv"][~on])


def test_the_ops_declare_their_state_by_role():
    """``slot_state`` of kind ``s6`` on both ops: what the engine finds
    the fifth kind of per-slot state by."""
    for name in ("s6_prefill", "s6_decode"):
        assert get_op(name).slot_state == ("s6", ("StateOut", "ConvOut"))
    block = types.SimpleNamespace(ops=[types.SimpleNamespace(
        type="s6_decode", output=lambda slot: [f"lm_{slot}_0"])])
    assert slot_state_vars(block) == {"s6": {
        "ConvOut": ["lm_ConvOut_0"], "StateOut": ["lm_StateOut_0"]}}
