"""The Pallas tier of ``kda_decode``'s state update
(``ops/pallas/kda_state.py``: one read and one write of the recurrent
state a step, in place) against ``ops/kda.py:_delta_step``, the refer
tier and ``kda_prefill``'s loop body — interpreted on the CPU; what the
chip's compiler makes of it is ``tests/test_aot_tpu_compile.py -k
kda_state``.

``TOL`` is ``tests/test_hybrid_lm.py``'s: both tiers compute in float32,
so what separates them is the order of the sums (1e-7 here); the two
mutants of ``test_a_mutant_fails`` move a result by 1e-3 or more."""

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

from paddle_tpu.core.registry import get_op  # noqa: E402
from paddle_tpu.ops import kda  # noqa: E402
from paddle_tpu.ops import pallas as plk  # noqa: E402
from paddle_tpu.ops.pallas import kda_state as ks  # noqa: E402

TOL = 2e-5
F32 = np.float32


def inputs(b, h, d, seed=0, dv=None, head_decay=False):
    """A state and one token's q, k, v, g, beta as ``_qkv`` and
    ``_token_terms`` give them: q, k normalised, g <= 0, beta in
    (0, 2). ``dv``: a value head of another size than the key head's
    ``d`` (a rectangular state); ``head_decay``: g [b, h, 1], one decay
    a head (``ops/gdn.py``'s)."""
    r = np.random.RandomState(seed)
    dv = dv or d
    s = r.randn(b, h, d, dv).astype(F32)
    q = r.randn(b, h, d).astype(F32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k = r.randn(b, h, d).astype(F32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.randn(b, h, dv).astype(F32)
    g = -np.abs(r.randn(b, h, 1 if head_decay else d)).astype(F32) * 0.1
    beta = (2.0 / (1.0 + np.exp(-r.randn(b, h)))).astype(F32)
    return s, q, k, v, g, beta


def rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / np.abs(np.asarray(b)).max())


def errors(b, h, d, heads, active, update=ks.kda_state_update, seed=0,
           unroll=ks.UNROLL, **shape):
    """(state error, output error) of the kernel over the ACTIVE slots,
    relative to ``_delta_step``'s largest value, and whether every
    inactive slot kept its state to the bit."""
    s, q, k, v, g, beta = inputs(b, h, d, seed, **shape)
    active = np.asarray(active, np.int32)
    new, o = update(jnp.asarray(s), q, k, v, g, beta, active, heads=heads,
                    unroll=unroll, interpret=True)
    want_s, want_o = kda._delta_step(jnp.asarray(s), q, k, v, g, beta)
    on = active > 0
    kept = np.array_equal(np.asarray(new)[~on], s[~on])
    if not on.any():
        return 0.0, 0.0, kept
    return (rel(np.asarray(new)[on], np.asarray(want_s)[on]),
            rel(np.asarray(o)[on], np.asarray(want_o)[on]), kept)


# slots, heads, head size, heads a grid step (0: ``head_block``'s own),
# which slots decode
CASES = {
    "all_active": (2, 8, 128, 8, [1, 1]),
    "none_active": (2, 8, 128, 8, [0, 0]),
    "mixed_block8": (4, 16, 128, 8, [1, 0, 1, 1]),
    "mixed_block16": (4, 16, 128, 16, [0, 1, 1, 0]),
    "mixed_block32": (3, 32, 128, 32, [1, 0, 1]),
    "two_blocks_of_32": (1, 64, 128, 0, [1]),
    "one_block_of_24": (2, 24, 128, 0, [1, 0]),
    "head_size_256": (2, 8, 256, 0, [0, 1]),
    # heads that are no whole sublane tiles: a block's vectors are padded
    "twenty_heads": (2, 20, 128, 0, [1, 0]),
    "three_heads": (2, 3, 128, 0, [1, 1]),
}
# a rectangular state [Dk, Dv] that is no whole lane tiles, one decay a
# head (Olmo-Hybrid's 30 heads of 96 x 192: two blocks of 15, three
# heads written out a turn): slots, heads, Dk, Dv, heads a grid step,
# which slots decode
RECTANGULAR = {
    "olmo_30x96x192": (3, 30, 96, 192, 0, [1, 0, 1]),
    "olmo_blocks_of_10": (2, 30, 96, 192, 10, [0, 1]),
    "wide_key_64x320": (2, 4, 64, 320, 0, [1, 1]),
    "tall_256x64": (2, 6, 256, 64, 3, [1, 0]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_delta_step(case):
    """The new state and the output of every active slot to ``TOL``; an
    inactive slot's state BIT FOR BIT what it was."""
    b, h, d, heads, active = CASES[case]
    s_err, o_err, kept = errors(b, h, d, heads, active)
    assert kept
    assert s_err <= TOL and o_err <= TOL, (s_err, o_err)


@pytest.mark.parametrize("head_decay", [False, True])
@pytest.mark.parametrize("case", sorted(RECTANGULAR))
def test_the_kernel_is_delta_step_on_a_rectangular_state(case, head_decay):
    """As above for a state [Dk, Dv] with Dk != Dv, with a decay a key
    channel and with ONE a head: the same kernel, the same step."""
    b, h, dk, dv, heads, active = RECTANGULAR[case]
    s_err, o_err, kept = errors(b, h, dk, heads, active, dv=dv,
                                head_decay=head_decay)
    assert kept
    assert s_err <= TOL and o_err <= TOL, (s_err, o_err)


@pytest.mark.parametrize("unroll", [1, 2, 8, 16])
def test_heads_written_out_or_looped_over_are_the_same_update(unroll):
    """A block of 16 heads as a loop over groups of ``unroll`` heads
    (the next group's columns rotate to the front of the transposed
    tile) and as 16 heads written out."""
    s_err, o_err, kept = errors(3, 16, 128, 16, [1, 0, 1], unroll=unroll)
    assert kept
    assert s_err <= TOL and o_err <= TOL, (s_err, o_err)


def test_a_block_of_heads_divides_the_heads():
    """The heads written out a turn are the largest count under
    ``unroll`` that divides the block (16 asked of 24: 12); a block
    that does not divide the heads is refused."""
    s, q, k, v, g, beta = inputs(1, 24, 128)
    new, _o = ks.kda_state_update(s, q, k, v, g, beta,
                                  np.ones(1, np.int32), unroll=16,
                                  interpret=True)
    want, _ = kda._delta_step(jnp.asarray(s), q, k, v, g, beta)
    assert rel(new, want) <= TOL
    with pytest.raises(ValueError, match="heads divides"):
        ks.kda_state_update(s, q, k, v, g, beta, np.ones(1, np.int32),
                            heads=16, interpret=True)


@pytest.mark.parametrize("n_head,key_dim,value_dim,want", [
    (64, 128, 128, 32),     # the hybrid cell: 2 MB a block
    (32, 128, 128, 32), (8, 128, 128, 8), (24, 128, 128, 24),
    (40, 128, 128, 20), (96, 128, 128, 32),
    (64, 256, 256, 8),      # a larger tile, fewer heads: the same 2 MB
    (20, 128, 128, 20),     # no whole sublane tiles: padded vectors
    (4, 128, 128, 4),
    (30, 96, 192, 15),      # Olmo-Hybrid: [96, 256] in VMEM, 21 fit
])
def test_head_block(n_head, key_dim, value_dim, want):
    assert ks.head_block(n_head, key_dim, value_dim) == want
    assert ks.supported(key_dim, value_dim, jnp.float32)


@pytest.mark.parametrize("n_head,key_dim,value_dim,dtype", [
    (4, 16, 16, jnp.float32),       # tests/test_hybrid_lm.py's family
    (4, 16, 32, jnp.float32),       # tests/test_gdn.py's
    (64, 64, 64, jnp.float32),      # half a lane tile
    (30, 100, 192, jnp.float32),    # key channels off the sublane tiles
    (64, 128, 128, jnp.bfloat16),   # a narrower state is another result
    (2, 1024, 1024, jnp.float32),   # one tile over a block's bytes
])
def test_what_the_kernel_is_not_written_for(n_head, key_dim, value_dim,
                                            dtype):
    assert not ks.supported(key_dim, value_dim, dtype)


def test_the_state_is_aliased_to_the_result_and_comes_first():
    """``input_output_aliases`` state -> new state (operands 0-2 are the
    prefetched scalars), so a caller that donates the state updates
    it in place; the state is the kernel's FIRST result, which is what
    the device trace names it by (``test_the_benchmarks_reader_*``)."""
    s, q, k, v, g, beta = inputs(2, 8, 128)
    jaxpr = jax.make_jaxpr(lambda *a: ks.kda_state_update(
        *a, interpret=True))(s, q, k, v, g, beta, np.ones(2, np.int32))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)
    (call,) = calls(jaxpr.jaxpr)
    assert tuple(call.params["input_output_aliases"]) == ((3, 0),)
    assert call.invars[3].aval.shape == (2, 8, 128, 128)
    # o as the kernel writes it: [slots, blocks, a block's heads, Dv]
    assert [o.aval.shape for o in call.outvars] == [(2, 8, 128, 128),
                                                    (2, 1, 8, 128)]


def _bf16_products(orig):
    def advance(s, alpha, ka, qa, bk, v, qbk):
        def low(x):
            return jax.lax.reduce_precision(x, 8, 7)
        u = jnp.sum(low(low(ka) * low(s)), axis=0, keepdims=True)
        red_q = jnp.sum(low(low(qa) * low(s)), axis=0, keepdims=True)
        dv = v - u
        return alpha * s + bk * dv, red_q + qbk * dv
    return advance


def _output_of_the_old_state(orig):
    def advance(s, alpha, ka, qa, bk, v, qbk):
        s_new, _o = orig(s, alpha, ka, qa, bk, v, qbk)
        return s_new, jnp.sum(qa * s, axis=0, keepdims=True)
    return advance


MUTANTS = {
    "bf16_product_inside_the_update": _bf16_products,
    "o_from_the_old_state": _output_of_the_old_state,
}


@pytest.mark.parametrize("mutant", [None] + sorted(MUTANTS))
def test_a_mutant_fails(monkeypatch, mutant):
    """``TOL`` bites: the kernel with a bfloat16 product in its
    reductions, or with ``o`` read off the decayed OLD state (without
    ``(q . beta k) (v - u)``), is outside it; the kernel as written,
    through the same untraced entry, inside."""
    if mutant:
        monkeypatch.setattr(ks, "_advance", MUTANTS[mutant](ks._advance))
    # the wrapper's jit would hand back the trace it made before the patch
    s_err, o_err, kept = errors(2, 8, 128, 8, [1, 1],
                                update=ks.kda_state_update.__wrapped__)
    assert kept
    if mutant:
        assert max(s_err, o_err) > 50 * TOL, (s_err, o_err)
    else:
        assert max(s_err, o_err) <= TOL


# ------------------------------------------------ which tier, and the counter

def _decode_inputs(b=2, h=8, d=128, m=32, taps=4, rank=8, dtype=jnp.float32):
    def z(*shape, dt=dtype):
        return [jax.ShapeDtypeStruct(shape, dt)]
    hd = h * d
    ins = {"X": z(b, 1, m), "Wq": z(m, hd), "Wk": z(m, hd), "Wv": z(m, hd),
           "Wo": z(hd, m), "ConvW": z(taps, 3 * hd), "ALog": z(h),
           "DtBias": z(hd), "WaDown": z(m, rank), "WaUp": z(rank, hd),
           "WBeta": z(m, h), "WgDown": z(m, rank), "WgUp": z(rank, hd),
           "ONorm": z(d), "State": z(b, h, d, d, dt=jnp.float32),
           "Conv": z(b, taps - 1, 3 * hd),
           "Active": z(b, 1, dt=jnp.int32)}
    return ins, {"n_head": h, "head_dim": d, "epsilon": 1e-5}


def _lowered_by(ins, attrs, mesh=None):
    """How much each path of ``paddle_kda_decode_lowered_total`` grew
    over one lowering of ``kda_decode``."""
    fam = kda.KDA_DECODE_LOWERED
    before = {p: fam.labels(path=p).value for p in ("kernel", "refer")}
    jax.eval_shape(lambda i: get_op("kda_decode").emit(
        types.SimpleNamespace(mesh=mesh), i, attrs), ins)
    return {p: fam.labels(path=p).value - before[p] for p in before}


@pytest.mark.parametrize("case,want", [
    ("cpu", "refer"),                   # no kernel tier off the chip
    ("chip", "kernel"),                 # the benchmark's cell
    ("chip-mesh2", "refer"),            # XLA cannot partition a Mosaic call
    ("chip-d64", "refer"),              # half a lane tile
    ("chip-heads20", "kernel"),         # a block's vectors are padded
    ("cpu-forced", "kernel"),           # the tests' way in: interpreted
    ("cpu-forced-d64", "refer"),        # ... where the kernel is written for
])
def test_the_tier_is_chosen_by_what_the_lowering_sees(case, want,
                                                      monkeypatch):
    """No flag and no configuration key: the backend, the mesh, the
    state's dtype and tile. ``on_tpu`` is steered here because it asks
    for a TPU backend; ``PADDLE_TPU_FORCE_PALLAS``
    (``pallas.forced_interpret``) is ``fused_linear_ce``'s convention
    for interpreting a kernel inside a whole program on the CPU. One increment a layer, under the tier."""
    words = case.split("-")
    if words[0] == "chip":
        monkeypatch.setattr(plk, "on_tpu", lambda: True)
        # the interpreter stands in for Mosaic under eval_shape
        monkeypatch.setattr(plk, "interpret_mode", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS",
                       "1" if "forced" in words else "0")
    mesh = None
    if "mesh2" in words:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
    ins, attrs = _decode_inputs(
        h=20 if "heads20" in words else 8, d=64 if "d64" in words else 128)
    grew = _lowered_by(ins, attrs, mesh)
    assert grew == {p: (1 if p == want else 0) for p in grew}


def test_a_bfloat16_model_keeps_its_float32_state_on_the_kernel(
        monkeypatch):
    """The hybrid cell stores weights and activations in bfloat16 and
    the recurrent state in float32: the tier goes by the STATE."""
    monkeypatch.setattr(plk, "on_tpu", lambda: True)
    monkeypatch.setattr(plk, "interpret_mode", lambda: True)
    ins, attrs = _decode_inputs(dtype=jnp.bfloat16)
    assert _lowered_by(ins, attrs) == {"kernel": 1, "refer": 0}


def test_the_counter_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_kda_decode_lowered_total" in \
        obs_metrics.default_registry().snapshot()


# ----------------------------- the whole decode view, both tiers, on the CPU

def _served(monkeypatch, forced):
    """Three requests through the hybrid family's slot views at a head
    size the kernel takes (one period: gqa, kda, kda, kda; 8 KDA heads of
    128), decoded together: tokens, each slot's states, and how the KDA
    layers of the decode view were lowered."""
    from test_hybrid_lm import FAMILY
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1" if forced else "0")
    fam = kda.KDA_DECODE_LOWERED
    before = {p: fam.labels(path=p).value for p in ("kernel", "refer")}
    engine = FAMILY.fresh(n_layer=4, kda_heads=8, kda_head_dim=128)
    grew = {p: fam.labels(path=p).value - before[p] for p in before}
    rng = np.random.RandomState(12)
    toks = {}
    for n, budget in ((11, 7), (4, 3), (7, 9)):
        slot, tok, _d = engine.admit(rng.randint(1, 96, n), max_new=budget)
        toks[slot] = [tok]
    states = {}
    while engine.active_count():
        for slot, tok, done in engine.step():
            toks[slot].append(tok)
            if done:            # before another step can touch the slot
                states[slot] = [np.asarray(engine.scope.find_var(n)[slot])
                                for n in engine.state_vars]
    return toks, states, grew


def test_the_decode_view_serves_the_same_through_either_tier(monkeypatch):
    """``decode_paged`` of the hybrid family with the kernel forced onto
    the interpreter against the view as the CPU lowers it: the same
    tokens, every slot's recurrent and conv state to ``TOL`` — and the
    counter names the tier of each of the three KDA layers, once a
    lowering of the decode view."""
    refer_toks, refer_states, refer_grew = _served(monkeypatch, False)
    kern_toks, kern_states, kern_grew = _served(monkeypatch, True)
    assert refer_grew["kernel"] == 0 and refer_grew["refer"] >= 3
    assert refer_grew["refer"] % 3 == 0
    assert kern_grew == {"kernel": refer_grew["refer"], "refer": 0}
    assert kern_toks == refer_toks
    assert sorted(kern_states) == sorted(refer_states) == [0, 1, 2]
    for slot, states in refer_states.items():
        for a, b in zip(kern_states[slot], states):
            assert rel(a, b) <= TOL if np.abs(b).max() else not a.any()


# ------------------------------------------- how the benchmark's reader sees it

# the kernel's event as the v5e's profiler printed it (my chip run, PR
# 36: the kernel alone at the hybrid cell's shape; the text goes on with
# the operands' layout constraints and the aliasing)
CHIP_HLO = (
    "%kda_state_update.1 = (f32[128,64,128,128]{3,2,1,0:T(8,128)}, "
    "f32[128,64,128]{2,1,0:T(8,128)}) custom-call(s32[128]{0:T(128)S(1)} "
    "%copy-done.1, f32[8192]{0:T(1024)S(1)} %reshape.0, "
    "f32[8192]{0:T(1024)S(1)} %reshape.1, "
    "f32[128,64,128,128]{3,2,1,0:T(8,128)} %s.1, "
    "f32[128,64,128]{2,1,0:T(8,128)S(1)} %custom-call, "
    "f32[128,64,128]{2,1,0:T(8,128)S(1)} %copy-done, "
    "f32[128,64,128]{2,1,0:T(8,128)} %v.1, "
    "f32[128,64,128]{2,1,0:T(8,128)} %g.1), "
    "custom_call_target=\"tpu_custom_call\", "
    "output_to_operand_aliasing={{0}: (3, {})}")


@pytest.mark.parametrize("hlo,selected", [
    (CHIP_HLO, True),
    # the output first: the event would carry [128,64,128], a shape the
    # gate and the projections' results have too, and the reader (by
    # result shape) would never see the kernel
    (CHIP_HLO.replace("(f32[128,64,128,128]{3,2,1,0:T(8,128)}, "
                      "f32[128,64,128]{2,1,0:T(8,128)})",
                      "(f32[128,64,128]{2,1,0:T(8,128)}, "
                      "f32[128,64,128,128]{3,2,1,0:T(8,128)})"), False),
])
def test_the_benchmarks_reader_selects_the_kernel_by_its_first_result(
        hlo, selected):
    """Why the state comes FIRST: ``trace_reduce.short_op_name`` keeps
    the first shape of a tuple result, and ``hybrid_ops.kda_shapes`` —
    the selection behind ``kda_state_ms_per_step`` and
    ``kda_state_roofline`` — goes by that shape."""
    from chipbench import trace_reduce
    from chipbench.layer_metrics import hybrid_ops
    build = {"n_slots": 128, "kda_heads": 64, "kda_head_dim": 128,
             "kda_conv_taps": 4, "dtype": "bfloat16"}
    short = trace_reduce.short_op_name(hlo)
    assert short.startswith("kda_state_update.1 custom-call ")
    assert short.rstrip().endswith("tpu_custom_call")
    words = short.split()
    assert any(s in words for s in hybrid_ops.kda_shapes(build)) == selected
