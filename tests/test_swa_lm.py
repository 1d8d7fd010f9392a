"""Window and full attention layers in one page pool
(``decoder_lm(..., layer_kinds=("swa", "swa", "swa", "gqa"))``:
sliding-window layers with rotary positions and QK-norm in the pool's
window group beside full layers with no positions in its full group,
norms after each sub-layer, a scaled embedding, every routed expert
held) against the plain reference of
``chipbench/reference/trinity_mini_26b_d5.py``, at a tiny size on the
CPU in float32: width 64, 4 query / 2 KV heads of 16, a window of 8
positions over pages of 4 (a ring of 3 pages a slot), contexts to 46
rows — so that a prompt is shorter than, as long as and longer than the
window, and every decode of a dozen steps crosses it and returns pages.

The tolerance of every comparison is ``TOL``: system and reference both
compute in float32 from the same weights, so what separates them is the
order of the sums — under 1e-6 here. Each fault moves a result by 1e-3
or more, and ``test_a_fault_fails_the_comparison`` shows each failing.
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
from chipbench.reference import trinity_mini_26b_d5 as ref  # noqa: E402
from chipbench.runners import serve_trinity  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.ops import kv_attention  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

TOL = 2e-5
BUILD = dict(
    n_layer=5, d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=32,
    max_new=16, prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["swa", "swa", "swa", "gqa"], first_k_dense=1,
    n_kv_head=2, head_dim=16, gqa_gate=True, qk_norm=True, window=8,
    rope_theta=10000.0, post_norms=True, embed_scale=8.0,
    n_routed_experts=8, n_experts_held=8, n_experts_per_tok=2,
    d_expert=24, n_shared_experts=1, norm_topk_prob=True,
    router_bias=True, routed_scaling_factor=2.826, rms_eps=1e-5,
    dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="trinity_mini_26b_d5")


def _drawn_gains(engine, build, seed):
    """Every gain (the norms', q_norm, k_norm) drawn from 0.5-1.5: a
    gain of 1 commutes with the rotation and would hide the order of
    norm and positions."""
    rng = np.random.RandomState(seed)
    for name in ref.param_names(build):
        if name.endswith(("_scale", "_norm")):
            shape = np.shape(engine.scope.find_var(name))
            engine.scope.set_var(name, jax.device_put(
                rng.uniform(0.5, 1.5, shape).astype(np.float32)))


FAMILY = families.Family(serve_trinity, CFG, ref,
                         serve_trinity.AttendedProbe, prepare=_drawn_gains)
params_of = FAMILY.params_of


@pytest.fixture(scope="module")
def engine():
    return FAMILY.shared()


def released():
    return smetrics.KV_WINDOW_PAGES_RELEASED.labels(model="lm").value


def worst(engine, prompt_len, max_new=14, seed=1, build=BUILD, **control):
    """(the largest relative error of the served logits, the number of
    (step, window layer) readings of what was attended that differ from
    the reference's, the largest margin) of one request."""
    prompt, toks, logits, seen = FAMILY.request(engine, prompt_len,
                                                max_new, seed, build)
    err, margin, positions = ref.compare(params_of(engine, build), prompt,
                                         toks, logits, build, **control)
    want = ref.attended(build, positions, control.get("window"))
    return err.max(), int((seen != want[:, None, :]).any(-1).sum()), \
        margin.max()


# prompts shorter than, as long as and longer than the window of 8, all
# but one SHORTER than their bucket (16 or 32): a generated row is then
# not at its token's position, and the window is counted over true
# positions; every one crosses the window while it decodes 14 tokens
@pytest.mark.parametrize("prompt_len", [3, 7, 8, 9, 16, 21, 30])
def test_prefill_then_decode_matches_the_full_forward(engine, prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through both page groups, and WHAT each window layer
    attended, against one full causal forward with no cache — while the
    slot returns the window pages it has left behind."""
    before = released()
    err, wrong, margin = worst(engine, prompt_len)
    assert err <= TOL
    assert wrong == 0
    assert margin == 0.0            # every served token the argmax
    assert released() > before
    assert engine.pool.stats()["window_pages_free"] \
        == engine.n_window_pages


@pytest.mark.parametrize("control", [
    dict(window=7), dict(window=9), dict(rope_full=True),
    dict(norm_last=True), dict(low_precision=True)])
def test_a_fault_fails_the_comparison(engine, control):
    """A window one key short or long, rotary positions on the full
    layer too, q and k normalised after their rotation, a forward one
    precision down: each is far outside the tolerance (and the window's
    faults in every reading of what was attended)."""
    err, wrong, _ = worst(engine, 21, **control)
    assert err > 50 * TOL
    if "window" in control:
        assert wrong > 0


def test_logits_are_the_same_with_and_without_release():
    """The same request through pages of 4 rows (a ring of 3: the slot
    returns a page every 4 steps) and through pages of 16 (a ring of 2,
    32 rows: nothing to return in 21 positions)."""
    prompt = np.random.RandomState(4).randint(1, BUILD["vocab"], 5)
    logits = []
    for page_size, returns in ((4, True), (16, False)):
        # pages of 4 rows are the module's own engine
        eng = FAMILY.shared(page_size=page_size)
        eng.reset()
        before = released()
        toks, rows, _seen = serve_trinity.serve_one(
            eng, prompt, 16, probe=FAMILY.probe(eng))
        assert (released() > before) == returns
        logits.append(rows)
    np.testing.assert_allclose(logits[0], logits[1], atol=1e-5, rtol=1e-5)


def test_a_long_prompt_attends_in_blocks(monkeypatch):
    """A prompt bucket longer than ``GQA_QUERY_BLOCK`` attends in blocks
    of queries, a window layer's block over the keys its band reaches
    alone: the same numbers."""
    monkeypatch.setattr(kv_attention, "GQA_QUERY_BLOCK", 8)
    eng = FAMILY.fresh()
    for prompt_len in (13, 27, 32):
        err, wrong, _ = worst(eng, prompt_len, max_new=4)
        assert err <= TOL and wrong == 0


def test_requests_live_together_and_through_the_server(engine):
    """Four requests of different lengths stepped together (one never
    reaches the window, one crosses it, two start past it), then the
    same prompts through ``ModelServer`` (steps dispatched ahead): the
    same tokens; every page of both groups comes back."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, BUILD["vocab"], n) for n in (2, 6, 19, 27)]
    budgets = [5, 12, 16, 9]
    served = serve_trinity.serve_together(
        engine, FAMILY.probe(engine), prompts, budgets)
    for prompt, (toks, logits, _seen) in zip(prompts, served):
        err, _margin, _pos = ref.compare(params_of(engine), prompt, toks,
                                         logits, BUILD)
        assert err.max() <= TOL
    server = serving.ModelServer()
    try:
        server.add_model(engine)
        futures = [server.submit_generate("lm", [p], max_new=b)
                   for p, b in zip(prompts, budgets)]
        for f, (toks, _l, _s) in zip(futures, served):
            np.testing.assert_array_equal(f.result(timeout=120)[0], toks)
    finally:
        server.stop()
    stats = engine.pool.stats()
    assert stats["window_pages_free"] == stats["window_pages_total"]
    assert stats["pages_free"] + stats["pages_cached"] \
        == stats["pages_total"]
    engine.reset()


def _pool_rows(engine):
    return {n: np.asarray(engine.scope.find_var(n))
            for n in engine._cb_decode.sig.state_names if "_page_" in n}


def test_a_step_writes_only_its_slots_pages(engine):
    """Pages that no running slot holds — a free slot's, the free
    lists' — are bit-identical before and after a step, in both
    groups."""
    engine.reset()
    rng = np.random.RandomState(9)
    for n in (5, 22):
        engine.admit(rng.randint(1, BUILD["vocab"], n), max_new=12)
    for _ in range(6):
        before = _pool_rows(engine)
        held = {"_page_w": set(engine._table_w[engine._active].ravel()),
                "_page_": set(engine._table[engine._active].ravel())}
        engine.step()
        for name, after in _pool_rows(engine).items():
            mine = held["_page_w" if "_page_w" in name else "_page_"]
            others = [p for p in range(after.shape[0]) if p not in mine]
            np.testing.assert_array_equal(after[others],
                                          before[name][others])
    engine.reset()


@pytest.mark.parametrize("end", ["cancel", "finish", "reset"])
def test_both_groups_come_back(engine, end):
    """A cancelled request, a finished one and a reset return the pages
    of both groups."""
    engine.reset()
    prompt = np.random.RandomState(2).randint(1, BUILD["vocab"], 20)
    slot, _tok, _done = engine.admit(prompt, max_new=6)
    assert engine.pool.window_free_count() < engine.n_window_pages
    for _ in range(3):
        engine.step()
    if end == "cancel":
        engine.release(slot)
    elif end == "finish":
        while engine.active_count():
            engine.step()
    else:
        engine.reset()
    assert engine.pool.window_free_count() == engine.n_window_pages
    assert engine.pool.available_count() == engine.n_pages
    assert (engine._table_w == engine.n_window_pages).all()
    engine.reset()


def test_admission_is_refused_when_the_window_group_is_out(engine):
    """Every slot's ring fits the group by construction; a group drained
    by hand refuses the next admission and leaves both groups as they
    were."""
    engine.reset()
    taken, engine.pool._wfree = engine.pool._wfree, []
    try:
        free = engine.pool.free_count()
        with pytest.raises(engine_mod.SlotExhaustedError,
                           match="free_window_pages=0"):
            engine.admit(np.arange(1, 9), max_new=4)
        assert engine.pool.free_count() == free
        assert engine.active_count() == 0
    finally:
        engine.pool._wfree = taken
    engine.reset()
