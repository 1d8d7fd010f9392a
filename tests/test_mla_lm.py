"""The latent-attention block of the slot views (``decoder_lm(...,
layer_kinds=("mla",))``: multi-head latent attention with rotary
positions and the DSA indexer's top-k selection through a latent plane
and an indexer-key plane of the paged pool, a leading dense SwiGLU
layer, bias-corrected routing) against the plain reference of
``chipbench/reference/glm5_744b_ep16_d5.py``, at a tiny size on the CPU
in float32: width 64, 4 heads (nope 8 / rope 8 / v 16) over a latent of
16, an indexer of 2 heads of 16 that keeps 8 rows, contexts to 40 rows —
so that the selection cuts in the prefill (buckets 16 and 32) and in
every decode step.

The tolerance of every comparison is ``TOL``: system and reference both
compute in float32 from the same weights, so what separates them is the
order of the sums — under 1e-6 here. Each fault moves a result by 1e-2
or more, and ``test_a_fault_fails_the_comparison`` shows each failing.
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
from chipbench.reference import glm5_744b_ep16_d5 as ref  # noqa: E402
from chipbench.runners import serve_glm5  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.analysis import contracts  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.ops import mla  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

TOL = 2e-5
BUILD = dict(
    n_layer=3, d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=32,
    max_new=8, prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["mla"], first_k_dense=1,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
    index_n_heads=2, index_head_dim=16, index_topk=8,
    n_routed_experts=16, n_experts_held=4, n_experts_per_tok=4,
    d_expert=24, n_shared_experts=1, norm_topk_prob=True,
    router_bias=True, routed_scaling_factor=2.5, rms_eps=1e-5,
    dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="glm5_744b_ep16_d5")


FAMILY = families.Family(serve_glm5, CFG, ref, serve_glm5.PickProbe)
params_of = FAMILY.params_of


@pytest.fixture(scope="module")
def engine():
    return FAMILY.shared()


def worst(engine, prompt_len, max_new=8, seed=1, build=BUILD):
    """(the largest relative error of the served logits, the smallest
    overlap of the attended sets, the largest margin) of one request
    against the reference."""
    prompt, toks, logits, picks = FAMILY.request(engine, prompt_len,
                                                 max_new, seed)
    logit_err, overlaps, margin = ref.compare(
        params_of(engine, build), prompt, toks, logits, picks, build)
    return logit_err.max(), overlaps.min(), margin.max()


# prompts SHORTER than their bucket (16 or 32): a generated row is then
# not at its token's position, and rows between the prompt's end and
# the bucket are padding; every one longer than index_topk = 8 rows
# after a step or two, 21 and 30 in the prefill already
@pytest.mark.parametrize("prompt_len", [3, 11, 16, 21, 30])
def test_prefill_then_decode_matches_the_full_forward(engine, prompt_len):
    """Logits of the prefill view at the prompt's true end, then of the
    decode view through the latent pages and the indexer's selection,
    and WHICH positions each step attended, against one full causal
    forward with expanded attention and no cache."""
    err, overlap, margin = worst(engine, prompt_len)
    assert err <= TOL
    assert overlap == 1.0           # the same rows, every step and layer
    assert margin == 0.0            # every served token the argmax


def test_a_released_slot_is_reused(engine):
    """A request ends, its slot is admitted again with a shorter prompt
    in the other bucket: what the first left in its pages is never
    scored or attended."""
    assert worst(engine, 29, seed=7)[0] <= TOL
    free = engine.free_count()
    assert free == engine.n_slots
    err, overlap, _ = worst(engine, 5, seed=8)
    assert err <= TOL and overlap == 1.0


def test_requests_live_together_and_through_the_server(engine):
    """Three requests of different lengths stepped together, then the
    same prompts through ``ModelServer`` (steps dispatched ahead): the
    same tokens."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, BUILD["vocab"], n) for n in (9, 27, 14)]
    budgets = [8, 5, 7]
    served = serve_glm5.serve_together(
        engine, FAMILY.probe(engine), prompts, budgets)
    for prompt, (toks, logits, picks) in zip(prompts, served):
        logit_err, overlaps, _ = ref.compare(
            params_of(engine), prompt, toks, logits, picks, BUILD)
        assert logit_err.max() <= TOL and overlaps.min() == 1.0
    server = serving.ModelServer()
    try:
        server.add_model(engine)
        futures = [server.submit_generate("lm", [p], max_new=b)
                   for p, b in zip(prompts, budgets)]
        for f, (toks, _l, _p) in zip(futures, served):
            np.testing.assert_array_equal(f.result(timeout=120)[0], toks)
    finally:
        server.stop()
    engine.reset()


def _row_for_position(orig):
    def feeds(self):
        out = orig(self)
        out["position"] = out["pos"]
        return out
    return feeds


FAULTS = {
    # the rotation at the slot's ROW (generated rows start at the bucket)
    "row_for_position": lambda mp: mp.setattr(
        engine_mod.SlotGenerativeModel, "_decode_feeds",
        _row_for_position(engine_mod.SlotGenerativeModel._decode_feeds)),
    # the rows between a prompt's end and its bucket scored and selected
    "padding_rows_live": lambda mp: mp.setattr(
        mla, "live_rows", lambda j, lens, gen0, pos:
        j[None, :] <= pos[:, None]),
    # no rotation at all
    "no_rotation": lambda mp: mp.setattr(
        mla, "rope", lambda x, pos, theta: x),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["dense_attention"])
def test_a_fault_fails_the_comparison(monkeypatch, fault):
    """Each fault, built into an engine of its own, against the
    unchanged reference: the logits leave ``TOL`` a hundredfold, or the
    attended sets stop agreeing."""
    # the dense layer and ONE latent layer show the same miss as two do,
    # in the one bucket the prompt of 19 takes
    changes = {"n_layer": BUILD["first_k_dense"] + len(BUILD["layer_kinds"]),
               "prompt_buckets": [32]}
    honest = FAMILY.build(**changes)      # what the reference is told
    if fault == "dense_attention":
        changes["index_topk"] = 64        # every row attended
    else:
        FAULTS[fault](monkeypatch)
    eng = FAMILY.fresh(**changes)
    err, overlap, _ = worst(eng, 19, seed=2, build=honest)
    assert err > 100 * TOL
    if fault in ("padding_rows_live", "dense_attention"):
        assert overlap < 0.8


# ------------------------------------------------------- the two ways

def _layer_weights(rng, a, d_model=64, q_lora=24):
    h, dc, dn, dr, dv = (a["n_head"], a["kv_lora_rank"],
                         a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                         a["v_head_dim"])
    j, di = a["index_n_heads"], a["index_head_dim"]

    def w(*shape):
        return jnp.asarray(rng.randn(*shape) * (2.0 / sum(shape)) ** 0.5,
                           jnp.float32)
    return {"Wdq": w(d_model, q_lora), "QNorm": jnp.ones(q_lora),
            "Wuq": w(q_lora, h * (dn + dr)), "Wdkv": w(d_model, dc + dr),
            "KvNorm": jnp.ones(dc), "Wuk": w(dc, h * dn),
            "Wuv": w(dc, h * dv), "Wo": w(h * dv, d_model),
            "Wiq": w(q_lora, j * di), "Wik": w(d_model, di),
            "IkScale": jnp.ones(di), "IkBias": jnp.zeros(di),
            "Wiw": w(d_model, j)}


SIZES = dict(n_head=4, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
             index_head_dim=16, index_topk=8)


@pytest.mark.parametrize("length", [5, 8, 20])
def test_absorbed_equals_expanded(length):
    """The decode step's absorbed contraction over the cached latent
    rows gives the last position what the prefill's expanded attention
    gives it, where both attend the same rows (index_topk covers them)."""
    rng = np.random.RandomState(length)
    a = {**SIZES, "index_topk": 64}
    w = _layer_weights(rng, a)
    x = jnp.asarray(rng.randn(length, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, row, ki, qi, wi = mla.token_terms(x, w, jnp.arange(length), a,
                                             1e6, 1e-5)
        expanded = mla.expanded_attention(q, row[:, :16], row[:, 16:24],
                                          qi, wi, ki, w, a)[-1]
        absorbed = mla.absorbed_attention(
            q[-1:], row[None], jnp.ones((1, length), bool), w, a)[0]
    assert row.shape[1] == 128 and not np.asarray(row[:, 24:]).any()
    np.testing.assert_allclose(absorbed, expanded,
                               atol=TOL * np.abs(expanded).max())


@pytest.mark.parametrize("case", ["fits", "cuts", "ties", "all_equal"])
def test_selection_by_threshold_is_top_k(case):
    """``select_topk`` (threshold and tie count, no sort) picks what
    ``jax.lax.top_k`` picks over the valid entries — the identity while
    they are no more than k, the lower index among equals."""
    rng = np.random.RandomState(11)
    n, s, k = 6, 40, 8
    scores = rng.randn(n, s).astype(np.float32)
    valid = np.arange(s)[None, :] <= np.asarray([3, 7, 8, 20, 33, 39])[:, None]
    if case == "fits":
        valid &= np.arange(s)[None, :] < k
    elif case == "ties":
        scores = np.round(scores)          # a handful of distinct values
    elif case == "all_equal":
        scores[:] = 0.25
    got = np.asarray(mla.select_topk(jnp.asarray(scores),
                                     jnp.asarray(valid), k))
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    want = np.zeros((n, s), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    want &= valid
    np.testing.assert_array_equal(got, want)
    if case == "fits":
        np.testing.assert_array_equal(got, valid)


# ------------------------------------------------- engine and family

def _family(**changes):
    build = {**BUILD, **changes}
    return T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes(), kv_codec="none",
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})


def test_the_family_verifier_passes_the_latent_family():
    family = _family()
    assert [d for d in contracts.verify_family(family)
            if d.severity >= contracts.Severity.ERROR] == []
    dec = family["decode_paged"]
    assert "position" in dec[2]
    gvars = dec[0].desc.global_block.vars
    # the latent plane padded to whole lane tiles, the index plane as is
    assert list(gvars["lm_page_c_0"].shape) == [40, 4, 128]
    assert list(gvars["lm_page_i_2"].shape) == [40, 4, 16]
    # layer 0 is dense, layers 1 and 2 have experts with a bias
    params = {p.name for p in dec[0].global_block().all_parameters()}
    assert "lm_l0_ffn.w_gate" in params and "lm_l0_moe.router" not in params
    assert "lm_l1_moe.router_bias" in params


def test_a_kinds_sizes_are_needed_only_where_it_occurs():
    with pytest.raises(ValueError, match="kv_lora_rank"):
        _family(kv_lora_rank=None)
    with pytest.raises(ValueError, match="one of"):
        _family(layer_kinds=["mha"])
    # no gqa or kda layer: none of their sizes is asked for
    assert "n_kv_head" not in BUILD and "kda_heads" not in BUILD


def test_rows_scored_and_selected_are_counted(engine):
    """Per decode step and latent-attention layer: the slot's live rows,
    and of them at most index_topk."""
    scored = smetrics.DSA_ROWS_SCORED.labels(model="lm")
    selected = smetrics.DSA_ROWS_SELECTED.labels(model="lm")
    s0, k0 = scored.value, selected.value
    prompt = np.random.RandomState(4).randint(1, BUILD["vocab"], 5)
    engine.admit(prompt, max_new=6)
    for _ in range(5):
        engine.step()
    # steps at 6, 7, 8, 9, 10 live rows, three layers, index_topk 8
    assert scored.value - s0 == 3 * (6 + 7 + 8 + 9 + 10)
    assert selected.value - k0 == 3 * (6 + 7 + 8 + 8 + 8)
    assert engine.free_count() == engine.n_slots
    # the gauges are what the LAST engine of this name was built with:
    # one built here, not the worker's shared one
    FAMILY.fresh(warm=False)
    pool_rows = 40 * 4
    assert smetrics.LATENT_CACHE_BYTES.labels(model="lm").value \
        == 3 * pool_rows * 128 * 4
    assert smetrics.INDEX_CACHE_BYTES.labels(model="lm").value \
        == 3 * pool_rows * 16 * 4


# --------------------------------- the two ways a decode step attends

@pytest.mark.parametrize("case", ["cuts", "ties", "under_topk"])
def test_selected_is_where_the_mask_holds(case):
    """``Selected`` of the in-place path is read off the mask the kernel
    consumes: the positions where ``keep`` holds, ascending, -1 after —
    ``jax.lax.top_k``'s set, on tied scores and on slots with fewer live
    rows than index_topk too."""
    rng = np.random.RandomState(17)
    n, s, k = 5, 48, 8
    scores = rng.randn(n, s).astype(np.float32)
    last = np.asarray([47, 30, 12, 9, 8])
    if case == "ties":
        # + 0.0: top_k orders -0.0 below 0.0, a threshold finds them equal
        scores = np.round(scores) + 0.0
    elif case == "under_topk":
        last = np.asarray([0, 3, 6, 7, -1])        # -1: no live row
    valid = np.arange(s)[None, :] <= last[:, None]
    keep = mla.select_topk(jnp.asarray(scores), jnp.asarray(valid), k) \
        & jnp.asarray(valid)
    got = np.asarray(mla.selected_rows(keep, k))
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    for row, (g, i, v) in enumerate(zip(got, np.asarray(idx), valid)):
        want = np.sort(i[v[i]])
        np.testing.assert_array_equal(g[:len(want)], want)
        assert (g[len(want):] == -1).all()
        np.testing.assert_array_equal(np.flatnonzero(np.asarray(keep)[row]),
                                      want)


def _decode_op(rng, s_len, ps, topk, n_pages=None, b=3, di=16):
    """(emit, ins) of one ``mla_decode_paged`` layer over ``b`` slots of
    ``s_len`` rows in pages of ``ps``, indexer keys ``di`` wide: slot 0
    long past index_topk, slot 1 under it with padding before its
    bucket, slot 2 inactive."""
    import types
    from paddle_tpu.core.registry import get_op
    a = {**SIZES, "index_topk": topk, "index_head_dim": di}
    mp = s_len // ps
    n_pages = n_pages or b * mp
    w = _layer_weights(rng, a)
    width = mla.latent_width(a["kv_lora_rank"], a["qk_rope_head_dim"])
    bucket = s_len // 2
    col = lambda *v: jnp.asarray(v, jnp.int32)[:, None]  # noqa: E731
    ins = {**w, "X": jnp.asarray(rng.randn(b, 1, 64), jnp.float32),
           "PageC": jnp.asarray(rng.randn(n_pages, ps, width), jnp.float32),
           "PageI": jnp.asarray(rng.randn(n_pages, ps, di), jnp.float32),
           "PageTable": jnp.asarray(
               rng.permutation(n_pages)[:b * mp].reshape(b, mp), jnp.int32),
           "SeqLen": col(bucket - 3, 5, 0), "GenStart": col(bucket, bucket, 0),
           "Pos": col(bucket + s_len // 3, bucket, -1),
           "Active": col(1, 1, 0), "Position": col(bucket + s_len // 3 - 3,
                                                   5, 0)}
    attrs = {**a, "rope_theta": 1e6, "epsilon": 1e-5}

    def emit(ins):
        out = get_op("mla_decode_paged").emit(
            types.SimpleNamespace(mesh=None), {k: [v] for k, v in ins.items()},
            attrs)
        return {k: v[0] for k, v in out.items()}
    return emit, ins


@pytest.mark.parametrize("index", ["pages", "rows"])
def test_in_place_and_gathered_decode_agree(monkeypatch, index):
    """One layer's decode step both ways over the same planes — the
    kernels interpreted, engaged as they would be on the chip — gives
    the same context and the same ``Selected``: the latent plane
    attended in place under scores of the index plane read in place
    (``pages``, PR 66) or of its gathered copy (``rows``, PR 34's)."""
    from paddle_tpu.ops import kv_attention as kv
    emit, ins = _decode_op(np.random.RandomState(23), s_len=256, ps=8,
                           topk=64)
    if index == "rows":
        monkeypatch.setattr(mla, "scores_in_place", lambda *a: False)
    lowered = mla.DSA_INDEX_LOWERED.labels(path=index)
    with jax.default_matmul_precision("highest"):
        rows = emit(ins)
        was = lowered.value
        monkeypatch.setattr(
            kv, "_gather_tier", lambda flat, scales, ps, mesh=None: "pages")
        monkeypatch.setattr(
            kv, "_paged_gather", lambda flat, s, table, ps, dt, mesh=None:
            jnp.take(flat.reshape(-1, ps, flat.shape[-1]), table, axis=0)
            .reshape(table.shape[0], -1, flat.shape[-1]).astype(dt))
        pages = emit(ins)
    assert lowered.value == was + 1
    live = np.asarray(ins["Active"])[:, 0] > 0
    np.testing.assert_array_equal(pages["Selected"], rows["Selected"])
    assert (np.asarray(pages["Selected"])[0] >= 0).all()          # 64 of 211
    assert (np.asarray(pages["Selected"])[1] >= 0).sum() == 6     # 5 + 1
    assert np.isfinite(np.asarray(pages["Out"])).all()
    scale = np.abs(np.asarray(rows["Out"])[live]).max()
    np.testing.assert_allclose(np.asarray(pages["Out"])[live],
                               np.asarray(rows["Out"])[live],
                               atol=TOL * scale)
    for plane in ("PageCOut", "PageIOut"):
        np.testing.assert_array_equal(pages[plane], rows[plane])


@pytest.mark.parametrize("s_len,path,index", [
    (128 * mla.ATTEND_PAGES_MAX_RATIO, "pages", "pages"),
    # past the ratio the selected rows are gathered; the indexer, whose
    # other way copies the whole plane, still scores in place
    (128 * mla.ATTEND_PAGES_MAX_RATIO + 128, "rows", "pages"),
    # 184 pages of 8 rows: no block of whole lane tiles divides them
    (1472, "rows", "rows"),
    (64, "rows", "rows")])      # everything attended: no selection, no score
def test_the_lowering_counter_names_the_path(monkeypatch, s_len, path,
                                             index):
    """``paddle_mla_decode_lowered_total`` and
    ``paddle_dsa_index_lowered_total``: one increment each a layer each
    time a decode program is traced, by what the geometry got — decided
    from the cache's length over index_topk and the table's blocks where
    the plane is one the chip reads a page per DMA, and ``rows``
    everywhere off the chip."""
    from paddle_tpu.ops import pallas as pk
    # indexer keys a lane tile wide, as the chip's kernels ask
    emit, ins = _decode_op(np.random.RandomState(1), s_len=s_len, ps=8,
                           topk=128, di=128)
    want = {mla.MLA_DECODE_LOWERED: path, mla.DSA_INDEX_LOWERED: index}
    read = lambda: {c: {p: c.labels(path=p).value              # noqa: E731
                        for p in ("pages", "rows")} for c in want}
    before = read()
    jax.eval_shape(emit, ins)                       # here: the CPU
    assert read() == {c: {**was, "rows": was["rows"] + 1}
                      for c, was in before.items()}
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    jax.eval_shape(lambda i: emit(i), ins)          # as on the chip
    for counter, after in read().items():
        was, got = before[counter], want[counter]
        assert after[got] == was[got] + 1 + (got == "rows")
        assert sum(after.values()) == sum(was.values()) + 2
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    for family in ("paddle_mla_decode_lowered_total",
                   "paddle_dsa_index_lowered_total"):
        assert family in obs_metrics.default_registry().snapshot()
