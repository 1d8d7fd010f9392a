"""A full grouped-KV layer's decode step through the slot engine both
ways (ISSUE 62): by copy (every table row gathered, the copies
contracted: what the CPU lowers) and IN PLACE (``attend_pages`` over the
slots' live pages of the K and V planes, the kernel interpreted).

Two engines of one tiny model (two ``gqa`` layers of 4 query heads over
2 KV heads of 64: planes of 128 lanes; float32 pages of 8 rows; tables
of 2 176 rows — longer than ``ATTEND_FLOOR_ROWS``, in blocks of 128) and
the same weights. The in-place one is traced with ``_gather_tier``
answering ``"pages"`` as the chip would — nothing else is steered: the
op and the engine both ask ``attends_in_place``, so the path the
programs lower and the rows the engine counts follow from it.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.runners import serve_hybrid  # noqa: E402
from paddle_tpu.ops import kv_attention as kv  # noqa: E402
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

BUILD = dict(
    n_layer=2, d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=512,
    max_new=1664, prompt_buckets=[16, 512], n_slots=3, page_size=8,
    layer_kinds=["gqa"], first_k_dense=1, n_kv_head=2, head_dim=64,
    gqa_gate=False, qk_norm=False, n_routed_experts=8, n_experts_held=4,
    held_start=0, n_experts_per_tok=2, d_expert=24, n_shared_experts=0,
    norm_topk_prob=True, router_bias=False, routed_scaling_factor=1.0,
    rms_eps=1e-5, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none")
ROWS = BUILD["prompt_len"] + BUILD["max_new"]


def lowered():
    return {p: kv.KV_DECODE_ATTEND_LOWERED.labels(path=p).value
            for p in ("in_place", "copy")}


def rows_read():
    return (smetrics.KV_FULL_ROWS_ATTENDED.labels(model="lm").value,
            smetrics.KV_FULL_ROWS_GATHERED.labels(model="lm").value)


@pytest.fixture(scope="module")
def engines():
    """{path: (engine, its probe, what its warm-up lowered)}; the patch
    stays while the module's tests run (a probe traces at its first
    call)."""
    out = {}
    with pytest.MonkeyPatch.context() as patch:
        for path in ("copy", "in_place"):
            if path == "in_place":
                patch.setattr(kv, "_gather_tier",
                              lambda flat, scales, ps, mesh=None: "pages")
            before = lowered()
            engine = families.Family(serve_hybrid, CFG).fresh(seed=11)
            out[path] = (engine, serve_hybrid.LogitProbe(engine),
                         {p: n - before[p] for p, n in lowered().items()})
        yield out


def test_the_engine_learns_the_path_from_the_ops_own_predicate(engines):
    """Every lowering of the decode view counted its two layers under
    the path's label, and the engine — which asked ``attends_in_place``
    of the decode ops' attrs and pool variables — counts as many."""
    for path, (engine, _probe, grew) in engines.items():
        other = "copy" if path == "in_place" else "in_place"
        assert grew[path] and grew[path] % 2 == 0 and not grew[other]
        assert engine._full_layers == 2
        # blocks of 16 pages = 128 rows, both layers
        assert engine._in_place_blocks == (
            {16: 2} if path == "in_place" else {})
        assert engine.cache_len == ROWS > kv.ATTEND_FLOOR_ROWS


@pytest.mark.parametrize("prompt_len,blocks", [
    # 11 rows in a bucket of 16: prompt and generated rows in block 0
    (11, 1),
    # 300 rows span 3 blocks of 128; the generated rows start block 4
    (300, 4),
    # a prompt that fills its bucket: 4 blocks and the generated rows'
    (512, 5)])
def test_a_step_adds_the_rows_it_reads(engines, prompt_len, blocks):
    """Four decode steps behind the prefill's token, two full layers.
    By copy a step adds every row of every slot's table, live or not;
    in place the rows of the running slot's live blocks (whole blocks of
    128 rows: the kernel's DMA units) and nothing of the idle slots'.
    Attended is the live rows either way."""
    prompt = np.random.RandomState(prompt_len).randint(1, BUILD["vocab"],
                                                       prompt_len)
    lives = [prompt_len + 1 + i for i in range(4)]
    for path, (engine, probe, _grew) in engines.items():
        a0, g0 = rows_read()
        serve_hybrid.serve_one(engine, prompt, 5, probe)
        a1, g1 = rows_read()
        assert a1 - a0 == 2 * sum(lives)
        assert g1 - g0 == 2 * 4 * (BUILD["n_slots"] * ROWS
                                   if path == "copy" else blocks * 128)


def test_requests_live_together_read_the_same_logits_both_ways(engines):
    """Three requests of other lengths stepped together (one shorter
    than its bucket, one that fills it, one of a single page), the
    slots released at different steps: same tokens, and the logits each
    token was chosen from agree to the float32 order of sums."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, BUILD["vocab"], n) for n in (300, 512, 5)]
    budgets = [9, 6, 12]
    served = {path: serve_hybrid.serve_together(engine, probe, prompts,
                                                budgets)
              for path, (engine, probe, _grew) in engines.items()}
    for (toks, logits, _), (want_toks, want, _) in zip(served["in_place"],
                                                       served["copy"]):
        np.testing.assert_array_equal(toks, want_toks)
        assert np.isfinite(logits).all()
        np.testing.assert_allclose(logits, want, atol=2e-5)


def test_the_floor_is_two_of_the_kernels_blocks():
    """``ATTEND_FLOOR_ROWS`` and the kernel's block are one decision
    written in two modules: a table of exactly two blocks copies, one
    block more attends in place."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    assert kv.ATTEND_FLOOR_ROWS == 2 * pa._ATTEND_ROWS


@pytest.mark.parametrize("page_size,block_pages", [(16, 64), (16, 16),
                                                   (8, 16)])
def test_attend_rows_read_against_a_brute_count(page_size, block_pages):
    """The host's count of what the kernel reads against a count made
    row by row: every block of the table that holds a live row, whole."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(page_size * block_pages)
    rows, block = 64 * block_pages * page_size // 8, block_pages * page_size
    gen0 = rng.randint(1, rows // 2, 40)
    lens = np.minimum(rng.randint(0, rows // 2, 40), gen0)
    pos = gen0 + rng.randint(-1, rows // 2, 40)     # -1: nothing generated
    lens[:2], pos[:2] = 0, gen0[:2] - 1             # no live row at all
    lens[2], pos[3] = gen0[2], rows - 1             # a full bucket; the end
    j = np.arange(rows)
    live = (j[None] < lens[:, None]) \
        | ((j[None] >= gen0[:, None]) & (j[None] <= pos[:, None]))
    brute = live.reshape(40, -1, block).any(axis=2).sum(axis=1) * block
    got = pa.attend_rows_read(lens, gen0, pos, page_size, block_pages)
    np.testing.assert_array_equal(got, brute)
    assert not got[:2].any() and (got[2:] >= live[2:].sum(axis=1)).all()
