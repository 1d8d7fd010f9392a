"""Speculative decoding tests (ISSUE 19, docs/serving.md "Speculative
decoding"): the draft-verify slot engine must be LOSSLESS — greedy
output bit-identical to the non-speculative slot scheduler and the
sequential full-forward oracle under
``forbid_compiles``, seeded sampling replays deterministically, EOS
truncates mid-window commits — plus the acceptance-economy metrics
(proposed/accepted counters, the tokens-per-step histogram) asserted
against a CANNED accept/reject schedule through the scrape endpoint,
the n-gram and small-draft-model proposer arms, and the verify view's
build-time geometry validation."""

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving import engine as seng
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.models import transformer as T


_LM_CFG = dict(prompt_len=8, max_new=8, vocab=32, d_model=16,
               d_inner=32, n_head=2, n_layer=2)

_CACHE = {}


def _spec_lm(spec_k=3):
    """One warmed draft-verify engine per spec_k, shared by the module
    (warmup costs several jit compiles on CPU). Tests that swap
    ``m.drafter`` must restore it — the fixture resets state, not the
    proposer."""
    key = f"spec_paged_{spec_k}"
    m = _CACHE.get(key)
    if m is None:
        m = seng.make_slot_model(
            "lm_" + key,
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=T.slot_modes(spec=True),
                n_slots=4, spec_k=spec_k, page_size=4))
        m.warmup()
        _CACHE[key] = m
    m.reset()
    m.drafter = seng.NgramDrafter()
    return m


def _base_lm():
    key = "base_paged"
    m = _CACHE.get(key)
    if m is None:
        m = seng.make_slot_model(
            "lm_" + key,
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=T.slot_modes(), n_slots=4, page_size=4))
        m.warmup()
        _CACHE[key] = m
    m.reset()
    return m


def _oracle_lm():
    gm = _CACHE.get("oracle")
    if gm is None:
        gm = serving.GenerativeModel(
            "lm_spec_oracle", T.build_decoder_lm_programs(**_LM_CFG),
            serving.BucketPolicy((2, 4)))
        _CACHE["oracle"] = gm
    return gm


class _CannedDrafter:
    """Scripted proposer: knows the TRUE token stream (prompt + the
    reference continuation) and proposes its next-k continuation,
    corrupting every position >= ``sched[call]`` — so the engine's
    accept/reject counts per dispatch are known in advance. hist stays
    a prefix of the target under ANY schedule because rejected drafts
    are replaced by the target model's own (true) samples."""

    def __init__(self, target, vocab, sched=None):
        self.target = [int(t) for t in target]
        self.vocab = int(vocab)
        self.sched = sched
        self.calls = 0

    def propose(self, tokens, k):
        n = len(tokens)
        assert self.target[:n] == [int(t) for t in tokens], \
            "engine committed a token off the reference stream"
        d = self.target[n:n + k]
        keep = len(d) if self.sched is None else self.sched[self.calls]
        self.calls += 1
        return [t if i < keep else (t + 1) % self.vocab
                for i, t in enumerate(d)]


# ---------------------------------------------------------------------------
# losslessness: greedy bit-parity, zero recompiles
# ---------------------------------------------------------------------------

def test_spec_greedy_bit_identical_zero_recompiles():
    """Acceptance criterion: greedy speculative output == the
    non-speculative slot scheduler == the sequential full-forward
    oracle, token for token, with the WHOLE speculative generation
    under forbid_compiles (one verify executable serves every
    draft-length mix via the win_len feed)."""
    m = _spec_lm()
    mb = _base_lm()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 32, (int(n),)) for n in (3, 4, 7, 8, 5, 2)]
    gm = _oracle_lm()                    # chunk: oracle buckets top at 4
    want = (gm.full_forward_generate(prompts[:3], max_new=6)
            + gm.full_forward_generate(prompts[3:], max_new=6))
    base = mb.generate(prompts, max_new=6)
    with smetrics.forbid_compiles():
        got = m.generate(prompts, max_new=6)
    for i, (a, b, c) in enumerate(zip(want, base, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"oracle/base {i}")
        np.testing.assert_array_equal(b, c, err_msg=f"base/spec {i}")


def test_spec_commits_multiple_tokens_per_dispatch():
    """The perf witness at engine level: with a perfect proposer a
    budget-8 request finishes in ceil((8-1)/(K+1)) = 2 verify
    dispatches, not 7 sequential ones."""
    m = _spec_lm()
    prompt = [7, 3, 11]
    ref = _base_lm().generate([prompt], max_new=8)[0]
    m.reset()
    m.drafter = _CannedDrafter(list(prompt) + list(ref), _LM_CFG["vocab"])
    d0 = smetrics.DECODE_STEPS.labels(model=m.name).value
    got = m.generate([prompt], max_new=8)[0]
    np.testing.assert_array_equal(got, ref)
    disp = smetrics.DECODE_STEPS.labels(model=m.name).value - d0
    assert disp == 2, disp               # 4 + 3 committed after admit


# ---------------------------------------------------------------------------
# sampling: seeded replay determinism (lossless at temperature > 0)
# ---------------------------------------------------------------------------

def test_spec_sampled_matches_nonspec_and_replays():
    """temperature > 0: acceptance compares drafts against the EXACT
    counter-based sample of each (seed, step), so the speculative
    stream equals the sequential one draw for draw — and replaying the
    same seeds (fresh engine state = restart) reproduces it."""
    m = _spec_lm()
    mb = _base_lm()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 32, (int(n),)) for n in (3, 6, 8)]
    seeds = [101, 202, 303]
    kw = dict(max_new=7, temperature=0.8, top_k=0, seeds=seeds)
    want = mb.generate(prompts, **kw)
    with smetrics.forbid_compiles():
        got = m.generate(prompts, **kw)
        again = m.generate(prompts, **kw)
    for a, b, c in zip(want, got, again):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, c)


def test_spec_sampled_survives_restart():
    """Cross-engine determinism: a SECOND engine built from scratch
    (the restart scenario — fresh program build, init, warmup; here
    even a different verify window) replays the identical seeded stream,
    because the Gumbel noise is a pure function of (seed, step,
    vocab index) — no mutable RNG stream survives in either process."""
    m = _spec_lm()
    m2 = _spec_lm(spec_k=2)
    prompts = [[9, 4, 2, 17], [21, 5]]
    kw = dict(max_new=6, temperature=1.1, top_k=4, seeds=[7, 8])
    a = m.generate(prompts, **kw)
    b = m2.generate(prompts, **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# EOS mid-window + drafter arms
# ---------------------------------------------------------------------------

def test_spec_eos_truncates_window_commits():
    """An EOS landing INSIDE an accepted window must end the request
    there: no tokens after EOS are emitted even when later window
    positions were accepted."""
    mb = _base_lm()
    prompt = [5, 1, 19]
    ref = mb.generate([prompt], max_new=8)[0]
    eos = int(ref[2])                    # a token the stream DOES emit
    want = mb.generate([prompt], max_new=8, eos_id=eos)[0]
    assert len(want) <= 3 and int(want[-1]) == eos
    m = _spec_lm()
    m.drafter = _CannedDrafter(list(prompt) + list(ref), _LM_CFG["vocab"])
    got = m.generate([prompt], max_new=8, eos_id=eos)[0]
    np.testing.assert_array_equal(got, want)


def test_ngram_drafter_prompt_lookup():
    d = seng.NgramDrafter(max_ngram=3)
    # suffix [4, 5] recurs — propose what followed it last time
    assert d.propose([1, 4, 5, 6, 7, 2, 4, 5], 2) == [6, 7]
    assert d.propose([1, 2, 3], 0) == []
    assert d.propose([1], 4) == []       # nothing to match on
    # no recurrence anywhere -> no proposal (engine falls back to a
    # single-token window, i.e. plain decode)
    assert d.propose([1, 2, 3, 4], 3) == []


def test_model_drafter_arm_stays_lossless():
    """The optional small-draft-model arm: ANY proposer is lossless
    under exact-match acceptance — here the draft model is the target
    model's own full view, so acceptance is near-perfect and the
    output still bit-matches the sequential reference."""
    m = _spec_lm()
    ref = _base_lm().generate([[3, 14, 15]], max_new=6)[0]
    m.reset()
    m.drafter = seng.ModelDrafter(_oracle_lm())
    with smetrics.forbid_compiles():
        got = m.generate([[3, 14, 15]], max_new=6)[0]
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# metrics: canned accept/reject schedule through the scrape endpoint
# ---------------------------------------------------------------------------

def test_spec_metrics_canned_schedule_on_scrape_endpoint():
    """Satellite: the proposed/accepted counters and the
    tokens-per-step histogram, asserted against a KNOWN schedule.
    budget=8 leaves 7 post-admit tokens. Dispatch 1 drafts
    kq = min(K, remaining-1) = 3, all accepted -> commits 4;
    dispatch 2 drafts kq = 2 with the schedule accepting 1 ->
    commits 2; dispatch 3 has remaining = 1, so it drafts NOTHING
    (single-token window = plain decode) and commits the last token.
    So proposed = 3+2 = 5, accepted = 3+1 = 4, and the histogram
    sees observations {4, 2, 1} summing to 7. All three families
    must render through the scrape endpoint."""
    import urllib.request
    from paddle_tpu.observability.exporters import MetricsServer
    m = _spec_lm()
    prompt = [2, 29, 13]
    ref = _base_lm().generate([prompt], max_new=8)[0]
    m.reset()
    m.drafter = _CannedDrafter(list(prompt) + list(ref),
                               _LM_CFG["vocab"], sched=[3, 1])
    prop0 = smetrics.SPEC_PROPOSED.labels(model=m.name).value
    acc0 = smetrics.SPEC_ACCEPTED.labels(model=m.name).value
    hist = smetrics.TOKENS_PER_STEP.labels(model=m.name)
    cnt0, sum0 = hist.count, hist.snapshot()[1]
    got = m.generate([prompt], max_new=8)[0]
    np.testing.assert_array_equal(got, ref)
    assert m.drafter.calls == 2          # the kq=0 dispatch never drafts
    prop = smetrics.SPEC_PROPOSED.labels(model=m.name).value - prop0
    acc = smetrics.SPEC_ACCEPTED.labels(model=m.name).value - acc0
    assert (prop, acc) == (5, 4)
    assert hist.count - cnt0 == 3
    # sum of committed counts = the 7 post-admit tokens; mean
    # acceptance length = 7/3
    assert hist.snapshot()[1] - sum0 == pytest.approx(7.0)
    msrv = MetricsServer(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://{msrv.endpoint}/metrics",
            timeout=10).read().decode()
    finally:
        msrv.stop()
    name = m.name
    cur_prop = smetrics.SPEC_PROPOSED.labels(model=name).value
    cur_acc = smetrics.SPEC_ACCEPTED.labels(model=name).value
    assert (f'paddle_serving_spec_proposed_tokens_total'
            f'{{model="{name}"}} {cur_prop:g}') in body
    assert (f'paddle_serving_spec_accepted_tokens_total'
            f'{{model="{name}"}} {cur_acc:g}') in body
    assert (f'paddle_serving_tokens_per_step_bucket'
            f'{{model="{name}"') in body
    assert f'paddle_serving_tokens_per_step_count{{model="{name}"}}' \
        in body


# ---------------------------------------------------------------------------
# build-time geometry validation
# ---------------------------------------------------------------------------

def test_verify_view_geometry_validation():
    with pytest.raises(ValueError):      # spec_k must be >= 1
        T.decoder_lm("decode_verify_paged", **_LM_CFG, n_slots=2,
                     spec_k=-1)
    with pytest.raises(ValueError):      # window must fit the budget
        T.decoder_lm("decode_verify_paged", **_LM_CFG, n_slots=2,
                     spec_k=9)
    with pytest.raises(ValueError):      # verify views need a pool
        T.decoder_lm("decode_verify_paged", **_LM_CFG)


def test_slot_modes_spec_helper():
    # the positional "paged" is what the benchmark's runner passes
    assert T.slot_modes(spec=True) == T.slot_modes("paged", spec=True) == (
        "prefill_paged", "decode_paged", "decode_verify_paged")
    assert T.slot_modes() == ("prefill_paged", "decode_paged")
