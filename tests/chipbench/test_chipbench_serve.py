"""The serving runner at a tiny size on the CPU: both loops agree with
the plain reference, the closed loop primes by count, and the set-up's
dispatches do not depend on the seed (the defect of the refused PR 22
benchmark, pinned)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench.reference import gpt2_medium_d12 as ref  # noqa: E402


def logged_run(monkeypatch, traffic, seed, seconds=0.4):
    """Run the tiny cell; log every engine dispatch as (program key,
    shapes of its feeds) up to where the window opened."""
    return tiny.logged_run(monkeypatch, tiny.serve_config(),
                           tiny.serve_traffic(traffic), seed, seconds)


admissions = tiny.admissions


def primed(setup, traffic, seed, seconds):
    """(head, first, later, steps) of a closed loop's set-up: warm-up's
    3 buckets and the 2 compared requests come before the clients."""
    return tiny.priming(setup, tiny.serve_config(),
                        tiny.serve_traffic(traffic), seed, seconds, 3 + 2)


@pytest.mark.parametrize("traffic", ["closed_decode", "open_prefill"])
def test_tiny_serve_cell_agrees_with_the_reference(monkeypatch, traffic):
    run, obs, setup = logged_run(monkeypatch, traffic, 21)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["notes"]["reference"]["worst_margin_in_logit_std"] <= 0.05
    assert obs["compiles_in_window"] == 0
    assert obs["end_to_end"]["serve_tokens_per_s"] > 0
    tr = tiny.serve_traffic(traffic)
    # the pool's own gauges were read while the window was open
    assert 0 < obs["kv_pages_held"] <= 1
    assert obs["notes"]["runtime_start_s"] == run.runtime_start_s >= 0
    if traffic == "closed_decode":
        assert obs["kv_pages_held"] > 0.5     # every slot holds a lease
        assert obs["units"]["decode_steps"] > 0
        assert 0 < obs["slot_occupancy"] <= 1
        # primed by count: warm-up's 3 buckets, the 2 compared requests,
        # then exactly one admission per client's first request before
        # the window opens (counted by the scheduler's own admissions:
        # a request after a client's first is its next one)
        head, first, later, steps = primed(setup, traffic, 21, 0.4)
        assert len(admissions(head)) == 3 + 2
        assert len(first) == tr["clients"] >= len(later)
        assert steps >= tr["prime_decode_steps"]
    else:
        assert obs["end_to_end"]["ttft_p95_ms"] >= \
            obs["end_to_end"]["ttft_p50_ms"] > 0
        assert obs["units"]["prefills"] == obs["attempted"]
        assert len(admissions(setup)) == 3 + 2 + tr["prime_requests"]
        assert len(obs["lateness_s"]) == obs["attempted"]


@pytest.mark.parametrize("traffic", ["closed_decode", "open_prefill"])
def test_setup_dispatches_the_same_work_for_two_seeds(monkeypatch, traffic):
    seeds = (3, 2 ** 31 + 5)
    _r1, _o1, setup1 = logged_run(monkeypatch, traffic, seeds[0], 0.2)
    _r2, _o2, setup2 = logged_run(monkeypatch, traffic, seeds[1], 0.2)
    tr = tiny.serve_traffic(traffic)
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    if traffic == "open_prefill":
        # every admission: same program (bucket), same shapes, same
        # order (warm-up's 3, the 2 compared requests, then the priming
        # by count: one thread submits, in the schedule's order)
        n = 3 + 2 + tr["prime_requests"]
        assert admissions(setup1)[:n] == admissions(setup2)[:n]
        assert len(admissions(setup1)) >= n
        assert steps[0] == steps[1]
        return
    # a closed loop's clients are threads, and the scheduler admits them
    # as they come: what is the same for two seeds is counted by its own
    # events — the dispatches up to the first client's admission, one
    # for one; then the clients' first requests, the same programs and
    # shapes as a multiset; and never fewer decode steps than the
    # priming asks for (a decode step lasts under a millisecond here, so
    # the scheduler is some steps past the count when the runner sees
    # it: how many is the host's load, not the seed's)
    (head1, first1, _l1, after1), (head2, first2, _l2, after2) = (
        primed(s, traffic, seed, 0.2)
        for s, seed in zip((setup1, setup2), seeds))
    assert head1 == head2 and len(admissions(head1)) == 3 + 2
    assert len(head1) - 5 >= 1 + 2 * 5
    assert sorted(first1) == sorted(first2) and len(first1) == tr["clients"]
    assert min(after1, after2) >= tr["prime_decode_steps"]
    assert min(steps) >= 1 + 2 * 5 + tr["prime_decode_steps"]


def test_reference_catches_a_wrong_token():
    cfg = tiny.serve_config()["build"]
    rng = np.random.RandomState(0)
    shapes = {"emb": (cfg["vocab"], 32), "head_w": (32, cfg["vocab"]),
              "attn": (32, 32), "ffn1_w": (32, 64), "ffn1_b": (64,),
              "ffn2_w": (64, 32), "ffn2_b": (32,)}
    params = {}
    for name in ref.param_names(cfg):
        key = next(k for k in shapes if k in name) \
            if any(k in name for k in shapes) else None
        if key:
            params[name] = rng.randn(*shapes[key]).astype(np.float32) * 0.2
        else:
            params[name] = (np.ones if "scale" in name else np.zeros)(
                32, np.float32)
    prompt = rng.randint(1, cfg["vocab"], 5)
    # greedy continuation by the reference itself: margin 0
    out = []
    for _ in range(4):
        ids = np.concatenate([prompt, out]).astype(np.int32)[None]
        logits = ref.next_token_logits(
            params, ids, np.asarray([[ids.shape[1] - 1]]), cfg)
        out.append(int(np.argmax(np.asarray(logits)[0, 0])))
    assert ref.worst_margin(params, [prompt], [np.asarray(out)], cfg) == 0.0
    wrong = list(out)
    wrong[2] = (wrong[2] + 1) % cfg["vocab"]
    assert ref.worst_margin(params, [prompt], [np.asarray(wrong)], cfg) > 0.05
