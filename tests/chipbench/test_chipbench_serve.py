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

from chipbench import harness  # noqa: E402
from chipbench.reference import gpt2_medium_d12 as ref  # noqa: E402


def logged_run(monkeypatch, traffic, seed, seconds=0.4):
    """Run the tiny cell; log every engine dispatch as (program key,
    shapes of its feeds) and where in the log the window opened."""
    from paddle_tpu.serving import engine as eng
    log, opened = [], []
    real_run = eng.GenerativeModel._run
    real_open = harness.Run.open_window

    def spy(self, cb, aot_key, feeds):
        log.append((aot_key, tuple(sorted(
            (k, tuple(np.shape(v))) for k, v in feeds.items()))))
        return real_run(self, cb, aot_key, feeds)

    def open_window(self):
        opened.append(len(log))
        return real_open(self)

    for cls in (eng.GenerativeModel, eng.SlotGenerativeModel):
        monkeypatch.setattr(cls, "_run", spy)
    monkeypatch.setattr(harness.Run, "open_window", open_window)
    run, obs = tiny.run_cell(tiny.serve_config(),
                             tiny.serve_traffic(traffic), seed, seconds)
    return run, obs, log[:opened[0]]


def admissions(setup_log):
    return [entry for entry in setup_log if entry[0][0].startswith("prefill")]


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
        # then exactly one admission per client before the window opens
        assert len(admissions(setup)) == 3 + 2 + tr["clients"]
    else:
        assert obs["end_to_end"]["ttft_p95_ms"] >= \
            obs["end_to_end"]["ttft_p50_ms"] > 0
        assert obs["units"]["prefills"] == obs["attempted"]
        assert len(admissions(setup)) == 3 + 2 + tr["prime_requests"]
        assert len(obs["lateness_s"]) == obs["attempted"]


@pytest.mark.parametrize("traffic", ["closed_decode", "open_prefill"])
def test_setup_dispatches_the_same_work_for_two_seeds(monkeypatch, traffic):
    _r1, _o1, setup1 = logged_run(monkeypatch, traffic, 3, 0.2)
    _r2, _o2, setup2 = logged_run(monkeypatch, traffic, 2 ** 31 + 5, 0.2)
    # every admission: same program (bucket), same shapes, same order
    # (warm-up's 3, the 2 compared requests, then the priming by count)
    tr = tiny.serve_traffic(traffic)
    n = 3 + 2 + tr.get("clients", tr.get("prime_requests"))
    assert admissions(setup1)[:n] == admissions(setup2)[:n]
    assert len(admissions(setup1)) >= n
    steps = [len(s) - len(admissions(s)) for s in (setup1, setup2)]
    if traffic == "open_prefill":
        assert steps[0] == steps[1]
    else:
        # at this size a decode step lasts under a millisecond, so the
        # scheduler may be a step or two past the count when the runner
        # sees it; never short of it
        floor = 1 + 2 * 5 + tr["prime_decode_steps"]
        assert min(steps) >= floor and abs(steps[0] - steps[1]) <= 6


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
