"""The training runner at a tiny size on the CPU: agreement with the
plain reference, a reference that notices a wrong program, and set-up
that dispatches the same work whatever the seed (the defect of the
refused PR 22 benchmark, pinned)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import harness, weights  # noqa: E402
from chipbench.reference import transformer_big_wmt as ref  # noqa: E402


def logged_run(monkeypatch, seed, traffic="resident_feed", chips=1):
    """Run the tiny cell; log every Executor.run as (program, shapes of
    the feeds, iterations) and where in the log the window opened."""
    import paddle_tpu.fluid as fluid
    log, programs, opened = [], {}, []
    real_run = fluid.Executor.run
    real_open = harness.Run.open_window

    def spy(self, program=None, feed=None, **kw):
        key = programs.setdefault(id(program), len(programs))
        log.append((key, tuple(sorted((k, tuple(np.shape(v)))
                                      for k, v in (feed or {}).items())),
                    kw.get("iterations")))
        return real_run(self, program, feed=feed, **kw)

    def open_window(self):
        opened.append(len(log))
        return real_open(self)

    monkeypatch.setattr(fluid.Executor, "run", spy)
    monkeypatch.setattr(harness.Run, "open_window", open_window)
    run, obs = tiny.run_cell(tiny.train_config(),
                             tiny.train_traffic(traffic), seed, 0.3, chips)
    return run, obs, log[:opened[0]]


def test_tiny_train_cell_agrees_with_the_reference(monkeypatch):
    run, obs, setup = logged_run(monkeypatch, 11)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["loss_rel_err"] < 1e-5 and seen["grad_norm_rel_err"] < 1e-4
    assert obs["end_to_end"]["train_tokens_per_s_chip"] > 0
    assert obs["compiles_in_window"] == 0
    assert run.setup_s >= sum(run.phase_s.values()) - 1e-6
    # start-up, the twin's one step, then warm_dispatches chunks
    warm = tiny.train_traffic()["warm_dispatches"]
    assert len(setup) == 2 + warm
    assert [it for _p, _f, it in setup[2:]] == [2] * warm


def test_setup_dispatches_the_same_work_for_two_seeds(monkeypatch):
    _r1, obs1, setup1 = logged_run(monkeypatch, 1)
    _r2, obs2, setup2 = logged_run(monkeypatch, 2 ** 31 + 99)
    assert setup1 == setup2
    # and the seed does reach the weights: another loss on the sample
    assert obs1["notes"]["reference"]["loss"][1] != \
        obs2["notes"]["reference"]["loss"][1]


def test_tiny_dp4_cell_agrees_with_the_reference(monkeypatch):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    _run, obs, _setup = logged_run(monkeypatch, 5, "resident_feed_dp4", 4)
    assert obs["correct"] and obs["chips"] == 4
    assert obs["notes"]["reference"]["grad_norm_rel_err"] < 1e-4


def test_reference_catches_a_program_that_computes_something_else():
    """The comparison must fail when the reference and the program
    disagree: swap two same-shaped weights (Wq and Wk of one layer)."""
    cfg = tiny.train_config()["build"]
    rng = np.random.RandomState(0)
    params = [rng.randn(*shape).astype(np.float32) * 0.1
              for _role, shape in ref.param_shapes(cfg)]
    ids = [rng.randint(1, 96, (2, cfg["max_len"])) for _ in range(3)]
    loss, norms = ref.loss_and_grad_norms(params, *ids, cfg, [3])
    swapped = list(params)
    swapped[3], swapped[4] = swapped[4], swapped[3]
    loss2, norms2 = ref.loss_and_grad_norms(swapped, *ids, cfg, [3])
    assert abs(loss - loss2) / loss > 1e-4 or \
        abs(norms[0] - norms2[0]) / norms[0] > 1e-2


def test_param_shapes_follow_the_configuration():
    cfg = tiny._load("configs", "transformer_big_wmt")
    shapes = ref.param_shapes(cfg["build"])
    assert len(shapes) == 187
    picked = [shapes[i][0] for i in cfg["check"]["grad_params"]]
    assert picked == ["wq", "ffn2_w", "ln_scale"]
    n = sum(int(np.prod(s)) for _r, s in shapes)
    assert 2.7e8 < n < 2.8e8    # 176 M in the layers, 98 M in 3 tables


def test_weights_spec_picks_matrices_and_their_std():
    spec = weights.matrix_spec({"emb": (50257, 1024), "w": (1024, 4096),
                                "b": (4096,), "head": (1024, 50257)}, 1024)
    assert [s[0] for s in spec] == ["emb", "head", "w"]
    std = {n: s for n, _shape, s in spec}
    assert std["emb"] == pytest.approx(1024 ** -0.5)
    assert std["w"] == pytest.approx((2 / 5120) ** 0.5)
