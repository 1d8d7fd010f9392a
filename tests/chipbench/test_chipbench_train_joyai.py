"""The language-model training runner (``runners/train_lm.py``) at a
tiny size on the CPU, in the pattern of ``test_chipbench_train.py``:
agreement with the plain reference (loss, the named gradient norms, the
routers' bias update), a reference that notices the faults the
configuration's check names, set-up that dispatches the same work
whatever the seed, and the operation count against a hand count."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import flops_mla_train, harness  # noqa: E402
from chipbench.generators import lm_token_batches  # noqa: E402
from chipbench.reference import joyai_llm_flash_ep16_d6 as ref  # noqa: E402

CELL = "train_joyai_seq8k_1chip"


def joyai_config():
    cfg = tiny._load("configs", "joyai_llm_flash_ep16_d6")
    cfg["amp"] = False          # XLA:CPU has no bf16 x bf16 -> f32 dot
    cfg["build"].update(
        seq_len=64, n_layer=2, d_model=64, d_inner=128, n_head=4, vocab=96,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
        n_experts_held=4, n_experts_per_tok=4, d_expert=32)
    cfg["check"]["grad_params"] = ["l1_mla.wuq", "l1_moe.w_down",
                                   "l1_moe.router", "mtp0_eh_proj", "emb"]
    return cfg


def joyai_traffic():
    tr = tiny._load("traffic", "resident_feed_seq8k")
    tr.update(seq_len=64, sequences_per_step=2,
              check={"sequences": 2, "seq_len": 64})
    return tr


def logged_run(monkeypatch, seed):
    """Run the tiny cell; log every Executor.run as (program, shapes of
    the feeds, iterations) up to where the window opened."""
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
    run, obs = tiny.run_cell(joyai_config(), joyai_traffic(), seed, 0.3)
    return run, obs, log[:opened[0]]


def test_tiny_joyai_cell_agrees_with_the_reference(monkeypatch):
    run, obs, setup = logged_run(monkeypatch, 11)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 0
    seen = obs["notes"]["reference"]
    assert seen["loss_rel_err"] < 1e-5 and seen["grad_norm_rel_err"] < 1e-4
    assert seen["grad_sample_rel_err"] < 1e-4
    assert seen["bias_update_exact"] and seen["bias_sign_share"] == 0.0
    # the matrices moved as Adam moves them, on the step's own gradient
    # (a float32 entry near 1 holds a change of lr to 1e-4 of it) and,
    # float32 both sides so no sign flips, on the reference's
    assert seen["update_rel_err"] < 5e-4
    assert max(seen["update_vs_reference_rel_errs"].values()) < 1e-2
    assert obs["end_to_end"]["train_tokens_per_s_chip"] > 0
    assert obs["compiles_in_window"] == 0
    first, last = obs["notes"]["loss_first_last"]
    assert np.isfinite([first, last]).all() and first != last
    # start-up, the check's one step, then warm_dispatches chunks
    warm = joyai_traffic()["warm_dispatches"]
    assert len(setup) == 2 + warm
    assert [it for _p, _f, it in setup[2:]] == [2] * warm
    # the window's picks: every step gives every expert layer B * T * K
    counts = obs["moe_counts"]
    assert counts.shape == (2, 2, 16)          # l1 and the MTP layer
    assert (counts[:, 0].sum(axis=1) == obs["moe_steps"] * 2 * 64 * 4).all()


def test_setup_dispatches_the_same_work_for_two_seeds(monkeypatch):
    _r1, obs1, setup1 = logged_run(monkeypatch, 1)
    _r2, obs2, setup2 = logged_run(monkeypatch, 2 ** 31 + 99)
    assert setup1 == setup2
    # and the seed does reach the weights: another loss on the sample
    assert obs1["notes"]["reference"]["loss"][1] != \
        obs2["notes"]["reference"]["loss"][1]


@pytest.mark.parametrize("lr_times, reads", [(0.0, 1.0), (1.1, 0.1)])
def test_the_check_sees_an_optimizer_that_moves_the_weights_wrongly(
        lr_times, reads):
    """The state left unchanged (the learning rate in the scope zeroed:
    Adam's ops run and move nothing) reads 1 on ``update_rel_err``, a
    learning rate 10 % high reads 0.1; every other limit passes, and
    the step is not correct."""
    import time
    from chipbench.runners import train_lm
    cfg = joyai_config()
    cell = {"name": "tiny", "chips": 1, "config": "-", "traffic": "-"}
    run = harness.Run(tiny.BENCH, cell, cfg, joyai_traffic(), 5, 0.3, False,
                      time.time(), allow_cpu=True)
    exe, scope, main, loss, _totals, data, _sets = train_lm.start(run)
    lr = next(op.desc.input("LearningRate")[0]
              for op in main.global_block().ops if op.type == "adam")
    scope.set_var(lr, np.asarray(scope.find_var(lr)) * np.float32(lr_times))
    ok, seen = train_lm.compare_with_reference(run, exe, scope, main, loss,
                                               data["check"])
    assert not ok
    assert seen["update_rel_err"] == pytest.approx(reads, rel=1e-3)
    tol = seen["tolerance"]
    assert seen["loss_rel_err"] <= tol["loss_rel"]
    assert seen["grad_sample_rel_err"] <= tol["grad_sample_rel"]
    assert seen["bias_update_exact"]


@pytest.mark.parametrize("fault", [{"mtp_shift": 1}, {"rotate_key": False},
                                   {"low_precision": True}])
def test_reference_catches_a_step_that_computes_something_else(fault):
    """The faults the configuration's check names, built into the
    reference: each moves the loss or a gradient norm past the float32
    limits."""
    cfg = joyai_config()
    build, tol = cfg["build"], cfg["check"]["tolerance"]["fp32"]
    rng = np.random.RandomState(0)
    params = {r: (np.ones(s) if len(s) == 1 else
                  rng.randn(*s) * (1.0 if r == "emb" else 0.1)
                  ).astype(np.float32) for r, s in ref.param_shapes(build)}
    feeds = lm_token_batches.feeds_of(rng.randint(0, 96, (2, 66)))
    ids = [feeds[n][..., 0] for n in ("ids", "lbl_ids", "lbl2_ids")]
    which = cfg["check"]["grad_params"]
    loss, norms, _, samples = ref.loss_and_grad_norms(params, *ids, build,
                                                      which)
    loss2, norms2, _, samples2 = ref.loss_and_grad_norms(
        params, *ids, build, which, **fault)
    # entry by entry every fault is plain, whatever the norms say
    assert max(np.linalg.norm(a - b) / np.linalg.norm(a)
               for a, b in zip(samples, samples2)) > 50 * tol[
        "grad_sample_rel"]
    assert abs(loss - loss2) / loss > tol["loss_rel"] or max(
        abs(a - b) / a for a, b in zip(norms, norms2)) > tol["grad_norm_rel"]


def test_param_shapes_follow_the_configuration():
    cfg = tiny._load("configs", "joyai_llm_flash_ep16_d6")
    shapes = dict(ref.param_shapes(cfg["build"]))
    assert all(r in shapes for r in cfg["check"]["grad_params"])
    assert shapes["l1_mla.wuq"] == (1536, 32 * 192)
    assert shapes["l4_moe.w_down"] == (16, 768, 2048)
    assert shapes["mtp0_eh_proj"] == (4096, 2048)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert 6.80e8 < n < 6.81e8                  # ISSUE 47: 680.4 M


def test_flops_count_against_a_hand_count():
    """ISSUE 47's arithmetic: an expert layer 67.9 M FLOPs a token in
    matrices and 83.9 M in attention at 4 096 keys on average, the dense
    layer's matrices 140.8 M, 27.8 TFLOP a step, attention 44 % of it."""
    build = tiny._load("configs", "joyai_llm_flash_ep16_d6")["build"]
    assert 2 * flops_mla_train.layer_matrix_macs(build, False) == \
        pytest.approx(67.9e6, rel=2e-3)
    assert 2 * flops_mla_train.layer_matrix_macs(build, True) == \
        pytest.approx(140.8e6, rel=2e-3)
    attn = flops_mla_train.mla_attention_fwd_flops(8192, 1, 32, 192, 128)
    assert attn / 8192 == pytest.approx(4096 * 32 * 320 * 2)
    step = flops_mla_train.lm_train_flops_per_step(build, 8192, 1)
    assert step == pytest.approx(27.8e12, rel=3e-3)
    share = flops_mla_train.mla_attention_train_flops(build, 8192, 1) / step
    assert share == pytest.approx(0.44, abs=0.01)


def test_the_cell_and_its_metrics_are_declared():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and config["runner"] == "train_lm"
    assert traffic["generator"] == "lm_token_batches"
    names = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert {"attn_ms_per_step.train", "experts_ms_per_step.train",
            "mtp_ms_per_step.train", "moe_load_max_over_mean.train",
            "flash_mla_roofline.train", "train_mfu_pct",
            "peak_hbm_gb.train"} <= names
    for name in names:       # every reader's file and module exist
        spec = harness.load_json("layer_metrics", name + ".json")
        __import__("chipbench.layer_metrics." + spec["reader"])
    # the published config.json, verbatim at the top level
    for key, value in config["published"]["config"].items():
        assert config[key] == value, key


# ------------------------------------- the reader on a hand-laid step

MS = 1e6
STEP_MODULE = "jit_block3_s5b8b_x2"
# (scope, instruction, what the event says, ms) of one execution of 2 steps
LAID = [
    ("mla_full/attend", "attend.1",
     "custom-call bf16[32,8192,128] tpu_custom_call", 8.0),
    ("mla_full/project", "fusion.2", "fusion f32[8192,6144]", 3.0),
    ("expert_ffn_held/up", "ragged-dot-none.3",
     "custom-call f32[5120,768] tpu_custom_call", 2.0),
    ("mtp/mla_full/attend", "attend.4",
     "custom-call bf16[32,8192,128] tpu_custom_call", 4.0),
    ("mtp/dense", "fusion.5", "fusion f32[8192,16160]", 1.5),
    ("grad/mtp/expert_ffn_held/down", "fusion.6", "fusion f32[5120,2048]", 1.0),
    ("grad/mla_full/attend", "attend.7",
     "custom-call bf16[32,8192,192] tpu_custom_call", 20.0),
    ("grad/mla_full/project", "fusion.8", "fusion f32[1536,6144]", 5.0),
    ("adam", "fusion.9", "fusion f32[16,2048,768]", 6.0),
]


def laid_observations(monkeypatch, executions=3, with_map=True):
    from chipbench import trace_reduce as tr
    from chipbench.layer_metrics import scope_ms
    events, modules, at = [], [], 1.0
    for _ in range(executions):
        start = at
        for _scope, name, what, ms in LAID:
            events.append([f"{name} {what} ", at * MS, ms * MS])
            at += ms
        modules.append([f"{STEP_MODULE}(7)", start * MS, (at - start) * MS])
        at += 0.5
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": events},
        {"name": tr.MODULES_LINE, "events": modules}]}]}
    table = {STEP_MODULE: {name: scope for scope, name, _w, _ms in LAID}}
    monkeypatch.setattr(scope_ms, "program_scopes", lambda: (
        (table, {"seconds": 0.1}) if with_map else (None, None)))
    cfg = tiny._load("configs", "joyai_llm_flash_ep16_d6")
    return {"reduced": tr.reduce_window(trace, 0.0, (at + 1.0) * MS, []),
            "units": {"steps": 2 * executions}, "chips": 1,
            "config": {"name": "not-a-cell", "build": cfg["build"]},
            "traffic": tiny._load("traffic", "resident_feed_seq8k"),
            "peaks": harness.peaks_for("TPU v5 lite")}


def test_lm_train_reads_scopes_kernels_and_the_roofline(monkeypatch):
    from chipbench.layer_metrics import lm_train
    obs = laid_observations(monkeypatch)
    ms = lambda *s: lm_train.read(obs, "scope_ms", scopes=s)     # noqa: E731
    # a step is half an execution; forward and grad/ alike; the MTP
    # module's attention and experts count under those scopes too
    assert ms("mla_full") == pytest.approx((8 + 3 + 4 + 20 + 5) / 2)
    assert ms("expert_ffn_held") == pytest.approx((2 + 1) / 2)
    assert ms("mtp") == pytest.approx((4 + 1.5 + 1) / 2)
    # the kernels under mla_full alone (the experts' grouped products
    # are custom calls too, under another scope)
    kernels = (8 + 4 + 20) / 2
    assert lm_train.read(obs, "kernel_ms") == pytest.approx(kernels)
    build = obs["config"]["build"]
    want = 100.0 * flops_mla_train.mla_attention_train_flops(
        build, 8192, 1) / 197e12 / (kernels / 1e3)
    assert lm_train.read(obs, "roofline") == pytest.approx(want, rel=1e-6)
    # the declared files go through the same reader
    spec = harness.load_json("layer_metrics", "attn_ms_per_step.train.json")
    assert spec["reader"] == "lm_train"
    assert lm_train.read(obs, **spec["args"]) == ms("mla_full")


def test_lm_train_reads_nothing_from_a_program_without_scopes(monkeypatch):
    from chipbench.layer_metrics import lm_train
    obs = laid_observations(monkeypatch, with_map=False)
    assert lm_train.read(obs, "scope_ms", scopes=["mla_full"]) is None
    assert lm_train.read(obs, "roofline") is None
    obs["units"] = {}
    assert lm_train.read(obs, "roofline") is None
