"""Pass pipeline + autotune cache (paddle_tpu/passes): numeric parity
of every registered TPU pass over runnable programs, vjp-merge
correctness, the committed-table determinism contract (zero
measurements at build time), and the BuildStrategy/bench wiring.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import passes
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.passes import autotune


def _run_steps(main, startup, loss, feeds, n=3, scope=None):
    scope = scope or fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return [float(exe.run(main, feed=f, fetch_list=[loss],
                          scope=scope)[0])
            for f in (feeds * n)[:n]]


def _ops(main):
    return [op.type for op in main.desc.global_block.ops]


# ------------------------------------------------------------- pipelines

def _conv_chain_prog(seed=3):
    """conv+bias+relu, a transpose pair, a reshape pair, fc, SGD."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 8, 8], dtype="float32")
        c = layers.conv2d(img, 4, 3, padding=1, act=None)
        r = layers.relu(c)
        t1 = layers.transpose(r, perm=[0, 2, 3, 1])
        t2 = layers.transpose(t1, perm=[0, 3, 1, 2])
        rs1 = layers.reshape(t2, shape=[0, 4, 64])
        rs2 = layers.reshape(rs1, shape=[-1, 256])
        y = layers.fc(rs2, 8, bias_attr=False)
        loss = layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_train_pipeline_parity_and_structure():
    rng = np.random.RandomState(0)
    feeds = [{"img": rng.rand(2, 3, 8, 8).astype(np.float32)}]

    m1, s1, l1 = _conv_chain_prog()
    base = _run_steps(m1, s1, l1, feeds)

    m2, s2, l2 = _conv_chain_prog()
    applied = passes.apply_pipeline(m2, feed_names=["img"],
                                    fetch_names=[l2.name])
    assert applied == list(passes.TRAIN_PIPELINE)
    ops = _ops(m2)
    assert "conv2d_fusion" in ops
    assert ops.count("transpose") == 1      # pair composed into one
    assert ops.count("reshape") == 1
    fused = _run_steps(m2, s2, l2, feeds)
    np.testing.assert_allclose(base, fused, rtol=1e-6, atol=1e-7)


def test_conv_residual_fuse_train_parity():
    def build(seed=9):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = seed
        startup.random_seed = seed
        with fluid.program_guard(main, startup):
            img = layers.data(name="img", shape=[3, 8, 8],
                              dtype="float32")
            a = layers.conv2d(img, 4, 3, padding=1, act=None)
            b = layers.conv2d(img, 4, 3, padding=1, bias_attr=False)
            r = layers.relu(layers.elementwise_add(a, b))
            loss = layers.mean(r)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(1)
    feeds = [{"img": rng.rand(2, 3, 8, 8).astype(np.float32)}]
    m1, s1, l1 = build()
    base = _run_steps(m1, s1, l1, feeds)

    m2, s2, l2 = build()
    passes.apply_pipeline(m2, feed_names=["img"], fetch_names=[l2.name])
    fused_op = next(o for o in m2.desc.global_block.ops
                    if o.type == "conv2d_fusion")
    assert fused_op.inputs.get("Bias") and \
        fused_op.inputs.get("ResidualData")
    assert fused_op.attrs["activation"] == "relu"
    # ONE merged __vjp__ replaced the conv/bias-add/resid-add/relu
    # backward quartet (4 -> 1)
    n_vjp1 = _ops(m1).count("__vjp__")
    n_vjp2 = _ops(m2).count("__vjp__")
    assert n_vjp2 == n_vjp1 - 3
    fused = _run_steps(m2, s2, l2, feeds)
    np.testing.assert_allclose(base, fused, rtol=1e-5, atol=1e-6)


def test_conv_bn_fold_infer_parity():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    startup.random_seed = 5
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 8, 8], dtype="float32")
        c = layers.conv2d(img, 4, 3, padding=1, act=None)
        bn = layers.batch_norm(c, is_test=True)
        out = layers.mean(layers.relu(bn))
    main._is_test = True
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    bn_op = next(o for o in main.desc.global_block.ops
                 if o.type == "batch_norm")
    rng = np.random.RandomState(1)
    scope.set_var(bn_op.inputs["Mean"][0],
                  rng.rand(4).astype(np.float32) * 0.3)
    scope.set_var(bn_op.inputs["Variance"][0],
                  rng.rand(4).astype(np.float32) + 0.5)
    scope.set_var(bn_op.inputs["Scale"][0],
                  rng.rand(4).astype(np.float32) + 0.5)
    scope.set_var(bn_op.inputs["Bias"][0],
                  rng.rand(4).astype(np.float32) - 0.5)
    feed = {"img": rng.rand(2, 3, 8, 8).astype(np.float32)}
    (before,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)

    applied = passes.apply_pipeline(main, scope=scope, is_test=True,
                                    feed_names=["img"],
                                    fetch_names=[out.name])
    assert "conv_bn_fold_pass" in applied
    ops = _ops(main)
    # the whole conv+bias+bn+relu region is ONE op now
    assert "batch_norm" not in ops and "relu" not in ops
    assert "conv2d_fusion" in ops
    (after,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-5)


def test_conv_bn_fold_skips_residual_head():
    """BN over conv+residual scales the residual term too — a
    filter/bias fold cannot represent that, so the fold must keep the
    composed form (and the numerics must stay identical)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 23
    startup.random_seed = 23
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 8, 8], dtype="float32")
        a = layers.conv2d(img, 4, 3, padding=1, act=None)
        b = layers.conv2d(img, 4, 3, padding=1, bias_attr=False)
        s = layers.elementwise_add(a, b)
        bn = layers.batch_norm(s, is_test=True)
        out = layers.mean(bn)
    main._is_test = True
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    bn_op = next(o for o in main.desc.global_block.ops
                 if o.type == "batch_norm")
    rng = np.random.RandomState(4)
    scope.set_var(bn_op.inputs["Mean"][0],
                  rng.rand(4).astype(np.float32) * 0.3)
    scope.set_var(bn_op.inputs["Variance"][0],
                  rng.rand(4).astype(np.float32) + 0.5)
    scope.set_var(bn_op.inputs["Scale"][0],
                  rng.rand(4).astype(np.float32) + 0.5)  # gamma != 1
    feed = {"img": rng.rand(2, 3, 8, 8).astype(np.float32)}
    (before,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    passes.apply_pipeline(main, scope=scope, is_test=True,
                          feed_names=["img"], fetch_names=[out.name])
    ops = _ops(main)
    # fusion created the residual conv2d_fusion, but BN stays composed
    assert "conv2d_fusion" in ops and "batch_norm" in ops
    (after,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-5)


def test_layout_pass_skips_multiuse_intermediate():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[2, 3, 4], dtype="float32")
        t1 = layers.transpose(x, perm=[0, 2, 3, 1])
        layers.transpose(t1, perm=[0, 3, 1, 2])
        layers.mean(t1)                       # second consumer of t1
    from paddle_tpu.fluid.ir_pass import Graph, get_pass
    passes.register_all()
    get_pass("layout_assignment_pass")(Graph(main.desc.global_block))
    assert _ops(main).count("transpose") == 2   # untouched


def test_layout_pass_nhwc_after_passes_parity():
    """The pass pipeline then contrib.layout NHWC over the fused
    program — the bench ordering — stays numerically identical (the
    snapshot mirror must find the pass-created fused vjps)."""
    rng = np.random.RandomState(2)
    feeds = [{"img": rng.rand(2, 3, 8, 8).astype(np.float32)}]
    m1, s1, l1 = _conv_chain_prog(seed=7)
    base = _run_steps(m1, s1, l1, feeds)

    m2, s2, l2 = _conv_chain_prog(seed=7)
    passes.apply_pipeline(m2, feed_names=["img"], fetch_names=[l2.name])
    from paddle_tpu.contrib.layout import rewrite_program_nhwc
    rewrite_program_nhwc(m2)
    fused = _run_steps(m2, s2, l2, feeds)
    np.testing.assert_allclose(base, fused, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------- autotune cache

def test_fingerprint_and_buckets():
    assert autotune.fingerprint("k", {"b": True, "a": 3}) == "k|a=3|b=1"
    assert autotune.bucket_pow2(1) == 1
    assert autotune.bucket_pow2(255) == 128
    assert autotune.bucket_pow2(256) == 256
    assert autotune.shape_bucket([-1, 300, 4096]) == (-1, 256, 4096)


def test_committed_table_loads_and_serves():
    table = autotune.load_table()
    assert table["version"] == autotune.TABLE_VERSION
    assert table["entries"], "committed table must not be empty"
    entry = autotune.lookup("flash_attention",
                            autotune.flash_params(512, 128, True))
    assert entry is not None and entry["impl"] == "flash"
    assert (entry["bq"], entry["bk"]) == (512, 512)
    # per-model pipeline winners serve pipeline_for
    assert passes.pipeline_for(model="resnet50", batch_size=128) == \
        ["layout_assignment_pass", "conv_block_fuse_pass"]
    assert passes.pipeline_for(model="transformer_big",
                               batch_size=16) == \
        ["layout_assignment_pass"]
    # no committed winner -> static default
    assert passes.pipeline_for(model="nosuchmodel", batch_size=4) == \
        list(passes.TRAIN_PIPELINE)


def test_flash_engage_reads_unified_table():
    import sys
    import paddle_tpu.ops.pallas.flash_attention  # noqa: F401
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    # the migrated winners (previously the in-code AUTOTUNE dict)
    assert fa.flash_engage(512, 512, 128, True) == (512, 512)
    assert fa.flash_engage(512, 512, 64, False) == (512, 512)
    assert fa.flash_engage(1024, 1024, 128, False) == (512, 1024)
    assert fa.flash_engage(2048, 2048, 128, True) == (512, 512)
    # model-A/B tie below the crossover: fused block keeps the row
    assert fa.flash_engage(256, 256, 128, True) is None
    # off-grid T falls to the heuristics, not a wrong bucket's blocks
    assert fa.flash_engage(768, 768, 128, True) is None
    assert fa.flash_engage(4096, 4096, 128, True) == (512, 1024)


def test_lookup_counters_move():
    before = autotune.lookup_counts("flash_attention")
    autotune.lookup("flash_attention",
                    autotune.flash_params(512, 128, True))
    autotune.lookup("flash_attention",
                    autotune.flash_params(512, 96, True))   # no entry
    after = autotune.lookup_counts("flash_attention")
    assert after["hit"] == before["hit"] + 1
    assert after["miss"] == before["miss"] + 1


def test_measurement_guard():
    with autotune.forbid_measurement():
        assert autotune.measurement_forbidden()
        with pytest.raises(autotune.MeasurementForbiddenError):
            autotune.measure_ms(lambda: 1, iters=1,
                                fence=lambda x: x)
    n0 = autotune.measurement_count()
    autotune.measure_ms(lambda: 1, iters=1, fence=lambda x: x)
    assert autotune.measurement_count() == n0 + 1


def test_zero_measurement_building_zoo_program():
    """The acceptance contract: with the committed table present,
    building a zoo program (pass pipeline + CompiledBlock) performs
    ZERO timing measurements — enforced by the forbid guard, confirmed
    by the measurement counter."""
    from paddle_tpu.core.lowering import CompiledBlock
    n0 = autotune.measurement_count()
    with autotune.forbid_measurement():
        m, s, loss = _conv_chain_prog(seed=11)
        passes.apply_pipeline(m, feed_names=["img"],
                              fetch_names=[loss.name])
        cb = CompiledBlock(m.desc, 0, ["img"], [loss.name])
    assert autotune.measurement_count() == n0
    assert cb.autotune_lookups == {"hit": 0, "miss": 0}


def test_table_roundtrip_and_version_gate(tmp_path):
    path = str(tmp_path / "table.json")
    t = {}
    autotune.record(t, "flash_attention", {"T": 512, "d": 64,
                                           "causal": 1},
                    {"impl": "flash", "bq": 256, "bk": 512})
    autotune.save_table(t, path)
    assert autotune.lookup("flash_attention",
                           {"T": 512, "d": 64, "causal": 1},
                           path=path)["bq"] == 256
    # wrong version -> refused (empty entries), with a warning
    import json
    with open(path, "w") as f:
        json.dump({"version": 999, "entries": {"x": {}}}, f)
    with pytest.warns(UserWarning, match="version"):
        table = autotune.load_table(path, refresh=True)
    assert table["entries"] == {}


# --------------------------------------------------- strategy/bench hooks

def test_build_strategy_tpu_knobs():
    m, s, loss = _conv_chain_prog(seed=13)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(s, scope=scope)
    cp = CompiledProgram(m).with_build_strategy(
        BuildStrategy(fuse_conv_blocks=True, canonicalize_layouts=True))
    rng = np.random.RandomState(3)
    feed = {"img": rng.rand(2, 3, 8, 8).astype(np.float32)}
    exe.run(cp, feed=feed, fetch_list=[loss], scope=scope)
    ops = _ops(m)
    assert "conv2d_fusion" in ops and ops.count("transpose") == 1
    # the rewritten program was flagged for post-pass verification
    assert getattr(m.desc, "_verify_requested", False)


def test_build_strategy_tuned_classmethod():
    bs = BuildStrategy.tuned(model="resnet50", batch_size=128)
    assert bs.ir_passes == ["layout_assignment_pass",
                            "conv_block_fuse_pass"]
    assert bs.verify_program


def test_bench_apply_helper_control_arm():
    from bench import _apply_tpu_passes
    m, s, loss = _conv_chain_prog(seed=17)
    assert _apply_tpu_passes(m, "x", 1, "none", False, ["img"],
                             [loss.name]) == []
    assert "conv2d_fusion" not in _ops(m)
    applied = _apply_tpu_passes(m, "x", 1, "layout_assignment_pass",
                                False, ["img"], [loss.name])
    assert applied == ["layout_assignment_pass"]


# ------------------------------------------------------ model-zoo parity

# dropout pinned to 0 where configurable: rng keys salt on op INDEX, and
# a pass that removes ops shifts indices — the rewritten program would
# draw different (equally valid) dropout masks, which is not a parity
# bug but would defeat the exact comparison
_ZOO_CFGS = {
    "mnist": {},
    "smallnet": {},
    "deepfm": dict(num_fields=4, vocab_size=100),
    "roofline_probe": dict(d=16, depth=2),
}
_ZOO_HEAVY = {
    "resnet": dict(class_dim=10, image_size=32),
    "se_resnext": dict(class_dim=10, image_size=32),
    "googlenet": dict(class_dim=10, image_size=128),
    "transformer": dict(src_vocab=50, tgt_vocab=50, max_len=8,
                        d_model=16, d_inner=32, n_head=2, n_layer=1,
                        dropout=0.0),
}


def _synth_feeds(feed_specs, bs=4, seed=0):
    rng = np.random.RandomState(seed)
    feeds = {}
    for name, (shape, dtype) in feed_specs.items():
        shape = [bs if d == -1 else d for d in shape]
        if dtype.startswith("int"):
            feeds[name] = rng.randint(0, 10, size=shape).astype(dtype)
        else:
            feeds[name] = rng.rand(*shape).astype(dtype)
    return feeds


def _zoo_parity(name, kw):
    from paddle_tpu import models

    def build(seed=21):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = seed
        startup.random_seed = seed
        with fluid.program_guard(main, startup):
            out = getattr(models, name).build(**kw)
        return main, startup, out[0], out[2]

    m1, s1, l1, specs = build()
    feeds = [_synth_feeds(specs)]
    base = _run_steps(m1, s1, l1, feeds, n=2)

    m2, s2, l2, specs2 = build()
    applied = passes.apply_pipeline(m2, feed_names=sorted(specs2),
                                    fetch_names=[l2.name])
    assert applied, name
    fused = _run_steps(m2, s2, l2, feeds, n=2)
    np.testing.assert_allclose(base, fused, rtol=2e-5, atol=1e-6,
                               err_msg=name)


@pytest.mark.parametrize("name", sorted(_ZOO_CFGS))
def test_zoo_pass_parity(name):
    """Every registered grad-aware pass over the zoo: forward/backward
    numerically identical to the unrewritten program (2 SGD steps)."""
    _zoo_parity(name, _ZOO_CFGS[name])


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(_ZOO_HEAVY))
def test_zoo_pass_parity_heavy(name):
    _zoo_parity(name, _ZOO_HEAVY[name])
