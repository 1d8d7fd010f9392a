"""contrib coverage: BF16 inference transpiler + mixed-precision decorate
(reference: contrib/float16/float16_transpiler.py and the later
fluid.contrib.mixed_precision.decorate capability)."""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_bf16_transpiler_fetch_consumed_downstream():
    """The fetched var is ALSO consumed by a later op — the rewrite must
    keep that consumer reading the produced value."""
    from paddle_tpu.contrib.float16 import BF16Transpiler
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        hidden = layers.fc(x, size=8, act="relu")
        out = layers.fc(hidden, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.random.RandomState(0).rand(3, 8).astype(np.float32)
    ref_h, ref_o = exe.run(main, feed={"x": xv},
                           fetch_list=[hidden, out])

    BF16Transpiler().transpile(main, scope=fluid.global_scope(),
                               feed_names=["x"],
                               fetch_names=[hidden.name, out.name])
    h2, o2 = exe.run(main, feed={"x": xv}, fetch_list=[hidden, out])
    assert np.asarray(h2).dtype == np.float32
    assert np.asarray(o2).dtype == np.float32
    np.testing.assert_allclose(np.asarray(o2), np.asarray(ref_o),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(ref_h),
                               rtol=5e-2, atol=5e-2)


def test_amp_decorate_trains():
    from paddle_tpu.contrib import mixed_precision
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 9
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[10], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = mixed_precision.decorate(
            fluid.optimizer.SGD(learning_rate=0.05),
            init_loss_scaling=2.0 ** 8, use_dynamic_loss_scaling=True,
            incr_every_n_steps=5, decr_every_n_nan_or_inf=2)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    w = rng.rand(10, 1).astype(np.float32)
    losses = []
    for _ in range(30):
        xv = rng.rand(16, 10).astype(np.float32)
        yv = xv @ w
        (l,) = exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.5, losses
    scale = np.asarray(fluid.global_scope().find_var("loss_scaling@AMP"))
    assert float(scale.reshape(())) >= 2.0 ** 8  # grew or held, never shrank


def test_amp_decr_every_n_nan_or_inf():
    """A single overflow step must NOT shrink the scale when
    decr_every_n_nan_or_inf=2; two consecutive overflows must."""
    from paddle_tpu.contrib import mixed_precision
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = mixed_precision.decorate(
            fluid.optimizer.SGD(learning_rate=0.0),
            init_loss_scaling=1024.0, use_dynamic_loss_scaling=True,
            incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
            decr_ratio=0.5)
        opt.minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    def run(xv):
        exe.run(main, feed={"x": xv, "y": np.zeros((2, 1), np.float32)},
                fetch_list=[loss])
        return float(np.asarray(
            fluid.global_scope().find_var("loss_scaling@AMP")).reshape(()))

    finite = np.ones((2, 4), np.float32)
    overflow = np.full((2, 4), np.inf, np.float32)
    assert run(finite) == 1024.0
    assert run(overflow) == 1024.0        # first bad step: hold
    assert run(overflow) == 512.0         # second consecutive: shrink
    assert run(overflow) == 512.0         # counter reset after shrink
    assert run(overflow) == 256.0


def test_quantize_transpiler_qat():
    """QAT transpile inserts fake quant/dequant pairs and the program still
    trains (reference: contrib/quantize/quantize_transpiler.py:81,
    tests in contrib/tests/test_quantize_transpiler.py)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import QuantizeTranspiler

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 2
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        QuantizeTranspiler().training_transpile(main)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)

    types = [op.type for op in main.desc.global_block.ops]
    assert "fake_quantize_abs_max" in types
    assert "fake_dequantize_max_abs" in types

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    xs = rng.rand(32, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.3).astype(np.float32)
    losses = []
    for _ in range(40):
        (lv,) = exe.run(main, feed={"x": xs, "y": ys},
                        fetch_list=[loss.name])
        losses.append(float(np.asarray(lv).reshape(())))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_fake_quantize_abs_max_grid():
    import numpy as np
    import sys
    sys.path.insert(0, "tests")
    from op_test import run_single_op
    x = np.array([[-1.0, 0.5, 0.25, 1.0]], np.float32)
    out = run_single_op("fake_quantize_abs_max", {"X": {"x": x}},
                        attrs={"bit_length": 8},
                        out_slots=("Out", "OutScale"))
    q = out["__out_Out_0"]
    assert float(out["__out_OutScale_0"]) == 1.0
    np.testing.assert_allclose(q, np.round(x * 127.0), atol=0.5)


def test_quantize_transpiler_range_abs_max():
    """range_abs_max activations keep a persistable scale window updated
    across steps (reference: fake_quantize_range_abs_max window buffers)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import QuantizeTranspiler

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, 8, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        QuantizeTranspiler(activation_quantize_type="range_abs_max",
                           window_size=16).training_transpile(main)
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)

    types = [op.type for op in main.desc.global_block.ops]
    assert "fake_quantize_range_abs_max" in types
    assert "fake_quantize_abs_max" in types     # weights still abs_max

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    xs = rng.rand(16, 8).astype(np.float32)
    ys = xs.sum(axis=1, keepdims=True).astype(np.float32) * 0.2
    for _ in range(10):
        (lv,) = exe.run(main, feed={"x": xs, "y": ys},
                        fetch_list=[loss.name])
    assert np.isfinite(float(np.asarray(lv).reshape(())))


def test_amp_bf16_rewrite_trains():
    """Pure-bf16 MXU compute mode (rewrite_program_amp): tagged ops cast to
    bf16, training still converges and matches fp32 within bf16 tolerance."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import rewrite_program_amp

    def build(amp):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 12
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 16, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            if amp:
                n = rewrite_program_amp(main)
                assert n >= 2        # both fc muls tagged
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    xs = rng.rand(32, 8).astype(np.float32)
    ys = xs.sum(axis=1, keepdims=True).astype(np.float32) * 0.3

    results = {}
    for amp in (False, True):
        main, startup, loss = build(amp)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        losses = []
        for _ in range(25):
            (lv,) = exe.run(main, feed={"x": xs, "y": ys},
                            fetch_list=[loss.name], scope=scope)
            losses.append(float(np.asarray(lv).reshape(())))
        results[amp] = losses
    assert results[True][-1] < results[True][0] * 0.5
    # same trajectory within bf16 noise
    np.testing.assert_allclose(results[True][0], results[False][0],
                               rtol=0.05)


def test_amp_rewrite_after_minimize_tags_backward():
    """rewrite_program_amp after minimize() must reach the __vjp__ ops'
    forward snapshots (review repro: bench --amp tags post-minimize)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib.mixed_precision import rewrite_program_amp

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, 1), y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        n = rewrite_program_amp(main)
    tagged_vjp = [op for op in main.desc.global_block.ops
                  if op.type == "__vjp__"
                  and op.attrs.get("fwd_op", {}).get("attrs", {})
                  .get("__amp_bf16__")]
    assert tagged_vjp, "backward mul snapshot not tagged"
    assert n >= 2      # fwd mul + its vjp snapshot

    # and the program still trains
    import numpy as np
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xs = np.random.RandomState(0).rand(16, 4).astype(np.float32)
    ys = xs.sum(1, keepdims=True).astype(np.float32)
    losses = [float(np.asarray(exe.run(main, feed={"x": xs, "y": ys},
                                       fetch_list=[loss.name])[0]))
              for _ in range(20)]
    assert losses[-1] < losses[0]


def test_bf16_transpiled_interior_stays_bf16():
    """Non-AMP mul/conv outputs follow input dtype (review finding: fp32
    forcing defeated the BF16Transpiler's bf16 interior)."""
    import jax.numpy as jnp
    import jax
    from paddle_tpu.core.registry import get_op, EmitContext
    ctx = EmitContext(base_key=jax.random.PRNGKey(0))
    x = jnp.ones((2, 3), jnp.bfloat16)
    w = jnp.ones((3, 4), jnp.bfloat16)
    out = get_op("mul").emit(ctx, {"X": [x], "Y": [w]}, {})["Out"][0]
    assert out.dtype == jnp.bfloat16


def test_nhwc_layout_rewrite_exact_parity():
    """contrib.layout NHWC rewrite: one full train step (fwd + backward +
    momentum update) is bit-identical to the NCHW program in fp32 — the
    rewrite is attr-only, transposes live inside the tagged emitters and
    gradients mirror the forward layout via the __vjp__ re-trace."""
    import numpy as np
    from paddle_tpu.contrib.layout import rewrite_program_nhwc

    def run_once(rewrite):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 7
        startup.random_seed = 7
        scope = fluid.Scope()
        with fluid.program_guard(main, startup):
            img = layers.data(name="img", shape=[3, 16, 16],
                              dtype="float32")
            lbl = layers.data(name="lbl", shape=[1], dtype="int64")
            c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1)
            b = layers.batch_norm(c, act="relu")
            c2 = layers.conv2d(b, num_filters=8, filter_size=3, padding=1)
            res = layers.elementwise_add(c2, c)          # residual
            p = layers.pool2d(res, pool_type="avg", global_pooling=True)
            logits = layers.fc(p, size=4)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(loss)
            if rewrite:
                n = rewrite_program_nhwc(main)
                assert n >= 4, n   # conv x2 + bn + pool tagged
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(3)
            feeds = {"img": rng.rand(4, 3, 16, 16).astype(np.float32),
                     "lbl": rng.randint(0, 4, (4, 1)).astype(np.int64)}
            lv, = exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
            wname = next(op.inputs["Filter"][0]
                         for op in main.desc.global_block.ops
                         if op.type == "conv2d")
            w = np.asarray(scope.find_var(wname))
        return float(np.asarray(lv).reshape(())), w

    l_nchw, w_nchw = run_once(False)
    l_nhwc, w_nhwc = run_once(True)
    assert l_nchw == l_nhwc
    np.testing.assert_array_equal(w_nchw, w_nhwc)


def test_nhwc_layout_squeeze_excitation_parity():
    """The SE gate multiply — elementwise_mul(x [B,C,H,W], gates [B,C],
    axis=0) — stays inside the NHWC region (the emitter re-aims the gate
    to [B,1,1,C]); the rewrite remains bit-exact AND the SE op no longer
    falsifies residency (one full train step, fp32)."""
    import numpy as np
    from paddle_tpu.contrib.layout import rewrite_program_nhwc

    def run_once(rewrite):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 9
        startup.random_seed = 9
        scope = fluid.Scope()
        with fluid.program_guard(main, startup):
            img = layers.data(name="img", shape=[8, 8, 8],
                              dtype="float32")
            lbl = layers.data(name="lbl", shape=[1], dtype="int64")
            c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1)
            b = layers.batch_norm(c, act="relu")
            pool = layers.pool2d(b, pool_type="avg", global_pooling=True)
            sq = layers.fc(pool, size=4, act="relu")
            gates = layers.fc(sq, size=8, act="sigmoid")
            se = layers.elementwise_mul(b, gates, axis=0)
            c2 = layers.conv2d(se, num_filters=8, filter_size=3, padding=1)
            p2 = layers.pool2d(c2, pool_type="avg", global_pooling=True)
            logits = layers.fc(p2, size=4)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
            if rewrite:
                rewrite_program_nhwc(main)
                # the SE multiply got the re-aim tag (its X stayed NHWC)
                assert any(op.attrs.get("__nhwc_bcast_bc__")
                           for op in main.desc.global_block.ops
                           if op.type == "elementwise_mul")
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(5)
            feeds = {"img": rng.rand(2, 8, 8, 8).astype(np.float32),
                     "lbl": rng.randint(0, 4, (2, 1)).astype(np.int64)}
            lv, = exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
        return float(np.asarray(lv).reshape(()))

    assert run_once(False) == run_once(True)


def test_nhwc_layout_untracked_and_fetch_boundaries():
    """Review regressions: (1) an agnostic op on the raw feed must not
    mark downstream convs in-ready (feed vars are fixed NCHW); (2) a
    trailing-axis broadcast the emitter cannot re-aim forces NCHW; (3)
    fetching an NHWC-resident intermediate returns declared-NCHW data."""
    import numpy as np
    from paddle_tpu.contrib.layout import rewrite_program_nhwc

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[3, 8, 8], dtype="float32")
        s = layers.scale(img, scale=2.0)                 # (1)
        c = layers.conv2d(s, num_filters=4, filter_size=3, padding=1)
        wvec = layers.fill_constant([8], "float32", 0.5)
        a = layers.elementwise_add(c, wvec, axis=-1)     # (2)
        c2 = layers.conv2d(a, num_filters=4, filter_size=3, padding=1)
        p = layers.pool2d(c2, pool_type="avg", global_pooling=True)
        loss = layers.mean(p)
    rewrite_program_nhwc(main)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feeds = {"img": np.ones((2, 3, 8, 8), np.float32)}
    lv, cv = exe.run(main, feed=feeds, fetch_list=[loss, c2])  # (3)
    assert np.isfinite(float(np.asarray(lv).reshape(())))
    assert np.asarray(cv).shape == (2, 4, 8, 8)


def test_nhwc_layout_concat_channel_axis():
    """Inception-style channel concat (axis=1) stays inside the NHWC
    region: the emitter re-aims the concat at the physical last axis and
    results match NCHW."""
    import numpy as np
    from paddle_tpu.contrib.layout import rewrite_program_nhwc

    def run_once(rewrite):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        startup.random_seed = 11
        scope = fluid.Scope()
        with fluid.program_guard(main, startup):
            img = layers.data(name="img", shape=[3, 8, 8],
                              dtype="float32")
            b1 = layers.conv2d(img, num_filters=4, filter_size=1)
            b2 = layers.conv2d(img, num_filters=4, filter_size=3,
                               padding=1)
            cat = layers.concat([b1, b2], axis=1)
            c = layers.conv2d(cat, num_filters=4, filter_size=1)
            p = layers.pool2d(c, pool_type="avg", global_pooling=True)
            loss = layers.mean(p)
            if rewrite:
                n = rewrite_program_nhwc(main)
                assert n >= 5, n     # 3 convs + concat + pool
                cat_ops = [op for op in main.desc.global_block.ops
                           if op.type == "concat"]
                assert cat_ops[0].attrs.get("__nhwc_concat__"), \
                    "concat not kept inside the NHWC region"
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            feeds = {"img": np.random.RandomState(1)
                     .rand(2, 3, 8, 8).astype(np.float32)}
            lv, = exe.run(main, feed=feeds, fetch_list=[loss],
                          scope=scope)
        return float(np.asarray(lv).reshape(()))

    a, b = run_once(False), run_once(True)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_recompute_rewrite_gradient_parity():
    """contrib.recompute: tagged ops' backward re-runs their forward
    (jax.checkpoint in the __vjp__ re-trace) — one full train step is
    bit-identical with and without the rewrite; the memory effect is
    checkpoint's contract (residuals = op inputs only)."""
    import numpy as np
    from paddle_tpu.contrib.recompute import rewrite_program_recompute

    def build(remat):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 21
        startup.random_seed = 21
        scope = fluid.Scope()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[64, 32], dtype="float32")
            y = layers.data(name="y", shape=[1], dtype="int64")
            q = layers.fc(x, size=32, num_flatten_dims=2)
            k = layers.fc(x, size=32, num_flatten_dims=2)
            v = layers.fc(x, size=32, num_flatten_dims=2)
            # [B, T, D] -> [B, 1, T, D] single-head for the fused op
            att = layers.scaled_dot_product_attention(
                layers.unsqueeze(q, axes=[1]),
                layers.unsqueeze(k, axes=[1]),
                layers.unsqueeze(v, axes=[1]))
            pooled = layers.reduce_mean(layers.squeeze(att, axes=[1]),
                                        dim=1)
            logits = layers.fc(pooled, size=4)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            if remat:
                n = rewrite_program_recompute(main,
                                              op_types=("attention",))
                assert n >= 2          # fwd op + vjp snapshot
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            rng = np.random.RandomState(2)
            feeds = {"x": rng.rand(2, 64, 32).astype(np.float32),
                     "y": rng.randint(0, 4, (2, 1)).astype(np.int64)}
            lv, = exe.run(main, feed=feeds, fetch_list=[loss],
                          scope=scope)
            wname = next(op.inputs["Y"][0]
                         for op in main.desc.global_block.ops
                         if op.type == "mul")     # layers.fc weight
            w = np.asarray(scope.find_var(wname))
        return float(np.asarray(lv).reshape(())), w

    l0, w0 = build(False)
    l1, w1 = build(True)
    assert l0 == l1
    np.testing.assert_array_equal(w0, w1)


def _ffn_trainer(remat):
    """x -> swiglu_ffn -> mean, SGD; the FFN tagged for recomputation."""
    from paddle_tpu.contrib.recompute import rewrite_program_recompute
    from paddle_tpu.fluid.initializer import NormalInitializer
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[16, 32], dtype="float32")
        h = layers.swiglu_ffn(layers.fc(x, size=32, num_flatten_dims=2),
                              32, 64, "ffn", NormalInitializer(0.0, 0.1))
        loss = layers.mean(h * h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        if remat:
            assert rewrite_program_recompute(main, ("swiglu_ffn",)) == 2
    return main, startup, loss


def _lowered_text(main, loss):
    import re
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.lowering import CompiledBlock
    cb = CompiledBlock(main.desc, 0, ["x"], [loss.name])
    gvars = main.desc.global_block.vars

    def struct(n):
        return jax.ShapeDtypeStruct(tuple(gvars[n].shape),
                                    jnp.dtype(gvars[n].dtype))
    text = cb.fn.lower(
        {n: struct(n) for n in cb.sig.state_names},
        {n: struct(n) for n in cb.sig.const_names},
        {"x": jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)},
        jax.ShapeDtypeStruct((), jnp.uint32)).as_text()
    # without locations and the module's name (a process-wide count)
    return re.sub(r"loc\(.*?\)|@jit_block\w+", "", text)


def test_recompute_without_a_named_value_lowers_as_the_bare_checkpoint(
        monkeypatch):
    """A tagged op in which no kernel names a value (``swiglu_ffn``)
    saves nothing: its step lowers to the same text under the policy of
    ``contrib/recompute.py:KEPT`` as with ``KEPT`` emptied — the bare
    checkpoint — and to another than the untagged program's."""
    from paddle_tpu.contrib import recompute
    tagged = _lowered_text(*_ffn_trainer(True)[::2])
    monkeypatch.setattr(recompute, "KEPT", ())
    assert _lowered_text(*_ffn_trainer(True)[::2]) == tagged
    assert "optimization_barrier" in tagged
    assert "optimization_barrier" not in _lowered_text(
        *_ffn_trainer(False)[::2])


def test_recomputed_ops_are_lowered_with_their_backward():
    """``grad_ops.recomputed_pairs``: a tagged forward op is paired with
    its `__vjp__` by the snapshot's identity and the same inputs, the
    forward op first; an untagged op, a snapshot without the tag, other
    inputs or a `__vjp__` alone pair with nothing (the `__vjp__` then
    re-traces its forward, as every untagged op's does)."""
    from paddle_tpu.ops import grad_ops
    main, _startup, _loss = _ffn_trainer(True)
    block = main.desc.global_block
    every = range(len(block.ops))
    (at, vjp), = grad_ops.recomputed_pairs(block, every).items()
    assert block.ops[at].type == "swiglu_ffn"
    assert vjp.type == "__vjp__" \
        and vjp.attrs["fwd_op"]["type"] == "swiglu_ffn"
    later = block.ops.index(vjp)
    assert at < later
    # the `__vjp__` without its forward op, and the reverse
    assert grad_ops.recomputed_pairs(block, range(at + 1, len(block.ops))) \
        == {}
    assert grad_ops.recomputed_pairs(block, range(later)) == {}
    # a snapshot that lost the tag, or whose inputs are another op's
    del vjp.attrs["fwd_op"]["attrs"]["__remat__"]
    assert grad_ops.recomputed_pairs(block, every) == {}
    vjp.attrs["fwd_op"]["attrs"]["__remat__"] = True
    vjp.inputs["FwdIn"] = list(reversed(vjp.inputs["FwdIn"]))
    assert grad_ops.recomputed_pairs(block, every) == {}
    assert grad_ops.recomputed_pairs(_ffn_trainer(False)[0].desc.global_block,
                                     every) == {}


def test_a_recomputed_ops_vjp_alone_re_traces_its_forward(monkeypatch):
    """The gradient of a tagged op is the untagged program's whether the
    pair is lowered from one trace (the executor's step) or the
    `__vjp__` runs without its forward op in the sequence (fetching a
    gradient from a scope the forward already ran in is not a program
    the executor builds, so the pairing is switched off instead)."""
    from paddle_tpu.ops import grad_ops

    def grads(remat):
        main, startup, loss = _ffn_trainer(remat)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        xv = np.random.RandomState(4).rand(2, 16, 32).astype(np.float32)
        return [np.asarray(o) for o in exe.run(
            main, feed={"x": xv}, scope=scope,
            fetch_list=[loss.name, "ffn.w_gate@GRAD", "ffn.w_down@GRAD"])]

    want = grads(False)
    for a, b in zip(grads(True), want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    monkeypatch.setattr(grad_ops, "recomputed_pairs",
                        lambda block, indices: {})
    for a, b in zip(grads(True), want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_memory_usage_estimator():
    """contrib memory_usage (reference: contrib/memory_usage_calc.py) —
    parameters + persistables + an activation band, batch dim resolved."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import memory_usage

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        h = fluid.layers.fc(x, 512)          # W [256,512] + b [512]
        fluid.layers.mean(h)
    u = memory_usage(main, batch_size=64, optimizer_slots=0)
    w_bytes = 256 * 512 * 4 + 512 * 4
    assert u["parameters"] == w_bytes
    # activations include x [64,256] and h [64,512]
    assert u["activations"] >= (64 * 256 + 64 * 512) * 4
    assert u["total_low"] <= u["total_high"]
    # batch scaling: doubling the batch grows activations, not params
    u2 = memory_usage(main, batch_size=128, optimizer_slots=0)
    assert u2["parameters"] == u["parameters"]
    assert u2["activations"] > u["activations"]


def test_transformer_noam_schedule_trains():
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 2
    with fluid.program_guard(main, startup):
        loss, _, feed_specs = models.transformer.build(
            is_train=True, src_vocab=64, tgt_vocab=64, max_len=8,
            d_model=32, d_inner=64, n_head=4, n_layer=1,
            lr_scheduler="noam", warmup=10, lr=1.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {n: rng.randint(0, 64, [2 if d == -1 else d for d in sh])
            .astype(dt) for n, (sh, dt) in feed_specs.items()}
    vals = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
            for _ in range(4)]
    assert all(np.isfinite(v) for v in vals)
    assert vals[-1] < vals[0]        # warmup lr tiny but nonzero


# -- contrib high-level Trainer/Inferencer (reference: contrib/trainer.py,
# inferencer.py — the book-notebook "simple API") ---------------------------

def test_contrib_trainer_inferencer_roundtrip(tmp_path):
    import numpy as np

    from paddle_tpu import contrib
    from paddle_tpu.fluid import layers

    def train_func():
        x = layers.data("hx", shape=[4], dtype="float32")
        y = layers.data("hy", shape=[1], dtype="float32")
        pred = layers.fc(x, 1, name="hl")
        return layers.mean(layers.square(pred - y))

    def opt_func():
        return fluid.optimizer.SGD(learning_rate=0.05)

    trainer = contrib.Trainer(train_func, opt_func)
    rng = np.random.RandomState(0)
    wt = rng.rand(4, 1).astype("float32")

    def reader():
        for _ in range(8):
            xb = rng.rand(8, 4).astype("float32")
            yield {"hx": xb, "hy": xb @ wt}

    seen = []

    def handler(ev):
        if isinstance(ev, contrib.high_level.EndStepEvent):
            seen.append(float(np.asarray(ev.metrics[0]).reshape(())))

    trainer.train(num_epochs=3, event_handler=handler, reader=reader)
    assert len(seen) == 24 and seen[-1] < seen[0]
    pdir = str(tmp_path / "hl_params")
    trainer.save_params(pdir)

    def infer_func():
        x = layers.data("hx", shape=[4], dtype="float32")
        return layers.fc(x, 1, name="hl")

    inf = contrib.Inferencer(infer_func, pdir)
    xb = np.ones((2, 4), np.float32)
    (out,) = inf.infer({"hx": xb})
    # parity vs the trained weights applied by hand
    w = np.asarray(trainer.scope.find_var("hl.w_0"))
    b = np.asarray(trainer.scope.find_var("hl.b_0"))
    np.testing.assert_allclose(np.asarray(out), xb @ w + b, rtol=1e-5)


def test_op_freq_statistic():
    from paddle_tpu import contrib
    from paddle_tpu.fluid import layers

    x = layers.data("fx", shape=[4], dtype="float32")
    h = layers.fc(x, 4, act="relu")
    layers.fc(h, 4, act="relu")
    uni, adj = contrib.op_freq_statistic(fluid.default_main_program())
    d = dict(uni)
    assert d.get("mul", 0) >= 2 and d.get("relu", 0) == 2
    assert any("->" in k for k, _ in adj)
