"""Fused attention-block tests: the zero-relayout custom-VJP region
(ops/attention_block.py) must match the composed reference math —
projections + scaled-dot attention + softmax(+dropout) — in both values
and gradients (OpTest-style numeric contract, SURVEY §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops.attention_block import attention_block


def _ref_block(x_q, x_kv, wq, wk, wv, wo, n_head, causal):
    """Plain-jnp composition: fc → split heads → qk/softmax/pv → merge →
    fc, the graph the reference builds (benchmark transformer prep)."""
    b, tq, m = x_q.shape
    tk = x_kv.shape[1]
    h, d = n_head, m // n_head

    def split(x, w):
        y = (x.reshape(-1, m) @ w).reshape(b, -1, h, d)
        return y.transpose(0, 2, 1, 3)

    q, k, v = split(x_q, wq), split(x_kv, wk), split(x_kv, wv)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    if causal:
        qp = jnp.arange(tq) + (tk - tq)
        s = jnp.where((qp[:, None] >= jnp.arange(tk)[None, :])[None, None],
                      s, -2.0 ** 30)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, tq, m)
    return ctx.reshape(-1, m) @ wo

def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


@pytest.mark.parametrize("causal,cross", [(False, False), (True, False),
                                          (False, True)])
def test_forward_matches_composed(causal, cross):
    b, tq, tk, m, h = 2, 8, 8 if not cross else 12, 16, 4
    x_q = jnp.asarray(_rand((b, tq, m), 0))
    x_kv = x_q if not cross else jnp.asarray(_rand((b, tk, m), 1))
    ws = [jnp.asarray(_rand((m, m), 10 + i) * 0.3) for i in range(4)]
    seed = jnp.zeros((1,), jnp.int32)

    got = attention_block(x_q, x_kv, *ws, seed, h, causal, 0.0)
    want = _ref_block(x_q, x_kv, *ws, h, causal).reshape(got.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,cross", [(False, False), (True, True)])
def test_grads_match_composed(causal, cross):
    b, tq, tk, m, h = 2, 6, 6 if not cross else 10, 16, 4
    x_q = jnp.asarray(_rand((b, tq, m), 2))
    x_kv = x_q if not cross else jnp.asarray(_rand((b, tk, m), 3))
    ws = [jnp.asarray(_rand((m, m), 20 + i) * 0.3) for i in range(4)]
    seed = jnp.zeros((1,), jnp.int32)

    def f_fused(x_q, x_kv, *ws):
        return attention_block(x_q, x_kv, *ws, seed, h, causal,
                               0.0).sum()

    def f_ref(x_q, x_kv, *ws):
        return _ref_block(x_q, x_kv, *ws, h, causal).sum()

    g_fused = jax.grad(f_fused, argnums=tuple(range(6)))(x_q, x_kv, *ws)
    g_ref = jax.grad(f_ref, argnums=tuple(range(6)))(x_q, x_kv, *ws)
    for i, (a, bb) in enumerate(zip(g_fused, g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=3e-4, atol=3e-5,
                                   err_msg=f"grad arg {i}")


def test_dropout_matches_composed_mask_semantics():
    """With dropout the block must equal the composed graph that applies
    the SAME hash keep mask (upscale_in_train) to the probabilities —
    and the backward must be consistent with the forward (vjp check)."""
    from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
    b, t, m, h = 2, 8, 16, 4
    p_drop = 0.4
    x = jnp.asarray(_rand((b, t, m), 4))
    ws = [jnp.asarray(_rand((m, m), 30 + i) * 0.3) for i in range(4)]
    seed = jnp.asarray([1234], jnp.int32)

    got = attention_block(x, x, *ws, seed, h, False, p_drop)

    d = m // h
    def split(xx, w):
        y = (xx.reshape(-1, m) @ w).reshape(b, t, h, d)
        return y.transpose(0, 2, 1, 3)
    q, k, v = split(x, ws[0]), split(x, ws[1]), split(x, ws[2])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    p = jax.nn.softmax(s, -1)
    bh = jnp.arange(b * h).reshape(b, h, 1, 1)
    keep = hash_keep_mask(seed.reshape(-1)[0], bh,
                          jnp.arange(t)[None, None, :, None],
                          jnp.arange(t)[None, None, None, :], p_drop)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", p * keep, v)
    want = (ctx.transpose(0, 2, 1, 3).reshape(b, t, m) @ ws[3])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    # fwd/bwd consistency: numeric directional derivative vs vjp
    def f(xx):
        return attention_block(xx, xx, *ws, seed, h, False, p_drop).sum()
    g = jax.grad(f)(x)
    dx = jnp.asarray(_rand(x.shape, 99)) * 1e-3
    num = (f(x + dx) - f(x - dx)) / 2
    np.testing.assert_allclose(float(jnp.vdot(g, dx)), float(num),
                               rtol=2e-2)


def test_layer_builds_and_trains_in_program():
    """fluid.layers.fused_multi_head_attention inside a Program: builds,
    trains, loss decreases; params named per projection."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8, 16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[8, 16], dtype="float32")
        out = layers.fused_multi_head_attention(x, x, 16, 4, causal=True)
        loss = layers.mean(layers.square_error_cost(out, y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(4, 8, 16).astype(np.float32),
            "y": rng.rand(4, 8, 16).astype(np.float32)}
    losses = [float(np.asarray(exe.run(main, feed=feed,
                                       fetch_list=[loss.name])[0]))
              for _ in range(25)]
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_transformer_model_fused_matches_unfused():
    """The model's fused path (now the fused block) must track the
    unfused composed graph's loss within bf16-free tolerance when both
    start from identical params (dropout 0)."""
    from paddle_tpu import models

    def build(fused):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            loss, _, feed_specs = models.transformer.build(
                is_train=True, src_vocab=32, tgt_vocab=32, max_len=8,
                d_model=16, d_inner=32, n_head=2, n_layer=1, dropout=0.0,
                lr=1e-3, label_smooth_eps=0.0, fused_attention=fused)
        return main, startup, loss, feed_specs

    results = {}
    for fused in (False, True):
        main, startup, loss, feed_specs = build(fused)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        feed = {n: np.random.RandomState(7).randint(
                    0, 32, [4 if d == -1 else d for d in sh]).astype("int64")
                for n, (sh, dt) in feed_specs.items()}
        vals = [float(np.asarray(exe.run(main, feed=feed, scope=scope,
                                         fetch_list=[loss.name])[0])
                      .reshape(())) for _ in range(5)]
        results[fused] = vals
    # different parameterization (fused block params vs fc params) means
    # different inits — compare the starting loss (same softmax-CE over
    # near-uniform logits) loosely and require both to train
    assert abs(results[True][0] - results[False][0]) < 0.6, results
    assert results[True][-1] < results[True][0]
    assert results[False][-1] < results[False][0]


# ---------------------------------------------------------------------------
# heads of 64: the flash kernels of ops/pallas/flash_pairs.py (two heads a
# lane tile) under the same contract, interpreted on the CPU
# ---------------------------------------------------------------------------

def _pair_inputs(b=2, t=256, h=2, cross=False):
    m = 64 * h
    x_q = jnp.asarray(_rand((b, t, m), 40))
    x_kv = jnp.asarray(_rand((b, t, m), 41)) if cross else x_q
    ws = [jnp.asarray(_rand((m, m), 50 + i) * m ** -0.5) for i in range(4)]
    return x_q, x_kv, ws, h


PAIR_CASES = [(False, False, 0.0), (True, False, 0.0), (False, True, 0.0),
              (False, False, 0.3), (True, False, 0.3), (False, True, 0.3)]


@pytest.mark.parametrize("causal,cross,dropout_p", PAIR_CASES)
def test_flash_block_matches_composed_block(causal, cross, dropout_p):
    """d = 64 through the pair kernels against the composed block: the
    forward and EVERY gradient (x_q, x_kv, Wq, Wk, Wv, Wo), causal / not /
    cross with Tq == Tk, two query blocks, dropout from the
    same seed (the gradients agree only if all three masks do)."""
    from paddle_tpu.ops.attention_block import flash_block
    x_q, x_kv, ws, h = _pair_inputs(cross=cross)
    seed = jnp.asarray([4321], jnp.int32)
    tangent = jnp.asarray(_rand(x_q.shape, 60))

    def f_flash(x_q, x_kv, *ws):
        return jnp.sum(tangent * flash_block(
            x_q, x_kv, *ws, seed, h, causal, dropout_p, 128, True))

    def f_composed(x_q, x_kv, *ws):
        return jnp.sum(tangent * attention_block(
            x_q, x_kv, *ws, seed, h, causal, dropout_p))

    got = flash_block(x_q, x_kv, *ws, seed, h, causal, dropout_p, 128,
                      True)
    want = attention_block(x_q, x_kv, *ws, seed, h, causal, dropout_p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if cross:
        args, nums = (x_q, x_kv, *ws), tuple(range(6))
        g_flash = jax.grad(f_flash, argnums=nums)(*args)
        g_composed = jax.grad(f_composed, argnums=nums)(*args)
    else:       # self-attention: x_q IS x_kv, one gradient for both roles
        g_flash = jax.grad(lambda x, *ws: f_flash(x, x, *ws),
                           argnums=tuple(range(5)))(x_q, *ws)
        g_composed = jax.grad(lambda x, *ws: f_composed(x, x, *ws),
                              argnums=tuple(range(5)))(x_q, *ws)
    for i, (a, bb) in enumerate(zip(g_flash, g_composed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f"grad arg {i}")


def _dist(shape):
    from paddle_tpu.parallel.mesh import DistributeConfig, make_mesh
    n = int(np.prod(list(shape.values())))
    return DistributeConfig(mesh=make_mesh(shape, jax.devices()[:n]),
                            data_axis="dp", model_axis="tp")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dropout_p", [0.0, 0.3])
def test_pair_kernels_on_shards_are_the_whole_batch_bit_for_bit(
        causal, dropout_p):
    """The two Mosaic calls mapped over a dp mesh of 4 (one row a
    device) give what they give on the whole batch, every bit: the
    shard's seed carries its first GLOBAL row, so the keep masks are the
    unmapped call's (and the composed block's under GSPMD), not four
    copies of row 0's."""
    from paddle_tpu.ops.attention_block import _on_shards
    from paddle_tpu.ops.pallas.flash_pairs import (pairs_backward,
                                                   pairs_forward)
    h, t = 2, 256
    q, k, v, do = (jnp.asarray(_rand((4, t, 64 * h), 80 + i))
                   for i in range(4))
    seed = jnp.asarray([2 ** 31 - 5], jnp.int32)    # the mix wraps

    def fwd(seed, q, k, v):
        return pairs_forward(q, k, v, seed, h, causal, dropout_p, 128, True)

    def bwd(seed, q, k, v, do):
        return pairs_backward(q, k, v, do, seed, h, causal, dropout_p, 128,
                              True)

    for kernel, args in ((fwd, (q, k, v)), (bwd, (q, k, v, do))):
        want = jax.tree_util.tree_leaves(kernel(seed, *args))
        got = jax.tree_util.tree_leaves(jax.jit(_on_shards(
            kernel, _dist({"dp": 4}).mesh, "dp", h))(seed, *args))
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if dropout_p > 0:       # and the rows do differ: no mask is shared
        o = np.asarray(fwd(seed, q[:1].repeat(4, 0), k[:1].repeat(4, 0),
                           v[:1].repeat(4, 0)))
        assert not np.array_equal(o[0], o[1])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dropout_p", [0.0, 0.3])
def test_mapped_flash_block_equals_unmapped(causal, dropout_p):
    """``flash_block`` under a ("dp",) mesh of 4 against ``flash_block``
    on the whole batch: the output and both dx — made row by row — bit
    for bit, dropout or not; the four dW are sums over (b, t) that the
    mesh makes as four partial sums and an all-reduce, so they agree to
    float32 rounding (a mask drawn from the local row would be off by
    the whole gradient)."""
    from paddle_tpu.ops.attention_block import flash_block
    x_q, x_kv, ws, h = _pair_inputs(b=4, cross=True)
    seed = jnp.asarray([4321], jnp.int32)
    tangent = jnp.asarray(_rand(x_q.shape, 60))

    def run(mesh, axis):
        def f(x_q, x_kv, *ws):
            out = flash_block(x_q, x_kv, *ws, seed, h, causal, dropout_p,
                              128, True, mesh, axis)
            return jnp.sum(tangent * out), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=tuple(range(6)), has_aux=True))(x_q, x_kv, *ws)
        return out, grads

    want, g_want = run(None, None)
    got, g_got = run(_dist({"dp": 4}).mesh, "dp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for i in (0, 1):
        np.testing.assert_array_equal(np.asarray(g_got[i]),
                                      np.asarray(g_want[i]),
                                      err_msg=f"grad arg {i}")
    for i in range(2, 6):
        np.testing.assert_allclose(np.asarray(g_got[i]),
                                   np.asarray(g_want[i]),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"grad arg {i}")


def _lowered(path, d_head):
    from paddle_tpu.ops import nn_ops
    return nn_ops._ATTENTION_BLOCK_LOWERED.labels(
        path=path, d_head=str(d_head)).value


def _run_block_op(monkeypatch, t_q, t_k, d_model, n_head, forced=True,
                  dist=None, batch=1):
    """One ``fused_attention_block`` through the executor on the CPU,
    kernels on the interpreter where the gate lets them, under
    ``dist``'s mesh if given; returns the output and how much each path
    of the counter grew."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1" if forced else "0")
    d_head = d_model // n_head
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        xq = fluid.layers.data(name="xq", shape=[t_q, d_model],
                               dtype="float32")
        xkv = fluid.layers.data(name="xkv", shape=[t_k, d_model],
                                dtype="float32")
        out = layers.fused_multi_head_attention(xq, xkv, d_model, n_head)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    was = _lowered("flash", d_head), _lowered("composed", d_head)
    prog = main if dist is None else \
        fluid.CompiledProgram(main).with_sharding(dist)
    got = exe.run(prog, feed={"xq": _rand((batch, t_q, d_model), 70),
                              "xkv": _rand((batch, t_k, d_model), 71)},
                  fetch_list=[out])[0]
    return got, (_lowered("flash", d_head) - was[0],
                 _lowered("composed", d_head) - was[1])


def test_op_takes_the_pair_kernels_at_heads_of_64(monkeypatch):
    """T = 512, two heads of 64: the emitter routes to the kernels
    (forced onto the interpreter here), says so in the counter, and the
    result is the composed block's."""
    got, grew = _run_block_op(monkeypatch, 512, 512, 128, 2)
    assert grew == (1, 0)
    want, grew = _run_block_op(monkeypatch, 512, 512, 128, 2, forced=False)
    assert grew == (0, 1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t_q,t_k,d_model,n_head,why", [
    (512, 512, 192, 3, "an odd number of heads leaves half a lane tile"),
    (640, 640, 128, 2, "T is not a multiple of the table's blocks"),
    (512, 1024, 128, 2, "Tq != Tk under 2 048"),
    (256, 256, 128, 2, "below the T = 512 crossover"),
    (512, 512, 64, 2, "heads of 32"),
])
def test_op_refuses_odd_shapes_and_says_composed(monkeypatch, t_q, t_k,
                                                 d_model, n_head, why):
    _, grew = _run_block_op(monkeypatch, t_q, t_k, d_model, n_head)
    assert grew == (0, 1), why


def test_op_maps_the_pair_kernels_over_a_dp_mesh(monkeypatch):
    """Under a mesh that is the data axis alone the emitter keeps the
    kernels, mapped over the axis, and counts ``flash``; the result is
    the unsharded program's."""
    got, grew = _run_block_op(monkeypatch, 512, 512, 128, 2,
                              dist=_dist({"dp": 4}), batch=4)
    assert grew == (1, 0)
    want, grew = _run_block_op(monkeypatch, 512, 512, 128, 2, batch=4)
    assert grew == (1, 0)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,why", [
    ({"dp": 2, "tp": 2}, "a second axis of size 2 shards M"),
    ({"tp": 4}, "no data axis in the mesh"),
])
def test_op_under_other_meshes_says_composed(monkeypatch, shape, why):
    _, grew = _run_block_op(monkeypatch, 512, 512, 128, 2,
                            dist=_dist(shape), batch=4)
    assert grew == (0, 1), why


def test_rule_refuses_a_batch_the_data_axis_does_not_divide(monkeypatch):
    """The executor pads a feed to the data axis, so the rule is asked
    directly: 6 rows over dp = 4 stay composed, 8 are mapped; a mesh of
    one device is no mesh."""
    from paddle_tpu.ops import nn_ops
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    mesh = _dist({"dp": 4}).mesh

    def blocks(mesh, batch):
        return nn_ops._attention_kernel_blocks(512, 512, 128, 2, True, mesh,
                                               "dp", batch)
    want = blocks(None, 6)
    assert want is not None
    assert blocks(mesh, 6) is None
    assert blocks(mesh, 8) == want
    assert blocks(_dist({"dp": 1}).mesh, 6) == want


def test_counter_is_in_the_exporter_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_attention_block_lowered_total" in \
        obs_metrics.default_registry().snapshot()
