"""Pallas kernel tier self-test — every kernel compared against the refer
(jnp) tier, like the reference's jit/test.cc which cross-checks all
registered microkernel implementations against refer/ scalar versions.
Runs the kernels in interpreter mode on the CPU test backend; on real TPU
the same code paths compile."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _r(*shape, seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return (rng.randn(*shape) * scale).astype(np.float32)


def _ref_attention(q, k, v, causal=False, scale=None):
    from paddle_tpu.parallel.ring_attention import full_attention
    return np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal,
                                     scale=scale))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_refer(causal):
    from paddle_tpu.ops.pallas import flash_attention
    b, h, t, d = 2, 3, 16, 8
    q, k, v = _r(b, h, t, d), _r(b, h, t, d, seed=1), _r(b, h, t, d, seed=2)
    out = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal, None,
        8, 8, True))
    expect = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)


def test_flash_attention_cross_len():
    from paddle_tpu.ops.pallas import flash_attention
    b, h, tq, tk, d = 1, 2, 8, 24, 8
    q = _r(b, h, tq, d)
    k = _r(b, h, tk, d, seed=1)
    v = _r(b, h, tk, d, seed=2)
    out = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), True, None,
        8, 8, True))
    expect = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_matches_refer():
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.parallel.ring_attention import full_attention
    b, h, t, d = 1, 2, 8, 4
    q, k, v = _r(b, h, t, d), _r(b, h, t, d, seed=1), _r(b, h, t, d, seed=2)
    qa, ka, va = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

    def loss_flash(q_, k_, v_):
        o = flash_attention(q_, k_, v_, True, None, 8, 8, True)
        return jnp.sum(o * o)

    def loss_ref(q_, k_, v_):
        o = full_attention(q_, k_, v_, causal=True)
        return jnp.sum(o * o)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(qa, ka, va)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qa, ka, va)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


def test_fused_lstm_matches_dynamic_lstm():
    from paddle_tpu.ops.pallas import fused_lstm_train
    from paddle_tpu.core.registry import get_op, EmitContext
    t, b, hd = 5, 3, 4
    xproj = _r(t, b, 4 * hd, scale=0.5)
    w = _r(hd, 4 * hd, seed=1, scale=0.3)
    h0 = np.zeros((b, hd), np.float32)
    c0 = np.zeros((b, hd), np.float32)
    # the production tier: zero peepholes + full lengths = plain cell
    hid, cell, _, _ = fused_lstm_train(
        jnp.asarray(xproj), jnp.asarray(w),
        jnp.zeros((1, 3 * hd), jnp.float32),
        jnp.full((b, 1), t, jnp.int32),
        jnp.asarray(h0), jnp.asarray(c0), True)
    ctx = EmitContext(base_key=jax.random.PRNGKey(0))
    ref = get_op("dynamic_lstm").emit(
        ctx, {"Input": [jnp.asarray(xproj.transpose(1, 0, 2))],
              "Weight": [jnp.asarray(w)]}, {})
    np.testing.assert_allclose(np.asarray(hid).transpose(1, 0, 2),
                               np.asarray(ref["Hidden"][0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cell).transpose(1, 0, 2),
                               np.asarray(ref["Cell"][0]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ptype", ["SUM", "AVERAGE", "SQRT", "MAX"])
def test_masked_seqpool_matches_refer(ptype):
    from paddle_tpu.ops.pallas import masked_seqpool
    b, t, d = 3, 6, 4
    x = _r(b, t, d)
    lens = np.array([6, 3, 1], np.int32)
    out = np.asarray(masked_seqpool(jnp.asarray(x), jnp.asarray(lens),
                                    ptype, interpret=True))
    mask = np.arange(t)[None, :] < lens[:, None]
    xm = np.where(mask[:, :, None], x, 0.0)
    if ptype == "SUM":
        expect = xm.sum(1)
    elif ptype == "AVERAGE":
        expect = xm.sum(1) / lens[:, None]
    elif ptype == "SQRT":
        expect = xm.sum(1) / np.sqrt(lens[:, None])
    else:
        expect = np.where(mask[:, :, None], x, -np.inf).max(1)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_masked_seqpool_grad():
    from paddle_tpu.ops.pallas import masked_seqpool
    b, t, d = 8, 5, 4
    x = jnp.asarray(_r(b, t, d))
    lens = jnp.asarray(np.array([5, 3, 1, 2, 5, 4, 2, 1], np.int32))

    def loss(x_):
        return jnp.sum(masked_seqpool(x_, lens, "AVERAGE", True) ** 2)

    g = jax.grad(loss)(x)

    def ref_loss(x_):
        mask = (jnp.arange(t)[None, :] < lens[:, None])[:, :, None]
        s = jnp.sum(jnp.where(mask, x_, 0.0), axis=1) / lens[:, None]
        return jnp.sum(s ** 2)

    gr = jax.grad(ref_loss)(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_blockwise_bwd_multiblock(causal):
    """Blockwise Pallas backward across multiple q/k blocks matches the
    refer-path gradients (bq=bk=8 over T=24 → 3x3 tiles)."""
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.parallel.ring_attention import full_attention
    b, h, t, d = 1, 2, 24, 8
    q, k, v = (jnp.asarray(_r(b, h, t, d, seed=s)) for s in range(3))
    gseed = jnp.asarray(_r(b, h, t, d, seed=7))

    def loss_flash(q_, k_, v_):
        o = flash_attention(q_, k_, v_, causal, None, 8, 8, True)
        return jnp.sum(o * gseed)

    def loss_ref(q_, k_, v_):
        o = full_attention(q_, k_, v_, causal=causal)
        return jnp.sum(o * gseed)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


def test_flash_attention_blockwise_bwd_cross_len():
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.parallel.ring_attention import full_attention
    b, h, tq, tk, d = 1, 1, 8, 24, 4
    q = jnp.asarray(_r(b, h, tq, d))
    k = jnp.asarray(_r(b, h, tk, d, seed=1))
    v = jnp.asarray(_r(b, h, tk, d, seed=2))

    def lf(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, None, 8, 8,
                                       True) ** 2)

    def lr(q_, k_, v_):
        return jnp.sum(full_attention(q_, k_, v_, causal=True) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


def test_fused_gru_matches_dynamic_gru():
    """GRU jit-tier parity (reference: operators/jit gru microkernels vs
    math/gru_compute.cc refer)."""
    from paddle_tpu.ops.pallas import fused_gru_train
    from paddle_tpu.core.registry import get_op, EmitContext
    t, b, hd = 5, 3, 4
    xproj = _r(t, b, 3 * hd, scale=0.5)
    w = _r(hd, 3 * hd, seed=1, scale=0.3)
    h0 = np.zeros((b, hd), np.float32)
    hid, _ = fused_gru_train(jnp.asarray(xproj), jnp.asarray(w),
                             jnp.full((b, 1), t, jnp.int32),
                             jnp.asarray(h0), True)
    ctx = EmitContext(base_key=jax.random.PRNGKey(0))
    ref = get_op("dynamic_gru").emit(
        ctx, {"Input": [jnp.asarray(xproj.transpose(1, 0, 2))],
              "Weight": [jnp.asarray(w)]}, {})
    np.testing.assert_allclose(np.asarray(hid).transpose(1, 0, 2),
                               np.asarray(ref["Hidden"][0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hid)[-1],
                               np.asarray(ref["LastHidden"][0]),
                               rtol=1e-4, atol=1e-5)


def test_flash_attention_bf16_fwd_bwd_parity():
    """The bf16 operand path (round-3: storage-dtype MXU dots, fp32
    accumulation, post-dot scale) — every other flash test runs fp32
    where the casts are no-ops; this one exercises the AMP path the
    2.3x speedup claim rests on, against the composed reference in
    matched precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas as pk

    B, H, T, D = 1, 2, 256, 128
    q = jax.random.normal(jax.random.key(0), (B, H, T, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, H, T, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, H, T, D), jnp.bfloat16)
    scale = D ** -0.5

    def flash_loss(q, k, v):
        o = pk.flash_attention(q, k, v, True, scale, 128, 128, True,
                               0.0, None)
        return (o.astype(jnp.float32) ** 2).sum()

    def comp_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(T)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s,
                      -1e30)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return (o.astype(jnp.float32) ** 2).sum()

    lf, gf = jax.value_and_grad(flash_loss, (0, 1, 2))(q, k, v)
    lc, gc = jax.value_and_grad(comp_loss, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(lc), rtol=2e-2)
    for a, b, name in zip(gf, gc, "qkv"):
        a32 = np.asarray(a, np.float32)
        b32 = np.asarray(b, np.float32)
        denom = np.abs(b32).max() + 1e-6
        assert np.abs(a32 - b32).max() / denom < 5e-2, name


# ------------------------------------------------------------------ ISSUE 48
# the causal schedule: the grid runs over the visible tiles alone

def _flash_module():
    import sys
    import paddle_tpu.ops.pallas  # noqa: F401  (the name is the function's)
    return sys.modules["paddle_tpu.ops.pallas.flash_attention"]


def _composed(q, k, v, scale, dropout_p, seed):
    """Causal softmax -> hashed dropout -> product, plain jnp, with the
    diagonal shifted by tk - tq; -> (context, log-sum-exp)."""
    fa = _flash_module()
    b, h, tq, _ = q.shape
    tk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qpos = (tk - tq) + jnp.arange(tq)
    s = jnp.where(qpos[:, None] >= jnp.arange(tk)[None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0:
        w = w * fa.hash_keep_mask(
            seed, jnp.arange(b * h).reshape(b, h, 1, 1),
            qpos[None, None, :, None], jnp.arange(tk)[None, None, None, :],
            dropout_p)
    return (jnp.einsum("bhqk,bhkd->bhqd", w, v),
            jax.scipy.special.logsumexp(s, axis=-1))


# (tq, tk) -> blocks with bq < bk, bq == bk, bq > bk; with tq < tk the
# diagonal starts 24 keys in, inside a key block of 16
_CAUSAL_SHAPES = {(48, 48): ((8, 16), (16, 16), (16, 8)),
                  (24, 48): ((8, 16), (8, 8), (24, 8))}
_CAUSAL_GRID = [(tq, tk, bq, bk) for (tq, tk), blocks
                in _CAUSAL_SHAPES.items() for bq, bk in blocks]


@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("dropout_p", [0.0, 0.3])
@pytest.mark.parametrize("d,dv", [(192, 128), (128, 128)])
@pytest.mark.parametrize("tq,tk,bq,bk", _CAUSAL_GRID)
def test_causal_schedule_forward_and_gradients(tq, tk, bq, bk, d, dv,
                                               dropout_p, with_lse):
    """Forward and all three gradients of the causal kernels
    (interpreted) against the composed attention: query blocks smaller
    than, equal to and larger than the key blocks, the diagonal shifted
    by tk - tq, both head sizes, dropout's keep mask from the tile's own
    coordinates, and the log-sum-exp's cotangent."""
    fa = _flash_module()
    rng = np.random.RandomState(tq + bq * 7 + bk)
    q, k = (jnp.asarray(rng.randn(1, 2, t, d), jnp.float32)
            for t in (tq, tk))
    v = jnp.asarray(rng.randn(1, 2, tk, dv), jnp.float32)
    g = jnp.asarray(rng.randn(1, 2, tq, dv), jnp.float32)
    glse = jnp.asarray(rng.randn(1, 2, tq), jnp.float32)
    seed = jnp.asarray([11], jnp.int32)
    scale = d ** -0.5

    def kernels(q, k, v):
        args = (q, k, v, True, scale, bq, bk, True, dropout_p,
                seed if dropout_p > 0 else None)
        if with_lse:
            return fa.flash_attention_lse(*args)
        return fa.flash_attention(*args)

    def composed(q, k, v):
        out = _composed(q, k, v, scale, dropout_p, 11)
        return out if with_lse else out[0]

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(kernels, q, k, v)
        want, want_pull = jax.vjp(composed, q, k, v)
        cot = (g, glse) if with_lse else g
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        for a, b in zip(pull(cot), want_pull(cot)):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("key_major", [False, True],
                         ids=["query_major", "key_major"])
@pytest.mark.parametrize("tq,tk,bq,bk", _CAUSAL_GRID + [
    (8192, 8192, 512, 1024), (8192, 8192, 512, 512), (2048, 8192, 256, 1024),
    (4096, 4096, 1024, 256)])
def test_causal_schedule_visits_the_visible_tiles(tq, tk, bq, bk, key_major):
    """The schedule's tiles ARE the tiles with a key at or under some
    query, each once, and the order is what the kernels' first / last
    step rests on: a query block's key blocks are 0..its last, a key
    block's query blocks its first..the last."""
    fa = _flash_module()
    qi, kj = fa.causal_schedule(tq, tk, bq, bk, key_major)
    under = (np.arange(tq)[:, None] + (tk - tq) >= np.arange(tk)[None, :]) \
        .reshape(tq // bq, bq, tk // bk, bk)
    assert sorted(zip(qi.tolist(), kj.tolist())) == \
        [tuple(t) for t in np.argwhere(under.any(axis=(1, 3))).tolist()]
    outer, inner = (kj, qi) if key_major else (qi, kj)
    assert (np.diff(outer) >= 0).all()
    for o in np.unique(outer):
        run = inner[outer == o]
        np.testing.assert_array_equal(
            run, np.arange(run[0], run[0] + len(run)))
        assert run[-1] == tq // bq - 1 if key_major else run[0] == 0


def test_causal_schedule_is_refused_where_a_query_block_sees_no_key():
    """tq > tk: the first queries see no key; the call keeps the dense
    grid (rows of zeros, as before) and the counter says so."""
    fa = _flash_module()
    assert fa.causal_schedule(48, 24, 8, 8) is None
    read = lambda: {k: fa.CAUSAL_BLOCKS.labels(kernel="fwd", kind=k).value  # noqa
                    for k in ("visited", "computed")}
    before = read()
    q = jnp.ones((1, 1, 48, 8), jnp.float32)
    out = fa.flash_attention(q, q[:, :, :24], q[:, :, :24], True, None,
                             8, 8, True)
    np.testing.assert_array_equal(out[0, 0, :24], 0.0)
    np.testing.assert_allclose(out[0, 0, 24:], 1.0, rtol=1e-6)
    after = read()
    # 6 x 3 tiles visited, the 6 under the shifted diagonal computed
    assert {k: after[k] - before[k] for k in after} == {
        "visited": 18, "computed": 6}


def test_causal_blocks_counter_on_the_trained_cells_shape():
    """``paddle_flash_causal_blocks_total`` at the trained latent cell's
    call (32 heads of 192 / 128 over 8 192 tokens, the blocks
    ``causal_attention`` picks): every visited tile computes — 36 a
    head at 1024 x 1024, where the dense grid at 512 x 1024 visited
    128 to compute 72."""
    from paddle_tpu.ops import pallas as pk
    fa = _flash_module()
    read = lambda: {(kern, kind): fa.CAUSAL_BLOCKS.labels(  # noqa: E731
        kernel=kern, kind=kind).value
        for kern in ("fwd", "dq", "dkv")
        for kind in ("visited", "computed")}
    bq, bk = pk.causal_blocks(8192, 192, 128)
    qk = jax.ShapeDtypeStruct((1, 32, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    before = read()
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, True, 192 ** -0.5, bq, bk).astype(jnp.float32)),
        argnums=(0, 1, 2)), qk, qk, v)
    grew = {k: n - before[k] for k, n in read().items()}
    assert (bq, bk) == (1024, 1024)
    for kern in ("fwd", "dq", "dkv"):
        # the gradient's trace runs the forward once more (custom_vjp)
        assert grew[kern, "visited"] / (32 * 36) in (1, 2), grew
        assert grew[kern, "computed"] == grew[kern, "visited"]
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_flash_causal_blocks_total" in \
        obs_metrics.default_registry().snapshot()


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold,
    in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _walk(sub)


def _kernel_shas(fn, *shapes):
    """sha256 of each ``pallas_call`` of a trace, in order — its body,
    grid and block mappings, operands and results — without what names
    the checkout (source locations). What lies between the kernels is
    not hashed: a ``name`` equation (``flash_attention._named``, PR 50)
    renumbers every variable after it."""
    import hashlib
    import re
    out = []
    for e in _walk(jax.make_jaxpr(fn)(*shapes).jaxpr):
        if e.primitive.name != "pallas_call":
            continue
        text = (f"{e.params['jaxpr']}\n{e.params['grid_mapping']}\n"
                f"{[v.aval for v in e.invars]}\n"
                f"{[v.aval for v in e.outvars]}")
        text = re.sub(r" at [^\s]+:\d+", "", text)
        text = re.sub(r"/[\w/.\-]+\.py(:\d+)?", "", text)
        text = re.sub(r"0x[0-9a-f]+", "", text)
        out.append(hashlib.sha256(text.encode()).hexdigest()[:12])
    return out


@pytest.mark.parametrize("causal,tq,tk,dropout_p,want", [
    (False, 1024, 2048, 0.0,
     ("5cf6130b0852", "065e6d5740aa", "b87572983d6d")),
    (False, 1024, 2048, 0.3,
     ("a44ba2f3bb55", "6b1d74578900", "1b9c6340f05b")),
    (True, 2048, 1024, 0.0,
     ("7d9f52425bbc", "d6c9b4fec16b", "e64ccc1828ad")),
    (True, 2048, 1024, 0.3,
     ("dc50dd2389ed", "531a5a53da34", "299bfa2ad5b2"))])
def test_the_dense_grid_lowers_as_before_the_causal_schedule(
        causal, tq, tk, dropout_p, want):
    """A non-causal call, and a causal one with tq > tk, trace to the
    kernels they traced to at PR 47 (forward, dQ and dK/dV with the
    log-sum-exp's cotangent, heads of 192 / 128, blocks 256 x 512): held
    as ``_kernel_shas`` reads PR 48's commit (the hashes that stood here
    before PR 50 were of the jaxpr's whole text, which a ``name``
    equation renumbers)."""
    fa = _flash_module()
    seed = jnp.asarray([3], jnp.int32)

    def loss(q, k, v):
        o, lse = fa.flash_attention_lse(
            q, k, v, causal, None, 256, 512, False, dropout_p,
            seed if dropout_p > 0 else None)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse * lse)

    shapes = [jax.ShapeDtypeStruct((2, 2, t, d), jnp.bfloat16)
              for t, d in ((tq, 192), (tk, 192), (tk, 128))]
    assert tuple(_kernel_shas(jax.grad(loss, argnums=(0, 1, 2)),
                              *shapes)) == want


# ------------------------------------------------------------------ ISSUE 50
# the forward rules name ``out`` and ``lse``: a checkpoint whose policy
# saves contrib/recompute.py:KEPT keeps the pair and its backward holds
# no forward kernel; anywhere else a name is an identity

@pytest.mark.parametrize("with_lse", [False, True],
                         ids=["flash_attention", "flash_attention_lse"])
@pytest.mark.parametrize("causal", [False, True])
def test_a_checkpoint_that_saves_the_named_pair_runs_the_forward_once(
        with_lse, causal):
    """Both interfaces (ring attention's ``flash_attention_lse`` with
    the log-sum-exp's cotangent too) under ``jax.checkpoint`` with the
    recomputed ops' policy: the three gradients equal the plain call's
    bit for bit, and the gradient's jaxpr holds the forward
    ``pallas_call`` once where the bare checkpoint's holds it twice."""
    from paddle_tpu.contrib.recompute import KEPT
    fa = _flash_module()
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 256, d).astype(np.float32))
               for d in (24, 24, 16))

    def attend(q, k, v):
        if with_lse:
            o, lse = fa.flash_attention_lse(q * 1.5, k, v, causal, None,
                                            128, 128, True)
            return jnp.sum(o * o) + jnp.sum(jnp.sin(lse))
        return jnp.sum(fa.flash_attention(q * 1.5, k, v, causal, None,
                                          128, 128, True) ** 2)

    policy = jax.checkpoint_policies.save_only_these_names(*KEPT)
    kept = jax.grad(jax.checkpoint(attend, policy=policy), (0, 1, 2))
    bare = jax.grad(jax.checkpoint(attend), (0, 1, 2))
    want = jax.grad(attend, (0, 1, 2))(q, k, v)
    for a, b in zip(kept(q, k, v), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def is_forward(e):
        return (e.primitive.name == "pallas_call" and len(e.outvars) == 2
                and e.outvars[1].aval.shape[-1] == 1)

    def forwards(fn):
        return sum(map(is_forward,
                       _walk(jax.make_jaxpr(fn)(q, k, v).jaxpr)))

    assert (forwards(kept), forwards(bare)) == (1, 2)
    # everything else IS recomputed: the backward's checkpoint scales
    # the query again and runs the two backward kernels, no forward one
    (again,) = [e for e in jax.make_jaxpr(kept)(q, k, v).jaxpr.eqns
                if e.primitive.name.startswith(("remat", "checkpoint"))]
    inside = list(_walk(again.params["jaxpr"]))
    assert any(e.primitive.name == "mul" for e in inside)
    assert sum(e.primitive.name == "pallas_call" for e in inside) == 2
    assert not any(map(is_forward, inside))


def test_a_name_outside_a_policy_lowers_to_nothing():
    """The forward rules' names leave no trace in a program that saves
    nothing by name: the gradient's lowered text holds no ``name``."""
    fa = _flash_module()
    q = jax.ShapeDtypeStruct((1, 2, 256, 16), jnp.float32)
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, True, None, 128, 128, True)), (0, 1, 2))).lower(
            q, q, q).as_text()
    assert "flash_out" not in text and "flash_lse" not in text


# ------------------------------------------------------------------ ISSUE 52
# grouped key heads: the forward's key / value index maps read head
# bh // G, and the serving prefill (kv_attention._gqa_attend) takes the
# kernel by shape

def _grouped_case(g, d, t, dtype=jnp.float32, n_kv=2, b=2):
    rng = np.random.RandomState(g * 1000 + d + t)
    q = jnp.asarray(rng.randn(b, t, n_kv, g, d), dtype)
    k, v = (jnp.asarray(rng.randn(b, t, n_kv, d), dtype) for _ in "kv")
    return q, k, v


def _grouped_reference(q, k, v, scale):
    """float32, every key head written out for its G query heads."""
    q, k, v = (np.asarray(z, np.float64) for z in (q, k, v))
    t = q.shape[1]
    s = np.einsum("btkgd,bskd->bkgts", q, k) * scale
    s = np.where(np.arange(t)[:, None] >= np.arange(t)[None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bkgts,bskd->btkgd", p, v)


@pytest.mark.parametrize("scale", [None, 0.3], ids=["scale_d", "scale_0.3"])
@pytest.mark.parametrize("t,bq,bk", [(256, 128, 128), (512, 128, 256),
                                     (512, 256, 128)])
@pytest.mark.parametrize("g,d", [(4, 64), (4, 128), (8, 128)])
def test_grouped_forward_matches_the_composition_and_a_reference(
        g, d, t, bq, bk, scale, monkeypatch):
    """The causal forward kernel (interpreted) over 2 key heads for 2 G
    query heads, two batch rows, against ``_gqa_attend``'s blocked
    composition and against a float64 reference that broadcasts the
    keys: blocks of 128-256 over 256-512 rows, ``bq != bk`` among them,
    the op's scale and the default."""
    from paddle_tpu.ops import kv_attention as kv
    fa = _flash_module()
    q, k, v = _grouped_case(g, d, t)
    b, _t, n_kv = k.shape[:3]
    monkeypatch.setattr(kv, "GQA_QUERY_BLOCK", 128)
    heads_first = lambda z: jnp.swapaxes(z, 1, 2)              # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = heads_first(fa.flash_attention(
            heads_first(q.reshape(b, t, n_kv * g, d)), heads_first(k),
            heads_first(v), True, scale, bq, bk, True)).reshape(q.shape)
        blocked, _ = kv._gqa_attend(q, k, v, scale=scale)
    want = _grouped_reference(q, k, v, d ** -0.5 if scale is None else scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, blocked, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("g,d", [(4, 64), (8, 128)])
def test_grouped_forward_rounds_the_probabilities_as_the_composition(
        g, d, monkeypatch):
    """In bfloat16 both ways hold float32 scores and statistics and
    round the probabilities to bfloat16 for ``p . V``: they agree to
    that rounding (2 ** -7 of the largest value), through
    ``_gqa_attend`` itself with the kernel forced on."""
    from paddle_tpu.ops import kv_attention as kv
    q, k, v = _grouped_case(g, d, 256, jnp.bfloat16)
    monkeypatch.setattr(kv, "GQA_QUERY_BLOCK", 128)
    blocked, _ = kv._gqa_attend(q, k, v)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert kv._gqa_attend_tier(256, d, None)[0] == "flash"
    got, seen = kv._gqa_attend(q, k, v)
    assert seen is None and got.dtype == jnp.bfloat16
    want = _grouped_reference(*(z.astype(jnp.float32) for z in (q, k, v)),
                              d ** -0.5)
    for other in (np.asarray(blocked, np.float32), want):
        np.testing.assert_allclose(np.asarray(got, np.float32), other,
                                   atol=2.0 ** -7 * np.abs(want).max())


@pytest.mark.parametrize("entry", ["flash_attention", "flash_attention_lse"])
def test_a_gradient_through_grouped_key_heads_is_refused(entry):
    """The backward kernels have no grouped index maps: both
    ``custom_vjp`` entries refuse in their forward rule; the plain
    forward of each runs."""
    fa = _flash_module()
    q = jnp.ones((1, 4, 16, 8), jnp.float32)
    kv_ = jnp.ones((1, 2, 16, 8), jnp.float32)
    call = lambda q, k, v: getattr(fa, entry)(                 # noqa: E731
        q, k, v, True, None, 8, 8, True)
    out = jax.tree_util.tree_leaves(call(q, kv_, kv_))[0]
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="forward-only"):
        jax.grad(lambda q: jnp.sum(jax.tree_util.tree_leaves(
            call(q, kv_, kv_))[0]))(q)
    with pytest.raises(ValueError, match="whole number of groups"):
        call(q, jnp.ones((1, 3, 16, 8), jnp.float32),
             jnp.ones((1, 3, 16, 8), jnp.float32))


def test_a_group_of_one_keeps_the_maps_it_had_and_groups_change_two():
    """The grouped call's kernel is the ungrouped call's but for the
    key and value block mappings (``bh // G``): same body, same query
    and output maps."""
    fa = _flash_module()

    def mappings(n_kv):
        fn = lambda q, k, v: fa.flash_attention(               # noqa: E731
            q, k, v, True, None, 128, 128)
        q = jax.ShapeDtypeStruct((2, 8, 256, 64), jnp.bfloat16)
        kv_ = jax.ShapeDtypeStruct((2, n_kv, 256, 64), jnp.bfloat16)
        (e,) = [e for e in _walk(jax.make_jaxpr(fn)(q, kv_, kv_).jaxpr)
                if e.primitive.name == "pallas_call"]
        return ([str(m.index_map_jaxpr)
                 for m in e.params["grid_mapping"].block_mappings],
                str(e.params["jaxpr"]))
    one, body_one = mappings(8)
    four, body_four = mappings(2)
    assert body_one == body_four
    assert [a == b for a, b in zip(one, four)] == [
        True, False, False, True, True]
    assert "div" in four[1] and "div" not in one[1]


def _attend_lowered():
    from paddle_tpu.ops import kv_attention as kv
    return {p: kv.GQA_PREFILL_ATTEND_LOWERED.labels(path=p).value
            for p in ("flash", "blocked", "whole")}


@pytest.mark.parametrize("forced,t,d,window,want", [
    ("1", 1024, 64, None, "flash"),        # a long full layer
    ("1", 2048, 128, None, "flash"),
    ("1", 1024, 64, 256, "blocked"),       # a window layer keeps the band
    ("0", 1024, 64, None, "blocked"),      # no TPU, nothing forced
    ("1", 512, 64, None, "whole"),         # one square of scores
    ("1", 512, 64, 256, "whole"),
    ("0", 256, 128, None, "whole")])
def test_gqa_attend_chooses_by_shape_and_the_counter_says_which(
        forced, t, d, window, want, monkeypatch):
    """``paddle_gqa_prefill_attend_lowered_total{path}`` grows by one a
    lowering of ``_gqa_attend``, under the label of the implementation
    its shapes chose; the flash path counts its causal tiles too."""
    from paddle_tpu.ops import kv_attention as kv
    fa = _flash_module()
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)
    q = jax.ShapeDtypeStruct((1, t, 2, 4, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
    tiles = lambda: fa.CAUSAL_BLOCKS.labels(                   # noqa: E731
        kernel="fwd", kind="computed").value
    before, tiles_before = _attend_lowered(), tiles()
    out, seen = jax.eval_shape(
        lambda q, k, v: kv._gqa_attend(q, k, v, window), q, k, k)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert (seen is None) == (window is None)
    grew = {p: n - before[p] for p, n in _attend_lowered().items()}
    assert grew == {p: float(p == want) for p in grew}
    bq, bk = fa.causal_blocks(t, d, d)
    assert tiles() - tiles_before == (
        8 * len(fa._visible_tiles(t, t, bq, bk)) if want == "flash" else 0)


@pytest.mark.parametrize("devices,t,d,want,want_blocks", [
    (1, 4096, 64, "flash", (1024, 1024)),  # LFM2's rows of the table
    (1, 2048, 64, "flash", (1024, 1024)),
    (1, 16384, 128, "flash", (1024, 1024)),    # Trinity's full layer
    (1, 1024, 128, "flash", (512, 1024)),  # Granite's short bucket
    (4, 4096, 64, "blocked", None),        # XLA cannot partition Mosaic
    (1, 4096, 16, "blocked", None),        # heads under half a lane tile
    (1, 512, 64, "whole", None)])
def test_gqa_attend_tier_on_a_chip_and_under_a_mesh(devices, t, d, want,
                                                    want_blocks,
                                                    monkeypatch):
    """On a TPU (steered: the rule asks ``on_tpu``) the kernel is taken
    off a mesh at aligned shapes only, at the committed table's blocks
    (``causal_blocks``) or ``pick_blocks``'s; under a mesh of more than
    one device the composition, which XLA partitions, stays."""
    from jax.sharding import Mesh
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("dp",))
    tier, blocks = kv._gqa_attend_tier(t, d, None, mesh)
    assert (tier, blocks) == (want, want_blocks)
    assert kv._gqa_attend_tier(t, d, 2048, mesh)[0] != "flash"


def test_the_attend_counter_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_gqa_prefill_attend_lowered_total" in \
        obs_metrics.default_registry().snapshot()


# ------------------------------------------------------------------ ISSUE 34
# attend_pages: a slot's live pages attended in place under a mask,
# against the gathered path (the page gather, then _decode_contract)

def _attend_case(case, dtype):
    """(q, pool, table, lens, gen0, pos, keep) of four slots over a
    table of 32 pages a slot; a page is one sublane tile of the dtype,
    a bucket 16 pages, a block 128 rows (so a slot walks several)."""
    rng = np.random.RandomState(sum(map(ord, case)))
    ps = 8 if dtype == jnp.float32 else 16
    b, h, w, mp, n_pages = 4, 8, 256, 32, 160
    s_len, bucket = mp * ps, 16 * ps
    pool = rng.randn(n_pages * ps, w).astype(np.float32)
    q = rng.randn(b, h, w).astype(np.float32)
    table = rng.permutation(n_pages)[:b * mp].reshape(b, mp)
    lens = np.asarray([bucket, bucket - 3 * ps - 2, 5, bucket - 1])
    gen0 = np.full(b, bucket)
    pos = gen0 + np.asarray([0, 2 * ps + 3, 9 * ps, 15 * ps + ps - 1])
    j = np.arange(s_len)
    live = (j[None] < lens[:, None]) | ((j[None] >= gen0[:, None])
                                        & (j[None] <= pos[:, None]))
    keep = live & (rng.rand(b, s_len) < 0.4)
    if case == "masked_row_dominates":
        # a live row NOT kept whose score is a hundred above the rest:
        # a kernel that ignores the mask returns that row alone
        keep[:, 3] = False
        pool[table[:, 0] * ps + 3] = 10.0 * q[:, 0] \
            / np.linalg.norm(q[:, 0], axis=-1, keepdims=True)
    elif case == "fewer_live_than_topk":
        lens[:], pos[:] = [3, 1, ps, 2], gen0 + [0, 1, 0, ps]
        keep = (j[None] < lens[:, None]) | ((j[None] >= gen0[:, None])
                                            & (j[None] <= pos[:, None]))
    elif case == "inactive_slot":
        lens[1], pos[1] = 0, -1           # what the engine feeds for one
        keep[1] = False
        table[1] = n_pages                # ... and its table: sentinels
    elif case == "bucket_padding":
        # rows between the prompt's end and the bucket hold what an
        # older lease left: huge, and in no extent
        lens[:] = [1, ps + 1, bucket - ps, 7 * ps]
        keep &= (j[None] < lens[:, None]) | (j[None] >= bucket)
        for slot in range(b):
            dead = np.arange(-(-lens[slot] // ps), bucket // ps)
            rows = (table[slot, dead, None] * ps + np.arange(ps)).ravel()
            pool[rows] = 1e30
    elif case == "part_written_page":
        # the rows of the last page beyond pos: unwritten, and huge
        pos[:] = gen0 + [0, 1, ps + 2, 3 * ps - 2]
        keep &= j[None] <= pos[:, None]
        for slot in range(b):
            rows = table[slot, pos[slot] // ps] * ps \
                + np.arange(pos[slot] % ps + 1, ps)
            pool[rows] = -1e30
    elif case == "sentinel_pages":
        # leased pages only under the live rows, sentinels beyond —
        # and the leased ones in descending order
        for slot in range(b):
            last = pos[slot] // ps + 1
            table[slot, :last] = np.sort(table[slot, :last])[::-1]
            table[slot, last:] = n_pages + 7
    else:
        assert case == "random_masks"
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return (cast(q), cast(pool), jnp.asarray(table, jnp.int32),
            *(jnp.asarray(x, jnp.int32) for x in (lens, gen0, pos)),
            jnp.asarray(keep), ps)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", [
    "random_masks", "masked_row_dominates", "fewer_live_than_topk",
    "inactive_slot", "bucket_padding", "part_written_page",
    "sentinel_pages"])
def test_attend_pages_matches_the_gathered_path(case, dtype):
    """The kernel interpreted against ``_paged_gather`` +
    ``_decode_contract``, the path it replaces: float32 to 1e-5,
    bfloat16 to the storage's rounding. (The cases also pass under the
    TPU interpreter — ``pltpu.InterpretParams``: copies land when they
    are waited for, memory never written reads NaN — which is not used
    here: its callbacks run JAX operations of their own and deadlocked
    against the test's under six workers.)"""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops.pallas import paged_attention as pa
    q, pool, table, lens, gen0, pos, keep, ps = _attend_case(case, dtype)
    scale = 0.5 * q.shape[-1] ** -0.5
    # the bf16 cases ask for the first lane tile of the result alone
    vw = 128 if dtype == jnp.bfloat16 else 0
    got = pa.attend_pages(
        q, pool, table, lens, gen0, pos, keep, ps, scale, value_width=vw,
        block_rows=128, interpret=True)
    rows = kv._paged_gather(pool, None, table, ps, dtype)
    want = kv._decode_contract(q[:, None], rows, rows, keep[:, None],
                               dtype, n_kv=1, scale=scale)[:, 0]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if vw:
        assert not got[..., vw:].any()
        got, want = got[..., :vw], want[..., :vw]
    none = ~np.asarray(keep).any(axis=1)
    assert np.isfinite(got).all()
    assert not got[none].any()            # a slot that attends nothing
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got[~none], want[~none],
                               atol=tol * np.abs(want[~none]).max())
    if case == "masked_row_dominates":
        ignored = np.asarray(pool[table[:, 0] * ps + 3], np.float32)
        assert np.abs(got - ignored[:, None, :got.shape[-1]]).max() > 1.0


# ------------------------------------------------------------------ ISSUE 66
# score_pages: the DSA indexer's scores of a slot's live pages in place,
# against the gathered path (the page gather, then mla._slot_scores)

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", [
    "random_masks", "inactive_slot", "bucket_padding", "part_written_page",
    "sentinel_pages"])
def test_score_pages_matches_the_gathered_scores(case, dtype):
    """The kernel interpreted against ``_paged_gather`` +
    ``_slot_scores``, the path it replaces, on every LIVE row (a dead
    row's entry is no score): generated rows that start a block of their
    own, an idle slot and a prompt of no row, prompts shorter than their
    bucket (the padding holds 1e30), a part-written last page, sentinel
    table entries. A table block without a live row is never walked and
    holds zeros."""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import mla
    from paddle_tpu.ops.pallas import paged_attention as pa
    qi, pool, table, lens, gen0, pos, _, ps = _attend_case(case, dtype)
    b, s_len = table.shape[0], table.shape[1] * ps
    wi = jnp.asarray(np.random.RandomState(5).randn(b, qi.shape[1]),
                     jnp.float32)
    got = np.asarray(pa.score_pages(qi, wi, pool, table, lens, gen0, pos,
                                    ps, block_rows=128, interpret=True))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mla._slot_scores(
            qi, wi, kv._paged_gather(pool, None, table, ps, dtype)))
    live = np.asarray(mla.live_rows(jnp.arange(s_len), lens, gen0, pos))
    assert got.shape == want.shape == (b, s_len) and got.dtype == np.float32
    assert live.any(axis=1).sum() == (b - 1 if case == "inactive_slot"
                                      else b)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got[live], want[live],
                               atol=tol * np.abs(want[live]).max())
    dead = ~live.reshape(b, -1, 128).any(axis=-1)
    assert not got.reshape(b, -1, 128)[dead].any()
    if case == "inactive_slot":
        assert dead[1].all()


def test_score_pages_refuses_a_table_without_a_block():
    from paddle_tpu.ops.pallas import paged_attention as pa
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)           # noqa: E731
    with pytest.raises(ValueError, match="no block of whole lane tiles"):
        pa.score_pages(jnp.zeros((2, 4, 128)), jnp.zeros((2, 4)),
                       jnp.zeros((80, 128)), i32(2, 5), i32(2), i32(2),
                       i32(2), 8, interpret=True)


# ------------------------------------------------------------ ISSUEs 58, 62
# attend_pages with a VALUE plane: a full grouped-KV layer's decode
# attention over its live pages in place, against the path it replaces
# (both planes gathered whole, then _decode_contract)

_TWO_PLANE_GEOMETRIES = {
    # tiny twins of the four served geometries that attend in place:
    # heads, KV heads, key head, value head
    "g16-dk192-dv128": (32, 2, 192, 128),     # MiMo: keys of 1.5 lane tiles
    "g8-d128": (16, 2, 128, 128),             # Trinity
    "g4-d128": (8, 2, 128, 128),              # Granite
    # Olmo-Hybrid's multi-head layers, published: a group of ONE, rows
    # of 3 840 + 3 840 (the geometry the block's byte budget is for)
    "g1-h30-d128": (30, 30, 128, 128),
}
_SLOT_STATES = [
    "prompt_short_of_bucket", "nothing_generated", "inactive_and_empty",
    "sentinel_tail", "shared_prefix_page", "one_live_page",
    "every_page_live"]


def _two_plane_case(geometry, state, dtype):
    """(q [B,1,H,Dk], clean key and value planes, the same planes DIRTY,
    table, lens, gen0, pos, active, live, page size) of three slots over
    tables of 32 pages, a bucket of 16, in the slot state that breaks a
    plan. In the dirty planes every page that holds no token of any slot
    — the padding between a prompt's end and its bucket, pages leased
    beyond ``pos``, pages no slot leases (what a sentinel clamps to
    among them) — is NaN, and a dead row INSIDE a live page is 1e4 (it
    is read and masked: its probability is an exact 0, and 0 * NaN would
    be NaN — on the chip such rows are finite by the pool's zero
    start)."""
    h, n_kv, dk, dv = _TWO_PLANE_GEOMETRIES[geometry]
    rng = np.random.RandomState(sum(map(ord, geometry + state)))
    ps = 8 if dtype == jnp.float32 else 16
    b, mp, n_pages = 3, 32, 120
    bucket = 16 * ps
    q = rng.randn(b, 1, h, dk).astype(np.float32)
    keys = rng.randn(n_pages * ps, n_kv * dk).astype(np.float32)
    vals = rng.randn(n_pages * ps, n_kv * dv).astype(np.float32)
    table = rng.permutation(n_pages)[:b * mp].reshape(b, mp)
    gen0 = np.full(b, bucket)
    active = np.ones(b, bool)
    # a prompt that fills its bucket, one that ends inside a page, a
    # short one; generated rows from one to many pages
    lens = np.asarray([bucket, bucket - 2 * ps - 3, 5])
    pos = gen0 + np.asarray([0, 3 * ps + 1, 11 * ps])
    if state == "prompt_short_of_bucket":
        # dead pages between ``lens`` and ``gen0``, whole blocks of them
        lens = np.asarray([bucket - 9 * ps - 3, ps + 1, 2 * ps])
    elif state == "nothing_generated":
        pos = gen0 - 1                      # pos < gen0: the prompt alone
    elif state == "inactive_and_empty":
        # what the engine feeds for a free slot, and a live slot whose
        # rows are none: zeros both, and no page read
        active[0] = False
        lens[1], pos[1] = 0, gen0[1] - 1
    elif state == "sentinel_tail":
        # pages leased only under the live rows, sentinels beyond
        for slot in range(b):
            table[slot, pos[slot] // ps + 1:] = n_pages + slot
    elif state == "shared_prefix_page":
        # the first pages of slot 0's prompt are slot 1's and slot 2's too
        lens[:] = [bucket, 4 * ps + 2, 2 * ps]
        table[1, :4] = table[0, :4]
        table[2, :2] = table[0, :2]
    elif state == "one_live_page":
        lens[:], pos[:] = [3, ps, 0], [bucket - 1, bucket - 1, bucket + 2]
    elif state == "every_page_live":
        lens[:], pos[:] = bucket, mp * ps - 1
    else:
        raise ValueError(state)
    j = np.arange(mp * ps)
    live = ((j[None] < lens[:, None])
            | ((j[None] >= gen0[:, None]) & (j[None] <= pos[:, None])))
    live &= active[:, None]
    holds = np.zeros(n_pages * ps, bool)
    for slot in range(b):
        at = j[live[slot]]
        holds[table[slot, at // ps] * ps + at % ps] = True
    page_live = np.repeat(holds.reshape(n_pages, ps).any(axis=1), ps)
    dirty = []
    for plane in (keys, vals):
        plane = plane.copy()
        plane[~page_live] = np.nan
        plane[page_live & ~holds] = 1e4
        dirty.append(plane)
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    ints = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return (cast(q), cast(keys), cast(vals), cast(dirty[0]), cast(dirty[1]),
            ints(table), ints(lens), ints(gen0), ints(pos),
            jnp.asarray(active), jnp.asarray(live), ps)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("state", _SLOT_STATES)
@pytest.mark.parametrize("geometry", sorted(_TWO_PLANE_GEOMETRIES))
def test_attend_in_place_matches_the_copy_path(geometry, state, dtype):
    """``_attend_in_place`` (the kernel interpreted, blocks of 128 rows
    so that a slot walks several) over the DIRTY planes against
    ``_paged_gather`` + ``_decode_contract`` over the clean ones:
    float32 to 1e-5, bfloat16 to the storage's rounding — the tolerance
    of ``attend_pages``' own tests. Finite everywhere: no page without a
    token was read; a slot with no live row gives zeros."""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops.pallas import paged_attention as pa
    h, n_kv, dk, dv = _TWO_PLANE_GEOMETRIES[geometry]
    (q, keys, vals, dirty_k, dirty_v, table, lens, gen0, pos, active, live,
     ps) = _two_plane_case(geometry, state, dtype)
    scale = 0.05 if dk != dv else None           # MiMo states attn_scale
    want = kv._decode_contract(
        q, kv._paged_gather(keys, None, table, ps, dtype),
        kv._paged_gather(vals, None, table, ps, dtype), live[:, None],
        dtype, n_kv, scale)
    blocks = functools.partial(pa.attend_pages.__wrapped__, block_rows=128,
                               interpret=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "attend_pages", blocks)
        got = kv._attend_in_place(q, dirty_k, dirty_v, table, lens, gen0,
                                  pos, active, live, ps, n_kv, scale)
    assert got.shape == want.shape == (3, 1, h, dv) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    none = ~np.asarray(live).any(axis=1)
    assert np.isfinite(got).all()
    assert not got[none].any()
    if state == "inactive_and_empty":
        assert none.tolist() == [True, True, False]
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got[~none], want[~none],
                               atol=tol * np.abs(want[~none]).max())


def test_attend_pages_refuses_a_cut_value_plane():
    """``value_width`` cuts a row that is key and value at once; a value
    plane of its own is attended whole, and in whole lane tiles."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    q, keys, vals, _, _, table, lens, gen0, pos, _, live, ps = \
        _two_plane_case("g4-d128", "every_page_live", jnp.float32)
    args = (q[:, 0, :, :1].repeat(keys.shape[1], -1), keys, table, lens,
            gen0, pos, live, ps, 1.0)
    with pytest.raises(ValueError, match="value_width"):
        pa.attend_pages(*args, value_width=128, interpret=True, values=vals)
    with pytest.raises(ValueError, match="value_width"):
        pa.attend_pages(*args, interpret=True, values=vals[:, :192])


# the published full-layer geometries of the seven served configurations
# that share ``kv_attention_decode_paged`` (ISSUE 62's table): model
# width, heads, KV heads (None: no groups), key head, value head, rows a
# table, slots, dtype, and the way the chip attends
_SERVED_FULL_LAYERS = {
    "mimo_v2_flash_ep16_d7": (4096, 64, 4, 192, 128, 36864, 24, "bfloat16",
                              "in_place"),
    "trinity_mini_26b_d5": (2048, 32, 4, 128, 128, 20480, 32, "bfloat16",
                            "in_place"),
    # blocks of 256 rows: 176 pages have no longer one of whole tiles
    "granite4_h_small_ep4_d10": (4096, 32, 8, 128, 128, 2816, 128,
                                 "bfloat16", "in_place"),
    # a group of one: rows of 3 840 + 3 840, so blocks of 256 rows (48
    # pages divide the table too, and would ask VMEM for 23.6 MB)
    "olmo_hybrid_7b_pp2_d16": (3840, 30, 30, 128, 128, 8448, 8, "bfloat16",
                               "in_place"),
    # exactly two kernel blocks long: under the floor (condition 4)
    "solar_open2_250b_ep8_d4": (4096, 64, 8, 128, 128, 2048, 128,
                                "bfloat16", "copy"),
    # 268 pages: no block of whole lane tiles divides it (condition 3)
    "lfm2_8b_a1b_d12": (2048, 32, 8, 64, 64, 4288, 64, "bfloat16", "copy"),
    # no grouped heads (condition 1); float32, a table of one block
    "gpt2_medium_d12": (1024, 16, None, 64, 64, 1024, 48, "float32",
                        "copy"),
}


def _served_full_layer(name, ps=16):
    """(attrs, key plane, value plane, page size, a table's pages, the
    expected path) of a served configuration's full layer, as structs."""
    _, h, n_kv, dk, dv, rows, slots, dtype, want = _SERVED_FULL_LAYERS[name]
    attrs = {"n_head": h, "codec": "none"}
    if n_kv is not None:
        attrs.update(n_kv_head=n_kv, head_dim=dk)
        if dv != dk:
            attrs["v_head_dim"] = dv
    pool = lambda w: jax.ShapeDtypeStruct(                     # noqa: E731
        (slots * rows, (n_kv or h) * w), jnp.dtype(dtype))
    return attrs, pool(dk), pool(dv), ps, rows // ps, want


@pytest.mark.parametrize("name", sorted(_SERVED_FULL_LAYERS))
def test_the_table_of_served_geometries_is_the_committed_configs(name):
    """The literal table above against the cells' own files."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", name + ".json")) as f:
        build = json.load(f)["build"]
    m, h, n_kv, dk, dv, rows, slots, dtype, _ = _SERVED_FULL_LAYERS[name]
    assert build["page_size"] == 16 and rows % 16 == 0
    assert rows == max(build["prompt_buckets"]) + build["max_new"]
    assert (m, h, slots) == (build["d_model"], build["n_head"],
                             build["n_slots"])
    assert n_kv == build.get("n_kv_head")
    assert dk == (build.get("head_dim") or build["d_model"] // h)
    assert dv == (build.get("gqa_v_head_dim") or dk)
    assert dtype == (build.get("dtype") or "float32")


@pytest.mark.parametrize("name", sorted(_SERVED_FULL_LAYERS))
def test_which_served_decode_geometries_attend_in_place(name, monkeypatch):
    """The selection from shapes alone, as the chip sees them: four in
    place, three by copy, each by the condition the table names (GLM-5,
    the eighth served configuration, has no such layer: its latent
    plane goes by ``ops/mla.py:attends_in_place``). At every one of
    them the CPU, a mesh of more than one device, an int8 codec and the
    window variant take the copy path."""
    from jax.sharding import Mesh
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import paged_attention as pa
    assert kv.ATTEND_FLOOR_ROWS == 2 * pa._ATTEND_ROWS
    attrs, keys, vals, ps, mp, want = _served_full_layer(name)
    assert not kv.attends_in_place(attrs, keys, vals, None, ps, mp)  # the CPU
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    assert kv.attends_in_place(attrs, keys, vals, None, ps, mp) \
        == (want == "in_place")
    one = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    assert kv.attends_in_place(attrs, keys, vals, None, ps, mp, one) \
        == (want == "in_place")
    four = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    assert not kv.attends_in_place(attrs, keys, vals, None, ps, mp, four)
    codes = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.int8)  # noqa: E731
    scales = jax.ShapeDtypeStruct((keys.shape[0], 4), jnp.float32)
    assert not kv.attends_in_place(attrs, codes(keys), codes(vals), scales,
                                   ps, mp)
    assert not kv.attends_in_place({**attrs, "window": 128}, keys, vals,
                                   None, ps, mp)
    grouped = {**attrs, "n_kv_head": attrs["n_head"],
               "head_dim": keys.shape[1] // attrs["n_head"]} \
        if "n_kv_head" not in attrs else attrs
    # each condition alone: the floor, a table with no block, the groups
    long = 4096 // ps
    assert kv.attends_in_place(grouped, keys, vals, None, ps, long)
    assert not kv.attends_in_place(grouped, keys, vals, None, ps,
                                   kv.ATTEND_FLOOR_ROWS // ps)
    assert kv.attends_in_place(grouped, keys, vals, None, ps,
                               kv.ATTEND_FLOOR_ROWS // ps + 8)
    assert not kv.attends_in_place(grouped, keys, vals, None, ps, long + 1)
    assert not kv.attends_in_place({"n_head": attrs["n_head"]}, keys, vals,
                                   None, ps, long)


# the block ``attend_pages`` walks each in-place configuration's table
# in, and what its tiles (both buffers of both planes) take of VMEM:
# PRs 57 and 58 measured the first three at exactly these blocks
_SERVED_BLOCKS = {
    "mimo_v2_flash_ep16_d7": (64, 2 * 1024 * (768 + 512) * 2),
    "trinity_mini_26b_d5": (64, 2 * 1024 * (512 + 512) * 2),
    "granite4_h_small_ep4_d10": (16, 2 * 256 * (1024 + 1024) * 2),
    "olmo_hybrid_7b_pp2_d16": (16, 2 * 256 * (3840 + 3840) * 2),
}


def test_every_in_place_configuration_has_its_block_in_the_table():
    assert sorted(_SERVED_BLOCKS) == sorted(
        name for name, row in _SERVED_FULL_LAYERS.items()
        if row[-1] == "in_place")


@pytest.mark.parametrize("name", sorted(_SERVED_BLOCKS))
def test_the_block_follows_the_row_width(name, monkeypatch):
    """The selection's block for each in-place configuration: the most
    pages that divide the table, fill whole lane tiles, hold at most
    1 024 rows AND keep the tiles within the kernel's VMEM budget —
    which binds for the dense geometry alone (48 pages divide its table
    and would take 23.6 MB). The engine counts rows by the same block."""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    attrs, keys, vals, ps, mp, _ = _served_full_layer(name)
    pages, tile_bytes = _SERVED_BLOCKS[name]
    row_bytes = pa.attend_row_bytes(keys, vals)
    assert row_bytes == (keys.shape[1] + vals.shape[1]) * 2
    assert kv.in_place_block_pages(attrs, keys, vals, None, ps, mp) == pages
    assert 2 * pages * ps * row_bytes == tile_bytes <= pa._ATTEND_TILE_BYTES
    assert mp % pages == 0 and (pages * ps) % 128 == 0
    assert pages * ps <= pa._ATTEND_ROWS
    # without the budget: the block PR 58 would have asked for
    unbounded = pa.attend_block_pages(mp, ps)
    assert (unbounded > pages) == (name == "olmo_hybrid_7b_pp2_d16")
    if unbounded > pages:
        assert 2 * unbounded * ps * row_bytes == 23592960


@pytest.mark.parametrize("kv_heads,want", [(64, 8), (72, 0)])
def test_rows_too_wide_for_one_lane_tile_of_rows_take_the_copy(
        kv_heads, want, monkeypatch):
    """Where not even a block of one lane tile of rows keeps its tiles
    in the budget the selection says ``copy`` — from the byte count,
    never by failing at the compile — and ``attend_pages`` itself
    refuses the geometry."""
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    S = jax.ShapeDtypeStruct
    ps, mp, b = 16, 512, 2
    plane = S((b * mp * ps, kv_heads * 128), jnp.bfloat16)
    attrs = {"n_head": kv_heads, "n_kv_head": kv_heads, "head_dim": 128,
             "codec": "none"}
    assert kv.in_place_block_pages(attrs, plane, plane, None, ps, mp) == want
    assert kv.attends_in_place(attrs, plane, plane, None, ps, mp) \
        == bool(want)
    if not want:
        with pytest.raises(ValueError, match="no block"):
            jax.eval_shape(
                lambda *a: pa.attend_pages(*a, page_size=ps, scale=1.0,
                                           values=a[1]),
                S((b, kv_heads, plane.shape[1]), jnp.bfloat16), plane,
                S((b, mp), jnp.int32), *[S((b,), jnp.int32)] * 3,
                S((b, mp * ps), jnp.bool_))


def _decode_lowered():
    from paddle_tpu.ops import kv_attention as kv
    return {p: kv.KV_DECODE_ATTEND_LOWERED.labels(path=p).value
            for p in ("in_place", "copy")}


@pytest.mark.parametrize("rows,forced,want", [
    (8192, True, "in_place"), (8192, False, "copy"), (2048, True, "copy")])
def test_the_decode_attend_counter_says_which_path_a_layer_took(
        rows, forced, want, monkeypatch):
    """``paddle_kv_decode_attend_lowered_total{path}`` grows by one a
    full layer and lowering of ``kv_attention_decode_paged``, and by
    none for a window layer; the in-place lowering holds no gather and
    the copy lowering two (``paddle_kv_gather_lowered_total``). A sink
    beside an in-place layer is refused."""
    import types
    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import kv_attention as kv
    from paddle_tpu.ops import pallas as pk
    b, ps, h, n_kv, d, m = 2, 16, 8, 2, 64, 128
    mp = rows // ps
    S = jax.ShapeDtypeStruct
    col = S((b, 1), jnp.int32)
    ins = {"X": [S((b, 1, m), jnp.bfloat16)],
           "Wq": [S((m, h * d), jnp.bfloat16)],
           "Wk": [S((m, n_kv * d), jnp.bfloat16)],
           "Wv": [S((m, n_kv * d), jnp.bfloat16)],
           "Wo": [S((h * d, m), jnp.bfloat16)],
           "PageK": [S((b * mp, ps, n_kv * d), jnp.bfloat16)],
           "PageV": [S((b * mp, ps, n_kv * d), jnp.bfloat16)],
           "PageTable": [S((b, mp), jnp.int32)],
           "Pos": [col], "SeqLen": [col], "GenStart": [col],
           "Active": [col]}
    attrs = {"n_head": h, "n_kv_head": n_kv, "head_dim": d, "codec": "none"}
    if forced:
        monkeypatch.setattr(pk, "on_tpu", lambda: True)
        # the kernels' calls are abstract here: nothing is lowered for
        # a chip this process has not got
        monkeypatch.setattr(pk, "interpret_mode", lambda: True)
    gathers = lambda: sum(                                     # noqa: E731
        kv.KV_GATHER_LOWERED.labels(path=p).value
        for p in ("pages", "rows", "take"))
    Ctx = lambda: types.SimpleNamespace(mesh=None)             # noqa: E731
    emit = get_op("kv_attention_decode_paged").emit
    before, gathered = _decode_lowered(), gathers()
    out = jax.eval_shape(lambda ins: emit(Ctx(), ins, attrs), ins)
    assert out["Out"][0].shape == (b, 1, m)
    grew = {p: n - before[p] for p, n in _decode_lowered().items()}
    assert grew == {p: float(p == want) for p in grew}
    assert gathers() - gathered == (0 if want == "in_place" else 2)
    # a window layer is not counted: it has the copy path alone
    before = _decode_lowered()
    ring = kv.window_ring(32, ps)
    wins = {**ins, "PageTable": [S((b, ring), jnp.int32)],
            "PageK": [S((b * ring, ps, n_kv * d), jnp.bfloat16)],
            "PageV": [S((b * ring, ps, n_kv * d), jnp.bfloat16)]}
    jax.eval_shape(lambda ins: emit(Ctx(), ins, {**attrs, "window": 32}),
                   wins)
    assert _decode_lowered() == before
    if want == "in_place":
        with pytest.raises(ValueError, match="no sink"):
            jax.eval_shape(lambda ins: emit(Ctx(), ins, attrs),
                           {**ins, "Sink": [S((h,), jnp.float32)]})


def test_the_decode_attend_counter_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_kv_decode_attend_lowered_total" in \
        obs_metrics.default_registry().snapshot()


@pytest.mark.parametrize("geometry", sorted(_TWO_PLANE_GEOMETRIES))
def test_the_decode_op_in_place_and_by_copy_agree(geometry, monkeypatch):
    """One full layer's ``kv_attention_decode_paged`` both ways over the
    same pools — the kernel interpreted, engaged as it would be on the
    chip (the floor lowered under this table's rows) — writes the same
    pools, the step's own row among them BEFORE the kernel reads, and
    gives the active slots the same output."""
    import types
    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import kv_attention as kv
    h, n_kv, dk, dv = _TWO_PLANE_GEOMETRIES[geometry]
    q, keys, vals, _, _, table, lens, gen0, pos, active, _, ps = \
        _two_plane_case(geometry, "inactive_and_empty", jnp.float32)
    rng = np.random.RandomState(3)
    b, m = q.shape[0], 128
    w = lambda *shape: jnp.asarray(                            # noqa: E731
        rng.randn(*shape) / np.sqrt(shape[0]), jnp.float32)
    col = lambda x: x.astype(jnp.int32)[:, None]               # noqa: E731
    ins = {"X": jnp.asarray(rng.randn(b, 1, m), jnp.float32),
           "Wq": w(m, h * dk), "Wk": w(m, n_kv * dk), "Wv": w(m, n_kv * dv),
           "Wo": w(h * dv, m),
           "PageK": keys.reshape(-1, ps, n_kv * dk),
           "PageV": vals.reshape(-1, ps, n_kv * dv),
           "PageTable": table, "Pos": col(jnp.maximum(pos, gen0)),
           "SeqLen": col(lens), "GenStart": col(gen0), "Active": col(active)}
    attrs = {"n_head": h, "codec": "none", "n_kv_head": n_kv,
             "head_dim": dk}
    if dv != dk:
        attrs.update(v_head_dim=dv, attn_scale=0.05)

    def emit():
        out = get_op("kv_attention_decode_paged").emit(
            types.SimpleNamespace(mesh=None),
            {k: [v] for k, v in ins.items()}, attrs)
        return {k: np.asarray(v[0]) for k, v in out.items()}
    before = _decode_lowered()
    with jax.default_matmul_precision("highest"):
        by_copy = emit()
        monkeypatch.setattr(
            kv, "_gather_tier", lambda flat, scales, ps, mesh=None: "pages")
        monkeypatch.setattr(kv, "ATTEND_FLOOR_ROWS", table.shape[1] * ps - 1)
        in_place = emit()
    assert {p: n - before[p] for p, n in _decode_lowered().items()} \
        == {"in_place": 1.0, "copy": 1.0}
    on = np.asarray(active)
    for plane in ("PageKOut", "PageVOut"):
        np.testing.assert_array_equal(in_place[plane], by_copy[plane])
    assert (by_copy["PageKOut"] != np.asarray(ins["PageK"])).any()
    assert np.isfinite(in_place["Out"]).all()
    np.testing.assert_allclose(
        in_place["Out"][on], by_copy["Out"][on],
        atol=1e-5 * np.abs(by_copy["Out"][on]).max())


@pytest.mark.parametrize("state", _SLOT_STATES)
def test_attend_rows_read_counts_the_plans_live_blocks(state):
    """What the engine counts a step (numpy, on the host) is what the
    kernel's plan walks: a slot's live blocks, whole."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    *_, table, lens, gen0, pos, active, live, ps = _two_plane_case(
        "g8-d128", state, jnp.bfloat16)
    mp = table.shape[1]
    lens, pos = jnp.where(active, lens, 0), jnp.where(active, pos, -1)
    for block_rows in (128, 256, 512):
        pb = pa.attend_block_pages(mp, ps, block_rows)
        n = np.asarray(pa._attend_plan(table, lens, gen0, pos, ps, pb,
                                       200)[3])
        got = pa.attend_rows_read(np.asarray(lens), np.asarray(gen0),
                                  np.asarray(pos), ps, pb)
        np.testing.assert_array_equal(got, n * pb * ps)
        has = np.asarray(live).any(axis=1)
        assert (got[has] > 0).all() and not got[~has].any()
        assert (got >= np.asarray(live).sum(axis=1)).all()
