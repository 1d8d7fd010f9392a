"""Fused multi-head attention BLOCK: q/k/v/out projections + attention
dots + softmax(+dropout) as one custom-VJP region with hand-spelled
gradients.

Why (measured on v5e, Transformer-base bs128: docs/performance.md):
composed XLA attention spends ~7.4 ms/step in layout copies — the
q/k/v/ctx (fwd) and grad (bwd) relayouts between the T-major residual
stream ([B,T,H,D]) and the (b,h)-batch attention dots ([B,H,T,K]).
A dot_general's output is always batch-major, so every grad that must
"return to [B,T,H,D]" materializes a transpose — IF it is ever
materialized in that layout. This block never does: the region's
boundary tensors are the T-major residual stream (x_q, x_kv, dout) and
the weights; every internal tensor is consumed by the next dot_general
*in the layout the previous one produced*:

  fwd: q/k/v land [B,T,H,Dk] (projection dot: lhs-free order, a free
       reshape of [B,T,M]); the attention dots take them with batch dims
       IN PLACE ((0,2)); ctx lands [B,H,T,Dk] and the out-projection
       contracts its (h,d) dims directly — zero transposes.
  bwd: d_ctx lands [B,T,H,Dk] (lhs-free order again) and feeds the dp
       dot with batch dims in place; dq/dk/dv land batch-major
       [B,H,T,Dk] and the projection backward contracts their (h,d)/
       (b,t) dims directly into dx [B,T,M] and dW — zero transposes.

The reference composes this from matmul/softmax/transpose ops
(benchmark transformer prep; operators/fused/fused_attention exists only
in later reference versions) — this is the TPU-native fused form.

Numerics match parallel/ring_attention.full_attention: fp32 MXU
accumulation via preferred_element_type, softmax in fp32, probabilities
stored/applied in the storage dtype, attention-weight dropout
(upscale_in_train) via the same hash_keep_mask as the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG = -2.0 ** 30


def _keep_mask(seed, b, h, tq, tk, dropout_p):
    from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
    s = jnp.asarray(seed, jnp.int32).reshape(-1)[0]
    bh = jnp.arange(b * h).reshape(b, h, 1, 1)
    qpos = (tk - tq) + jnp.arange(tq)
    return hash_keep_mask(s, bh, qpos[None, None, :, None],
                          jnp.arange(tk)[None, None, None, :], dropout_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def attention_block(x_q, x_kv, wq, wk, wv, wo, seed,
                    n_head, causal, dropout_p):
    """x_q [B,Tq,M], x_kv [B,Tk,M], w* [M,M] → [B,Tq,M].
    seed: int32 scalar (traced ok; only read when dropout_p > 0)."""
    out, _ = _fwd_impl(x_q, x_kv, wq, wk, wv, wo, seed,
                       n_head, causal, dropout_p)
    return out


def _proj(x, w, h):
    """[B,T,M] @ [M,H,Dk] → [B,T,H,Dk]: lhs-free output order IS the
    T-major layout; no transpose exists to fold or materialize."""
    m = w.shape[0]
    w4 = w.reshape(m, h, m // h)
    return jax.lax.dot_general(x, w4, (((2,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


def _fwd_impl(x_q, x_kv, wq, wk, wv, wo, seed, n_head, causal, dropout_p):
    b, tq, m = x_q.shape
    tk = x_kv.shape[1]
    h, d = n_head, m // n_head
    scale = float(d) ** -0.5

    q = _proj(x_q, wq, h)                       # [B,Tq,H,D]
    k = _proj(x_kv, wk, h)                      # [B,Tk,H,D]
    v = _proj(x_kv, wv, h)                      # [B,Tk,H,D]

    # batch dims (b, h) IN PLACE — no operand relayout. At long T the
    # [B,H,Tq,Tk] score tensor crosses the dot→softmax fusion boundary in
    # the STORAGE dtype (at T=512 the fp32 form was 26 ms/step of
    # HBM-bound matmul fusions at 855 GB/s — half of it the extra fp32
    # bytes; measured +7.6% step time recovered). At shorter T the same
    # cast BREAKS a fusion XLA would otherwise form and costs ~1.5 MFU
    # points (T=256 measured) — so it is size-gated. Softmax math is fp32
    # in-register either way.
    s = jax.lax.dot_general(q, k, (((3,), (3,)), ((0, 2), (0, 2))),
                            preferred_element_type=jnp.float32)
    if tq * tk >= 512 * 512:
        s = s.astype(x_q.dtype)
    s = s.astype(jnp.float32) * scale
    if causal:
        qp = jnp.arange(tq) + (tk - tq)
        s = jnp.where((qp[:, None] >= jnp.arange(tk)[None, :])[None, None],
                      s, _NEG)
    p = jax.nn.softmax(s, axis=-1)              # fp32 [B,H,Tq,Tk]
    pd = p
    if dropout_p > 0:
        pd = p * _keep_mask(seed, b, h, tq, tk, dropout_p)
    pd = pd.astype(x_q.dtype)                   # storage dtype for the MXU

    # [B,H,Tq,Tk] x [B,Tk,H,D] → [B,H,Tq,D]; batch dims in place again
    ctx = jax.lax.dot_general(pd, v, (((3,), (1,)), ((0, 1), (0, 2))),
                              preferred_element_type=jnp.float32
                              ).astype(x_q.dtype)

    # out[b,q,n] = ctx[b,h,q,d] · wo[(h,d),n] — contracts (h, d) directly
    # from ctx's batch-major layout; output order (b, q, n) is T-major
    wo3 = wo.reshape(h, d, m)
    out = jax.lax.dot_general(ctx, wo3, (((1, 3), (0, 1)), ((), ())),
                              preferred_element_type=jnp.float32
                              ).astype(x_q.dtype)
    # p (not pd) is the residual: backward regenerates the keep mask from
    # the seed, exactly like the flash kernels
    return out, (x_q, x_kv, wq, wk, wv, wo, seed, q, k, v,
                 p.astype(x_q.dtype), ctx)


def _vjp_fwd(x_q, x_kv, wq, wk, wv, wo, seed, n_head, causal, dropout_p):
    return _fwd_impl(x_q, x_kv, wq, wk, wv, wo, seed,
                     n_head, causal, dropout_p)


def _vjp_bwd(n_head, causal, dropout_p, res, dout):
    x_q, x_kv, wq, wk, wv, wo, seed, q, k, v, p_st, ctx = res
    b, tq, m = x_q.shape
    tk = x_kv.shape[1]
    h, d = n_head, m // n_head
    scale = float(d) ** -0.5
    dt = x_q.dtype
    wo3 = wo.reshape(h, d, m)

    # dWo[h,d,n] = ctx[b,h,q,d] · dout[b,q,n] over (b, q) — both operands
    # consumed in their stored layouts
    dwo = jax.lax.dot_general(ctx, dout, (((0, 2), (0, 1)), ((), ())),
                              preferred_element_type=jnp.float32
                              ).astype(dt).reshape(m, m)

    # d_ctx lands [B,Tq,H,D] (lhs-free order) — the T-major layout, which
    # the dp dot below takes with batch dims in place; no transpose
    dctx = jax.lax.dot_general(dout, wo3, (((2,), (2,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(dt)

    # dp[b,h,q,k] = dctx[b,q,h,d] · v[b,k,h,d] — same dot shape as fwd s;
    # crosses the fusion boundary in the storage dtype at long T
    # (size-gated like the forward score tensor, see _fwd_impl)
    dpd = jax.lax.dot_general(dctx, v, (((3,), (3,)), ((0, 2), (0, 2))),
                              preferred_element_type=jnp.float32)
    if tq * tk >= 512 * 512:
        dpd = dpd.astype(dt)
    dpd = dpd.astype(jnp.float32)

    p32 = p_st.astype(jnp.float32)
    if dropout_p > 0:
        keep = _keep_mask(seed, b, h, tq, tk, dropout_p)
        dp = dpd * keep
        pd_st = (p32 * keep).astype(dt)
    else:
        dp = dpd
        pd_st = p_st
    # softmax vjp (rows where p == 0 under the causal mask give ds == 0)
    ds = (p32 * (dp - jnp.sum(dp * p32, axis=-1, keepdims=True)) * scale
          ).astype(dt)

    # dv[b,h,k,d] = pd[b,h,q,k] · dctx[b,q,h,d] over q, batch (b, h) in
    # place on both operands
    dv = jax.lax.dot_general(pd_st, dctx, (((2,), (1,)), ((0, 1), (0, 2))),
                             preferred_element_type=jnp.float32).astype(dt)
    # dq[b,h,q,d] = ds[b,h,q,k] · k[b,k,h,d];  dk[b,h,k,d] = dsᵀ · q
    dq = jax.lax.dot_general(ds, k, (((3,), (1,)), ((0, 1), (0, 2))),
                             preferred_element_type=jnp.float32).astype(dt)
    dk = jax.lax.dot_general(ds, q, (((2,), (1,)), ((0, 1), (0, 2))),
                             preferred_element_type=jnp.float32).astype(dt)

    # projection backward consumes the batch-major grads DIRECTLY:
    #   dx[b,t,m] contracts their (h, d) dims against W,
    #   dW[m,h,d]  contracts their (b, t) dims against x —
    # neither ever needs them in [B,T,H,D]
    def dx_of(g, w):                      # g [B,H,T,D], w [M,M]
        w4 = w.reshape(m, h, d)
        return jax.lax.dot_general(g, w4, (((1, 3), (1, 2)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ).astype(dt)

    def dw_of(x, g):                      # x [B,T,M], g [B,H,T,D]
        return jax.lax.dot_general(x, g, (((0, 1), (0, 2)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ).astype(dt).reshape(m, m)

    dx_q = dx_of(dq, wq)
    dx_kv = dx_of(dk, wk) + dx_of(dv, wv)
    dwq, dwk, dwv = dw_of(x_q, dq), dw_of(x_kv, dk), dw_of(x_kv, dv)

    return (dx_q, dx_kv, dwq, dwk, dwv, dwo, _zero_seed_cot(seed))


def _zero_seed_cot(seed):
    if seed is None:
        return None
    import numpy as _np
    return _np.zeros(jnp.shape(seed), dtype=jax.dtypes.float0)


attention_block.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def flash_block(x_q, x_kv, wq, wk, wv, wo, seed, n_head, causal, dropout_p,
                bq, interpret, mesh=None, data_axis=None):
    """``attention_block``'s contract with the core — scores, mask,
    softmax, dropout, ``p . V`` and their backward — in the Pallas
    kernels of ``ops/pallas/flash_pairs.py`` (heads of 64, two a lane
    tile, ``Tq == Tk`` in one key block). q, k, v and o stay
    ``[B, T, M]``, the projections' output viewed flat, so the four
    projections and their backward are plain matrix products; no
    ``[B, H, Tq, Tk]`` tensor and no ``[B, H, T, D]`` relayout reaches
    HBM. The backward takes o from its own kernel, not from the
    forward's (``flash_pairs``' docstring).

    ``mesh`` / ``data_axis``: under a mesh of several devices, all along
    ``data_axis``, which divides the batch, the two kernels — and
    nothing else — run inside a ``shard_map`` over it (``_on_shards``):
    XLA cannot partition a Mosaic call, and partitions the projections
    and their gradients around the manual region as it does for the
    composed block."""
    return _flash_fwd(x_q, x_kv, wq, wk, wv, wo, seed, n_head, causal,
                      dropout_p, bq, interpret, mesh, data_axis)[0]


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(a.dtype)


def _on_shards(kernel, mesh, data_axis, n_head):
    """``kernel(seed, *rows)`` — a ``flash_pairs`` call closed over its
    static arguments, every array ``[B, T, M]`` in and out — on each
    device's rows of the batch. The kernels' programs are batch-parallel,
    so nothing crosses the axis. The dropout bits stay the GLOBAL row's:
    a kernel hashes ``seed + (b * H + h) * 0x27D4EB2F`` in uint32
    (``flash_attention._seed_mix``) with ``b`` its program id, the local
    row, so a shard whose first row is ``b0`` is handed
    ``seed + b0 * H * 0x27D4EB2F`` and draws what the unmapped call draws
    on the whole batch (and the composed block under GSPMD). The results
    of a ``pallas_call`` carry no replication annotation: ``check_vma``
    is off, as in ``ring_attention.sp_attention``."""
    if mesh is None or mesh.size == 1:
        return kernel
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.ops.pallas.flash_attention import _seed_mix

    def shard(seed, rows):
        b0 = jax.lax.axis_index(data_axis) * rows[0].shape[0]
        seed = jax.lax.bitcast_convert_type(
            _seed_mix(seed, b0 * n_head), jnp.int32)
        return kernel(seed, *rows)

    spec = P(data_axis, None, None)     # a prefix: every array in and out
    mapped = jax.shard_map(shard, mesh=mesh, in_specs=(P(None), spec),
                           out_specs=spec, check_vma=False)
    return lambda seed, *rows: mapped(seed, rows)


def _flash_fwd(x_q, x_kv, wq, wk, wv, wo, seed, n_head, causal, dropout_p,
               bq, interpret, mesh, data_axis):
    from paddle_tpu.ops.pallas.flash_pairs import pairs_forward
    q, k, v = (_mm(x, w, ((2,), (0,)))
               for x, w in ((x_q, wq), (x_kv, wk), (x_kv, wv)))
    o = _on_shards(
        lambda seed, q, k, v: pairs_forward(
            q, k, v, seed, n_head, causal, dropout_p, bq, interpret),
        mesh, data_axis, n_head)(seed, q, k, v)
    return _mm(o, wo, ((2,), (0,))), (x_q, x_kv, wq, wk, wv, wo, seed,
                                      q, k, v)


def _flash_bwd(n_head, causal, dropout_p, bq, interpret, mesh, data_axis,
               res, dout):
    from paddle_tpu.ops.pallas.flash_pairs import pairs_backward
    x_q, x_kv, wq, wk, wv, wo, seed, q, k, v = res
    do = _mm(dout, wo, ((2,), (1,)))
    dq, dk, dv, o = _on_shards(
        lambda seed, q, k, v, do: pairs_backward(
            q, k, v, do, seed, n_head, causal, dropout_p, bq, interpret),
        mesh, data_axis, n_head)(seed, q, k, v, do)

    def dx(g, w):                       # [B,T,M] . w^T
        return _mm(g, w, ((2,), (1,)))

    def dw(x, g):                       # over (b, t)
        return _mm(x, g, ((0, 1), (0, 1)))

    return (dx(dq, wq), dx(dk, wk) + dx(dv, wv), dw(x_q, dq), dw(x_kv, dk),
            dw(x_kv, dv), dw(o, dout), _zero_seed_cot(seed))


flash_block.defvjp(_flash_fwd, _flash_bwd)
