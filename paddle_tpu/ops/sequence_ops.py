"""Sequence ops: the reference's LoD (level-of-detail) capability redesigned
for XLA static shapes.

The reference stores a batch of variable-length sequences as one flat tensor
plus LoD offset tables (reference: framework/lod_tensor.h:58-110) and gives
each sequence op a ragged kernel (reference: operators/sequence_ops/ —
sequence_pool_op.cc, sequence_softmax_op.cc, sequence_conv_op.cc,
sequence_expand_op.cc, sequence_concat_op.cc, sequence_reverse_op.h,
sequence_slice_op.cc, sequence_erase_op.cc, sequence_enumerate_op.cc,
sequence_pad_op.cc, sequence_unpad_op.cc, sequence_reshape_op.cc,
sequence_mask_op.cc; edit_distance_op.cc). XLA has no ragged tensors, so the
TPU-native representation is padded ``[B, T, ...]`` + ``SeqLens [B]`` — every
op here is a masked dense computation that XLA fuses and tiles onto the
MXU/VPU; nothing is data-dependently shaped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import first, register_op


def _lens_or_full(seq_lens, B, T, dtype=jnp.int32):
    if seq_lens is None:
        return jnp.full((B,), T, dtype=dtype)
    return seq_lens.reshape(-1).astype(dtype)


def _mask_bt(seq_lens, B, T):
    """[B, T] bool validity mask."""
    lens = _lens_or_full(seq_lens, B, T)
    return jnp.arange(T)[None, :] < lens[:, None]


@register_op("sequence_mask", no_grad=True,
             ref="operators/sequence_ops/sequence_mask_op.cc")
def _sequence_mask(ctx, ins, attrs):
    """X: lengths [B] (or any shape) -> Y [..., maxlen]."""
    x = first(ins, "X")
    maxlen = int(attrs.get("maxlen", -1))
    if maxlen < 0:
        # the reference derives maxlen = max(x) at run time; XLA needs a
        # static extent, so it must be given (sequence_mask_op.cc maxlen attr)
        raise ValueError("sequence_mask on TPU requires a static `maxlen` "
                         "attr (no dynamic output shapes under XLA)")
    dtype = attrs.get("out_dtype", "int64")
    y = (jnp.arange(maxlen)[None, :] < x.reshape(-1, 1)).astype(
        jnp.dtype(dtype if dtype != "int64" else "int32"))
    return {"Y": [y.reshape(tuple(x.shape) + (maxlen,))]}


@register_op("sequence_pool",
             ref="operators/sequence_ops/sequence_pool_op.cc; "
                 "math/sequence_pooling.cc")
def _sequence_pool(ctx, ins, attrs):
    """X [B,T,D] (+ optional SeqLens [B]) -> Out [B,D].
    pooltype: SUM/AVERAGE/SQRT/MAX/LAST/FIRST (OpMaker attr)."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    B, T = x.shape[0], x.shape[1]
    pooltype = str(attrs.get("pooltype", "AVERAGE")).upper()
    # Pallas tier (ops/pallas/seqpool.py): one-pass masked pool on TPU for
    # the plain [B, T, D] SUM/AVG/SQRT cases with lane-aligned D. The
    # kernel keeps an [8, T, D] fp32 block in VMEM, so cap T*D at a ~4 MB
    # budget — beyond that the refer tier's XLA pipeline wins anyway.
    if (x.ndim == 3 and pooltype in ("SUM", "AVERAGE", "SQRT")):
        from paddle_tpu.ops import pallas as pk
        if (pk.kernel_enabled(128, x.shape[2], mesh=ctx.mesh)
                and 8 * T * x.shape[2] * 4 <= 4 * 1024 * 1024):
            lens_ = _lens_or_full(seq_lens, B, T)
            return {"Out": [pk.masked_seqpool(x, lens_, pooltype, False)]}
    mask = _mask_bt(seq_lens, B, T)
    lens = _lens_or_full(seq_lens, B, T).astype(x.dtype)
    fmask = mask.astype(x.dtype).reshape(B, T, *([1] * (x.ndim - 2)))
    lens_b = jnp.maximum(lens, 1).reshape(B, *([1] * (x.ndim - 2)))
    outs = {}
    if pooltype == "SUM":
        out = jnp.sum(x * fmask, axis=1)
    elif pooltype == "AVERAGE":
        out = jnp.sum(x * fmask, axis=1) / lens_b
    elif pooltype == "SQRT":
        out = jnp.sum(x * fmask, axis=1) / jnp.sqrt(lens_b)
    elif pooltype == "MAX":
        neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        masked = jnp.where(fmask > 0, x, neg)
        # zero-length rows pool to 0, not dtype-min (which would overflow
        # downstream matmuls to inf/nan)
        nonempty = (lens > 0).reshape(B, *([1] * (x.ndim - 2)))
        out = jnp.where(nonempty, jnp.max(masked, axis=1), 0)
        outs["MaxIndex"] = [jnp.argmax(masked, axis=1).astype(jnp.int32)]
    elif pooltype == "LAST":
        idx = (_lens_or_full(seq_lens, B, T) - 1).clip(0)
        nonempty = (lens > 0).reshape(B, *([1] * (x.ndim - 2)))
        out = jnp.take_along_axis(
            x, idx.reshape(B, 1, *([1] * (x.ndim - 2))), axis=1
        ).squeeze(1)
        out = jnp.where(nonempty, out, 0)
    elif pooltype == "FIRST":
        nonempty = (lens > 0).reshape(B, *([1] * (x.ndim - 2)))
        out = jnp.where(nonempty, x[:, 0], 0)
    else:
        raise ValueError(f"unknown pooltype {pooltype!r}")
    outs["Out"] = [out]
    return outs


@register_op("sequence_softmax",
             ref="operators/sequence_ops/sequence_softmax_op.cc")
def _sequence_softmax(ctx, ins, attrs):
    """Masked softmax over the time axis of X [B,T] or [B,T,1]."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    x2 = x.reshape(x.shape[0], x.shape[1]) if squeeze else x
    B, T = x2.shape
    mask = _mask_bt(seq_lens, B, T)
    z = jnp.where(mask, x2, jnp.finfo(x2.dtype).min)
    out = jax.nn.softmax(z, axis=1)
    out = jnp.where(mask, out, 0.0).astype(x.dtype)
    if squeeze:
        out = out.reshape(x.shape)
    return {"Out": [out]}


@register_op("sequence_expand",
             ref="operators/sequence_ops/sequence_expand_op.cc")
def _sequence_expand(ctx, ins, attrs):
    """X [B, D] broadcast to Y's time extent: Out [B, T, D] with positions
    past Y's seq_lens zeroed. (The reference repeats each LoD sequence to
    match Y's lod at ref_level; with one-sequence-per-row padding this is a
    masked broadcast.)"""
    x = first(ins, "X")
    y = first(ins, "Y")
    seq_lens = first(ins, "SeqLens")
    B = x.shape[0]
    T = y.shape[1]
    mask = _mask_bt(seq_lens, B, T).astype(x.dtype)
    out = x[:, None, ...] * mask.reshape(B, T, *([1] * (x.ndim - 1)))
    return {"Out": [out]}


@register_op("sequence_expand_as",
             ref="operators/sequence_ops/sequence_expand_as_op.cc")
def _sequence_expand_as(ctx, ins, attrs):
    return _sequence_expand(ctx, ins, attrs)


@register_op("sequence_conv",
             ref="operators/sequence_ops/sequence_conv_op.cc; "
                 "math/context_project.h")
def _sequence_conv(ctx, ins, attrs):
    """X [B,T,D], Filter [ctxLen*D, M] -> Out [B,T,M]. A context window of
    `contextLength` rows starting at `contextStart` (relative, usually
    negative half-window) is flattened per step and hit with one MXU matmul
    — the reference's context_project im2col + gemm, fused."""
    x = first(ins, "X")
    f = first(ins, "Filter")
    seq_lens = first(ins, "SeqLens")
    ctx_len = int(attrs.get("contextLength", 3))
    ctx_start = int(attrs.get("contextStart", -(ctx_len - 1) // 2))
    B, T, D = x.shape
    mask = _mask_bt(seq_lens, B, T).astype(x.dtype)
    xm = x * mask[:, :, None]
    # gather shifted copies: position t sees rows t+ctx_start .. +ctx_len-1
    cols = []
    for k in range(ctx_len):
        shift = ctx_start + k
        idx = jnp.arange(T) + shift
        valid = (idx >= 0) & (idx < T)
        g = jnp.take(xm, idx.clip(0, T - 1), axis=1)
        g = g * valid.astype(x.dtype)[None, :, None]
        # rows outside the *sequence* (>= len) contribute zero via xm
        cols.append(g)
    col = jnp.concatenate(cols, axis=-1)          # [B, T, ctx_len*D]
    out = jnp.einsum("btc,cm->btm", col, f)
    out = out * mask[:, :, None]
    return {"Out": [out]}


@register_op("sequence_concat",
             ref="operators/sequence_ops/sequence_concat_op.cc")
def _sequence_concat(ctx, ins, attrs):
    """Concatenate each row's valid prefix across the X inputs along time.
    inputs: X = [x1 [B,T1,D], x2 [B,T2,D], ...], SeqLens = matching [B]
    int vectors. Out [B, sum(Ti), D], NewLens [B]."""
    xs = ins.get("X") or []
    lens_list = ins.get("SeqLens") or [None] * len(xs)
    B = xs[0].shape[0]
    Tout = sum(int(x.shape[1]) for x in xs)
    feat = xs[0].shape[2:]
    dtype = xs[0].dtype
    out = jnp.zeros((B, Tout) + tuple(feat), dtype=dtype)
    offset = jnp.zeros((B,), dtype=jnp.int32)
    rows = jnp.arange(B)[:, None]
    for x, sl in zip(xs, lens_list):
        T = x.shape[1]
        lens = _lens_or_full(sl, B, T)
        t = jnp.arange(T)[None, :]
        valid = t < lens[:, None]
        dest = jnp.where(valid, offset[:, None] + t, Tout)  # Tout drops
        out = out.at[rows, dest].add(
            jnp.where(valid.reshape(B, T, *([1] * len(feat))), x, 0),
            mode="drop")
        offset = offset + lens
    return {"Out": [out], "NewLens": [offset]}


@register_op("sequence_reverse",
             ref="operators/sequence_ops/sequence_reverse_op.h")
def _sequence_reverse(ctx, ins, attrs):
    """Reverse each row's valid prefix; padding stays in place."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    B, T = x.shape[0], x.shape[1]
    lens = _lens_or_full(seq_lens, B, T)
    t = jnp.arange(T)[None, :]
    idx = jnp.where(t < lens[:, None], lens[:, None] - 1 - t, t)
    out = jnp.take_along_axis(
        x, idx.reshape(B, T, *([1] * (x.ndim - 2))).astype(jnp.int32), axis=1)
    return {"Y": [out], "Out": [out]}


@register_op("sequence_slice",
             ref="operators/sequence_ops/sequence_slice_op.cc")
def _sequence_slice(ctx, ins, attrs):
    """Per-row subsequence: Offset [B], Length [B]. Out [B,T,...] left-aligned
    with NewLens = Length (positions >= Length zeroed)."""
    x = first(ins, "X")
    offset = first(ins, "Offset").reshape(-1).astype(jnp.int32)
    length = first(ins, "Length").reshape(-1).astype(jnp.int32)
    B, T = x.shape[0], x.shape[1]
    t = jnp.arange(T)[None, :]
    idx = (offset[:, None] + t).clip(0, T - 1)
    g = jnp.take_along_axis(
        x, idx.reshape(B, T, *([1] * (x.ndim - 2))), axis=1)
    valid = (t < length[:, None]).reshape(B, T, *([1] * (x.ndim - 2)))
    out = jnp.where(valid, g, 0)
    return {"Out": [out], "NewLens": [length]}


@register_op("sequence_erase", no_grad=True,
             ref="operators/sequence_ops/sequence_erase_op.cc")
def _sequence_erase(ctx, ins, attrs):
    """Remove tokens in attr `tokens` from each row's valid prefix and
    left-compact. X [B,T] int ids -> Out [B,T] (pad 0), NewLens [B]."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    tokens = jnp.asarray(list(attrs.get("tokens", [])) or [-1 << 30],
                         dtype=x.dtype)
    B, T = x.shape
    valid = _mask_bt(seq_lens, B, T)
    keep = valid & ~jnp.isin(x, tokens)
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    dest = jnp.where(keep, pos, T)
    out = jnp.zeros((B, T), dtype=x.dtype).at[
        jnp.arange(B)[:, None], dest].add(
        jnp.where(keep, x, 0), mode="drop")
    new_lens = jnp.sum(keep.astype(jnp.int32), axis=1)
    return {"Out": [out], "NewLens": [new_lens]}


@register_op("sequence_enumerate", no_grad=True,
             ref="operators/sequence_ops/sequence_enumerate_op.cc")
def _sequence_enumerate(ctx, ins, attrs):
    """Sliding windows of ids: X [B,T] -> Out [B,T,win]; window positions
    past the end filled with pad_value."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    win = int(attrs.get("win_size", 2))
    pad_value = attrs.get("pad_value", 0)
    B, T = x.shape
    lens = _lens_or_full(seq_lens, B, T)
    t = jnp.broadcast_to(
        jnp.arange(T)[None, :, None] + jnp.arange(win)[None, None, :],
        (B, T, win))
    in_seq = t < lens[:, None, None]
    g = jnp.take_along_axis(
        x, t.reshape(B, -1).clip(0, T - 1), axis=1).reshape(B, T, win)
    out = jnp.where(in_seq, g, jnp.asarray(pad_value, dtype=x.dtype))
    return {"Out": [out]}


@register_op("sequence_pad",
             ref="operators/sequence_ops/sequence_pad_op.cc")
def _sequence_pad(ctx, ins, attrs):
    """Set positions past each row's seq_len to PadValue. (The reference
    converts LoD-ragged -> padded; our tensors are already padded, so this
    normalizes the padding region.) Outputs Out and Length."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    pv = first(ins, "PadValue")
    if pv is None:
        pv = jnp.asarray(attrs.get("pad_value", 0.0), dtype=x.dtype)
    B, T = x.shape[0], x.shape[1]
    # honor padded_length (reference attr): pad or truncate the time extent
    padded_len = int(attrs.get("padded_length", -1))
    if padded_len > 0 and padded_len != T:
        if padded_len > T:
            fill = jnp.zeros((B, padded_len - T) + x.shape[2:], dtype=x.dtype)
            x = jnp.concatenate([x, fill], axis=1)
        else:
            x = x[:, :padded_len]
        T = padded_len
    mask = _mask_bt(seq_lens, B, T).reshape(B, T, *([1] * (x.ndim - 2)))
    out = jnp.where(mask, x, jnp.broadcast_to(pv, x.shape).astype(x.dtype))
    lens = _lens_or_full(seq_lens, B, T).clip(0, T)
    return {"Out": [out], "Length": [lens]}


@register_op("sequence_unpad",
             ref="operators/sequence_ops/sequence_unpad_op.cc")
def _sequence_unpad(ctx, ins, attrs):
    """Inverse of sequence_pad. XLA cannot produce the reference's ragged
    flat output, so the unpadded form is the padded tensor with the pad
    region zeroed + Length — the (tensor, seq_lens) pair IS our LoD."""
    x = first(ins, "X")
    length = first(ins, "Length")
    B, T = x.shape[0], x.shape[1]
    mask = _mask_bt(length, B, T).reshape(B, T, *([1] * (x.ndim - 2)))
    return {"Out": [jnp.where(mask, x, 0)],
            "Length": [_lens_or_full(length, B, T)]}


@register_op("sequence_reshape",
             ref="operators/sequence_ops/sequence_reshape_op.cc")
def _sequence_reshape(ctx, ins, attrs):
    """[B, T, D] -> [B, T*D//new_dim, new_dim]; lens scale by D/new_dim."""
    x = first(ins, "X")
    seq_lens = first(ins, "SeqLens")
    new_dim = int(attrs["new_dim"])
    B, T, D = x.shape
    out = x.reshape(B, T * D // new_dim, new_dim)
    lens = _lens_or_full(seq_lens, B, T) * D // new_dim
    return {"Out": [out], "NewLens": [lens]}


@register_op("edit_distance", no_grad=True,
             ref="operators/edit_distance_op.cc")
def _edit_distance(ctx, ins, attrs):
    """Levenshtein distance per row. Hyps [B,T1] + HypLens, Refs [B,T2] +
    RefLens; attr `normalized` divides by ref length. Out [B,1],
    SequenceNum [1]. Dynamic program as a lax.scan over hyp positions with
    an associative-min inner scan over ref positions."""
    hyp = first(ins, "Hyps")
    ref = first(ins, "Refs")
    hyp_lens = _lens_or_full(first(ins, "HypsLens"), hyp.shape[0],
                             hyp.shape[1])
    ref_lens = _lens_or_full(first(ins, "RefsLens"), ref.shape[0],
                             ref.shape[1])
    normalized = bool(attrs.get("normalized", False))
    T1, T2 = hyp.shape[1], ref.shape[1]

    def one(h, r, hl, rl):
        row0 = jnp.arange(T2 + 1, dtype=jnp.float32)

        def outer(dp, i):
            hi = h[i]
            sub_cost = (r != hi).astype(jnp.float32)      # [T2]

            def inner(left, j):
                val = jnp.minimum(jnp.minimum(dp[j + 1] + 1.0, left + 1.0),
                                  dp[j] + sub_cost[j])
                return val, val

            first_col = (i + 1).astype(jnp.float32)
            _, rest = lax.scan(inner, first_col, jnp.arange(T2))
            new_dp = jnp.concatenate([first_col[None], rest])
            return new_dp, new_dp

        _, rows = lax.scan(outer, row0, jnp.arange(T1))
        all_rows = jnp.concatenate([row0[None, :], rows], axis=0)
        return all_rows[hl, rl]

    d = jax.vmap(one)(hyp, ref, hyp_lens, ref_lens)
    if normalized:
        d = d / jnp.maximum(ref_lens.astype(jnp.float32), 1.0)
    return {"Out": [d.reshape(-1, 1)],
            "SequenceNum": [jnp.asarray([hyp.shape[0]], dtype=jnp.int32)]}
