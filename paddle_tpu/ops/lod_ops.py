"""LoD-tensor infrastructure ops + fused CPU-tier op parity.

Reference targets: operators/lod_reset_op.cc, lod_rank_table_op.cc,
lod_array_length_op.cc, array_to_lod_tensor_op.cc, lod_tensor_to_array_op.cc,
controlflow/tensor_array_read_write_op.cc (write_to_array/read_from_array
registered names), split_lod_tensor_op.cc, merge_lod_tensor_op.cc,
reorder_lod_tensor_by_rank_op.cc, shrink_rnn_memory_op.cc,
rnn_memory_helper_op.cc, max_sequence_len_op.cc, recurrent_op.cc,
sequence_ops/sequence_scatter_op.cc, tensor_array_to_tensor (1.3);
fused tier: fused/fused_embedding_seq_pool_op.cc, fused/fusion_gru_op.cc,
fused/fusion_lstm_op.cc, fused/fused_elemwise_activation_op.cc,
fused/fusion_seqpool_concat_op.cc, fused/fusion_transpose_flatten_concat_op.cc,
fused/fusion_seqconv_eltadd_relu_op.cc, fused/fusion_seqexpand_concat_fc_op.cc,
fused/conv_fusion_op.cc, operators/lstmp_op.cc, operators/gru_op.cc,
operators/lstm_op.cc, fused/attention_lstm_op.cc.

TPU redesign notes:
- LoD structure is carried as SeqLens [B] beside padded tensors (see
  paddle_tpu/ops/sequence_ops.py); "rank tables" become explicit sorted
  index vectors.
- split/merge_lod_tensor keep static shapes: split emits full-size masked
  copies, merge re-selects rows by the mask — the IfElse capability without
  data-dependent row counts.
- The reference's fused CPU ops exist because its interpreter can't fuse;
  XLA fuses automatically, so these emitters simply compose the primitive
  emitters — registered for program-level parity (a reference program using
  fusion_gru runs unchanged) while compiling to the same fused HLO the
  unfused graph would.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import first, get_op, register_op, single
from paddle_tpu.ops.sequence_ops import _mask_bt


def _alias(new_name, existing, ref):
    spec = get_op(existing)

    @register_op(new_name, no_grad=spec.no_grad, ref=ref)
    def _emit(ctx, ins, attrs, _spec=spec):
        return _spec.emit(ctx, ins, attrs)
    return _emit


_alias("write_to_array", "array_write",
       "operators/controlflow/tensor_array_read_write_op.cc WriteToArray")
_alias("read_from_array", "array_read",
       "operators/controlflow/tensor_array_read_write_op.cc ReadFromArray")
_alias("lod_array_length", "array_length",
       "operators/lod_array_length_op.cc")
_alias("gru", "dynamic_gru", "operators/gru_op.cc (sequence GRU)")
_alias("lstm", "dynamic_lstm", "operators/lstm_op.cc (sequence LSTM)")
_alias("recurrent", "scan",
       "operators/recurrent_op.cc RecurrentOp (StaticRNN backend) — same "
       "scan lowering as the scan op")


@register_op("lod_reset", ref="operators/lod_reset_op.cc")
def _lod_reset(ctx, ins, attrs):
    """Re-associate sequence lengths: X stays, lengths come from Y's lens
    or the target_lod attr (offsets converted to lengths)."""
    x = first(ins, "X")
    y_lens = first(ins, "YLens")
    if y_lens is None:
        y_lens = first(ins, "Y")
    if y_lens is not None:
        lens = y_lens.reshape(-1).astype(jnp.int32)
    else:
        lod = [int(v) for v in attrs["target_lod"]]
        lens = jnp.asarray(np.diff(np.asarray(lod)), jnp.int32)
    return {"Out": [x], "OutLens": [lens]}


@register_op("lod_rank_table", no_grad=True,
             ref="operators/lod_rank_table_op.cc")
def _lod_rank_table(ctx, ins, attrs):
    """Sort batch items by descending length: Index [B] (original row per
    rank), Lens [B] (sorted lengths). The explicit-tensor form of the
    reference's LoDRankTable (framework/lod_rank_table.h)."""
    lens = first(ins, "SeqLens")
    if lens is None:
        x = first(ins, "X")
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    lens = lens.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(-lens, stable=True)
    return {"Index": [order.astype(jnp.int32)], "Lens": [lens[order]]}


@register_op("max_sequence_len", no_grad=True,
             ref="operators/max_sequence_len_op.cc")
def _max_sequence_len(ctx, ins, attrs):
    lens = first(ins, "RankTable")
    if lens is None:
        lens = first(ins, "SeqLens")
    return single(jnp.max(lens.reshape(-1)).astype(jnp.int64))


@register_op("reorder_lod_tensor_by_rank",
             ref="operators/reorder_lod_tensor_by_rank_op.cc")
def _reorder_by_rank(ctx, ins, attrs):
    x = first(ins, "X")
    order = first(ins, "RankTable").reshape(-1).astype(jnp.int32)
    return single(x[order])


@register_op("lod_tensor_to_array", ref="operators/lod_tensor_to_array_op.cc")
def _lod_tensor_to_array(ctx, ins, attrs):
    """Padded [B, T, ...] → time-major array tensor [T, B, ...] (the
    fixed-capacity tensor-array convention of control_flow.py)."""
    x = first(ins, "X")
    return single(jnp.moveaxis(x, 1, 0))


@register_op("array_to_lod_tensor", ref="operators/array_to_lod_tensor_op.cc")
def _array_to_lod_tensor(ctx, ins, attrs):
    x = first(ins, "X")                  # [T, B, ...]
    return single(jnp.moveaxis(x, 0, 1))


@register_op("split_lod_tensor", ref="operators/split_lod_tensor_op.cc")
def _split_lod_tensor(ctx, ins, attrs):
    """Static-shape IfElse split: both outputs keep X's shape; rows not
    selected are zeroed and flagged in the companion masks."""
    x = first(ins, "X")
    mask = first(ins, "Mask").reshape(-1)
    m = mask.astype(bool)
    bshape = (-1,) + (1,) * (x.ndim - 1)
    mt = m.reshape(bshape)
    return {"OutTrue": [jnp.where(mt, x, 0)],
            "OutFalse": [jnp.where(mt, jnp.zeros_like(x), x)]}


@register_op("merge_lod_tensor", ref="operators/merge_lod_tensor_op.cc")
def _merge_lod_tensor(ctx, ins, attrs):
    in_true = first(ins, "InTrue")
    in_false = first(ins, "InFalse")
    mask = first(ins, "Mask").reshape(-1).astype(bool)
    bshape = (-1,) + (1,) * (in_true.ndim - 1)
    return single(jnp.where(mask.reshape(bshape), in_true, in_false))


@register_op("shrink_rnn_memory", ref="operators/shrink_rnn_memory_op.cc")
def _shrink_rnn_memory(ctx, ins, attrs):
    """Masked form of per-step batch shrinking: rows whose sequence ended
    before step I keep their previous value zeroed-out contribution (the
    reference physically shrinks the batch using the rank table)."""
    x = first(ins, "X")
    i = first(ins, "I").reshape(()).astype(jnp.int32)
    lens = first(ins, "RankTableLens").reshape(-1)
    alive = (i < lens).astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
    return single(x * alive)


@register_op("rnn_memory_helper", ref="operators/rnn_memory_helper_op.cc")
def _rnn_memory_helper(ctx, ins, attrs):
    return single(first(ins, "X"))


@register_op("sequence_scatter",
             ref="operators/sequence_ops/sequence_scatter_op.cc")
def _sequence_scatter(ctx, ins, attrs):
    """X [B, D]; Ids [B, S] (pad -1), Updates [B, S] → out[b, ids[b,s]] +=
    upd[b,s] (padded form of the per-sequence LoD scatter)."""
    x = first(ins, "X")
    ids = first(ins, "Ids").astype(jnp.int32)
    upd = first(ins, "Updates")
    valid = ids >= 0
    safe = jnp.clip(ids, 0, x.shape[1] - 1)

    def one(xr, ir, ur, vr):
        return xr.at[ir].add(jnp.where(vr, ur, 0.0))

    return single(jax.vmap(one)(x, safe, upd, valid))


@register_op("tensor_array_to_tensor",
             ref="operators/tensor_array_to_tensor_op.cc")
def _tensor_array_to_tensor(ctx, ins, attrs):
    xs = ins.get("X", [])
    axis = attrs.get("axis", 0)
    if attrs.get("use_stack", False):
        out = jnp.stack(xs, axis=axis)
    else:
        out = jnp.concatenate(xs, axis=axis)
    idx = jnp.asarray([x.shape[axis] for x in xs], jnp.int32)
    return {"Out": [out], "OutIndex": [idx]}


# ---------------------------------------------------------------------------
# fused tier — compositions of primitive emitters
# ---------------------------------------------------------------------------

@register_op("fused_embedding_seq_pool",
             ref="operators/fused/fused_embedding_seq_pool_op.cc")
def _fused_embedding_seq_pool(ctx, ins, attrs):
    """lookup_table + sum-pool over time: W [V, D], Ids [B, T] (pad 0 with
    SeqLens mask) → [B, D]."""
    w = first(ins, "W")
    ids = first(ins, "Ids").astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    lens = first(ins, "SeqLens")
    # Pallas tier (ops/pallas/embed_pool.py): gather + masked sum-pool in
    # ONE pass on TPU for lane-aligned tables — the [B, T, D] gathered
    # intermediate never reaches HBM. The jnp composition below is the
    # refer/interpreter tier (and the only tier off-TPU).
    if w.ndim == 2 and ids.ndim == 2:
        from paddle_tpu.ops import pallas as pk
        if pk.kernel_enabled(128, w.shape[1], mesh=ctx.mesh):
            return single(pk.fused_embed_seq_pool(w, ids, lens,
                                                  pk.interpret_mode()))
    emb = w[ids]                                   # [B, T, D]
    if lens is not None:
        mask = _mask_bt(lens, ids.shape[0], ids.shape[1]).astype(emb.dtype)
        emb = emb * mask[:, :, None]
    return single(jnp.sum(emb, axis=1))


@register_op("fusion_seqpool_concat",
             ref="operators/fused/fusion_seqpool_concat_op.cc")
def _fusion_seqpool_concat(ctx, ins, attrs):
    """Pool each [B, T, D] input over time (SUM/AVG/SQRT like
    sequence_pool) and concat features."""
    ptype = attrs.get("pooltype", "SUM").upper()
    lens_list = ins.get("SeqLens", [])
    outs = []
    for i, x in enumerate(ins.get("X", [])):
        t = x.shape[1]
        lens = lens_list[i] if i < len(lens_list) else None
        if lens is not None:
            mask = _mask_bt(lens, x.shape[0], t).astype(x.dtype)
            xm = x * mask[:, :, None]
            denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
        else:
            xm = x
            denom = jnp.full((x.shape[0], 1), float(t), x.dtype)
        s = jnp.sum(xm, axis=1)
        if ptype == "AVERAGE":
            s = s / denom
        elif ptype == "SQRT":
            s = s / jnp.sqrt(denom)
        outs.append(s)
    return single(jnp.concatenate(outs, axis=1))


@register_op("fused_elemwise_activation",
             ref="operators/fused/fused_elemwise_activation_op.cc")
def _fused_elemwise_activation(ctx, ins, attrs):
    """functor_list like ['elementwise_add', 'relu'] (binary then unary) or
    ['relu', 'elementwise_add'] (unary-of-Y then binary)."""
    x = first(ins, "X")
    y = first(ins, "Y")
    functors = [f.lower() for f in attrs["functor_list"]]
    unary = {"relu": lambda v: jnp.maximum(v, 0.0),
             "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
             "scale": lambda v: v * attrs.get("scale", 1.0),
             "gelu": jax.nn.gelu}
    binary = {"elementwise_add": jnp.add, "elementwise_sub": jnp.subtract,
              "elementwise_mul": jnp.multiply}
    f0, f1 = functors[0], functors[1]
    if f0 in binary:
        out = unary[f1](binary[f0](x, y))
        inter = binary[f0](x, y)
    else:
        inter = unary[f0](y)
        out = binary[f1](x, inter)
    return {"Out": [out], "IntermediateOut": [inter]}


@register_op("fusion_transpose_flatten_concat",
             ref="operators/fused/fusion_transpose_flatten_concat_op.cc")
def _fusion_tfc(ctx, ins, attrs):
    trans = [int(a) for a in attrs.get("trans_axis", [0, 2, 3, 1])]
    flat_axis = int(attrs.get("flatten_axis", 1))
    concat_axis = int(attrs.get("concat_axis", 1))
    outs = []
    for x in ins.get("X", []):
        t = jnp.transpose(x, trans)
        lead = int(np.prod(t.shape[:flat_axis])) if flat_axis > 0 else 1
        outs.append(t.reshape(lead, -1))
    return single(jnp.concatenate(outs, axis=concat_axis))


@register_op("conv2d_fusion", ref="operators/fused/conv_fusion_op.cc")
def _conv2d_fusion(ctx, ins, attrs):
    """conv2d + bias + activation (+ residual add) as ONE emitted region.
    NHWC-aware (contrib.layout tags it like a bare conv2d): the whole
    epilogue runs channels-last inside the region and transposes only at
    the region edge; `__nhwc_resid_ready__` records the residual graph
    var's own physical residency, which is independent of the op's."""
    nhwc = bool(attrs.get("__nhwc__"))
    sub = dict(attrs)
    if nhwc:
        sub["__nhwc_out_keep__"] = True      # epilogue runs channels-last
    conv = get_op("conv2d").emit(ctx, ins, sub)["Output"][0]
    bias = first(ins, "Bias")
    if bias is not None:
        bshape = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
        conv = conv + bias.reshape(bshape).astype(conv.dtype)
    resid = first(ins, "ResidualData")
    if resid is not None:
        resid_nhwc = bool(attrs.get("__nhwc_resid_ready__"))
        if nhwc and not resid_nhwc:
            resid = jnp.transpose(resid, (0, 2, 3, 1))
        elif not nhwc and resid_nhwc:
            resid = jnp.transpose(resid, (0, 3, 1, 2))
        conv = conv + resid.astype(conv.dtype)
    act = attrs.get("activation", "relu")
    if act == "relu":
        conv = jnp.maximum(conv, 0.0)
    elif act == "identity" or not act:
        pass
    elif act == "sigmoid":
        conv = jax.nn.sigmoid(conv)
    elif act == "tanh":
        conv = jnp.tanh(conv)
    if nhwc and not attrs.get("__nhwc_out_keep__"):
        conv = jnp.transpose(conv, (0, 3, 1, 2))
    return {"Output": [conv]}


def _seq_fc_then_rnn(ctx, ins, attrs, cell):
    """Common body of fusion_gru / fusion_lstm: project X by WeightX (+bias)
    then run the recurrent cell over time via the dynamic_* emitters."""
    x = first(ins, "X")                  # [B, T, Din]
    wx = first(ins, "WeightX")           # [Din, G*D]
    wh = first(ins, "WeightH")
    bias = first(ins, "Bias")
    proj = jnp.einsum("btd,dk->btk", x, wx)
    if bias is not None and cell == "gru":
        proj = proj + bias.reshape(1, 1, -1)
    sub_ins = {"Input": [proj], "Weight": [wh]}
    if first(ins, "SeqLens") is not None:
        sub_ins["SeqLens"] = [first(ins, "SeqLens")]
    if cell == "lstm" and bias is not None:
        sub_ins["Bias"] = [bias]
    if first(ins, "H0") is not None:
        sub_ins["H0"] = [first(ins, "H0")]
    if first(ins, "C0") is not None:
        sub_ins["C0"] = [first(ins, "C0")]
    op = "dynamic_gru" if cell == "gru" else "dynamic_lstm"
    return get_op(op).emit(ctx, sub_ins, attrs)


@register_op("fusion_gru", ref="operators/fused/fusion_gru_op.cc")
def _fusion_gru(ctx, ins, attrs):
    out = _seq_fc_then_rnn(ctx, ins, attrs, "gru")
    return {"Hidden": [out.get("Hidden", out.get("Out"))[0]]}


@register_op("fusion_lstm", ref="operators/fused/fusion_lstm_op.cc")
def _fusion_lstm(ctx, ins, attrs):
    out = _seq_fc_then_rnn(ctx, ins, attrs, "lstm")
    return {"Hidden": [out["Hidden"][0]], "Cell": [out["Cell"][0]]}


@register_op("fused_embedding_fc_lstm",
             ref="operators/fused/fused_embedding_fc_lstm_op.cc")
def _fused_embedding_fc_lstm(ctx, ins, attrs):
    """embedding lookup + fc + lstm, composed."""
    w = first(ins, "Embeddings")         # [V, G*D] (pre-multiplied table)
    ids = first(ins, "Ids").astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    proj = w[ids]                        # [B, T, 4D]
    sub_ins = {"Input": [proj], "Weight": [first(ins, "WeightH")]}
    for slot in ("Bias", "H0", "C0", "SeqLens"):
        if first(ins, slot) is not None:
            sub_ins[slot] = [first(ins, slot)]
    out = get_op("dynamic_lstm").emit(ctx, sub_ins, attrs)
    return {"Hidden": [out["Hidden"][0]], "Cell": [out["Cell"][0]]}


@register_op("fusion_seqconv_eltadd_relu",
             ref="operators/fused/fusion_seqconv_eltadd_relu_op.cc")
def _fusion_seqconv_eltadd_relu(ctx, ins, attrs):
    out = get_op("sequence_conv").emit(ctx, ins, attrs)["Out"][0]
    bias = first(ins, "Bias")
    if bias is not None:
        out = out + bias.reshape(1, 1, -1)
    return single(jnp.maximum(out, 0.0))


@register_op("fusion_seqexpand_concat_fc",
             ref="operators/fused/fusion_seqexpand_concat_fc_op.cc")
def _fusion_seqexpand_concat_fc(ctx, ins, attrs):
    """First input [B, T, D0] is a sequence; remaining inputs [B, Di] are
    broadcast (seq-expanded) over T; concat on features, then fc + act."""
    xs = ins.get("X", [])
    seq = xs[0]
    b, t = seq.shape[0], seq.shape[1]
    parts = [seq]
    for x in xs[1:]:
        parts.append(jnp.broadcast_to(x[:, None, :], (b, t, x.shape[-1])))
    cat = jnp.concatenate(parts, axis=-1)
    w = first(ins, "FCWeight")
    out = jnp.einsum("btd,dk->btk", cat, w)
    bias = first(ins, "FCBias")
    if bias is not None:
        out = out + bias.reshape(1, 1, -1)
    act = attrs.get("fc_activation", "identity")
    if act == "relu":
        out = jnp.maximum(out, 0.0)
    elif act == "tanh":
        out = jnp.tanh(out)
    elif act == "sigmoid":
        out = jax.nn.sigmoid(out)
    return single(out)


@register_op("lstmp", ref="operators/lstmp_op.cc")
def _lstmp(ctx, ins, attrs):
    """LSTM with recurrent projection: h_t = proj(o * tanh(c_t)).
    Input [B, T, 4D] pre-projected like dynamic_lstm; ProjWeight [D, P]."""
    x = first(ins, "Input")
    wh = first(ins, "Weight")            # [P, 4D]
    wproj = first(ins, "ProjWeight")     # [D, P]
    bias = first(ins, "Bias")
    b, t, d4 = x.shape
    d = d4 // 4
    p = wproj.shape[1]
    if bias is not None:
        x = x + bias.reshape(1, 1, -1)[:, :, :d4]
    h0 = first(ins, "H0")
    c0 = first(ins, "C0")
    h = jnp.zeros((b, p), x.dtype) if h0 is None else h0
    c = jnp.zeros((b, d), x.dtype) if c0 is None else c0
    lens = first(ins, "SeqLens")
    steps = jnp.moveaxis(x, 1, 0)        # [T, B, 4D]

    def step(carry, xt_i):
        h_, c_ = carry
        xt, it = xt_i
        gates = xt + h_ @ wh                 # wh [P, 4D]
        i, f, cc, o = jnp.split(gates, 4, axis=1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c_ + i * jnp.tanh(cc)
        h_new = (o * jnp.tanh(c_new)) @ wproj
        if lens is not None:
            alive = (it < lens.reshape(-1, 1))
            c_new = jnp.where(alive, c_new, c_)
            h_new = jnp.where(alive, h_new, h_)
        return (h_new, c_new), (h_new, c_new)

    its = jnp.arange(t)[:, None]
    (_, _), (hs, cs) = lax.scan(step, (h, c), (steps, its))
    return {"Projection": [jnp.moveaxis(hs, 0, 1)],
            "Cell": [jnp.moveaxis(cs, 0, 1)]}


@register_op("attention_lstm", ref="operators/fused/attention_lstm_op.cc")
def _attention_lstm(ctx, ins, attrs):
    """Per-step additive attention over the input sequence feeding an LSTM
    cell (the reference's fused CPU op). X [B, T, D]; the attended context
    is the cell input at each step."""
    x = first(ins, "X")                  # [B, T, D]
    att_w = first(ins, "AttentionWeight")        # [D+D, 1]
    lstm_w = first(ins, "LSTMWeight")            # [D+D, 4D] (x + h)
    lstm_b = first(ins, "LSTMBias")              # [1, 4D]
    b, t, d = x.shape
    h0 = first(ins, "H0")
    c0 = first(ins, "C0")
    h = jnp.zeros((b, d), x.dtype) if h0 is None else h0
    c = jnp.zeros((b, d), x.dtype) if c0 is None else c0
    lens = first(ins, "SeqLens")
    mask = None
    if lens is not None:
        mask = _mask_bt(lens, b, t)
    # hoist the x-dependent half of the additive score out of the scan:
    # score_t = x @ w[:d] + h @ w[d:]  — only the h half changes per step
    x_score = jnp.einsum("btd,do->bt", x, att_w[:d])         # [B, T]

    def step(carry, it):
        h_, c_ = carry
        scores = x_score + (h_ @ att_w[d:])                  # [B, T]+[B,1]
        if mask is not None:
            scores = jnp.where(mask, scores, -1e9)
        alpha = jax.nn.softmax(scores, axis=1)
        ctx_vec = jnp.einsum("bt,btd->bd", alpha, x)         # [B, D]
        gates = jnp.concatenate([ctx_vec, h_], axis=-1) @ lstm_w
        if lstm_b is not None:
            gates = gates + lstm_b.reshape(1, -1)
        i, f, cc, o = jnp.split(gates, 4, axis=1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c_new = f * c_ + i * jnp.tanh(cc)
        h_new = o * jnp.tanh(c_new)
        if lens is not None:
            alive = (it < lens.reshape(-1, 1))
            c_new = jnp.where(alive, c_new, c_)
            h_new = jnp.where(alive, h_new, h_)
        return (h_new, c_new), h_new

    (h, c), hs = lax.scan(step, (h, c), jnp.arange(t))
    return {"Hidden": [jnp.moveaxis(hs, 0, 1)], "Cell": [c]}
