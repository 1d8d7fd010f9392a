"""Final op-corpus parity batch: model-average accumulators, metric/pool
stragglers, SelectedRows (sparse-rows) family, save/load as in-graph ops,
and documented terminal emitters for the reference's RPC/reader ops whose
capability lives elsewhere in this framework.

Reference targets: operators/average_accumulates_op.h:55, mean_iou_op.h,
pool_with_index_op.cc (3D), operators/fused/fusion_conv_inception_op.cc,
cudnn_lstm_op.cc, controlflow/conditional_block_op.cc, save_op.cc,
load_op.cc, save_combine_op.cc, load_combine_op.cc, split_ids_op.h,
merge_ids_op.h, split_selected_rows_op.cc, merge_selected_rows_op.cc,
get_tensor_from_selected_rows_op.cc, lookup_sparse_table_op.cc,
split_byref_op.cc, detection/generate_proposal_labels_op.cc,
distributed_ops/ (send/recv/barriers/prefetch/listen_and_serv/
checkpoint_notify/gen_nccl_id), reader/create_custom_reader_op.cc,
csp/go_op.cc, get_places_op.cc, delete_var_op.cc, tensorrt_engine_op.

SelectedRows note: XLA wants dense — sparse gradients are dense here with
scatter-add (SURVEY §7 hard-part 2), so the SelectedRows manipulation ops
become dense row ops with identical observable behavior."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import first, get_op, register_op, single
from paddle_tpu.ops.detection_ops import _iou_matrix


@register_op("average_accumulates", no_grad=True,
             ref="operators/average_accumulates_op.h:55")
def _average_accumulates(ctx, ins, attrs):
    """ModelAverage accumulator update — the three-tier sum buffers with
    window restarts, expressed as jnp.where selects (state round-trips
    through the Scope like the optimizer ops)."""
    param = first(ins, "param")
    s1 = first(ins, "in_sum_1")
    s2 = first(ins, "in_sum_2")
    s3 = first(ins, "in_sum_3")
    num_acc = first(ins, "in_num_accumulates").reshape(()).astype(jnp.int64)
    old_num = first(ins, "in_old_num_accumulates").reshape(()).astype(jnp.int64)
    num_upd = first(ins, "in_num_updates").reshape(()).astype(jnp.int64)
    avg_win = attrs.get("average_window", 0.0)
    # int32-safe sentinel: jax default x64-disabled truncates int64 consts
    max_win = min(int(attrs.get("max_average_window",
                                np.iinfo(np.int32).max)),
                  np.iinfo(np.int32).max)
    min_win = attrs.get("min_average_window", 10000)
    k_max = 16384           # kMaxNumAccumulates

    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + param
    spill = (num_upd % k_max) == 0
    s2 = jnp.where(spill, s2 + s1, s2)
    s1 = jnp.where(spill, jnp.zeros_like(s1), s1)
    win_full = (num_acc >= min_win) & (
        num_acc >= jnp.minimum(jnp.asarray(max_win, jnp.int64),
                               (num_upd.astype(jnp.float32)
                                * avg_win).astype(jnp.int64)))
    s3 = jnp.where(win_full, s1 + s2, s3)
    s1 = jnp.where(win_full, jnp.zeros_like(s1), s1)
    s2 = jnp.where(win_full, jnp.zeros_like(s2), s2)
    old_num = jnp.where(win_full, num_acc, old_num)
    num_acc = jnp.where(win_full, jnp.zeros_like(num_acc), num_acc)
    return {"out_sum_1": [s1], "out_sum_2": [s2], "out_sum_3": [s3],
            "out_num_accumulates": [num_acc.reshape(1)],
            "out_old_num_accumulates": [old_num.reshape(1)],
            "out_num_updates": [num_upd.reshape(1)]}


@register_op("mean_iou", no_grad=True, ref="operators/mean_iou_op.h")
def _mean_iou(ctx, ins, attrs):
    pred = first(ins, "Predictions").reshape(-1).astype(jnp.int32)
    label = first(ins, "Labels").reshape(-1).astype(jnp.int32)
    n = int(attrs["num_classes"])
    ph = jax.nn.one_hot(pred, n, dtype=jnp.int32)
    lh = jax.nn.one_hot(label, n, dtype=jnp.int32)
    correct = jnp.sum(ph * lh, axis=0)                      # per-class TP
    pred_cnt = jnp.sum(ph, axis=0)
    label_cnt = jnp.sum(lh, axis=0)
    wrong = pred_cnt + label_cnt - 2 * correct
    # streaming accumulation FIRST (mean_iou_op.h adds InWrongs/InCorrects
    # into the counts before computing the mean)
    in_w = first(ins, "InWrongs")
    in_c = first(ins, "InCorrects")
    if in_w is not None:
        wrong = wrong + in_w.reshape(-1)
    if in_c is not None:
        correct = correct + in_c.reshape(-1)
    denom = wrong + correct
    iou = jnp.where(denom > 0, correct / jnp.maximum(denom, 1), 0.0)
    valid = (denom > 0).astype(jnp.float32)
    mean = jnp.sum(iou) / jnp.maximum(jnp.sum(valid), 1.0)
    in_mean = first(ins, "InMeanIou")
    if in_mean is not None:
        # streaming mean of means, count-weighted equally per batch
        prior = in_mean.reshape(-1)
        mean = (jnp.sum(prior) + mean) / (prior.shape[0] + 1.0)
    return {"OutMeanIou": [mean.reshape(())],
            "OutWrong": [wrong.astype(jnp.int32)],
            "OutCorrect": [correct.astype(jnp.int32)]}


@register_op("max_pool3d_with_index",
             ref="operators/pool_with_index_op.cc (3D)")
def _max_pool3d_with_index(ctx, ins, attrs):
    from paddle_tpu.ops.image_ops import max_pool_with_index_nd
    x = first(ins, "X")                  # [N, C, D, H, W]
    k = attrs.get("ksize", [2, 2, 2])
    s = attrs.get("strides", k)
    p = attrs.get("paddings", [0, 0, 0])
    out, idx = max_pool_with_index_nd(
        x, (1, 1, k[0], k[1], k[2]), (1, 1, s[0], s[1], s[2]),
        ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]), (p[2], p[2])))
    return {"Out": [out], "Mask": [idx]}


@register_op("conv2d_inception_fusion",
             ref="operators/fused/fusion_conv_inception_op.cc")
def _conv2d_inception_fusion(ctx, ins, attrs):
    """Inception block: four parallel conv branches over the same input,
    channel-concatenated (the reference fuses the cudnn calls; XLA fuses
    the same graph here). Filter/Bias are parallel lists; branch i applies
    its convs in sequence with relu epilogues."""
    x = first(ins, "Input")
    filters = ins.get("Filter", [])
    biases = ins.get("Bias", [])
    outs = []
    for i, wf in enumerate(filters):
        bf = biases[i] if i < len(biases) else None
        kh = wf.shape[2]
        pad = kh // 2
        o = jax.lax.conv_general_dilated(
            x, wf, (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if bf is not None:
            o = o + bf.reshape(1, -1, 1, 1)
        outs.append(jnp.maximum(o, 0.0))
    return {"Output": [jnp.concatenate(outs, axis=1)]}


@register_op("cudnn_lstm", ref="operators/cudnn_lstm_op.cc (capability; "
                              "packed-weight multi-layer LSTM)")
def _cudnn_lstm(ctx, ins, attrs):
    """Multi-layer LSTM over packed weights. Input [T,B,D]; W flat: per
    layer, per direction, [Wx (Din,4H) | Wh (H,4H) | b (4H)] concatenated
    (the reference packs cudnn's filter layout; this op defines the
    TPU-native packing and runs each direction as one lax.scan).

    is_bidirec=True runs forward and time-reversed backward passes per
    layer and concatenates their hiddens on the feature axis ([T,B,2H] —
    the cudnn bidirectional contract), so the next layer sees Din=2H;
    per-layer final states stack to [num_layers*2, B, H] (fwd, bwd
    interleaved per layer, cudnn's order)."""
    x = first(ins, "Input")              # [T, B, Din]
    w = first(ins, "W").reshape(-1)
    hidden = int(attrs["hidden_size"])
    layers = int(attrs.get("num_layers", 1))
    bidirec = bool(attrs.get("is_bidirec", False))
    t, b, din = x.shape
    off = 0
    h_all = x
    spec = get_op("dynamic_lstm")
    last_hs, last_cs = [], []

    def run_dir(inp, d_in, off, reverse):
        wx = w[off:off + d_in * 4 * hidden].reshape(d_in, 4 * hidden)
        off += d_in * 4 * hidden
        wh = w[off:off + hidden * 4 * hidden].reshape(hidden, 4 * hidden)
        off += hidden * 4 * hidden
        bias = w[off:off + 4 * hidden].reshape(1, 4 * hidden)
        off += 4 * hidden
        seq = inp[::-1] if reverse else inp
        proj = jnp.einsum("tbd,dk->tbk", seq, wx)
        res = spec.emit(ctx, {"Input": [jnp.swapaxes(proj, 0, 1)],
                              "Weight": [wh], "Bias": [bias]}, {})
        h = jnp.swapaxes(res["Hidden"][0], 0, 1)       # [T, B, H]
        if reverse:
            h = h[::-1]
        return h, res["LastHidden"][0], res["LastCell"][0], off

    for layer in range(layers):
        d_in = h_all.shape[-1]
        h_fwd, lh, lc, off = run_dir(h_all, d_in, off, reverse=False)
        last_hs.append(lh)
        last_cs.append(lc)
        if bidirec:
            h_bwd, lh, lc, off = run_dir(h_all, d_in, off, reverse=True)
            last_hs.append(lh)
            last_cs.append(lc)
            h_all = jnp.concatenate([h_fwd, h_bwd], axis=-1)
        else:
            h_all = h_fwd
    # per-layer final states [num_layers(*2), B, H] (cudnn_lstm
    # LastH/LastC contract — feeding truncated-BPTT chunks needs every
    # layer's state)
    return {"Out": [h_all],
            "last_h": [jnp.stack(last_hs, axis=0)],
            "last_c": [jnp.stack(last_cs, axis=0)]}


@register_op("conditional_block",
             ref="operators/controlflow/conditional_block_op.cc (alias of "
                 "the cond emitter's lowering)")
def _conditional_block(ctx, ins, attrs):
    return get_op("cond").emit(ctx, ins, attrs)


# -- SelectedRows family (dense redesign) -----------------------------------

@register_op("split_ids", no_grad=True, ref="operators/split_ids_op.h")
def _split_ids(ctx, ins, attrs):
    """Shard ids by id %% n_parts; each shard keeps the original length
    with -1 where not owned (static-shape replacement for the reference's
    compacted per-shard lists)."""
    ids = first(ins, "Ids").reshape(-1).astype(jnp.int64)
    n = attrs.get("n_parts") or len(attrs.get("out_names", [])) or 2
    outs = [jnp.where(ids % n == k, ids, -1) for k in range(n)]
    return {"Out": outs}


@register_op("merge_ids", no_grad=True, ref="operators/merge_ids_op.h")
def _merge_ids(ctx, ins, attrs):
    """Inverse of split_ids + per-shard row lookup: for each original id,
    take the row from the shard that owns it. Ids [N], per-shard Rows
    [N, D] aligned with the split_ids outputs."""
    ids = first(ins, "Ids").reshape(-1).astype(jnp.int64)
    shards = ins.get("X", [])
    n = len(shards)
    out = jnp.zeros(shards[0].shape, shards[0].dtype)
    for k, rows in enumerate(shards):
        own = (ids % n == k)[:, None]
        out = jnp.where(own, rows, out)
    return single(out)


@register_op("split_selected_rows", no_grad=True,
             ref="operators/split_selected_rows_op.cc")
def _split_selected_rows(ctx, ins, attrs):
    x = first(ins, "X")
    sections = attrs.get("height_sections")
    if not sections:
        raise ValueError("split_selected_rows needs height_sections")
    idx = np.cumsum([int(s) for s in sections])[:-1]
    return {"Out": list(jnp.split(x, idx, axis=0))}


@register_op("merge_selected_rows", no_grad=True,
             ref="operators/merge_selected_rows_op.cc")
def _merge_selected_rows(ctx, ins, attrs):
    """The reference sums duplicate sparse rows; dense gradients are
    already merged — identity."""
    return single(first(ins, "X"))


@register_op("get_tensor_from_selected_rows", no_grad=True,
             ref="operators/get_tensor_from_selected_rows_op.cc")
def _get_tensor_from_selected_rows(ctx, ins, attrs):
    return single(first(ins, "X"))


@register_op("lookup_sparse_table",
             ref="operators/lookup_sparse_table_op.cc (auto-growing pserver "
                 "table → dense mesh-sharded table)")
def _lookup_sparse_table(ctx, ins, attrs):
    return get_op("lookup_table").emit(
        ctx, {"W": ins.get("W", []), "Ids": ins.get("Ids", [])}, attrs)


@register_op("split_byref", no_grad=True, ref="operators/split_byref_op.cc")
def _split_byref(ctx, ins, attrs):
    """Row split (the transpiler's zero-copy variant) — delegates to the
    split emitter pinned to axis 0."""
    attrs = dict(attrs)
    attrs["axis"] = 0
    return get_op("split").emit(ctx, ins, attrs)


@register_op("generate_proposal_labels", no_grad=True,
             ref="operators/detection/generate_proposal_labels_op.cc")
def _generate_proposal_labels(ctx, ins, attrs):
    """Fast-RCNN head sampling: label each RPN roi by best-gt IoU
    (fg >= fg_thresh, bg in [bg_lo, bg_hi)), sample fixed fg/bg quotas by
    random ranking, emit class labels + encoded box targets. Dense masks
    replace the reference's compacted sampled lists."""
    rois = first(ins, "RpnRois")         # [B, R, 4]
    gt_boxes = first(ins, "GtBoxes")     # [B, G, 4]
    gt_classes = first(ins, "GtClasses")  # [B, G]
    batch_size_per_im = int(attrs.get("batch_size_per_im", 256))
    fg_frac = attrs.get("fg_fraction", 0.25)
    fg_thresh = attrs.get("fg_thresh", 0.5)
    bg_hi = attrs.get("bg_thresh_hi", 0.5)
    bg_lo = attrs.get("bg_thresh_lo", 0.0)
    n_fg = int(batch_size_per_im * fg_frac)
    key = ctx.step_key()

    def one(rois_b, gtb, gtc, k):
        valid_gt = jnp.any(gtb != 0, axis=1)
        iou = _iou_matrix(rois_b, gtb, normalized=False)
        iou = jnp.where(valid_gt[None, :], iou, 0.0)
        best = jnp.argmax(iou, axis=1)
        best_iou = jnp.max(iou, axis=1)
        fg = best_iou >= fg_thresh
        bg = (best_iou < bg_hi) & (best_iou >= bg_lo) & ~fg
        rnd = jax.random.uniform(k, (rois_b.shape[0],))
        fg_rank = jnp.argsort(jnp.argsort(jnp.where(fg, rnd, 2.0)))
        fg = fg & (fg_rank < n_fg)
        n_bg = batch_size_per_im - jnp.sum(fg.astype(jnp.int32))
        bg_rank = jnp.argsort(jnp.argsort(jnp.where(bg, rnd, 2.0)))
        bg = bg & (bg_rank < n_bg)
        labels = jnp.where(fg, gtc[best], jnp.where(bg, 0, -1))
        matched = gtb[best]
        rw = rois_b[:, 2] - rois_b[:, 0] + 1.0
        rh = rois_b[:, 3] - rois_b[:, 1] + 1.0
        rcx = rois_b[:, 0] + 0.5 * rw
        rcy = rois_b[:, 1] + 0.5 * rh
        gw = matched[:, 2] - matched[:, 0] + 1.0
        gh = matched[:, 3] - matched[:, 1] + 1.0
        gcx = (matched[:, 0] + matched[:, 2]) * 0.5
        gcy = (matched[:, 1] + matched[:, 3]) * 0.5
        tgt = jnp.stack([(gcx - rcx) / rw, (gcy - rcy) / rh,
                         jnp.log(gw / rw), jnp.log(gh / rh)], axis=1)
        tgt = jnp.where(fg[:, None], tgt, 0.0)
        return labels.astype(jnp.int32), tgt, \
            (fg | bg).astype(jnp.float32)

    keys = jax.random.split(key, rois.shape[0])
    labels, targets, weights = jax.vmap(one)(rois, gt_boxes,
                                             gt_classes.astype(jnp.int32),
                                             keys)
    return {"Rois": [rois], "LabelsInt32": [labels],
            "BboxTargets": [targets],
            "BboxInsideWeights": [weights[..., None]],
            "BboxOutsideWeights": [weights[..., None]]}


# -- save/load as in-graph ops ----------------------------------------------

@register_op("save", no_grad=True, ref="operators/save_op.cc")
def _save(ctx, ins, attrs):
    """Host-side save via io_callback (the reference's save op writes its
    input tensor to file_path inside the executor loop)."""
    x = first(ins, "X")
    path = attrs["file_path"]

    def cb(arr):
        np.save(path, np.asarray(arr))
        return np.zeros((1,), np.int32)

    flag = jax.experimental.io_callback(
        cb, jax.ShapeDtypeStruct((1,), jnp.int32), x, ordered=True)
    return single(flag)


@register_op("load", no_grad=True, ref="operators/load_op.cc")
def _load(ctx, ins, attrs):
    path = attrs["file_path"]
    arr = np.load(path if path.endswith(".npy") else path + ".npy")
    return single(jnp.asarray(arr))


@register_op("save_combine", no_grad=True,
             ref="operators/save_combine_op.cc")
def _save_combine(ctx, ins, attrs):
    xs = ins.get("X", [])
    path = attrs["file_path"]
    names = attrs.get("var_names", [f"v{i}" for i in range(len(xs))])

    def cb(*arrs):
        np.savez(path, **{n: np.asarray(a) for n, a in zip(names, arrs)})
        return np.zeros((1,), np.int32)

    flag = jax.experimental.io_callback(
        cb, jax.ShapeDtypeStruct((1,), jnp.int32), *xs, ordered=True)
    return single(flag)


@register_op("load_combine", no_grad=True,
             ref="operators/load_combine_op.cc")
def _load_combine(ctx, ins, attrs):
    path = attrs["file_path"]
    data = np.load(path if path.endswith(".npz") else path + ".npz")
    names = attrs.get("var_names")
    if names is None:
        # save-order default names v0..vN — numeric order, NOT lexicographic
        # (sorted() would permute v10 before v2)
        names = [f"v{i}" for i in range(len(data.files))]
    return {"Out": [jnp.asarray(data[n]) for n in names]}


# -- documented terminal emitters -------------------------------------------
# The reference registers these as runtime ops; their capability here lives
# in a different subsystem. Programs containing them fail at lowering with
# a pointer to the TPU-native replacement — explicit, not silent.

def _register_redirect(op_type, ref, replacement):
    @register_op(op_type, no_grad=True, ref=ref)
    def _emit(ctx, ins, attrs, _op=op_type, _to=replacement):
        raise NotImplementedError(
            f"op {_op!r} is a {ref.split('/')[-1]} runtime op with no "
            f"TPU-native lowering; this capability is provided by {_to}")
    # machine-checkable marker: the smoke sweep asserts the redirect set
    # is EXACTLY the documented list (a gutted real op would not carry it)
    _emit.__redirect__ = True
    return _emit


_register_redirect(
    "send", "operators/distributed_ops/send_op.cc",
    "mesh sharding + XLA collectives (paddle_tpu.parallel; "
    "DistributeTranspiler models the send boundary as fetchable grads)")
_register_redirect(
    "recv", "operators/distributed_ops/recv_op.cc",
    "mesh sharding + XLA collectives (paddle_tpu.parallel)")
_register_redirect(
    "send_barrier", "operators/distributed_ops/send_barrier_op.cc",
    "XLA collective scheduling (no barrier protocol on ICI)")
_register_redirect(
    "fetch_barrier", "operators/distributed_ops/fetch_barrier_op.cc",
    "XLA collective scheduling")
_register_redirect(
    "prefetch", "operators/distributed_ops/prefetch_op.cc",
    "sharded-table all-to-all gather (paddle_tpu.distributed sparse tables)")
_register_redirect(
    "listen_and_serv", "operators/distributed_ops/listen_and_serv_op.cc",
    "fluid.transpiler.DistributeTranspiler.get_pserver_program — the "
    "pserver half runs as a fed program, no RPC loop")
_register_redirect(
    "checkpoint_notify", "operators/distributed_ops/checkpoint_notify_op.cc",
    "fluid.io.save_persistables (orbax-style direct checkpointing)")
_register_redirect(
    "gen_nccl_id", "operators/distributed_ops/gen_nccl_id_op.cc",
    "jax.distributed.initialize (coordination service replaces the NCCL "
    "id broadcast)")
_register_redirect(
    "nccl", "operators/nccl/nccl_op.cc",
    "XLA cross-replica collectives (psum/all_gather over ICI)")
_register_redirect(
    "go", "operators/csp/go_op.cc",
    "host-side Python threading (the CSP experiment has no XLA analogue)")
_register_redirect(
    "tensorrt_engine", "operators/tensorrt_engine_op (inference offload)",
    "XLA itself — the whole graph is already compiled; see "
    "paddle_tpu.inference")
_register_redirect(
    "read", "operators/reader/read_op (in-graph reader)",
    "paddle_tpu.data pipeline (host prefetch + device feed)")
_register_redirect(
    "create_custom_reader", "operators/reader/create_custom_reader_op.cc",
    "paddle_tpu.reader decorators over the data pipeline")


@register_op("delete_var", no_grad=True, ref="operators/delete_var_op.cc")
def _delete_var(ctx, ins, attrs):
    """No-op: buffer lifetime is XLA's liveness analysis (the reference
    frees scope vars mid-block for memory)."""
    return {}


@register_op("get_places", no_grad=True, ref="operators/get_places_op.cc")
def _get_places(ctx, ins, attrs):
    """Device-count introspection (the reference returns a places vector
    for ParallelDo); here: the device count as a tensor."""
    return single(jnp.asarray(len(jax.devices()), jnp.int32))
