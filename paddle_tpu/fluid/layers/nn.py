"""Neural-network layers (reference: python/paddle/fluid/layers/nn.py —
~160 functions; fc :191, embedding :300, conv2d :1753, batch_norm :2713,
pool2d, dropout, layer_norm, softmax_with_cross_entropy, topk ...).

Each layer builds IR ops via LayerHelper; parameters are created with the
two-program convention. The op set lowers to JAX/XLA (see paddle_tpu.ops),
so an `fc` is a single MXU matmul with a fused bias/activation epilogue.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from paddle_tpu.fluid import framework
from paddle_tpu.fluid.layer_helper import LayerHelper
from paddle_tpu.fluid.initializer import ConstantInitializer


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """reference: nn.py:191 — mul (+ sum for multi-input) + bias + act."""
    helper = LayerHelper("fc", name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_shape = inp.shape
        param_shape = [int(np.prod(in_shape[num_flatten_dims:]))] + [size]
        w = helper.create_parameter(param_attr, shape=param_shape, dtype=inp.dtype)
        tmp = helper.create_variable_for_type_inference(inp.dtype)
        helper.append_op("mul", inputs={"X": [inp], "Y": [w]},
                         outputs={"Out": [tmp]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(inputs[0].dtype)
        helper.append_op("sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, bias_attr, size,
                                    dim_start=num_flatten_dims)
    return helper.append_activation(pre_act, act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """reference: nn.py:300 — lookup_table. is_sparse/is_distributed are the
    pserver-sharded-table capability: on TPU the table shards over the mesh
    model axis (see paddle_tpu.parallel) and the gather is an all-to-all;
    the flags are accepted and recorded as sharding hints."""
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, shape=list(size), dtype=dtype)
    if is_distributed or is_sparse:
        # record the sharding hint: table rows split over the mesh model
        # axis (resolved by DistributeConfig._axes_for; the TPU form of the
        # pserver-sharded table, distribute_transpiler.py:1051
        # _init_splited_vars + parameter_prefetch.h:26)
        w.desc.attrs["dist_hint"] = ["__model__"] + \
            [None] * (len(size) - 1)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "lookup_table", inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": -1 if padding_idx is None else padding_idx})
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    """reference: nn.py:1753 — NCHW conv; use_cudnn accepted for parity
    (XLA autotunes, conv_cudnn_op.cu.cc has no TPU analogue)."""
    helper = LayerHelper("conv2d", name=name)
    num_channels = input.shape[1]
    fsize = filter_size if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    filter_shape = [num_filters, num_channels // groups] + list(fsize)
    std = (2.0 / (fsize[0] * fsize[1] * num_channels)) ** 0.5
    from paddle_tpu.fluid.initializer import NormalInitializer
    w = helper.create_parameter(param_attr, shape=filter_shape,
                                dtype=input.dtype,
                                default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv2d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": _pair(stride), "paddings": _pair(padding),
               "dilations": _pair(dilation), "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        with_b = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [with_b]}, attrs={"axis": 1})
        out = with_b
    return helper.append_activation(out, act)


def _transpose_filter_size(filter_size, output_size, in_spatial, stride,
                           padding, dilation, nd):
    """reference: nn.py conv2d_transpose — when filter_size is omitted,
    derive it from output_size:
    f[i] = (out[i] + 2*pad[i] - (in[i]-1)*stride[i] - 1) // dil[i] + 1."""
    if filter_size is not None:
        return (list(filter_size) if isinstance(filter_size, (list, tuple))
                else [filter_size] * nd)
    if output_size is None:
        raise ValueError(
            "conv_transpose: give filter_size or output_size")
    out = (list(output_size) if isinstance(output_size, (list, tuple))
           else [output_size] * nd)
    pad = padding if isinstance(padding, (list, tuple)) else [padding] * nd
    st = stride if isinstance(stride, (list, tuple)) else [stride] * nd
    dil = dilation if isinstance(dilation, (list, tuple)) else [dilation] * nd
    return [(out[i] + 2 * pad[i] - (in_spatial[i] - 1) * st[i] - 1)
            // dil[i] + 1 for i in range(nd)]


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", name=name)
    num_channels = input.shape[1]
    fsize = _transpose_filter_size(filter_size, output_size, input.shape[2:],
                                   stride, padding, dilation, 2)
    filter_shape = [num_channels, num_filters // groups] + list(fsize)
    w = helper.create_parameter(param_attr, shape=filter_shape, dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv2d_transpose", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": _pair(stride), "paddings": _pair(padding),
               "dilations": _pair(dilation), "groups": groups})
    if bias_attr is not False:
        out = helper.append_bias_op(out, bias_attr, num_filters, dim_start=1)
    return helper.append_activation(out, act)


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False,
           exclusive=True, name=None):
    """reference: nn.py pool2d → pool_op.cc."""
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": _pair(pool_size),
               "strides": _pair(pool_stride), "paddings": _pair(pool_padding),
               "global_pooling": global_pooling, "ceil_mode": ceil_mode,
               "exclusive": exclusive})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    """reference: nn.py:2713 → batch_norm_op.cc. Scale/Bias trainable;
    Mean/Variance are persistable running stats updated in the compiled step
    (written back to the Scope by the executor's state-return path)."""
    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[1]
    scale = helper.create_parameter(param_attr, shape=[c], dtype=input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype,
                                   is_bias=True)
    from paddle_tpu.fluid import unique_name
    mean_name = moving_mean_name or unique_name.generate(helper.name + ".mean")
    var_name = moving_variance_name or unique_name.generate(helper.name + ".var")
    block = helper.main_program.global_block()
    mean = block.create_var(name=mean_name, shape=[c], dtype=input.dtype,
                            persistable=True, stop_gradient=True)
    variance = block.create_var(name=var_name, shape=[c], dtype=input.dtype,
                                persistable=True, stop_gradient=True)
    sb = helper.startup_program.global_block()
    if not sb.has_var(mean_name):
        ConstantInitializer(0.0)(sb.create_var(name=mean_name, shape=[c],
                                               dtype=input.dtype, persistable=True), sb)
        ConstantInitializer(1.0)(sb.create_var(name=var_name, shape=[c],
                                               dtype=input.dtype, persistable=True), sb)
    saved_mean = helper.create_variable_for_type_inference(input.dtype)
    saved_var = helper.create_variable_for_type_inference(input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "use_global_stats": use_global_stats})
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """reference: nn.py layer_norm → layer_norm_op.cc."""
    helper = LayerHelper("layer_norm", name=name)
    norm_size = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, shape=[norm_size],
                                    dtype=input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, shape=[norm_size],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype)
    var = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"begin_norm_axis": begin_norm_axis,
                            "epsilon": epsilon})
    return helper.append_activation(out, act)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed or 0,
                            "dropout_implementation": dropout_implementation})
    return out


# -- losses -----------------------------------------------------------------

def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, label_smoothing=0.0):
    """label_smoothing (extension beyond the reference op): uniform-prior
    smoothing folded into the loss in closed form — equivalent to
    one_hot + label_smooth + soft_label CE but without materializing the
    [N, V] one-hot (several full-width passes at large V)."""
    if soft_label and label_smoothing:
        raise ValueError(
            "label_smoothing applies to hard integer labels; for soft "
            "labels smooth the distribution yourself (layers.label_smooth)")
    helper = LayerHelper("softmax_with_cross_entropy")
    loss = helper.create_variable_for_type_inference(logits.dtype)
    softmax = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Loss": [loss], "Softmax": [softmax]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index,
                            "label_smoothing": float(label_smoothing)})
    if return_softmax:
        return loss, softmax
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index,
                            "normalize": normalize})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("square_error_cost",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out]})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32"):
    helper = LayerHelper("label_smooth")
    out = helper.create_variable_for_type_inference(dtype)
    ins = {"X": [label]}
    if prior_dist is not None:
        ins["PriorDist"] = [prior_dist]
    helper.append_op("label_smooth", inputs=ins, outputs={"Out": [out]},
                     attrs={"epsilon": epsilon})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    residual = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("huber_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [residual]},
                     attrs={"delta": delta})
    return out


# -- reductions / elementwise / math ----------------------------------------

def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _reduce(op, input, dim, keep_dim, name):
    helper = LayerHelper(op, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is None:
        attrs = {"reduce_all": True, "keep_dim": keep_dim}
    else:
        attrs = {"dim": dim if isinstance(dim, (list, tuple)) else [dim],
                 "keep_dim": keep_dim}
    helper.append_op(op, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y, "alpha": alpha})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out, act)


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("softmax", inputs={"X": [input]}, outputs={"Out": [out]})
    return out


def log(x):
    helper = LayerHelper("log")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("log", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


# -- shape ------------------------------------------------------------------

def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("reshape", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out, act)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("squeeze", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("unsqueeze", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axes": list(axes)})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("transpose", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim % len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
        n_out = num
    else:
        num = 0
        sections = list(num_or_sections)
        n_out = len(sections)
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n_out)]
    helper.append_op("split", inputs={"X": [input]}, outputs={"Out": outs},
                     attrs={"axis": dim, "num": num, "sections": sections})
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op("stack", inputs={"X": list(x)}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": float(min), "max": float(max)})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


# -- metrics ----------------------------------------------------------------

def accuracy(input, label, k=1, correct=None, total=None):
    """reference: layers/metric_op.py accuracy — top_k + accuracy op."""
    helper = LayerHelper("accuracy")
    values, indices = topk(input, k=k)
    acc_out = helper.create_variable_for_type_inference("float32")
    if correct is None:
        correct = helper.create_variable_for_type_inference("int32")
    if total is None:
        total = helper.create_variable_for_type_inference("int32")
    helper.append_op("accuracy",
                     inputs={"Out": [values], "Indices": [indices],
                             "Label": [label]},
                     outputs={"Accuracy": [acc_out], "Correct": [correct],
                              "Total": [total]})
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1, slide_steps=1):
    """reference: layers/metric_op.py auc — streaming stat vars persist in
    the scope and the op returns the running AUC."""
    helper = LayerHelper("auc")
    stat_shape = [num_thresholds + 1]
    stat_pos = helper.create_global_variable(stat_shape, "float32",
                                             persistable=True)
    stat_neg = helper.create_global_variable(stat_shape, "float32",
                                             persistable=True)
    sb = helper.startup_program.global_block()
    for v in (stat_pos, stat_neg):
        if not sb.has_var(v.name):
            ConstantInitializer(0.0)(
                sb.create_var(name=v.name, shape=stat_shape, dtype="float32",
                              persistable=True), sb)
    auc_out = helper.create_variable_for_type_inference("float32")
    helper.append_op("auc",
                     inputs={"Predict": [input], "Label": [label],
                             "StatPos": [stat_pos], "StatNeg": [stat_neg]},
                     outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                              "StatNegOut": [stat_neg]},
                     attrs={"curve": curve, "num_thresholds": num_thresholds})
    return auc_out, [stat_pos, stat_neg]


def scaled_dot_product_attention(q, k, v, bias=None, causal=False,
                                 scale=None, sp="auto", sp_impl="ring",
                                 dropout_prob=0.0, layout="bhtd",
                                 name=None):
    """Fused attention over [B, H, T, D] tensors (TPU-native extension —
    the reference composes matmul+softmax+matmul; see ops.attention). With
    a mesh sp axis configured, computes ring attention / Ulysses over the
    sequence shards (parallel/ring_attention.py). dropout_prob applies
    attention-weight dropout (upscale_in_train — the reference's composed
    graph, dist_transformer.py:1044) inside the fused/flash kernels;
    disabled automatically in test-mode programs. layout="bthd" takes
    [B, T, H, D] tensors so the head split at the call site is a free
    reshape (no materialized transpose — parallel/ring_attention.py
    full_attention docstring)."""
    helper = LayerHelper("attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    ins = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        ins["Bias"] = [bias]
    helper.append_op("attention", inputs=ins, outputs={"Out": [out]},
                     attrs={"causal": causal, "scale": scale, "sp": sp,
                            "sp_impl": sp_impl, "layout": layout,
                            "dropout_prob": float(dropout_prob)})
    return out


def fused_multi_head_attention(q_in, kv_in, d_model, n_head, causal=False,
                               dropout_prob=0.0, param_attr=None,
                               name=None):
    """Whole attention block — q/k/v/out projections + scaled-dot
    attention — as ONE fused op (ops/attention_block.py): the custom VJP
    is spelled so no [B,T,H,D]↔[B,H,T,D] relayout is ever materialized,
    forward or backward (the composed graph's measured 7.4 ms/step copy
    band on Transformer-base, docs/performance.md). q_in [B,Tq,M],
    kv_in [B,Tk,M] (same var for self-attention) → [B,Tq,M].

    The reference composes this from fc+reshape+transpose+matmul+softmax
    (benchmark transformer prep); parameter names follow the fc
    convention so checkpoints keep the per-projection layout."""
    helper = LayerHelper("fused_multi_head_attention", name=name)
    if isinstance(param_attr, (list, tuple)):
        attrs4 = list(param_attr)           # one ParamAttr per projection
    elif param_attr is None:
        attrs4 = [None] * 4
    else:
        import copy
        attrs4 = []
        for tag in ("wq", "wk", "wv", "wo"):
            a = copy.deepcopy(param_attr)
            if a.name is not None:
                a.name = f"{a.name}.{tag}"
            attrs4.append(a)
    ws = [helper.create_parameter(a, shape=[d_model, d_model],
                                  dtype="float32") for a in attrs4]
    out = helper.create_variable_for_type_inference(q_in.dtype)
    helper.append_op("fused_attention_block",
                     inputs={"Xq": [q_in], "Xkv": [kv_in],
                             "Wq": [ws[0]], "Wk": [ws[1]],
                             "Wv": [ws[2]], "Wo": [ws[3]]},
                     outputs={"Out": [out]},
                     attrs={"n_head": int(n_head), "causal": bool(causal),
                            "dropout_prob": float(dropout_prob)})
    return out


def _attention_projection_params(helper, d_model, param_attr):
    """The four [M, M] projection weights, named exactly like
    fused_multi_head_attention's (``<base>.wq`` ... ``.wo``) so the same
    checkpoint/scope serves the training graph, the full-forward
    inference graph, AND the prefill/decode serving pair."""
    if isinstance(param_attr, (list, tuple)):
        attrs4 = list(param_attr)
    elif param_attr is None:
        attrs4 = [None] * 4
    else:
        import copy
        attrs4 = []
        for tag in ("wq", "wk", "wv", "wo"):
            a = copy.deepcopy(param_attr)
            if a.name is not None:
                a.name = f"{a.name}.{tag}"
            attrs4.append(a)
    return [helper.create_parameter(a, shape=[d_model, d_model],
                                    dtype="float32") for a in attrs4]


def _attention_weights(helper, x, d_model, n_head, param_attr, gqa, attrs):
    """([Wq, Wk, Wv, Wo], the layer's further inputs: {"Wg": [gate]},
    {"QNorm": .., "KNorm": ..} or {}) of a paged attention layer.
    ``gqa`` None is the multi-head family's four [M, M] float32
    matrices and leaves ``attrs`` alone (its programs stay what they
    were); ``gqa = {"n_kv_head", "head_dim", "gate"}`` (and where the
    layer has them ``qk_norm`` with ``rms_eps``, ``rope_theta``,
    ``window``, ``attn_scale``: what multiplies the scores in place of
    ``head_dim ** -0.5``; ``v_head_dim``: a value head of another size
    than a key head; ``rotary_dim``: the leading share of a head the
    rotation turns; ``value_scale``: what multiplies V; ``sink``: a
    learned float32 logit a query head, ``.sink`` [H], in a window
    layer's softmax) declares a grouped-KV layer in x's dtype — Wq
    [M, H*D], Wk [M, n_kv*D], Wv [M, n_kv*Dv], Wg [M, H*Dv], Wo
    [H*Dv, M], names ``<base>.wq`` ... ``.wg`` — and sets the attrs the
    op reads them by."""
    if gqa is None:
        return _attention_projection_params(helper, d_model, param_attr), {}
    import copy
    n_kv, d = int(gqa["n_kv_head"]), int(gqa["head_dim"])
    dv = int(gqa.get("v_head_dim") or d)
    attrs.update(n_kv_head=n_kv, head_dim=d)
    if dv != d:
        attrs["v_head_dim"] = dv
    shapes = {"wq": [d_model, int(n_head) * d], "wk": [d_model, n_kv * d],
              "wv": [d_model, n_kv * dv], "wo": [int(n_head) * dv, d_model]}
    if gqa.get("gate"):
        shapes["wg"] = [d_model, int(n_head) * dv]
    ws = {}
    for tag, shape in shapes.items():
        a = copy.deepcopy(param_attr)
        a.name = f"{a.name}.{tag}"
        ws[tag] = helper.create_parameter(a, shape=shape, dtype=x.dtype)
    extra = {"Wg": [ws["wg"]]} if "wg" in ws else {}
    # what only some grouped-KV layers have, attrs and inputs alike set
    # only where the layer has it: a norm of each q and k head (gains
    # ``.q_norm`` / ``.k_norm`` [D]), rotary positions, a window
    if gqa.get("qk_norm"):
        from paddle_tpu.fluid.param_attr import ParamAttr
        attrs.update(qk_norm=True, rms_eps=float(gqa["rms_eps"]))
        # "projection": ONE norm over all of a projection's heads (gains
        # [H*D] and [n_kv*D]: Olmo's) in place of one a head
        whole = gqa["qk_norm"] == "projection"
        if whole:
            attrs["qk_norm_whole"] = True
        for slot, tag, heads in (("QNorm", "q_norm", int(n_head)),
                                 ("KNorm", "k_norm", n_kv)):
            extra[slot] = [helper.create_parameter(
                ParamAttr(name=f"{param_attr.name}.{tag}"),
                shape=[heads * d if whole else d],
                dtype=x.dtype, default_initializer=ConstantInitializer(1.0))]
    if gqa.get("rope_theta"):
        attrs["rope_theta"] = float(gqa["rope_theta"])
        if gqa.get("rotary_dim") and int(gqa["rotary_dim"]) != d:
            attrs["rotary_dim"] = int(gqa["rotary_dim"])
    if gqa.get("window"):
        attrs["window"] = int(gqa["window"])
    if gqa.get("attn_scale"):
        attrs["attn_scale"] = float(gqa["attn_scale"])
    if gqa.get("value_scale"):
        attrs["value_scale"] = float(gqa["value_scale"])
    if gqa.get("sink"):
        a = copy.deepcopy(param_attr)
        a.name = f"{a.name}.sink"
        extra["Sink"] = [helper.create_parameter(
            a, shape=[int(n_head)], dtype="float32")]
    return [ws[t] for t in ("wq", "wk", "wv", "wo")], extra


def _attended_output(helper, gqa) -> dict:
    """A window layer's ``Attended`` output, named ``<weights'
    base>_attended`` so that a check can fetch it (per query the lowest
    key position attended and how many)."""
    if not gqa or not gqa.get("window"):
        return {}
    return {"Attended": [helper.block.create_var(
        name=gqa["attended_name"], dtype="int32")]}


def rms_norm(x, epsilon=1e-5, param_attr=None, name=None):
    """RMSNorm over the last axis (float32 statistics, the result in
    x's dtype), with a learned gain initialised to 1."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, shape=[int(x.shape[-1])], dtype=x.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("rms_norm", inputs={"X": [x], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs={"epsilon": epsilon})
    return out


def dense(x, size, param_attr=None, out_dtype=None, out_name=None,
          name=None, weight=None, scale=None):
    """x [..., M] @ W [M, size], multiplied in x's dtype with float32
    accumulation; the result in ``out_dtype`` (x's by default) — a
    bfloat16 model's logits stay float32 this way. ``out_name`` names
    the result so that a caller can fetch it. ``weight`` is a variable
    [size, M] that exists (a TIED head: the embedding's table, read as
    it lies and contracted over its columns, no transposed copy) in
    place of a parameter of the layer's own; ``scale`` multiplies the
    float32 result (a tied, scaled head: logits / logits_scaling)."""
    helper = LayerHelper("dense", name=name)
    attrs = {"out_dtype": out_dtype} if out_dtype else {}
    if weight is not None:
        w, attrs["transpose_w"] = weight, True
    else:
        w = helper.create_parameter(
            param_attr, shape=[int(x.shape[-1]), size], dtype=x.dtype)
    if scale is not None:
        attrs["scale"] = float(scale)
    dtype = out_dtype or x.dtype
    if out_name:
        out = helper.block.create_var(name=out_name, dtype=dtype)
    else:
        out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("dense", inputs={"X": [x], "W": [w]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def _kda_weights(helper, x, d_model, n_head, head_dim, conv_taps, rank,
                 base, init):
    """The weights of one KDA layer (ops/kda.py), named ``<base>.<tag>``;
    ``init`` initialises every matrix. The decay starts as
    Mamba-2 and Gated DeltaNet start it: A = exp(A_log) spread evenly
    over [1, 16] across heads, and a dt_bias whose softplus is spread
    log-evenly over [0.001, 0.1] across channels — so a head forgets
    within a token or two, or remembers for thousands."""
    from paddle_tpu.fluid.initializer import NumpyArrayInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr
    wide = n_head * head_dim
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), wide))
    # tag: (the op's slot, shape, the fixed float32 start or None: drawn)
    table = {
        "wq": ("Wq", [d_model, wide], None),
        "wk": ("Wk", [d_model, wide], None),
        "wv": ("Wv", [d_model, wide], None),
        "wo": ("Wo", [wide, d_model], None),
        "conv": ("ConvW", [conv_taps, 3 * wide], None),
        "a_log": ("ALog", [n_head], np.log(np.linspace(1.0, 16.0, n_head))),
        "dt_bias": ("DtBias", [wide], dt0 + np.log(-np.expm1(-dt0))),
        "wa_down": ("WaDown", [d_model, rank], None),
        "wa_up": ("WaUp", [rank, wide], None),
        "wbeta": ("WBeta", [d_model, n_head], None),
        "wg_down": ("WgDown", [d_model, rank], None),
        "wg_up": ("WgUp", [rank, wide], None),
        "onorm": ("ONorm", [head_dim], np.ones(head_dim))}
    out = {}
    for tag, (slot, shape, fixed) in table.items():
        attr = ParamAttr(
            name=f"{base}.{tag}",
            initializer=init if fixed is None else NumpyArrayInitializer(
                fixed.astype(np.float32)))
        out[slot] = [helper.create_parameter(
            attr, shape=shape,
            dtype=x.dtype if fixed is None else "float32")]
    return out


def kda(x, state, conv, d_model, n_head, head_dim, base, init, rank,
        conv_taps=4, epsilon=1e-5, seq_len=None, slot=None, active=None,
        name=None):
    """One Kimi Delta Attention layer (ops/kda.py) over the persistable
    per-slot ``state`` [n_slots, H, D, D] float32 and ``conv``
    [n_slots, taps-1, 3*H*D], both read and written under their own
    names (donated). With ``seq_len`` and ``slot`` it is the prefill of
    ONE request, x [1, T, M], writing slot ``slot``; with ``active`` the
    decode step of every slot, x [n_slots, 1, M]."""
    prefill = seq_len is not None
    op = "kda_prefill" if prefill else "kda_decode"
    helper = LayerHelper(op, name=name)
    inputs = _kda_weights(helper, x, d_model, n_head, head_dim, conv_taps,
                          rank, base, init)
    inputs.update(X=[x], State=[state], Conv=[conv])
    if prefill:
        inputs.update(SeqLen=[seq_len], Slot=[slot])
    else:
        inputs.update(Active=[active])
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state],
                              "ConvOut": [conv]},
                     attrs={"n_head": int(n_head),
                            "head_dim": int(head_dim),
                            "epsilon": float(epsilon)})
    return out


def ssd(x, state, conv, d_model, sizes, base, init, epsilon=1e-5,
        seq_len=None, slot=None, active=None, name=None):
    """One Mamba-2 (SSD) mixer layer (ops/ssd.py) over the persistable
    per-slot ``state`` [n_slots, N, H*P] float32 and ``conv``
    [n_slots, taps-1, H*P + 2*G*N], both read and written under their
    own names (donated). ``sizes``: ssd_heads (H), ssd_head_dim (P),
    ssd_d_state (N), ssd_groups (G), ssd_conv_taps, ssd_chunk. With
    ``seq_len`` and ``slot`` it is the prefill of ONE request, x
    [1, T, M], writing slot ``slot``; with ``active`` the decode step of
    every slot, x [n_slots, 1, M]. Weights ``<base>.<tag>``; the decay
    starts as Mamba-2 starts it: A = exp(A_log) spread evenly over
    [1, 16] across the heads and a dt_bias whose softplus is spread
    log-evenly over [0.001, 0.1] across them; D = 1."""
    from paddle_tpu.fluid.initializer import NumpyArrayInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr
    prefill = seq_len is not None
    op = "ssd_prefill" if prefill else "ssd_decode"
    helper = LayerHelper(op, name=name)
    h, p = int(sizes["ssd_heads"]), int(sizes["ssd_head_dim"])
    n, g = int(sizes["ssd_d_state"]), int(sizes["ssd_groups"])
    taps = int(sizes["ssd_conv_taps"])
    inner, wide = h * p, h * p + 2 * g * n
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), h))
    # tag: (the op's slot, shape, the fixed float32 start or None: drawn)
    table = {
        "w_in": ("WIn", [d_model, inner + wide + h], None),
        "w_out": ("WOut", [inner, d_model], None),
        "conv": ("ConvW", [taps, wide], None),
        "conv_bias": ("ConvB", [1, wide], None),
        "a_log": ("ALog", [h], np.log(np.linspace(1.0, 16.0, h))),
        "dt_bias": ("DtBias", [h], dt0 + np.log(-np.expm1(-dt0))),
        "d": ("D", [h], np.ones(h)),
        "norm": ("Norm", [inner], np.ones(inner))}
    inputs = {}
    for tag, (slot_name, shape, fixed) in table.items():
        attr = ParamAttr(
            name=f"{base}.{tag}",
            initializer=init if fixed is None else NumpyArrayInitializer(
                fixed.astype(np.float32)))
        inputs[slot_name] = [helper.create_parameter(
            attr, shape=shape,
            dtype=x.dtype if fixed is None else "float32")]
    inputs.update(X=[x], State=[state], Conv=[conv])
    if prefill:
        inputs.update(SeqLen=[seq_len], Slot=[slot])
    else:
        inputs.update(Active=[active])
    attrs = {"n_head": h, "head_dim": p, "d_state": n, "n_groups": g,
             "epsilon": float(epsilon)}
    if prefill:
        attrs["chunk"] = int(sizes["ssd_chunk"])
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state],
                              "ConvOut": [conv]}, attrs=attrs)
    return out


def s6(x, state, conv, d_model, sizes, base, init, epsilon=1e-6,
       seq_len=None, slot=None, active=None, name=None):
    """One Mamba-1 (S6) mixer layer (ops/s6.py) over the persistable
    per-slot ``state`` [n_slots, N, C] float32 and ``conv``
    [n_slots, taps-1, C], both read and written under their own names
    (donated). ``sizes``: s6_d_inner (C), s6_d_state (N), s6_dt_rank (R),
    s6_conv_taps, s6_chunk. With ``seq_len`` and ``slot`` it is the
    prefill of ONE request, x [1, T, M], writing slot ``slot``; with
    ``active`` the decode step of every slot, x [n_slots, 1, M]. Weights
    ``<base>.<tag>``; the decay starts as Mamba-1 starts it: A =
    exp(A_log) = 1..N along the state index in every channel (kept flat,
    [N * C]: ops/s6.py says why), a dt_bias whose softplus is spread
    log-evenly over [0.001, 0.1] across the channels, D = 1; the three
    norms' gains 1."""
    from paddle_tpu.fluid.initializer import NumpyArrayInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr
    prefill = seq_len is not None
    op = "s6_prefill" if prefill else "s6_decode"
    helper = LayerHelper(op, name=name)
    inner, n, r = (int(sizes[k]) for k in ("s6_d_inner", "s6_d_state",
                                           "s6_dt_rank"))
    taps = int(sizes["s6_conv_taps"])
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), inner))
    # tag: (the op's slot, shape, the fixed float32 start or None: drawn)
    table = {
        "w_in": ("WIn", [d_model, 2 * inner], None),
        "w_out": ("WOut", [inner, d_model], None),
        "conv": ("ConvW", [taps, inner], None),
        "conv_bias": ("ConvB", [1, inner], None),
        "w_x": ("WX", [inner, r + 2 * n], None),
        "dt_norm": ("DtNorm", [r], np.ones(r)),
        "b_norm": ("BNorm", [n], np.ones(n)),
        "c_norm": ("CNorm", [n], np.ones(n)),
        "w_dt": ("WDt", [r, inner], None),
        "dt_bias": ("DtBias", [inner], dt0 + np.log(-np.expm1(-dt0))),
        "a_log": ("ALog", [n * inner],
                  np.repeat(np.log(np.arange(1.0, n + 1.0)), inner)),
        "d": ("D", [inner], np.ones(inner))}
    inputs = {}
    for tag, (slot_name, shape, fixed) in table.items():
        attr = ParamAttr(
            name=f"{base}.{tag}",
            initializer=init if fixed is None else NumpyArrayInitializer(
                fixed.astype(np.float32)))
        inputs[slot_name] = [helper.create_parameter(
            attr, shape=shape,
            dtype=x.dtype if fixed is None else "float32")]
    inputs.update(X=[x], State=[state], Conv=[conv])
    if prefill:
        inputs.update(SeqLen=[seq_len], Slot=[slot])
    else:
        inputs.update(Active=[active])
    attrs = {"epsilon": float(epsilon)}
    if prefill:
        attrs["chunk"] = int(sizes["s6_chunk"])
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state],
                              "ConvOut": [conv]}, attrs=attrs)
    return out


def gdn(x, state, conv, d_model, sizes, base, init, epsilon=1e-5,
        seq_len=None, slot=None, active=None, name=None):
    """One Gated DeltaNet layer (ops/gdn.py) over the persistable
    per-slot ``state`` [n_slots, H, Dk, Dv] float32 and ``conv``
    [n_slots, taps-1, 2*H*Dk + H*Dv], both read and written under their
    own names (donated). ``sizes``: gdn_heads (H), gdn_key_dim (Dk),
    gdn_value_dim (Dv), gdn_conv_taps, gdn_chunk. With ``seq_len`` and
    ``slot`` it is the prefill of ONE request, x [1, T, M], writing slot
    ``slot``; with ``active`` the decode step of every slot, x
    [n_slots, 1, M]. Weights ``<base>.<tag>``; the decay starts as
    Mamba-2 and Gated DeltaNet start it, a head at a time: A =
    exp(A_log) spread evenly over [1, 16] across the heads and a dt_bias
    whose softplus is spread log-evenly over [0.001, 0.1] across them —
    so a head forgets within a token or two, or remembers for
    thousands."""
    from paddle_tpu.fluid.initializer import NumpyArrayInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr
    prefill = seq_len is not None
    op = "gdn_prefill" if prefill else "gdn_decode"
    helper = LayerHelper(op, name=name)
    h, dk, dv = (int(sizes[k]) for k in ("gdn_heads", "gdn_key_dim",
                                         "gdn_value_dim"))
    taps = int(sizes["gdn_conv_taps"])
    dt0 = np.exp(np.linspace(np.log(1e-3), np.log(0.1), h))
    # tag: (the op's slot, shape, the fixed float32 start or None: drawn)
    table = {
        "wq": ("Wq", [d_model, h * dk], None),
        "wk": ("Wk", [d_model, h * dk], None),
        "wv": ("Wv", [d_model, h * dv], None),
        "wz": ("Wz", [d_model, h * dv], None),
        "wo": ("Wo", [h * dv, d_model], None),
        "conv": ("ConvW", [taps, 2 * h * dk + h * dv], None),
        "a_log": ("ALog", [h], np.log(np.linspace(1.0, 16.0, h))),
        "dt_bias": ("DtBias", [h], dt0 + np.log(-np.expm1(-dt0))),
        "wa": ("Wa", [d_model, h], None),
        "wb": ("Wb", [d_model, h], None),
        "onorm": ("ONorm", [dv], np.ones(dv))}
    inputs = {}
    for tag, (slot_name, shape, fixed) in table.items():
        attr = ParamAttr(
            name=f"{base}.{tag}",
            initializer=init if fixed is None else NumpyArrayInitializer(
                fixed.astype(np.float32)))
        inputs[slot_name] = [helper.create_parameter(
            attr, shape=shape,
            dtype=x.dtype if fixed is None else "float32")]
    inputs.update(X=[x], State=[state], Conv=[conv])
    if prefill:
        inputs.update(SeqLen=[seq_len], Slot=[slot])
    else:
        inputs.update(Active=[active])
    attrs = {"n_head": h, "key_dim": dk, "value_dim": dv,
             "epsilon": float(epsilon)}
    if prefill:
        attrs["chunk"] = int(sizes["gdn_chunk"])
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state],
                              "ConvOut": [conv]}, attrs=attrs)
    return out


def shortconv(x, conv, d_model, taps, base, init, seq_len=None, slot=None,
              active=None, name=None):
    """One gated short convolution layer of the LFM2 family
    (ops/shortconv.py) over the persistable per-slot ``conv``
    [n_slots, taps-1, M], read and written under its own name (donated):
    the last rows of ``B * x``. With ``seq_len`` and ``slot`` it is the
    prefill of ONE request, x [1, T, M], writing slot ``slot``; with
    ``active`` the decode step of every slot, x [n_slots, 1, M]. Weights
    ``<base>.w_in`` [M, 3M] (B, C, x in that order), ``.conv`` [taps, M]
    (depthwise, no bias), ``.w_out`` [M, M], all drawn by ``init``."""
    from paddle_tpu.fluid.param_attr import ParamAttr
    prefill = seq_len is not None
    op = "shortconv_prefill" if prefill else "shortconv_decode"
    helper = LayerHelper(op, name=name)
    shapes = {"w_in": ("WIn", [d_model, 3 * d_model]),
              "conv": ("ConvW", [int(taps), d_model]),
              "w_out": ("WOut", [d_model, d_model])}
    inputs = {"X": [x], "Conv": [conv]}
    for tag, (slot_name, shape) in shapes.items():
        inputs[slot_name] = [helper.create_parameter(
            ParamAttr(name=f"{base}.{tag}", initializer=init),
            shape=shape, dtype=x.dtype)]
    if prefill:
        inputs.update(SeqLen=[seq_len], Slot=[slot])
    else:
        inputs.update(Active=[active])
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "ConvOut": [conv]})
    return out


def expert_ffn_held(x, d_model, d_expert, n_experts, n_held, top_k, base,
                    init, held_start=0, n_shared=1, norm_topk=True,
                    scaling=1.0, valid=None, seq_len=None, counts=None,
                    name=None, router_bias=False, d_shared=None,
                    scoring="sigmoid", load=False):
    """One expert-parallel member's share of a top-k routed expert layer
    plus the shared expert, where the model has one (``n_shared`` 0: no
    shared expert, no parameters of one) (ops/expert_ffn.py): the router is
    ``n_experts`` wide, the ``n_held`` experts from ``held_start`` are
    computed here. ``valid`` [n, 1] int or ``seq_len`` [1, 1] says which
    tokens are real; ``counts`` [2, n_held] int32 (persistable, donated)
    accumulates the tokens each held expert was given and the calls in
    which it was given any. ``router_bias`` declares the router's
    correction bias [1, n_experts] float32 (``<base>.router_bias``):
    experts are then picked by score + bias and weighed by score.
    ``d_shared`` is the shared expert's own width (``n_shared *
    d_expert`` when None); ``scoring`` the router's: ``"sigmoid"``
    scores over every expert, or ``"softmax_topk"`` — the best ``top_k``
    by logit, weighed by a softmax over those logits alone. With
    ``load`` (a trainer's layer) the result is (out, the step's picks
    per expert over the whole router [n_experts] int32) and the
    correction bias is no trainable parameter: no gradient reaches it,
    ``router_bias_update`` is its whole update."""
    from paddle_tpu.fluid.param_attr import ParamAttr
    helper = LayerHelper("expert_ffn_held", name=name)
    shared = n_shared * d_expert if d_shared is None else int(d_shared)
    shapes = {"router": ("RouterW", [d_model, n_experts]),
              "w_gate": ("WGate", [n_held, d_model, d_expert]),
              "w_up": ("WUp", [n_held, d_model, d_expert]),
              "w_down": ("WDown", [n_held, d_expert, d_model])}
    if shared:
        # a layer without a shared expert has no such parameters
        shapes.update({"s_gate": ("SGate", [d_model, shared]),
                       "s_up": ("SUp", [d_model, shared]),
                       "s_down": ("SDown", [shared, d_model])})
    inputs = {"X": [x]}
    for tag, (slot_name, shape) in shapes.items():
        inputs[slot_name] = [helper.create_parameter(
            ParamAttr(name=f"{base}.{tag}", initializer=init),
            shape=shape, dtype=x.dtype)]
    if router_bias:
        bias = helper.create_parameter(
            ParamAttr(name=f"{base}.router_bias", initializer=init,
                      trainable=not load),
            shape=[1, n_experts], dtype="float32")
        bias.stop_gradient = bool(load)
        inputs["RouterBias"] = [bias]
    outputs = {"Out": [helper.create_variable_for_type_inference(x.dtype)]}
    if valid is not None:
        inputs["Valid"] = [valid]
    if seq_len is not None:
        inputs["SeqLen"] = [seq_len]
    if counts is not None:
        inputs["Counts"], outputs["CountsOut"] = [counts], [counts]
    attrs = {"top_k": int(top_k), "held_start": int(held_start),
             "norm_topk": bool(norm_topk), "scaling": float(scaling)}
    if scoring != "sigmoid":
        # set only where it differs: the sigmoid routers' programs stay
        # what they were
        attrs["scoring"] = str(scoring)
    if load:
        attrs["load"] = True
        outputs["Load"] = [helper.create_variable_for_type_inference("int32")]
        outputs["Load"][0].stop_gradient = True
    helper.append_op("expert_ffn_held", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    if load:
        return outputs["Out"][0], outputs["Load"][0]
    return outputs["Out"][0]


def router_bias_update(bias, load, gamma, total_name=None, name=None):
    """After a step, move a router's correction ``bias`` [1, E] (in
    place) by ``gamma`` toward the experts that the step's ``load`` [E]
    gave fewer picks than the mean (ops/expert_ffn.py:
    ``router_bias_update``). With ``total_name`` a persistable [E] int32
    of that name accumulates the loads (what a load metric reads), and
    is returned."""
    from paddle_tpu.fluid.initializer import ConstantInitializer
    helper = LayerHelper("router_bias_update", name=name)
    inputs = {"Bias": [bias], "Load": [load]}
    outputs = {"BiasOut": [bias]}
    total = None
    if total_name:
        shape = [int(bias.shape[-1])]
        total = helper.main_program.global_block().create_var(
            name=total_name, shape=shape, dtype="int32", persistable=True,
            stop_gradient=True)
        startup = helper.startup_program.global_block()
        ConstantInitializer(0)(startup.create_var(
            name=total_name, shape=shape, dtype="int32", persistable=True),
            startup)
        inputs["LoadTotal"], outputs["LoadTotalOut"] = [total], [total]
    helper.append_op("router_bias_update", inputs=inputs, outputs=outputs,
                     attrs={"gamma": float(gamma)})
    return total


def swiglu_ffn(x, d_model, d_inner, base, init, name=None):
    """A dense SwiGLU feed-forward layer W_down (SiLU(W_gate x) * W_up x)
    of width ``d_inner`` (ops/expert_ffn.py), weights ``<base>.w_gate``,
    ``.w_up``, ``.w_down`` in x's dtype."""
    from paddle_tpu.fluid.param_attr import ParamAttr
    helper = LayerHelper("swiglu_ffn", name=name)
    shapes = {"w_gate": ("WGate", [d_model, d_inner]),
              "w_up": ("WUp", [d_model, d_inner]),
              "w_down": ("WDown", [d_inner, d_model])}
    inputs = {"X": [x]}
    for tag, (slot_name, shape) in shapes.items():
        inputs[slot_name] = [helper.create_parameter(
            ParamAttr(name=f"{base}.{tag}", initializer=init),
            shape=shape, dtype=x.dtype)]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("swiglu_ffn", inputs=inputs, outputs={"Out": [out]})
    return out


def _mla_weights(helper, x, d_model, sizes, base, init, indexer):
    """{op slot: [parameter]} of one latent attention layer, named
    ``<base>.<tag>`` (``init`` draws every matrix; gains start at 1):
    the eight of the attention itself and, with ``indexer``, the DSA
    indexer's five."""
    from paddle_tpu.fluid.initializer import ConstantInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr
    h, ql, dc = sizes["n_head"], sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    # tag: (the op's slot, shape, a fixed start or None: drawn)
    table = {
        "wdq": ("Wdq", [d_model, ql], None),
        "q_norm": ("QNorm", [ql], 1.0),
        "wuq": ("Wuq", [ql, h * (dn + dr)], None),
        "wdkv": ("Wdkv", [d_model, dc + dr], None),
        "kv_norm": ("KvNorm", [dc], 1.0),
        "wuk": ("Wuk", [dc, h * dn], None),
        "wuv": ("Wuv", [dc, h * dv], None),
        "wo": ("Wo", [h * dv, d_model], None)}
    if indexer:
        j, di = sizes["index_n_heads"], sizes["index_head_dim"]
        table.update({
            "wiq": ("Wiq", [ql, j * di], None),
            "wik": ("Wik", [d_model, di], None),
            "ik_scale": ("IkScale", [di], 1.0),
            "ik_bias": ("IkBias", [di], 0.0),
            "wiw": ("Wiw", [d_model, j], None)})
    return {slot: [helper.create_parameter(
        ParamAttr(name=f"{base}.{tag}",
                  initializer=init if fixed is None
                  else ConstantInitializer(fixed)),
        shape=shape, dtype=x.dtype)]
        for tag, (slot, shape, fixed) in table.items()}


def mla_full(x, d_model, sizes, base, init, rope_theta, epsilon=1e-5,
             name=None):
    """One multi-head latent attention layer WITHOUT an indexer over
    whole sequences x [B, T, M], causal (ops/mla.py: ``mla_full``; it
    has a gradient). ``sizes``: n_head, q_lora_rank, kv_lora_rank,
    qk_nope_head_dim, qk_rope_head_dim, v_head_dim. Its weights carry
    the names :func:`mla` gives the same matrices."""
    helper = LayerHelper("mla_full", name=name)
    inputs = {"X": [x], **_mla_weights(helper, x, d_model, sizes, base,
                                       init, indexer=False)}
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mla_full", inputs=inputs, outputs={"Out": [out]},
                     attrs={**{k: int(v) for k, v in sizes.items()},
                            "rope_theta": float(rope_theta),
                            "epsilon": float(epsilon)})
    return out


def mla(x, page_c, page_i, d_model, sizes, base, init, rope_theta,
        epsilon=1e-5, rows=None, decode=None, selected_name=None,
        name=None):
    """One multi-head latent attention layer with the DSA indexer
    (ops/mla.py) over the persistable LATENT plane ``page_c`` [n_pages,
    page_size, W] and INDEX plane ``page_i`` [n_pages, page_size, di],
    both read and written under their own names (donated). ``sizes``:
    n_head, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, index_n_heads, index_head_dim,
    index_topk. With ``rows`` it is the prefill of ONE request, x
    [1, T, M]; with ``decode`` (page_table, pos, seq_len, gen_start,
    active, position) the step of every slot, x [n_slots, 1, M], whose
    attended rows land in a variable named ``selected_name``."""
    op = "mla_prefill_paged" if rows is not None else "mla_decode_paged"
    helper = LayerHelper(op, name=name)
    inputs = {"X": [x], "PageC": [page_c], "PageI": [page_i],
              **_mla_weights(helper, x, d_model, sizes, base, init,
                             indexer=True)}
    out = helper.create_variable_for_type_inference(x.dtype)
    outputs = {"Out": [out], "PageCOut": [page_c], "PageIOut": [page_i]}
    if rows is not None:
        inputs["Rows"] = [rows]
    else:
        for slot, var in zip(("PageTable", "Pos", "SeqLen", "GenStart",
                              "Active", "Position"), decode):
            inputs[slot] = [var]
        outputs["Selected"] = [helper.block.create_var(
            name=selected_name, dtype="int32")]
    helper.append_op(op, inputs=inputs, outputs=outputs,
                     attrs={**{k: int(v) for k, v in sizes.items()},
                            "rope_theta": float(rope_theta),
                            "epsilon": float(epsilon)})
    return out


def kv_attention_prefill_paged(x, rows, d_model, n_head, page_k, page_v,
                               page_ks=None, page_vs=None, codec="none",
                               param_attr=None, name=None, gqa=None):
    """In-flight-batching prefill (ISSUE 9, 17): causal self-attention
    over the prompt whose K/V rows scatter into the LIVE paged pool
    caches, so a new request joins a running decode without disturbing
    the slots mid-flight
    (``page_k``/``page_v``, persistable [n_pages, page_size, H*D] vars
    read and written under the same names — donated state; the whole
    model width on the minor dimension keeps them row-major at rest on
    the TPU, ops/kv_attention.py:_paged_pools) at the
    per-position flat row indices ``rows`` [B*T, 1] from the slot's
    page-table lease. Sentinel rows (>= n_pages*page_size) DROP — how
    prefix-SHARED pages are skipped (already resident, bit-identical:
    K/V at position j depends only on token j) and how copy-on-write
    stays a recompute, never a device copy. ``codec='int8'`` quantizes
    on write into ``page_ks``/``page_vs`` scale planes
    (ops/kv_attention.py; docs/serving.md 'Paged KV cache')."""
    helper = LayerHelper("kv_attention_prefill_paged", name=name)
    attrs = {"n_head": int(n_head), "codec": str(codec)}
    ws, gate = _attention_weights(helper, x, d_model, n_head, param_attr,
                                  gqa, attrs)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Wq": [ws[0]], "Wk": [ws[1]],
              "Wv": [ws[2]], "Wo": [ws[3]], **gate,
              "PageK": [page_k], "PageV": [page_v], "Rows": [rows]}
    outputs = {"Out": [out], "PageKOut": [page_k],
               "PageVOut": [page_v], **_attended_output(helper, gqa)}
    if codec == "int8":
        inputs["PageKS"], inputs["PageVS"] = [page_ks], [page_vs]
        outputs["PageKSOut"], outputs["PageVSOut"] = [page_ks], [page_vs]
    helper.append_op("kv_attention_prefill_paged",
                     inputs=inputs, outputs=outputs, attrs=attrs)
    return out


def kv_attention_decode_paged(x, page_table, pos, seq_len, gen_start,
                              active, d_model, n_head, page_k, page_v,
                              page_ks=None, page_vs=None, codec="none",
                              param_attr=None, name=None, gqa=None):
    """One-token decode over the PAGED KV pool with fully per-row
    geometry (``pos`` each row's cache write index, ``gen_start`` where
    its generated region begins, ``seq_len`` its true prompt length,
    ``active`` == 0 a free slot that flows through untouched): the
    cache row for logical
    position j of slot b resolves through the page-table feed
    (``page_table`` [B, max_pages] int — a STATIC-shape feed, so every
    join/leave/page mix dispatches the same executable, zero
    steady-state compiles). ``page_k``/``page_v`` are the
    [n_pages, page_size, H*D] pools of ``kv_attention_prefill_paged``
    (+ [n_pages, page_size, H] scale planes under ``codec='int8'``).
    The gather runs the scalar-prefetch Pallas
    kernel on TPU (ops/pallas/paged_attention.py) and dequantizes
    in-gather under ``codec='int8'``. x [B, 1, M] -> [B, 1, M]
    (ops/kv_attention.py; docs/serving.md 'Paged KV cache')."""
    helper = LayerHelper("kv_attention_decode_paged", name=name)
    attrs = {"n_head": int(n_head), "codec": str(codec)}
    ws, gate = _attention_weights(helper, x, d_model, n_head, param_attr,
                                  gqa, attrs)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Wq": [ws[0]], "Wk": [ws[1]],
              "Wv": [ws[2]], "Wo": [ws[3]], **gate,
              "PageK": [page_k], "PageV": [page_v],
              "PageTable": [page_table], "Pos": [pos],
              "SeqLen": [seq_len], "GenStart": [gen_start],
              "Active": [active]}
    outputs = {"Out": [out], "PageKOut": [page_k],
               "PageVOut": [page_v], **_attended_output(helper, gqa)}
    if codec == "int8":
        inputs["PageKS"], inputs["PageVS"] = [page_ks], [page_vs]
        outputs["PageKSOut"], outputs["PageVSOut"] = [page_ks], [page_vs]
    helper.append_op("kv_attention_decode_paged",
                     inputs=inputs, outputs=outputs, attrs=attrs)
    return out


def kv_attention_verify_paged(x, page_table, pos, seq_len, gen_start,
                              active, win_len, d_model, n_head, page_k,
                              page_v, page_ks=None, page_vs=None,
                              codec="none", param_attr=None, name=None):
    """Speculative-decode verify step (ISSUE 19) over the paged KV pool:
    score a [B, K+1] token window — position 0 the row's last committed
    token, positions 1..K the drafts — in ONE causal dispatch, writing
    window position i's k/v at logical cache row ``pos + i`` (resolved
    through the page-table feed) where ``active`` and ``i < win_len``.
    Position i attends over {j < seq_len} ∪ {gen_start <= j <= pos + i},
    so its output is what i sequential ``kv_attention_decode_paged``
    steps over the same tokens produce — the losslessness guarantee the
    engine's accept rule rests on. Rollback of rejected positions is
    overwrite-in-place: they sit above the committed frontier and the
    mask never admits them. Writes that fall past the
    slot's leased span resolve to the sentinel page and DROP — a draft
    window can never write another slot's pages (admission reserves the
    draft-window overshoot, ``PagePool.span_for(draft_window=K)``).
    x [B, K+1, M], page_table [B, max_pages] int,
    page_k/page_v [n_pages, page_size, H*D],
    pos/seq_len/gen_start/active/win_len [B, 1] int -> [B, K+1, M]
    (ops/kv_attention.py; docs/serving.md 'Speculative decoding')."""
    helper = LayerHelper("kv_attention_verify_paged", name=name)
    ws = _attention_projection_params(helper, d_model, param_attr)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Wq": [ws[0]], "Wk": [ws[1]],
              "Wv": [ws[2]], "Wo": [ws[3]],
              "PageK": [page_k], "PageV": [page_v],
              "PageTable": [page_table], "Pos": [pos],
              "SeqLen": [seq_len], "GenStart": [gen_start],
              "Active": [active], "WinLen": [win_len]}
    outputs = {"Out": [out], "PageKOut": [page_k],
               "PageVOut": [page_v]}
    if codec == "int8":
        inputs["PageKS"], inputs["PageVS"] = [page_ks], [page_vs]
        outputs["PageKSOut"], outputs["PageVSOut"] = [page_ks], [page_vs]
    helper.append_op("kv_attention_verify_paged",
                     inputs=inputs, outputs=outputs,
                     attrs={"n_head": int(n_head), "codec": str(codec)})
    return out


def token_sample(logits, temperature, top_k, seed, step_idx, name=None):
    """On-device next-token selection (ops/kv_attention.py): greedy
    argmax when ``temperature <= 0`` or ``top_k == 1`` (bit-identical to
    a host argmax over the same logits — the parity oracle), otherwise
    temperature-scaled top-k Gumbel sampling keyed ONLY by the
    per-request ``seed`` and the ``step_idx`` token index, so a sampled
    stream replays identically across processes and server restarts.
    logits [B, V]; temperature [B, 1] float; top_k [B, 1] int (<=0: no
    filter); seed/step_idx [B, 1] int -> [B, 1] int64."""
    helper = LayerHelper("token_sample", name=name)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("token_sample",
                     inputs={"Logits": [logits],
                             "Temperature": [temperature],
                             "TopK": [top_k], "Seed": [seed],
                             "StepIdx": [step_idx]},
                     outputs={"Out": [out]})
    return out


def fused_linear_cross_entropy(input, label, num_classes, label_smoothing=0.0,
                               ignore_index=-100, param_attr=None,
                               name=None):
    """Classifier head: `fc(input, num_classes)` + label-smoothed
    softmax-cross-entropy, fused so the [N, num_classes] logits never
    materialize in HBM (Pallas streaming kernel, ops/pallas/fused_ce.py;
    composed-op fallback off-TPU). input [N, D] (flatten upstream), label
    [N, 1] int. Returns per-row Loss [N, 1]. TPU-native extension of the
    reference's softmax_with_cross_entropy
    (softmax_with_cross_entropy_op.cc) that also fuses the projection."""
    helper = LayerHelper("fused_linear_ce", name=name)
    d = input.shape[-1]
    w = helper.create_parameter(param_attr, shape=[d, num_classes],
                                dtype="float32")
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op("fused_linear_ce",
                     inputs={"X": [input], "W": [w], "Label": [label]},
                     outputs={"Loss": [loss]},
                     attrs={"label_smoothing": float(label_smoothing),
                            "ignore_index": ignore_index})
    return loss


def cos_sim(X, Y, name=None):
    """reference: nn.py cos_sim / operators/cos_sim_op.cc."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None):
    """reference: nn.py nce / operators/nce_op.cc — NCE loss with a uniform
    noise sampler. Returns the per-example Cost [B, 1]."""
    helper = LayerHelper("nce", name=name)
    dim = input.shape[1]
    w = helper.create_parameter(param_attr, shape=[num_total_classes, dim],
                                dtype=input.dtype)
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(input.dtype)
    sample_labels = helper.create_variable_for_type_inference("int32")
    ins = {"Input": [input], "Label": [label], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_total_classes],
                                    dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b]
    if sample_weight is not None:
        ins["SampleWeight"] = [sample_weight]
    helper.append_op(
        "nce", inputs=ins,
        outputs={"Cost": [cost], "SampleLogits": [sample_logits],
                 "SampleLabels": [sample_labels]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples or 10})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """reference: nn.py hsigmoid / operators/hierarchical_sigmoid_op.cc —
    complete-binary-tree hierarchical softmax cost [B, 1]."""
    helper = LayerHelper("hsigmoid", name=name)
    dim = input.shape[1]
    w = helper.create_parameter(param_attr, shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    ins = {"X": [input], "Label": [label], "W": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[1, num_classes - 1],
                                    dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    pre_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "hierarchical_sigmoid", inputs=ins,
        outputs={"Out": [out], "PreOut": [pre_out]},
        attrs={"num_classes": num_classes})
    return out


def linear_chain_crf(input, label, seq_lens=None, param_attr=None, name=None):
    """reference: nn.py linear_chain_crf / operators/linear_chain_crf_op.cc.
    `input` is the padded emission [B, T, N] (+ seq_lens mask, the LoD
    replacement). Returns the per-sequence negative log-likelihood [B, 1];
    the learned Transition parameter is `<name>.w_0`-style and is what
    crf_decoding consumes."""
    helper = LayerHelper("linear_chain_crf", name=name)
    num_tags = input.shape[-1]
    transition = helper.create_parameter(param_attr,
                                         shape=[num_tags + 2, num_tags],
                                         dtype=input.dtype)
    ll = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    em_exps = helper.create_variable_for_type_inference(input.dtype)
    tr_exps = helper.create_variable_for_type_inference(input.dtype)
    ins = {"Emission": [input], "Transition": [transition], "Label": [label]}
    if seq_lens is not None:
        ins["SeqLens"] = [seq_lens]
    helper.append_op(
        "linear_chain_crf", inputs=ins,
        outputs={"LogLikelihood": [ll], "Alpha": [alpha],
                 "EmissionExps": [em_exps], "TransitionExps": [tr_exps]})
    return ll


def crf_decoding(input, param_attr, label=None, seq_lens=None, name=None):
    """reference: nn.py crf_decoding / operators/crf_decoding_op.cc.
    `param_attr` must name the transition parameter created by
    linear_chain_crf (pass its ParamAttr)."""
    helper = LayerHelper("crf_decoding", name=name)
    from paddle_tpu.fluid.param_attr import ParamAttr
    attr = ParamAttr._to_attr(param_attr)
    transition = helper.main_program.global_block().var(attr.name)
    path = helper.create_variable_for_type_inference("int64")
    ins = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        ins["Label"] = [label]
    if seq_lens is not None:
        ins["SeqLens"] = [seq_lens]
    helper.append_op("crf_decoding", inputs=ins,
                     outputs={"ViterbiPath": [path]})
    return path


def chunk_eval(input, label, chunk_scheme, num_chunk_types, seq_lens=None,
               excluded_chunk_types=None):
    """reference: nn.py chunk_eval / operators/metrics/chunk_eval_op.cc.
    Returns (precision, recall, f1, num_infer, num_label, num_correct)."""
    helper = LayerHelper("chunk_eval")
    p = helper.create_variable_for_type_inference("float32")
    r = helper.create_variable_for_type_inference("float32")
    f1 = helper.create_variable_for_type_inference("float32")
    ni = helper.create_variable_for_type_inference("int64")
    nl = helper.create_variable_for_type_inference("int64")
    nc = helper.create_variable_for_type_inference("int64")
    ins = {"Inference": [input], "Label": [label]}
    if seq_lens is not None:
        ins["SeqLens"] = [seq_lens]
    helper.append_op(
        "chunk_eval", inputs=ins,
        outputs={"Precision": [p], "Recall": [r], "F1-Score": [f1],
                 "NumInferChunks": [ni], "NumLabelChunks": [nl],
                 "NumCorrectChunks": [nc]},
        attrs={"num_chunk_types": num_chunk_types,
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": list(excluded_chunk_types or [])})
    return p, r, f1, ni, nl, nc


def beam_search(pre_ids, pre_scores, scores, beam_size, end_id, name=None):
    """reference: nn.py beam_search / operators/beam_search_op.cc. Dense
    [B, W] lane layout (see ops/beam_ops.py for the LoD divergence).
    Returns (selected_ids, selected_scores, parent_idx)."""
    helper = LayerHelper("beam_search", name=name)
    ids = helper.create_variable_for_type_inference("int32")
    sc = helper.create_variable_for_type_inference(scores.dtype)
    parent = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        "beam_search",
        inputs={"PreIds": [pre_ids], "PreScores": [pre_scores],
                "Scores": [scores]},
        outputs={"SelectedIds": [ids], "SelectedScores": [sc],
                 "ParentIdx": [parent]},
        attrs={"beam_size": beam_size, "end_id": end_id})
    return ids, sc, parent


def beam_search_decode(ids, parent_idx, scores, end_id=0, name=None):
    """reference: nn.py beam_search_decode /
    operators/beam_search_decode_op.cc. `ids`/`parent_idx` are the stacked
    per-step selections [T, B, W]. Returns (sentence_ids [B, W, T],
    sentence_scores [B, W])."""
    helper = LayerHelper("beam_search_decode", name=name)
    sent = helper.create_variable_for_type_inference("int32")
    ssc = helper.create_variable_for_type_inference(scores.dtype)
    helper.append_op(
        "beam_search_decode",
        inputs={"Ids": [ids], "ParentIdx": [parent_idx],
                "Scores": [scores]},
        outputs={"SentenceIds": [sent], "SentenceScores": [ssc]},
        attrs={"end_id": end_id})
    return sent, ssc


# -- misc-batch layers (reference: layers/nn.py — multiplex, log_loss,
# rank_loss, margin_rank_loss, crop, pad2d, pad_constant_like, random_crop,
# add_position_encoding, similarity_focus, bilinear_tensor_product, row_conv,
# unstack, argsort, sampling_id, bpr_loss, squared_l2_distance) ------------

def argsort(x, axis=-1, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    idx = helper.create_variable_for_type_inference("int64")
    helper.append_op("argsort", inputs={"X": [x]},
                     outputs={"Out": [out], "Indices": [idx]},
                     attrs={"axis": axis})
    return out, idx


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op("multiplex", inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    loss = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [loss]}, attrs={"epsilon": epsilon})
    return loss


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op("rank_loss",
                     inputs={"Label": [label], "Left": [left],
                             "Right": [right]},
                     outputs={"Out": [out]})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    act = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op("margin_rank_loss",
                     inputs={"Label": [label], "X1": [left], "X2": [right]},
                     outputs={"Out": [out], "Activated": [act]},
                     attrs={"margin": margin})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("bpr_loss", inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def crop(x, shape=None, offsets=None, name=None):
    if shape is None:
        raise ValueError("crop() requires `shape` (a Variable whose shape is "
                         "the crop target, or a list of ints)")
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    attrs = {}
    if hasattr(shape, "desc"):          # a Variable reference shape
        inputs["Y"] = [shape]
    else:
        attrs["shape"] = list(shape)
    if offsets is not None:
        attrs["offsets"] = list(offsets)
    helper.append_op("crop", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": pad_value,
                            "data_format": data_format})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op("pad_constant_like", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"pad_value": pad_value})
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop")
    out = helper.create_variable_for_type_inference(x.dtype)
    seed_out = helper.create_variable_for_type_inference("int64")
    helper.append_op("random_crop", inputs={"X": [x]},
                     outputs={"Out": [out], "SeedOut": [seed_out]},
                     attrs={"shape": list(shape), "seed": seed or 0})
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    helper = LayerHelper("add_position_encoding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("add_position_encoding", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"alpha": alpha, "beta": beta})
    return out


def similarity_focus(input, axis, indexes, name=None):
    helper = LayerHelper("similarity_focus", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("similarity_focus", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"axis": axis, "indexes": list(indexes)})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", name=name,
                         param_attr=param_attr, bias_attr=bias_attr)
    dx, dy = x.shape[-1], y.shape[-1]
    w = helper.create_parameter(attr=helper.param_attr, shape=[size, dx, dy],
                                dtype=x.dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        bias = helper.create_parameter(attr=helper.bias_attr, shape=[1, size],
                                       dtype=x.dtype, is_bias=True)
        inputs["Bias"] = [bias]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out, act)


def row_conv(input, future_context_size, seq_lens=None, param_attr=None,
             act=None):
    helper = LayerHelper("row_conv", param_attr=param_attr)
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[future_context_size + 1,
                                       input.shape[-1]],
                                dtype=input.dtype)
    inputs = {"X": [input], "Filter": [w]}
    if seq_lens is not None:
        inputs["SeqLens"] = [seq_lens]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("row_conv", inputs=inputs, outputs={"Out": [out]})
    return helper.append_activation(out, act)


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(num)]
    helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": num})
    return outs


def sampling_id(x, min=0.0, max=1.0, seed=0):
    helper = LayerHelper("sampling_id")
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("sampling_id", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"min": min, "max": max, "seed": seed})
    return out


# -- image-op layers (reference: layers/nn.py image_resize, resize_bilinear,
# roi_pool, roi_align (1.3 backport), affine_grid, grid_sampler, unpool;
# pool_with_index via pool2d max variant) ----------------------------------

def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 name=None):
    helper = LayerHelper("image_resize", name=name)
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    op = {"BILINEAR": "bilinear_interp", "NEAREST": "nearest_interp"}[resample]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(op, inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"out_h": int(out_shape[0]),
                            "out_w": int(out_shape[1])})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "BILINEAR", name)


def resize_nearest(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "NEAREST", name)


def roi_pool(input, rois, pooled_height=1, pooled_width=1, spatial_scale=1.0,
             rois_batch_id=None):
    helper = LayerHelper("roi_pool")
    out = helper.create_variable_for_type_inference(input.dtype)
    argmax = helper.create_variable_for_type_inference("int32")
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_batch_id is not None:
        inputs["RoisBatchId"] = [rois_batch_id]
    helper.append_op("roi_pool", inputs=inputs,
                     outputs={"Out": [out], "Argmax": [argmax]},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale})
    return out


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, rois_batch_id=None):
    helper = LayerHelper("roi_align")
    out = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_batch_id is not None:
        inputs["RoisBatchId"] = [rois_batch_id]
    helper.append_op("roi_align", inputs=inputs, outputs={"Out": [out]},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale,
                            "sampling_ratio": sampling_ratio})
    return out


def affine_grid(theta, out_shape, name=None):
    if hasattr(out_shape, "desc"):
        raise NotImplementedError(
            "affine_grid with a Variable out_shape is not supported on TPU "
            "(static shapes); pass a list of 4 ints")
    helper = LayerHelper("affine_grid", name=name)
    out = helper.create_variable_for_type_inference(theta.dtype)
    helper.append_op("affine_grid", inputs={"Theta": [theta]},
                     outputs={"Out": [out]},
                     attrs={"output_shape": [int(v) for v in out_shape]})
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("grid_sampler", inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    return out


def affine_channel(x, scale, bias, name=None):
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("affine_channel",
                     inputs={"X": [x], "Scale": [scale], "Bias": [bias]},
                     outputs={"Out": [out]})
    return out


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """reference: layers/nn.py warpctc → warpctc_op.cc. Padded layout:
    input [B, T, C] logits, label [B, S] (pad -1)."""
    helper = LayerHelper("warpctc")
    loss = helper.create_variable_for_type_inference(input.dtype)
    grad = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"Logits": [input], "Label": [label]}
    if input_length is not None:
        inputs["LogitsLength"] = [input_length]
    if label_length is not None:
        inputs["LabelLength"] = [label_length]
    helper.append_op("warpctc", inputs=inputs,
                     outputs={"Loss": [loss], "WarpCTCGrad": [grad]},
                     attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss


def ctc_greedy_decoder(input, blank, name=None):
    """reference: layers/nn.py ctc_greedy_decoder — argmax over classes then
    merge-repeats + drop-blanks (ctc_align). input [B, T, C] probs/logits;
    output [B, T] ids padded with -1."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    ids = helper.create_variable_for_type_inference("int64")
    helper.append_op("argmax", inputs={"X": [input]}, outputs={"Out": [ids]},
                     attrs={"axis": 2})
    out = helper.create_variable_for_type_inference("int32")
    helper.append_op("ctc_align", inputs={"Input": [ids]},
                     outputs={"Output": [out]},
                     attrs={"blank": blank, "merge_repeated": True})
    return out


# ---------------------------------------------------------------------------
# API-surface completion (round 3): every name the reference exports from
# fluid.layers resolves here too (machine-checked by
# tests/test_layers_api_parity.py)
# ---------------------------------------------------------------------------

def _triple(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v, v]


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    """reference: nn.py:1944 — NCDHW conv."""
    helper = LayerHelper("conv3d", name=name)
    num_channels = input.shape[1]
    fsize = _triple(filter_size)
    filter_shape = [num_filters, num_channels // groups] + fsize
    std = (2.0 / (fsize[0] * fsize[1] * fsize[2] * num_channels)) ** 0.5
    from paddle_tpu.fluid.initializer import NormalInitializer
    w = helper.create_parameter(param_attr, shape=filter_shape,
                                dtype=input.dtype,
                                default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv3d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": _triple(stride), "paddings": _triple(padding),
               "dilations": _triple(dilation), "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        with_b = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [with_b]}, attrs={"axis": 1})
        out = with_b
    return helper.append_activation(out, act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """reference: nn.py:3405."""
    helper = LayerHelper("conv3d_transpose", name=name)
    num_channels = input.shape[1]
    fsize = _transpose_filter_size(filter_size, output_size, input.shape[2:],
                                   stride, padding, dilation, 3)
    w = helper.create_parameter(
        param_attr, shape=[num_channels, num_filters // groups] + fsize,
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv3d_transpose", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": _triple(stride), "paddings": _triple(padding),
               "dilations": _triple(dilation), "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        with_b = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [with_b]}, attrs={"axis": 1})
        out = with_b
    return helper.append_activation(out, act)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    """reference: nn.py:2453."""
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool3d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": _triple(pool_size),
               "strides": _triple(pool_stride),
               "paddings": _triple(pool_padding),
               "global_pooling": global_pooling, "ceil_mode": ceil_mode,
               "exclusive": exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max",
                    require_index=False, name=None):
    """reference: nn.py:2526 (floor/ceil bin rule)."""
    if require_index:
        raise NotImplementedError(
            "adaptive_pool2d(require_index=True): use "
            "max_pool2d_with_index for the mask")
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("adaptive_pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooled_size": _pair(pool_size),
                            "pooling_type": pool_type})
    return out


def adaptive_pool3d(input, pool_size, pool_type="max",
                    require_index=False, name=None):
    """reference: nn.py adaptive_pool3d."""
    if require_index:
        raise NotImplementedError(
            "adaptive_pool3d(require_index=True) is not supported")
    helper = LayerHelper("adaptive_pool3d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("adaptive_pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooled_size": _triple(pool_size),
                            "pooling_type": pool_type})
    return out


def group_norm(input, groups, epsilon=1e-05, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    """reference: nn.py:3137 → group_norm_op.cc."""
    helper = LayerHelper("group_norm", name=name)
    c = input.shape[1]
    from paddle_tpu.fluid.initializer import ConstantInitializer
    scale = helper.create_parameter(
        param_attr, shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype,
                                   is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype)
    var = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("group_norm",
                     inputs={"X": [input], "Scale": [scale], "Bias": [bias]},
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out, act)


def data_norm(input, act=None, epsilon=1e-05, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    """reference: nn.py data_norm → data_norm_op.cc (batch-statistics
    normalization without learned scale/shift)."""
    helper = LayerHelper("data_norm", name=name)
    c = input.shape[1]
    import copy

    from paddle_tpu.fluid.initializer import ConstantInitializer
    from paddle_tpu.fluid.param_attr import ParamAttr

    def slot_attr(suffix):
        # one attr object per slot — create_parameter mutates attr.name,
        # so sharing one object would alias all three stats into one var
        a = copy.copy(ParamAttr._to_attr(param_attr))
        a.initializer = None
        if a.name is not None:
            a.name = a.name + suffix
        return a

    batch_size = helper.create_parameter(
        slot_attr(".batch_size"), shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0))
    batch_sum = helper.create_parameter(
        slot_attr(".batch_sum"), shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(0.0))
    batch_square_sum = helper.create_parameter(
        slot_attr(".batch_square_sum"), shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1e4))
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype)
    scales = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("data_norm",
                     inputs={"X": [input], "BatchSize": [batch_size],
                             "BatchSum": [batch_sum],
                             "BatchSquareSum": [batch_square_sum]},
                     outputs={"Y": [out], "Means": [means],
                              "Scales": [scales]},
                     attrs={"epsilon": epsilon})
    return helper.append_activation(out, act)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    """reference: nn.py:6125 → lrn_op.cc."""
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("lrn", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def prelu(x, mode, param_attr=None, name=None):
    """reference: nn.py:7758; mode in {'all','channel','element'}."""
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    else:
        raise ValueError("prelu mode must be all|channel|element")
    from paddle_tpu.fluid.initializer import ConstantInitializer
    alpha = helper.create_parameter(
        param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def soft_relu(x, threshold=40.0, name=None):
    """reference: nn.py:7873 — log(1 + exp(clip(x, -t, t))); composed
    from clip + softplus (exact same math)."""
    helper = LayerHelper("soft_relu", name=name)
    clipped = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [clipped]},
                     attrs={"min": -threshold, "max": threshold})
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("softplus", inputs={"X": [clipped]},
                     outputs={"Out": [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    """reference: nn.py:5699 → smooth_l1_loss_op.cc."""
    helper = LayerHelper("smooth_l1")
    out = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op("smooth_l1_loss", inputs=inputs,
                     outputs={"Out": [out], "Diff": [diff]},
                     attrs={"sigma": 1.0 if sigma is None else sigma})
    return out


def dice_loss(input, label, epsilon=1e-5):
    """reference: nn.py:6484 — composed from existing ops exactly as the
    reference composes it in python."""
    from paddle_tpu.fluid.layers.ops import (elementwise_add,
                                             elementwise_div,
                                             elementwise_mul)
    label = one_hot(label, depth=input.shape[-1])
    reduce_dim = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label), dim=reduce_dim)
    dice_denominator = elementwise_add(
        reduce_sum(input, dim=reduce_dim),
        reduce_sum(label, dim=reduce_dim))
    dice_score = scale(
        elementwise_div(
            scale(inse, scale=2.0),
            scale(dice_denominator, bias=epsilon)),
        scale=-1.0, bias=1.0)
    return reduce_mean(dice_score)


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    """reference: nn.py:5383 → im2sequence_op.cc."""
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    p = padding if isinstance(padding, (list, tuple)) else [padding] * 4
    if len(p) == 2:
        p = [p[0], p[0], p[1], p[1]]
    helper.append_op("im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": _pair(filter_size),
                            "strides": _pair(stride), "paddings": list(p)})
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """reference: nn.py:6751 — resize so the SHORT side equals
    out_short_len, preserving aspect ratio."""
    in_shape = input.shape
    hw = in_shape[2:4]
    short_idx = hw.index(min(hw))
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[1 - short_idx] = int(
        round(hw[1 - short_idx] * (out_short_len / hw[short_idx])))
    return image_resize(input, out_shape=out_shape, resample=resample)


def lod_reset(x, y=None, target_lod=None):
    """reference: nn.py:6029 → lod_reset_op.cc (here: re-binds SeqLens)."""
    helper = LayerHelper("lod_reset")
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    if y is not None:
        inputs["Y"] = [y]
    helper.append_op("lod_reset", inputs=inputs, outputs={"Out": [out]},
                     attrs={} if target_lod is None
                           else {"target_lod": list(target_lod)})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    """reference: nn.py:6195 → pad_op.cc."""
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings),
                            "pad_value": float(pad_value)})
    return out


def scatter(input, index, updates, name=None):
    """reference: nn.py:6836 → scatter_op.cc."""
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("scatter",
                     inputs={"X": [input], "Ids": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]})
    return out


def sum(x):
    """reference: nn.py:8392 → sum_op.cc (elementwise sum of a list)."""
    helper = LayerHelper("sum")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op("sum", inputs={"X": list(xs)}, outputs={"Out": [out]})
    return out


def mean_iou(input, label, num_classes):
    """reference: nn.py:7086 → mean_iou_op.cc."""
    helper = LayerHelper("mean_iou")
    iou = helper.create_variable_for_type_inference("float32")
    wrong = helper.create_variable_for_type_inference("int32")
    correct = helper.create_variable_for_type_inference("int32")
    helper.append_op("mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [iou], "OutWrong": [wrong],
                              "OutCorrect": [correct]},
                     attrs={"num_classes": num_classes})
    return iou, wrong, correct


def clip_by_norm(x, max_norm, name=None):
    """reference: nn.py:8764 → clip_by_norm_op.cc."""
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


def _logical(op, x, y=None, out=None, name=None):
    helper = LayerHelper(op, name=name)
    if out is None:
        out = helper.create_variable_for_type_inference("bool")
    inputs = {"X": [x]} if y is None else {"X": [x], "Y": [y]}
    helper.append_op(op, inputs=inputs, outputs={"Out": [out]})
    return out


def logical_and(x, y, out=None, name=None):
    """reference: nn.py:8615."""
    return _logical("logical_and", x, y, out, name)


def logical_or(x, y, out=None, name=None):
    return _logical("logical_or", x, y, out, name)


def logical_xor(x, y, out=None, name=None):
    return _logical("logical_xor", x, y, out, name)


def logical_not(x, out=None, name=None):
    return _logical("logical_not", x, None, out, name)


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    """reference: nn.py:8259 → gaussian_random_op.cc."""
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gaussian_random", inputs={}, outputs={"Out": [out]},
                     attrs={"shape": list(shape), "mean": mean, "std": std,
                            "seed": seed, "dtype": dtype})
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    """reference: nn.py:8208."""
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("uniform_random_batch_size_like",
                     inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape), "min": min, "max": max,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx,
                            "seed": seed, "dtype": dtype})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    """reference: nn.py:8338."""
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gaussian_random_batch_size_like",
                     inputs={"Input": [input]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape), "mean": mean, "std": std,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx,
                            "seed": seed, "dtype": dtype})
    return out


def hash(input, hash_size, num_hash=1, name=None):
    """reference: nn.py:9194 → hash_op.cc."""
    helper = LayerHelper("hash", name=name)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("hash", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"mod_by": hash_size, "num_hash": num_hash})
    return out


def lstm(input, init_h, init_c, max_len, hidden_size, num_layers,
         dropout_prob=0.0, is_bidirec=False, is_test=False, name=None,
         default_initializer=None, seed=-1):
    """reference: nn.py:489 (the cudnn multi-layer LSTM) → cudnn_lstm op.
    `input` [T, B, D]; returns (rnn_out, last_h, last_c)."""
    helper = LayerHelper("lstm", name=name)
    d_in = input.shape[-1]
    ndir = 2 if is_bidirec else 1
    # packed W: per layer, per direction, Wx (Din,4H) | Wh (H,4H) | b (4H)
    total = 0
    cur = d_in
    for _ in range(num_layers):
        total += ndir * (cur * 4 * hidden_size
                         + hidden_size * 4 * hidden_size + 4 * hidden_size)
        cur = hidden_size * ndir
    w = helper.create_parameter(None, shape=[total], dtype=input.dtype,
                                default_initializer=default_initializer)
    out = helper.create_variable_for_type_inference(input.dtype)
    last_h = helper.create_variable_for_type_inference(input.dtype)
    last_c = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("cudnn_lstm",
                     inputs={"Input": [input], "InitH": [init_h],
                             "InitC": [init_c], "W": [w]},
                     outputs={"Out": [out], "last_h": [last_h],
                              "last_c": [last_c]},
                     attrs={"hidden_size": hidden_size,
                            "num_layers": num_layers,
                            "is_bidirec": is_bidirec,
                            "dropout_prob": dropout_prob,
                            "is_test": is_test, "seed": seed})
    return out, last_h, last_c


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    """reference: nn.py:9395 → teacher_student_sigmoid_loss_op.cc."""
    helper = LayerHelper("teacher_student_sigmoid_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("teacher_student_sigmoid_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_max_up_bound": soft_max_up_bound,
                            "soft_max_lower_bound": soft_max_lower_bound})
    return out


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, rois_batch_id=None, name=None):
    """reference: nn.py psroi_pool → psroi_pool_op.cc (batch ids replace
    the reference's ROI LoD)."""
    helper = LayerHelper("psroi_pool", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_batch_id is not None:
        inputs["RoisBatchId"] = [rois_batch_id]
    helper.append_op("psroi_pool", inputs=inputs, outputs={"Out": [out]},
                     attrs={"output_channels": output_channels,
                            "spatial_scale": spatial_scale,
                            "pooled_height": pooled_height,
                            "pooled_width": pooled_width})
    return out


def roi_perspective_transform(input, rois, transformed_height,
                              transformed_width, spatial_scale=1.0,
                              rois_batch_id=None):
    """reference: detection/roi_perspective_transform_op.cc."""
    helper = LayerHelper("roi_perspective_transform")
    out = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"X": [input], "ROIs": [rois]}
    if rois_batch_id is not None:
        inputs["RoisBatchId"] = [rois_batch_id]
    helper.append_op("roi_perspective_transform", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"transformed_height": transformed_height,
                            "transformed_width": transformed_width,
                            "spatial_scale": spatial_scale})
    return out


def merge_selected_rows(x, name=None):
    """reference: merge_selected_rows_op.cc (dedup sparse rows)."""
    helper = LayerHelper("merge_selected_rows", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("merge_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def get_tensor_from_selected_rows(x, name=None):
    """reference: get_tensor_from_selected_rows_op.cc."""
    helper = LayerHelper("get_tensor_from_selected_rows", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("get_tensor_from_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """reference: nn.py:9653 → py_func_op.cc (host callback; backward_func
    is accepted for parity — gradients flow through jax.pure_callback's
    defined vjp only when provided)."""
    helper = LayerHelper("py_func")
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    helper.append_op("py_func", inputs={"X": list(xs)},
                     outputs={"Out": list(outs)},
                     attrs={"func": func,
                            "out_shapes": [list(o.shape) for o in outs],
                            "out_dtypes": [o.dtype for o in outs]})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference: nn.py:5780 — a persistable int64 counter incremented
    once per executed step."""
    helper = LayerHelper("global_step_counter")
    name = counter_name or "@STEP_COUNTER@"
    block = helper.main_program.global_block()
    if block.has_var(name):
        # reuse: the increment op was appended when the counter was
        # created — appending another would advance it twice per step
        # (reference appends the increment only for a fresh counter)
        return block.var(name)
    counter = helper.create_global_variable(
        shape=[1], dtype="int64", name=name, persistable=True)
    from paddle_tpu.fluid.initializer import ConstantInitializer
    startup_block = helper.startup_program.global_block()
    if not startup_block.has_var(name):
        sp = startup_block.create_var(name=name, shape=[1],
                                      dtype="int64", persistable=True)
        ConstantInitializer(float(begin - 1))(sp, startup_block)
    one = helper.create_variable_for_type_inference("int64")
    helper.append_op("fill_constant", inputs={}, outputs={"Out": [one]},
                     attrs={"shape": [1], "dtype": "int64",
                            "value": float(step)})
    helper.append_op("elementwise_add", inputs={"X": [counter], "Y": [one]},
                     outputs={"Out": [counter]})
    counter.stop_gradient = True
    return counter
