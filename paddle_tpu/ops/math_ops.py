"""Linear algebra, reductions, and tensor-shape ops.

Parity targets: operators/mul_op.cc, matmul_op.cc, reduce_ops/*,
scale_op.cc, sum_op.cc, reshape_op.cc, transpose_op.cc, concat_op.cc,
split_op.cc, slice_op.cc, cast_op.cc, softmax_op.cc, top_k_op.cc.

TPU notes: `mul`/`matmul` are the MXU ops — emitters keep them as single
large dot_generals (preferred_element_type left to XLA; bfloat16 inputs hit
the MXU natively).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op, single


def _flatten2d(x, num_col_dims):
    lead = int(np.prod(x.shape[:num_col_dims])) if num_col_dims > 0 else 1
    return x.reshape(lead, -1)


@register_op("mul", ref="operators/mul_op.cc")
def _mul(ctx, ins, attrs):
    """fluid's fc matmul: X flattened to 2D at x_num_col_dims, Y at
    y_num_col_dims, result reshaped back to X's leading dims."""
    x = first(ins, "X")
    y = first(ins, "Y")
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = _flatten2d(x, xn)
    y2 = y.reshape(int(np.prod(y.shape[:yn])), -1)
    amp = attrs.get("__amp_bf16__", False)
    if amp:
        x2 = x2.astype(jnp.bfloat16)
        y2 = y2.astype(jnp.bfloat16)
        # fp32 MXU accumulation either way; pure mode rounds the result
        # back to bf16 so the activation edge stays half-width
        out = jnp.matmul(x2, y2, preferred_element_type=jnp.float32)
        if attrs.get("__amp_keep_bf16__"):
            out = out.astype(jnp.bfloat16)
    else:
        out = x2 @ y2
    out_shape = x.shape[:xn] + y.shape[yn:]
    return single(out.reshape(out_shape))


def dense(x, w, out_dtype=None, transpose_w=False):
    """x [..., M] @ w [M, N] (``transpose_w``: w [N, M], contracted over
    its columns as it lies) in the weight's dtype with float32
    accumulation; the result in ``out_dtype`` (float32 by default)."""
    y = jax.lax.dot_general(
        x.astype(w.dtype), w,
        (((x.ndim - 1,), (1 if transpose_w else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y if out_dtype is None else y.astype(out_dtype)


def amp_dtypes(x, attrs):
    """(the dtype an op's products multiply in, the dtype of its
    result) under the mixed-precision rewrite's tags
    (contrib/mixed_precision.py): bfloat16 products over float32 master
    weights where ``__amp_bf16__`` is set, a bfloat16 result where
    ``__amp_keep_bf16__`` is; x's own dtype, twice, without them."""
    if not attrs.get("__amp_bf16__"):
        return x.dtype, x.dtype
    return jnp.bfloat16, (jnp.bfloat16 if attrs.get("__amp_keep_bf16__")
                          else x.dtype)


@register_op("dense",
             ref="X [..., M] @ W [M, N] in the weight's dtype with "
                 "float32 accumulation; attr out_dtype (default: X's); "
                 "with attr transpose_w W is [N, M], contracted over its "
                 "columns as it lies (a tied head); attr scale multiplies "
                 "the float32 result")
def _dense(ctx, ins, attrs):
    x, w = first(ins, "X"), first(ins, "W")
    dt, out_dt = amp_dtypes(x, attrs)
    if attrs.get("__amp_bf16__"):
        w = w.astype(dt)
    y = dense(x, w, transpose_w=bool(attrs.get("transpose_w")))
    if attrs.get("scale") is not None:
        y = y * float(attrs["scale"])
    return single(y.astype(attrs.get("out_dtype") or out_dt))


@register_op("matmul", ref="operators/matmul_op.cc")
def _matmul(ctx, ins, attrs):
    x = first(ins, "X")
    y = first(ins, "Y")
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    if attrs.get("__amp_bf16__"):
        x = x.astype(jnp.bfloat16)
        y = y.astype(jnp.bfloat16)
        out = jnp.matmul(x, y,
                         preferred_element_type=jnp.float32)
        if attrs.get("__amp_keep_bf16__"):
            out = out.astype(jnp.bfloat16)
    else:
        out = jnp.matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return single(out)


@register_op("scale", ref="operators/scale_op.cc")
def _scale(ctx, ins, attrs):
    x = first(ins, "X")
    scale = attrs.get("scale", 1.0)
    bias = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return single(x * scale + bias)
    return single((x + bias) * scale)


@register_op("sum", ref="operators/sum_op.cc")
def _sum(ctx, ins, attrs):
    xs = ins.get("X", [])
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return single(out)


@register_op("cast", ref="operators/cast_op.cc")
def _cast(ctx, ins, attrs):
    return single(first(ins, "X").astype(attrs.get("out_dtype", "float32")))


# -- reductions -------------------------------------------------------------

def _register_reduce(name, fn):
    @register_op(name, ref="operators/reduce_ops/" + name + "_op.cc")
    def _emit(ctx, ins, attrs, _fn=fn):
        x = first(ins, "X")
        if attrs.get("reduce_all", False):
            axes = tuple(range(x.ndim))
        else:
            dims = attrs.get("dim", [0])
            if isinstance(dims, int):
                dims = [dims]
            axes = tuple(d % x.ndim for d in dims)
        keep = attrs.get("keep_dim", False)
        return single(_fn(x, axis=axes, keepdims=keep))


_register_reduce("reduce_sum", jnp.sum)
_register_reduce("reduce_mean", jnp.mean)
_register_reduce("reduce_max", jnp.max)
_register_reduce("reduce_min", jnp.min)
_register_reduce("reduce_prod", jnp.prod)


@register_op("mean", ref="operators/mean_op.cc")
def _mean(ctx, ins, attrs):
    return single(jnp.mean(first(ins, "X")))


@register_op("argmax", no_grad=True, ref="operators/arg_max_op.cc")
def _argmax(ctx, ins, attrs):
    return single(jnp.argmax(first(ins, "X"), axis=attrs.get("axis", -1)).astype(jnp.int64))


@register_op("argmin", no_grad=True, ref="operators/arg_min_op.cc")
def _argmin(ctx, ins, attrs):
    return single(jnp.argmin(first(ins, "X"), axis=attrs.get("axis", -1)).astype(jnp.int64))


@register_op("top_k", no_grad=True, ref="operators/top_k_op.cc")
def _top_k(ctx, ins, attrs):
    x = first(ins, "X")
    k = attrs.get("k", 1)
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


# -- shape manipulation -----------------------------------------------------

@register_op("reshape", ref="operators/reshape_op.cc")
def _reshape(ctx, ins, attrs):
    x = first(ins, "X")
    shape = list(attrs.get("shape", ()))
    # fluid semantics: 0 means copy the input dim at that position
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)] \
        if any(s == 0 for s in shape) else shape
    return single(x.reshape(tuple(shape)))


@register_op("reshape2", ref="operators/reshape_op.cc (Reshape2: adds XShape)")
def _reshape2(ctx, ins, attrs):
    out = _reshape(ctx, ins, attrs)["Out"][0]
    x = first(ins, "X")
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("squeeze", ref="operators/squeeze_op.cc")
def _squeeze(ctx, ins, attrs):
    x = first(ins, "X")
    axes = attrs.get("axes", [])
    if not axes:
        return single(jnp.squeeze(x))
    return single(jnp.squeeze(x, axis=tuple(a % x.ndim for a in axes)))


@register_op("unsqueeze", ref="operators/unsqueeze_op.cc")
def _unsqueeze(ctx, ins, attrs):
    x = first(ins, "X")
    for a in sorted(attrs.get("axes", [])):
        x = jnp.expand_dims(x, a)
    return single(x)


@register_op("transpose", ref="operators/transpose_op.cc")
def _transpose(ctx, ins, attrs):
    return single(jnp.transpose(first(ins, "X"), attrs.get("axis")))


@register_op("transpose2", ref="operators/transpose_op.cc (Transpose2)")
def _transpose2(ctx, ins, attrs):
    x = first(ins, "X")
    out = jnp.transpose(x, attrs.get("axis"))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register_op("concat", ref="operators/concat_op.cc")
def _concat(ctx, ins, attrs):
    axis = attrs.get("axis", 0)
    xs = ins.get("X", [])
    if attrs.get("__nhwc_concat__"):
        # contrib.layout NHWC region: the channel concat (axis=1) re-aims
        # at the physical last axis
        axis = xs[0].ndim - 1
    return single(jnp.concatenate(xs, axis=axis))


@register_op("split", ref="operators/split_op.cc")
def _split(ctx, ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if num:
        outs = jnp.split(x, num, axis=axis)
    else:
        offsets = np.cumsum(sections[:-1]).tolist()
        outs = jnp.split(x, offsets, axis=axis)
    return {"Out": list(outs)}


@register_op("slice", ref="operators/slice_op.cc")
def _slice(ctx, ins, attrs):
    x = first(ins, "Input")
    axes = attrs.get("axes", [])
    starts = attrs.get("starts", [])
    ends = attrs.get("ends", [])
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return single(x[tuple(idx)])


@register_op("stack", ref="operators/stack_op.cc")
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins.get("X", []), axis=attrs.get("axis", 0))]}


@register_op("expand", ref="operators/expand_op.cc")
def _expand(ctx, ins, attrs):
    x = first(ins, "X")
    times = attrs.get("expand_times", [1] * x.ndim)
    return single(jnp.tile(x, tuple(times)))


@register_op("gather", ref="operators/gather_op.cc")
def _gather(ctx, ins, attrs):
    x = first(ins, "X")
    idx = first(ins, "Index")
    return single(jnp.take(x, idx.reshape(-1), axis=0))


@register_op("scatter", ref="operators/scatter_op.cc")
def _scatter(ctx, ins, attrs):
    x = first(ins, "X")
    idx = first(ins, "Ids").reshape(-1)
    upd = first(ins, "Updates")
    if attrs.get("overwrite", True):
        return single(x.at[idx].set(upd))
    return single(x.at[idx].add(upd))


@register_op("one_hot", no_grad=True, ref="operators/one_hot_op.cc")
def _one_hot(ctx, ins, attrs):
    x = first(ins, "X")
    depth = attrs.get("depth")
    squeezed = x.reshape(x.shape[:-1]) if x.shape and x.shape[-1] == 1 else x
    return single(jax.nn.one_hot(squeezed, depth, dtype=jnp.float32))


@register_op("range", no_grad=True, ref="operators/range_op.cc")
def _range(ctx, ins, attrs):
    start = first(ins, "Start")
    end = first(ins, "End")
    step = first(ins, "Step")
    # static version only (dynamic shapes don't exist under XLA)
    return single(jnp.arange(int(start), int(end), int(step)))


@register_op("cumsum", ref="operators/cum_op.h")
def _cumsum(ctx, ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", -1)
    if attrs.get("flatten", False):
        x = x.reshape(-1)
        axis = 0
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        out = out - x
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    return single(out)


@register_op("norm", ref="operators/norm_op.cc")
def _norm(ctx, ins, attrs):
    x = first(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register_op("squared_l2_norm", ref="operators/squared_l2_norm_op.cc")
def _squared_l2_norm(ctx, ins, attrs):
    x = first(ins, "X")
    return single(jnp.sum(jnp.square(x)).reshape(()))
