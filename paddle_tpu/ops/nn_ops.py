"""Neural-net ops: conv, pooling, normalization, embedding, losses.

Parity targets: operators/conv_op.cc (+conv_cudnn_op.cu.cc),
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, dropout_op.cc,
lookup_table_op.cc, softmax_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc.

TPU notes: convs lower to XLA's conv_general_dilated which tiles onto the
MXU; there is no cudnn-vs-plain kernel choice to make (XLA autotunes).
Layout is NCHW at the API for reference parity; XLA's layout assignment
re-tiles internally for TPU.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op, single
from paddle_tpu.observability import metrics as _metrics


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


def _amp_cast(attrs, *arrays):
    """bf16-compute cast for MXU ops tagged by contrib.mixed_precision.
    rewrite_program_amp; outputs stay fp32 via preferred_element_type/
    post-cast (master weights untouched in the Scope)."""
    if attrs.get("__amp_bf16__"):
        return [a.astype(jnp.bfloat16)
                if a is not None and jnp.issubdtype(a.dtype, jnp.floating)
                else a for a in arrays]
    return list(arrays)


def _amp_out(out, attrs):
    """Output dtype under AMP: pure mode (__amp_keep_bf16__) keeps the
    activation bf16 — downstream elementwise/norm ops run at half the HBM
    traffic — while conservative mode restores fp32 at every op edge."""
    if attrs.get("__amp_keep_bf16__"):
        return out
    return out.astype(jnp.float32)


def _nhwc_in(x, attrs):
    """contrib.layout region entry: transpose NCHW→NHWC unless the graph
    var is already NHWC-resident (producer kept it)."""
    if attrs.get("__nhwc__") and not attrs.get("__nhwc_in_ready__"):
        return jnp.transpose(x, (0, 2, 3, 1))
    return x


def _nhwc_out(out, attrs):
    """contrib.layout region exit: keep NHWC when every consumer handles
    it, else restore NCHW."""
    if attrs.get("__nhwc__") and not attrs.get("__nhwc_out_keep__"):
        return jnp.transpose(out, (0, 3, 1, 2))
    return out


@register_op("conv2d", ref="operators/conv_op.cc:44 Conv2DOp; conv_cudnn_op.cu.cc")
def _conv2d(ctx, ins, attrs):
    x = first(ins, "Input")          # NCHW
    w = first(ins, "Filter")         # OIHW
    amp = attrs.get("__amp_bf16__", False)
    x, w = _amp_cast(attrs, x, w)
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    x = _nhwc_in(x, attrs)
    dn = ("NHWC", "OIHW", "NHWC") if attrs.get("__nhwc__") \
        else ("NCHW", "OIHW", "NCHW")
    ig = w.shape[1]                  # input channels per group
    if 1 < groups and ig < 16 and groups <= 64:
        # lane-starved grouped conv (e.g. SE-ResNeXt cardinality 32 with
        # 4-8 channels/group): the MXU contracts only `ig` of its 128
        # lanes per group — measured 2-3% MXU efficiency, ~1 ms per conv
        # on v5e. Lower to a DENSE conv with a block-diagonal kernel:
        # 'groups'x the nominal FLOPs but at dense-conv efficiency, which
        # wins for ig < 16 (model FLOPs for MFU still count the grouped
        # formula — implementation FLOPs are excluded by convention).
        # The eye-mask product keeps AD exact: off-block grad leakage is
        # zeroed by the same mask in the vjp.
        o = w.shape[0]
        og = o // groups
        eye = jnp.eye(groups, dtype=w.dtype)
        w_g = w.reshape((groups, og) + w.shape[1:])
        dense = w_g[:, :, None] * eye[:, None, :, None, None, None]
        w = dense.reshape((o, groups * ig) + w.shape[2:])
        groups = 1
    out = jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=dn,
    )
    out = _nhwc_out(out, attrs)
    # under AMP the conv runs fully in bf16 (XLA accumulates fp32 on the
    # MXU internally) and the output returns to fp32 (master dtype);
    # preferred_element_type is avoided because its conv transpose rule
    # rejects mixed bf16-primal/f32-cotangent. Otherwise the output follows
    # the input dtype (a bf16-transpiled program stays bf16).
    return {"Output": [_amp_out(out, attrs) if amp else out]}


@register_op("depthwise_conv2d", ref="operators/conv_op.cc (depthwise registered alias)")
def _depthwise_conv2d(ctx, ins, attrs):
    x = first(ins, "Input")
    attrs = dict(attrs)
    # channel dim position depends on the residency of the graph var
    nhwc_resident = attrs.get("__nhwc__") and attrs.get("__nhwc_in_ready__")
    attrs["groups"] = x.shape[3] if nhwc_resident else x.shape[1]
    return _conv2d(ctx, ins, attrs)


def conv_transpose_nd(x, w, strides, pads, dilations, groups, nd):
    """Fluid-semantics transposed conv (out = (H-1)*s - 2p + d*(k-1) + 1):
    gradient-of-conv formulation — fractionally-strided input (lhs_dilation),
    spatially flipped kernel, padding d*(k-1)-p. w layout [Cin, Cout/G, *k]
    (conv_transpose_op.cc filter layout); validated numerically against
    torch.conv_transpose{2,3}d incl. groups/dilation. Do NOT use
    lax.conv_transpose: its explicit-padding semantics differ and it does
    not flip the kernel."""
    cin, coutg = w.shape[0], w.shape[1]
    k = w.shape[2:]
    w = w.reshape((groups, cin // groups, coutg) + k)
    w = jnp.moveaxis(w, 2, 1).reshape((groups * coutg, cin // groups) + k)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    pad_pairs = [(dilations[i] * (k[i] - 1) - pads[i],) * 2 for i in range(nd)]
    specs = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
             3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=pad_pairs,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dilations),
        feature_group_count=groups, dimension_numbers=specs)


@register_op("conv2d_transpose", ref="operators/conv_transpose_op.cc")
def _conv2d_transpose(ctx, ins, attrs):
    x = first(ins, "Input")
    w = first(ins, "Filter")         # IOHW in fluid's transpose conv
    amp = attrs.get("__amp_bf16__", False)
    x, w = _amp_cast(attrs, x, w)
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    out = conv_transpose_nd(x, w, strides, pads, dilations,
                            attrs.get("groups", 1), 2)
    return {"Output": [_amp_out(out, attrs) if amp else out]}


@register_op("conv3d", ref="operators/conv_op.cc Conv3DOp")
def _conv3d(ctx, ins, attrs):
    x = first(ins, "Input")          # NCDHW
    w = first(ins, "Filter")         # OIDHW
    amp = attrs.get("__amp_bf16__", False)
    x, w = _amp_cast(attrs, x, w)
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    out = jax.lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        feature_group_count=attrs.get("groups", 1),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    return {"Output": [_amp_out(out, attrs) if amp else out]}


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register_op("pool2d", ref="operators/pool_op.cc")
def _pool2d(ctx, ins, attrs):
    x = first(ins, "X")              # NCHW (NHWC inside a layout region)
    x = _nhwc_in(x, attrs)
    nhwc = attrs.get("__nhwc__", False)
    sp = (1, 2) if nhwc else (2, 3)  # spatial dim positions
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling", False):
        ksize = tuple(x.shape[d] for d in sp)
        pads = (0, 0)
        strides = (1, 1)
    window = [1, 1, 1, 1]
    strides4 = [1, 1, 1, 1]
    padding = [(0, 0)] * 4
    for i, d in enumerate(sp):
        window[d] = ksize[i]
        strides4[d] = strides[i]
        padding[d] = (pads[i], pads[i])
    window, strides4, padding = tuple(window), tuple(strides4), tuple(padding)
    if ptype == "max":
        # backward goes through XLA's select_and_scatter (first-max tie
        # rule, matching math/pooling.cc MaxPool2dGradFunctor). An
        # unrolled shifted-window custom-vjp formulation was measured
        # in-model on v5e and REJECTED: resnet50 2726->2128 img/s,
        # googlenet 5782->2327 (9 dilated pad+add passes do not fuse).
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window,
                                    strides4, padding)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4, padding)
        if attrs.get("exclusive", True) and (pads[0] or pads[1]):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides4, padding)
            out = summed / counts
        else:
            out = summed / float(ksize[0] * ksize[1])
    return single(_nhwc_out(out, attrs))


@register_op("pool3d", ref="operators/pool_op.cc Pool3D")
def _pool3d(ctx, ins, attrs):
    x = first(ins, "X")
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2, 2]), 3)
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    window = (1, 1) + tuple(ksize)
    strides5 = (1, 1) + tuple(strides)
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ptype == "max":
        out = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides5, padding)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides5, padding)
        out = summed / float(np.prod(ksize))
    return single(out)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def _bn_axes(x, caxis):
    """(reduction axes, broadcast shape) for channel axis `caxis`."""
    axes = tuple(i for i in range(x.ndim) if i != caxis)
    bshape = tuple(-1 if i == caxis else 1 for i in range(x.ndim))
    return axes, bshape


def _bn_fold_normalize(x, mean, var, scale, bias, eps, caxis=1):
    """Per-channel k/b fold: y = x·k + b in the activation dtype (one
    fused multiply-add off half-width reads; the k/b arithmetic is fp32)."""
    _, bshape = _bn_axes(x, caxis)
    inv = jax.lax.rsqrt(var + eps)
    k = (inv * scale).astype(x.dtype)
    b = (bias - mean * inv * scale).astype(x.dtype)
    return x * k.reshape(bshape) + b.reshape(bshape), inv


def _bn_lowp_impl(x, scale, bias, eps, caxis):
    """Folded train-mode batch norm for bf16/fp16 activations: fp32
    statistics off half-width reads, folded normalize. One-pass moments:
    jnp.var's two-pass (mean, then (x−mean)²) reads the activation twice;
    E[x²]−E[x]² lets XLA fuse both channel reductions into a single read
    (the fp32 accumulate keeps the cancellation benign for BN's use)."""
    axes, _ = _bn_axes(x, caxis)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    msq = jnp.mean(xf * xf, axis=axes)
    var = jnp.maximum(msq - mean * mean, 0.0)
    y, inv = _bn_fold_normalize(x, mean, var, scale, bias, eps, caxis)
    return y, mean, var, inv


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train_lowp(x, scale, bias, eps, caxis=1):
    y, mean, var, _ = _bn_lowp_impl(x, scale, bias, eps, caxis)
    return y, mean, var


def _bn_train_lowp_fwd(x, scale, bias, eps, caxis):
    y, mean, var, inv = _bn_lowp_impl(x, scale, bias, eps, caxis)
    return (y, mean, var), (x, scale, mean, inv)


def _bn_train_lowp_bwd(eps, caxis, res, cts):
    """Hand-written BN backward: jax.vjp of the fp32-statistics forward
    materializes fp32 copies of the activation for the variance chain;
    here every elementwise term stays in the activation dtype and only
    the two channel reductions accumulate fp32 — the bandwidth-optimal
    form (dx = k·(dy − mean(dy) − x̂·mean(dy·x̂)))."""
    dy, _dmean, _dvar = cts          # mean/var are state outputs: their
    x, scale, mean, inv = res        # EMA consumers sit behind
    xdt = x.dtype                    # stop_gradient in the emitter
    axes, bshape = _bn_axes(x, caxis)
    n = x.size // x.shape[caxis]
    dyl = dy.astype(xdt)
    xhat = (x - mean.astype(xdt).reshape(bshape)) \
        * inv.astype(xdt).reshape(bshape)
    sum_dy = jnp.sum(dyl, axis=axes, dtype=jnp.float32)
    sum_dy_xhat = jnp.sum(dyl * xhat, axis=axes, dtype=jnp.float32)
    k = (scale * inv).astype(xdt).reshape(bshape)
    m1 = (sum_dy / n).astype(xdt).reshape(bshape)
    m2 = (sum_dy_xhat / n).astype(xdt).reshape(bshape)
    dx = k * (dyl - m1 - xhat * m2)
    # cotangents must match the primal dtypes: scale/bias may themselves
    # be bf16 (e.g. a BF16Transpiler-converted program in train mode) and
    # custom_vjp rejects fp32 cotangents for bf16 primals
    return (dx, sum_dy_xhat.astype(scale.dtype),
            sum_dy.astype(scale.dtype))   # dscale = Σdy·x̂, dbias = Σdy


_bn_train_lowp.defvjp(_bn_train_lowp_fwd, _bn_train_lowp_bwd)


@register_op("batch_norm", ref="operators/batch_norm_op.cc:40")
def _batch_norm(ctx, ins, attrs):
    """Train mode: batch statistics + EMA update of Mean/Variance (the
    reference writes MeanOut/VarianceOut aliased onto the running stats;
    here they are returned and the executor writes them back to the Scope).
    Test mode: running statistics."""
    x = first(ins, "X")              # NCHW (or NC / NCL / NCDHW)
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    mean = first(ins, "Mean")
    var = first(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    x = _nhwc_in(x, attrs)
    caxis = (x.ndim - 1) if attrs.get("__nhwc__") else 1
    axes, bshape = _bn_axes(x, caxis)
    # bf16/fp16 activations (pure AMP): statistics accumulate in fp32
    # (XLA's convert+reduce fusion reads the half-width bytes), the
    # normalize runs in the activation dtype via folded per-channel
    # scale/shift — halves the HBM traffic of the bandwidth-bound step
    lowp = x.dtype in (jnp.bfloat16, jnp.float16)
    if is_test or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        saved_mean = mean
        saved_var = var
        mean_out, var_out = mean, var
        if lowp:
            y, _ = _bn_fold_normalize(x, use_mean, use_var, scale, bias,
                                      eps, caxis)
        else:
            inv = jax.lax.rsqrt(use_var.reshape(bshape) + eps)
            y = (x - use_mean.reshape(bshape)) * inv \
                * scale.reshape(bshape) + bias.reshape(bshape)
    else:
        if lowp:
            # custom-vjp path: fp32 statistics, activation-dtype compute
            # in BOTH directions (see _bn_train_lowp_bwd)
            y, use_mean, use_var = _bn_train_lowp(x, scale, bias, eps,
                                                  caxis)
        else:
            use_mean = jnp.mean(x, axis=axes)
            use_var = jnp.var(x, axis=axes)
            inv = jax.lax.rsqrt(use_var.reshape(bshape) + eps)
            y = (x - use_mean.reshape(bshape)) * inv \
                * scale.reshape(bshape) + bias.reshape(bshape)
        # EMA update is state maintenance, not on the loss path
        use_mean_s = jax.lax.stop_gradient(use_mean)
        use_var_s = jax.lax.stop_gradient(use_var)
        mean_out = mean * momentum + use_mean_s * (1.0 - momentum)
        var_out = var * momentum + use_var_s * (1.0 - momentum)
        saved_mean = use_mean
        saved_var = use_var
    y = _nhwc_out(y, attrs)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm", ref="operators/layer_norm_op.cc")
def _layer_norm(ctx, ins, attrs):
    x = first(ins, "X")
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    # same lowp treatment as batch_norm: fp32 statistics, activation-dtype
    # normalize
    lowp = x.dtype in (jnp.bfloat16, jnp.float16)
    stat_kw = {"dtype": jnp.float32} if lowp else {}
    mean = jnp.mean(x, axis=axes, keepdims=True, **stat_kw)
    var = jnp.var(x, axis=axes, keepdims=True, **stat_kw)
    inv = jax.lax.rsqrt(var + eps)
    if lowp:
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    else:
        y = (x - mean) * inv
    norm_shape = x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape).astype(y.dtype)
    if bias is not None:
        y = y + bias.reshape(norm_shape).astype(y.dtype)
    return {
        "Y": [y],
        "Mean": [mean.reshape(x.shape[:begin])],
        "Variance": [var.reshape(x.shape[:begin])],
    }


@register_op("rms_norm",
             ref="RMSNorm (Zhang & Sennrich 2019, arXiv:1910.07467) over "
                 "the last axis: float32 statistics, the result in the "
                 "input's dtype")
def _rms_norm(ctx, ins, attrs):
    x = first(ins, "X")
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                        + attrs.get("epsilon", 1e-5))
    y = xf * inv * first(ins, "Scale").astype(jnp.float32)
    return {"Y": [y.astype(x.dtype)]}


@register_op("group_norm", ref="operators/group_norm_op.cc")
def _group_norm(ctx, ins, attrs):
    x = first(ins, "X")              # NCHW
    scale = first(ins, "Scale")
    bias = first(ins, "Bias")
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, groups, c // groups) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": [y], "Mean": [mean.reshape(n, groups)], "Variance": [var.reshape(n, groups)]}


@register_op("lrn", ref="operators/lrn_op.cc")
def _lrn(ctx, ins, attrs):
    x = first(ins, "X")              # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = [(0, 0), (half, n - 1 - half), (0, 0), (0, 0)]
    sq_pad = jnp.pad(sq, pad)
    window = jax.lax.reduce_window(sq_pad, 0.0, jax.lax.add, (1, n, 1, 1), (1, 1, 1, 1), "VALID")
    return {"Out": [x / jnp.power(k + alpha * window, beta)], "MidOut": [window]}


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

@register_op("dropout", ref="operators/dropout_op.cc")
def _dropout(ctx, ins, attrs):
    x = first(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    # counter-based keep mask (the flash kernels' murmur-finalizer hash
    # over element index + a per-step seed) instead of
    # jax.random.bernoulli: the rng-bit-generator ops cost a measured
    # ~1.7 ms/step on Transformer-base T=256 (4.5% of device time) while
    # the hash fuses into the multiply pass over bytes it already moves.
    # The backward re-traces with the same ctx.step_key → same seed →
    # bit-identical mask, exactly like the bernoulli path it replaces.
    from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
    if p >= 1.0:
        # everything dropped: exact zeros (the 1/(1-p) upscale would be
        # inf and 0*inf = NaN) — reference: mask all-zero at p=1
        z = jnp.zeros_like(x)
        return {"Out": [z], "Mask": [z]}
    seed = jax.random.randint(ctx.step_key(), (), 0, 2 ** 31 - 1,
                              dtype=jnp.int32)
    idx = jax.lax.iota(jnp.int32, int(np.prod(x.shape))).reshape(x.shape)
    zero = jnp.int32(0)
    keep_upscaled = hash_keep_mask(seed, zero, idx, zero, p)  # keep/(1-p)
    mask = (keep_upscaled > 0).astype(x.dtype)
    if impl == "upscale_in_train":
        out = x * keep_upscaled.astype(x.dtype)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


# ---------------------------------------------------------------------------
# embedding (the sparse-table capability; reference: lookup_table_op.cc,
# distributed prefetch path nn.py:345-359 → here a dense gather that shards
# over the mesh's model axis for the pserver-sharded-table capability)
# ---------------------------------------------------------------------------

@register_op("lookup_table", ref="operators/lookup_table_op.cc")
def _lookup_table(ctx, ins, attrs):
    w = first(ins, "W")
    ids = first(ins, "Ids")
    padding_idx = attrs.get("padding_idx", -1)
    flat = ids.reshape(-1)
    out = jnp.take(w, flat, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((flat == padding_idx)[:, None], 0.0, out)
    out_shape = tuple(ids.shape[:-1] if ids.shape and ids.shape[-1] == 1 else ids.shape) + (w.shape[-1],)
    out = out.reshape(out_shape)
    if attrs.get("__amp_keep_bf16__") and out.dtype == jnp.float32:
        # pure-AMP: the embedding output STARTS the residual stream; left
        # fp32 it poisons every downstream elementwise/norm op with 2x HBM
        # traffic (master table stays fp32 in the Scope; the vjp casts the
        # gradient back up before the scatter-add)
        out = out.astype(jnp.bfloat16)
    return single(out)


# ---------------------------------------------------------------------------
# softmax / losses
# ---------------------------------------------------------------------------

@register_op("softmax", ref="operators/softmax_op.cc")
def _softmax(ctx, ins, attrs):
    return single(jax.nn.softmax(first(ins, "X"), axis=-1))


@register_op("log_softmax", ref="operators/softmax_op.cc (log variant)")
def _log_softmax(ctx, ins, attrs):
    return single(jax.nn.log_softmax(first(ins, "X"), axis=-1))


def _gather_label_prob(prob, label):
    # label: [N, 1] or [N] int -> pick prob[i, label[i]]
    lab = label.reshape(-1)
    return jnp.take_along_axis(prob, lab[:, None].astype(jnp.int32), axis=-1)


@register_op("cross_entropy", ref="operators/cross_entropy_op.cc")
def _cross_entropy(ctx, ins, attrs):
    x = first(ins, "X")              # probabilities [N, D]
    label = first(ins, "Label")
    if x.dtype in (jnp.bfloat16, jnp.float16):
        # loss boundary: log(p) and its 1/p gradient need fp32 (same
        # rationale as softmax_with_cross_entropy below)
        x = x.astype(jnp.float32)
    eps = 1e-9
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        picked = _gather_label_prob(x, label)
        loss = -jnp.log(picked + eps)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(label.reshape(-1, 1) == ignore, 0.0, loss)
    return {"Y": [loss]}


@register_op("softmax_with_cross_entropy",
             ref="operators/softmax_with_cross_entropy_op.cc")
def _softmax_with_cross_entropy(ctx, ins, attrs):
    logits = first(ins, "Logits")
    label = first(ins, "Label")
    lowp = logits.dtype in (jnp.bfloat16, jnp.float16)
    # uniform-prior label smoothing folded into the loss in closed form:
    # with q = (1-eps)*onehot + eps/V,  -SUM q*logp
    #   = lse - (1-eps)*picked - eps*mean(logits)
    # — no [N, V] one_hot / label_smooth materialization (the graph-level
    # one_hot+label_smooth+soft_label chain costs several full-width
    # passes at V=32k)
    eps = float(attrs.get("label_smoothing", 0.0))
    if not attrs.get("soft_label", False):
        # streaming form: an fp32 astype of the whole [N, V] logits would
        # materialize it at full width (4 GB at bs512xT64xV32k); the
        # convert+sub+exp chain instead fuses into the fp32-accumulating
        # reduces, so HBM sees only the native-width reads. max is exact
        # in bf16 (comparison, not arithmetic).
        m = jax.lax.stop_gradient(
            jnp.max(logits, axis=-1, keepdims=True).astype(jnp.float32))
        sumexp = jnp.sum(jnp.exp(logits.astype(jnp.float32) - m),
                         axis=-1, keepdims=True)
        lse = m + jnp.log(sumexp)                       # [..., 1] fp32
        lab = label.astype(jnp.int32).reshape(logits.shape[:-1] + (1,))
        picked = jnp.take_along_axis(logits, lab, axis=-1) \
                    .astype(jnp.float32)
        loss = lse - picked
        if eps:
            mean_logits = jnp.mean(logits.astype(jnp.float32),
                                   axis=-1, keepdims=True)
            loss = loss + eps * (picked - mean_logits)
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(lab == ignore, 0.0, loss)
        # native-dtype softmax output (DCE'd when unused)
        softmax = jnp.exp(logits.astype(jnp.float32) - lse) \
            .astype(logits.dtype)
        return {"Loss": [loss], "Softmax": [softmax]}
    if lowp:
        # soft-label path: upcast (bf16 exp/log cancellation destroys the
        # loss signal)
        logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}


@register_op("fused_linear_ce",
             ref="composed: mul_op.cc + softmax_with_cross_entropy_op.cc "
                 "(TPU-native fusion — the [N, V] logits never reach HBM)")
def _fused_linear_ce(ctx, ins, attrs):
    """X [N, D] @ W [D, V] -> label-smoothed CE Loss [N, 1]. Routes to the
    Pallas streaming kernel (ops/pallas/fused_ce.py) when the dims tile;
    otherwise emits the composed matmul + closed-form CE (identical
    math)."""
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.ops.pallas import fused_ce as fce

    x = first(ins, "X")
    w = first(ins, "W")
    label = first(ins, "Label")
    eps = float(attrs.get("label_smoothing", 0.0))
    ignore = attrs.get("ignore_index", -100)
    if attrs.get("__amp_bf16__"):
        x, w = _amp_cast(attrs, x, w)
    n, d = x.shape
    v = w.shape[1]
    use_kernel = (pk.kernel_enabled(128, d, mesh=ctx.mesh)
                  and fce.supported(n, d, v)) \
        or pk.forced_interpret()
    if use_kernel:
        loss = fce.fused_linear_ce(x, w, label.reshape(-1), eps, ignore,
                                   pk.interpret_mode())
        return {"Loss": [loss]}
    logits = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    if attrs.get("__amp_bf16__"):
        # the [N, V] logits cross to the CE fusions in storage dtype —
        # fp32 doubled every pass over the ~0.5 GB tensor (measured ~3
        # ms/step on transformer_big); CE math still reduces in fp32
        logits = logits.astype(x.dtype)
    outs = _softmax_with_cross_entropy(
        ctx, {"Logits": [logits], "Label": [label]},
        {"label_smoothing": eps, "ignore_index": ignore})
    return {"Loss": outs["Loss"]}


@register_op("sigmoid_cross_entropy_with_logits",
             ref="operators/sigmoid_cross_entropy_with_logits_op.cc")
def _sigmoid_ce(ctx, ins, attrs):
    x = first(ins, "X")
    label = first(ins, "Label")
    if x.dtype in (jnp.bfloat16, jnp.float16):
        x = x.astype(jnp.float32)    # loss boundary (see _cross_entropy)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        cnt = jnp.maximum(jnp.sum((label != ignore).astype(x.dtype)), 1.0)
        loss = loss / cnt
    return single(loss)


@register_op("square_error_cost", ref="operators/squared_l2_distance_op.cc / nn.py square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    x = first(ins, "X")
    y = first(ins, "Y")
    return single(jnp.square(x - y))


@register_op("huber_loss", ref="operators/huber_loss_op.cc")
def _huber_loss(ctx, ins, attrs):
    x = first(ins, "X")
    y = first(ins, "Y")
    delta = attrs.get("delta", 1.0)
    diff = y - x
    absd = jnp.abs(diff)
    loss = jnp.where(absd <= delta, 0.5 * diff * diff, delta * (absd - 0.5 * delta))
    return {"Out": [loss], "Residual": [diff]}


@register_op("smooth_l1_loss", ref="operators/smooth_l1_loss_op.cc")
def _smooth_l1(ctx, ins, attrs):
    x = first(ins, "X")
    y = first(ins, "Y")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = jnp.abs(x - y)
    loss = jnp.where(diff < 1.0 / s2, 0.5 * s2 * diff * diff, diff - 0.5 / s2)
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [loss], "Diff": [x - y]}


@register_op("label_smooth", ref="operators/label_smooth_op.cc")
def _label_smooth(ctx, ins, attrs):
    x = first(ins, "X")
    eps = attrs.get("epsilon", 0.0)
    dist = ins.get("PriorDist")
    if dist:
        out = (1.0 - eps) * x + eps * dist[0]
    else:
        out = (1.0 - eps) * x + eps / x.shape[-1]
    return single(out)


# ---------------------------------------------------------------------------
# sequence-ish dense helpers
# ---------------------------------------------------------------------------

@register_op("im2sequence", ref="operators/im2sequence_op.cc")
def _im2sequence(ctx, ins, attrs):
    """Image → patch sequence: X [N, C, H, W] → Out [N, OH*OW, C*kh*kw]
    (the padded-batch form of the reference's LoD output, one sequence per
    image with OH*OW steps; per-step feature layout is the reference's
    kOCF [C, kh, kw]). Lowers to ONE conv-patches extraction on the MXU
    path (lax.conv_general_dilated_patches), not per-window gathers."""
    if first(ins, "Y") is not None or "out_stride" in attrs:
        # the reference's dispensable per-image real-size input
        # (im2sequence_op.h: batch>1 + Y + out_stride computes per-image
        # output sizes) is a dynamic-shape path with no XLA analogue
        raise NotImplementedError(
            "im2sequence: per-image real-size (Y/out_stride) is not "
            "supported on TPU (static shapes) — pre-pad to a common size")
    x = first(ins, "X")
    kh, kw = [int(v) for v in attrs.get("kernels", [1, 1])]
    sh, sw = [int(v) for v in attrs.get("strides", [1, 1])]
    pu, pl, pd, pr = [int(v) for v in attrs.get("paddings", [0, 0, 0, 0])]
    n, c = x.shape[0], x.shape[1]
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(pu, pd), (pl, pr)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # patches: [N, C*kh*kw, OH, OW] with feature layout [C, kh, kw] (kOCF)
    oh, ow = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c * kh * kw, oh * ow)
    return single(jnp.swapaxes(patches, 1, 2))


@register_op("pad", ref="operators/pad_op.cc")
def _pad(ctx, ins, attrs):
    x = first(ins, "X")
    paddings = attrs.get("paddings", [0] * (2 * x.ndim))
    pairs = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return single(jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0)))


# ---------------------------------------------------------------------------
# fused attention (TPU-native extension; the reference composes this from
# matmul+softmax+matmul — benchmark/fluid transformer prep. With an sp axis
# configured, the op partitions its time dim over the mesh: ring attention /
# Ulysses, parallel/ring_attention.py — the long-context capability)
# ---------------------------------------------------------------------------

# exporter-catalog family (docs/observability.md). Counts LOWERINGS, as
# ``paddle_kda_decode_lowered_total`` does: one increment per attention
# block each time a program that holds it is traced, labelled with what
# runs its core — ``flash`` (a Pallas kernel; no [B,H,Tq,Tk] tensor in
# HBM) or ``composed`` (ops/attention_block.py) — and the head size.
# Only the op's own emission into a program counts: not the shape
# inference of program build (it sees no mesh), not the backward's
# re-trace of the forward (it takes the same path). The sequence-parallel
# branch (ring / Ulysses) is not counted.
_ATTENTION_BLOCK_LOWERED = _metrics.counter(
    "paddle_attention_block_lowered_total",
    "fused_attention_block ops lowered, by what runs the attention core "
    "(flash|composed) and head size",
    labelnames=("path", "d_head"))


def _attention_kernel_blocks(t_q, t_k, m, n_head, causal, mesh, data_axis,
                             batch):
    """(bq, bk) when a flash kernel runs the block's core, else None.
    What the kernels need decides: a chip (or, for the tests,
    ``pallas.forced_interpret``); heads of whole lane tiles for
    ``flash_attention``, heads of 64 in whole pairs with Tq == Tk for
    ``flash_pairs``; a shape for which ``flash_engage`` names blocks
    that divide it. Under a mesh of several devices only ``flash_pairs``
    runs, mapped over the data axis (``attention_block.flash_block``),
    and only where that axis is the whole mesh — every other axis of
    size 1 — and divides the batch: a model axis shards M, which the
    kernels' pairs of heads do not follow."""
    from paddle_tpu.ops import pallas as pk
    d = m // n_head
    if d == pk.flash_pairs.D_HEAD:
        if (mesh is not None and mesh.size > 1
                and not (data_axis in mesh.axis_names
                         and mesh.shape[data_axis] == mesh.size
                         and batch % mesh.size == 0)):
            return None
        eng = pk.flash_engage(t_q, t_k, d, causal)
        # no mesh to refuse: a mapped call sees one chip's rows
        if (eng and pk.flash_pairs.supported(t_q, t_k, m, n_head, *eng)
                and (pk.kernel_enabled() or pk.forced_interpret())):
            return eng
        return None
    if pk.kernel_enabled(128, d, mesh=mesh):
        return pk.flash_engage(t_q, t_k, d, causal)
    return None


@register_op("fused_attention_block",
             ref="composed: mul+transpose+matmul+softmax ops; TPU-native "
                 "fused projection+attention block (zero-relayout VJP, "
                 "ops/attention_block.py)")
def _fused_attention_block(ctx, ins, attrs):
    """inputs: Xq [B,Tq,M], Xkv [B,Tk,M], Wq/Wk/Wv/Wo [M,M].
    attrs: n_head, causal, dropout_prob. One custom-VJP region covering
    the q/k/v/out projections AND the attention dots, spelled so neither
    forward nor backward materializes a single layout copy (the measured
    7.4 ms/step relayout band of the composed path — docs/performance.md
    Transformer-base accounting). With a mesh sp axis configured, falls
    back to the projections + sequence-parallel ring/Ulysses attention
    (the relayout cost is negligible next to the ring collectives)."""
    from paddle_tpu.ops import attention_block as ab

    x_q, x_kv = first(ins, "Xq"), first(ins, "Xkv")
    wq, wk, wv, wo = (first(ins, n) for n in ("Wq", "Wk", "Wv", "Wo"))
    x_q, x_kv, wq, wk, wv, wo = _amp_cast(attrs, x_q, x_kv, wq, wk, wv, wo)
    n_head = int(attrs["n_head"])
    causal = bool(attrs.get("causal", False))
    dropout_p = float(attrs.get("dropout_prob") or 0.0)
    if ctx.is_test or attrs.get("is_test"):
        dropout_p = 0.0
    amp = attrs.get("__amp_bf16__", False)
    seed = jnp.zeros((1,), jnp.int32)
    if dropout_p > 0:
        seed = jax.random.randint(ctx.step_key(), (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)

    mesh = ctx.mesh
    sp_axis = getattr(ctx.dist, "sp_axis", None)
    t_q, t_k = x_q.shape[1], x_kv.shape[1]
    if (mesh is not None and sp_axis and sp_axis in mesh.axis_names
            and mesh.shape[sp_axis] > 1 and t_q == t_k
            and t_q % mesh.shape[sp_axis] == 0):
        from paddle_tpu.parallel import ring_attention as ra
        h = n_head
        m = x_q.shape[-1]
        d = m // h
        def sp_proj(x, w):
            # fp32 MXU accumulation like every other attention path
            return jax.lax.dot_general(
                x, w.reshape(m, h, d), (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
                ).astype(x_q.dtype).transpose(0, 2, 1, 3)
        q, k, v = sp_proj(x_q, wq), sp_proj(x_kv, wk), sp_proj(x_kv, wv)
        o = ra.sp_attention(q, k, v, mesh, sp_axis, causal=causal,
                            scale=float(d) ** -0.5,
                            impl=attrs.get("sp_impl", "ring"),
                            batch_axis=getattr(ctx.dist, "data_axis", None),
                            head_axis=getattr(ctx.dist, "model_axis", None),
                            dropout_p=dropout_p, seed=seed)
        o = o.transpose(0, 2, 1, 3).reshape(x_q.shape[0], t_q, m)
        out = jnp.matmul(o, wo.astype(o.dtype),
                         preferred_element_type=jnp.float32
                         ).astype(o.dtype)
        return single(_amp_out(out, attrs) if amp else out)

    # The choice between kernel and composed block is made here, from
    # what the emitter sees (d, Tq, Tk, causal, the mesh): flash_engage
    # reads the committed table (tools/flash_autotune.py; model rows
    # override region sweeps). Measured in the cell train_big_1chip
    # (transformer_big, [16, 512] a chip, dropout 0.3, one v5e):
    # 8 heads of 128 -> flash_attention, 73.2k -> 77.1k tok/s (2026-08,
    # not the published model); the PUBLISHED 16 heads of 64 ->
    # flash_pairs, 54.0k -> 76.9k tok/s (2026-09-30, PR 41; flash_attention
    # behind a [B,H,T,D] relayout read 55.8k and was not kept).
    # Under a mesh of several devices XLA cannot partition a Mosaic
    # call: flash_pairs runs mapped over the data axis when that axis is
    # the whole mesh and divides the batch (train_big_dp4, PR 45; the
    # masks and the all-reduces are the composed block's), every other
    # mesh keeps the composed block, which XLA partitions. Below T=512
    # the composed block's relayout-free dots keep the row.
    h = n_head
    m = x_q.shape[-1]
    d = m // h
    from paddle_tpu.ops import pallas as pk
    data_axis = getattr(ctx.dist, "data_axis", None)
    eng = _attention_kernel_blocks(t_q, t_k, m, h, causal, mesh, data_axis,
                                   x_q.shape[0])
    if ctx.op is not None and ctx.step_base_key is not None:
        _ATTENTION_BLOCK_LOWERED.labels(
            path="flash" if eng else "composed", d_head=str(d)).inc()
    if eng and d == pk.flash_pairs.D_HEAD:
        out = ab.flash_block(x_q, x_kv, wq, wk, wv, wo, seed, h, causal,
                             dropout_p, eng[0], pk.interpret_mode(),
                             mesh, data_axis)
        return single(_amp_out(out, attrs) if amp else out)
    if eng:
        bq, bk = eng
        def proj_bhtd(x, w):
            y = jax.lax.dot_general(x, w.reshape(m, h, d),
                                    (((2,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32
                                    ).astype(x.dtype)
            return y.transpose(0, 2, 1, 3)
        q = proj_bhtd(x_q, wq)
        k = proj_bhtd(x_kv, wk)
        v = proj_bhtd(x_kv, wv)
        o = pk.flash_attention(q, k, v, causal, float(d) ** -0.5,
                               bq, bk, False, dropout_p,
                               seed if dropout_p > 0 else None)
        o = o.transpose(0, 2, 1, 3).reshape(x_q.shape[0], t_q, m)
        out = jnp.matmul(o, wo.astype(o.dtype),
                         preferred_element_type=jnp.float32
                         ).astype(o.dtype)
        return single(_amp_out(out, attrs) if amp else out)

    out = ab.attention_block(x_q, x_kv, wq, wk, wv, wo, seed,
                             n_head, causal, dropout_p)
    return single(_amp_out(out, attrs) if amp else out)


@register_op("attention", ref="composed: matmul+softmax ops; TPU-native "
                              "fused/sequence-parallel redesign")
def _attention(ctx, ins, attrs):
    """inputs: Q, K, V [B, H, T, D]; optional Bias [*, Tq, Tk] additive
    mask. attrs: causal, scale (default D^-0.5), sp ("auto" to use the
    mesh's sp axis when present), sp_impl ("ring"|"ulysses")."""
    from paddle_tpu.parallel import ring_attention as ra

    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    bias = first(ins, "Bias")
    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale") or float(q.shape[-1]) ** -0.5
    # attention-weight dropout (upscale_in_train, matching the composed
    # softmax→dropout→matmul graph — reference dist_transformer.py:1044);
    # the keep mask derives from a per-step int32 seed so the flash
    # kernels regenerate it in their backward (ops/pallas/flash_attention)
    dropout_p = float(attrs.get("dropout_prob") or 0.0)
    if ctx.is_test or attrs.get("is_test"):
        dropout_p = 0.0
    seed = None
    if dropout_p > 0:
        seed = jax.random.randint(ctx.step_key(), (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)

    layout = attrs.get("layout", "bhtd")
    t_dim = 1 if layout == "bthd" else 2

    sp = attrs.get("sp", "auto")
    mesh = ctx.mesh
    sp_axis = getattr(ctx.dist, "sp_axis", None) if sp == "auto" else sp
    use_sp = (mesh is not None and sp_axis and sp_axis in mesh.axis_names
              and mesh.shape[sp_axis] > 1
              and q.shape[t_dim] % mesh.shape[sp_axis] == 0
              and k.shape[t_dim] % mesh.shape[sp_axis] == 0
              and q.shape[t_dim] == k.shape[t_dim])
    if use_sp and layout == "bthd":
        # the sequence-parallel schedules work on [B, H, T, D]; under sp
        # the transpose cost is negligible next to the ring/all-to-all
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if use_sp:
        if bias is not None:
            raise ValueError(
                "attention: additive Bias is not supported with sequence "
                "parallelism — use causal=True for the causal mask")
        out = ra.sp_attention(q, k, v, mesh, sp_axis, causal=causal,
                              scale=scale,
                              impl=attrs.get("sp_impl", "ring"),
                              batch_axis=getattr(ctx.dist, "data_axis",
                                                 None),
                              head_axis=getattr(ctx.dist, "model_axis",
                                                None),
                              dropout_p=dropout_p, seed=seed)
        if layout == "bthd":
            out = out.transpose(0, 2, 1, 3)
    else:
        out = ra.full_attention(q, k, v, causal=causal, scale=scale,
                                bias=bias, dropout_p=dropout_p, seed=seed,
                                layout=layout, mesh=mesh)
    return single(out)
