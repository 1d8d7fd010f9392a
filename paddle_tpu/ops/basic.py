"""Tensor creation, elementwise, and activation ops.

Capability parity with the reference's fill_constant_op.cc,
gaussian_random_op.cc, uniform_random_op.cc, elementwise/*.cc and
activation_op.cc — each a C++/CUDA kernel pair there; here a single JAX
emitter that XLA fuses into neighbouring ops (elementwise chains fuse into
matmul epilogues on TPU for free).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.registry import EmitContext, first, register_op, single


# ---------------------------------------------------------------------------
# creation ops
# ---------------------------------------------------------------------------

@register_op("feed", no_grad=True, ref="operators/controlflow/feed_op.cc")
def _feed(ctx, ins, attrs):
    # feed is handled natively by the Executor (feeds become jit arguments);
    # present for program-structure parity with executor.py:315.
    return {}


@register_op("fetch", no_grad=True, ref="operators/controlflow/fetch_op.cc")
def _fetch(ctx, ins, attrs):
    return {}


@register_op("fill_constant", no_grad=True, ref="operators/fill_constant_op.cc")
def _fill_constant(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", ()))
    dtype = attrs.get("dtype", "float32")
    value = attrs.get("value", 0.0)
    return single(jnp.full(shape, value, dtype=dtype))


@register_op("fill_zeros_like", no_grad=True, ref="operators/fill_zeros_like_op.cc")
def _fill_zeros_like(ctx, ins, attrs):
    return single(jnp.zeros_like(first(ins, "X")))


@register_op("fill_constant_batch_size_like", no_grad=True,
             ref="operators/fill_constant_batch_size_like_op.cc")
def _fill_constant_batch_size_like(ctx, ins, attrs):
    x = first(ins, "Input")
    shape = list(attrs.get("shape", ()))
    in_dim = attrs.get("input_dim_idx", 0)
    out_dim = attrs.get("output_dim_idx", 0)
    shape[out_dim] = x.shape[in_dim]
    return single(jnp.full(tuple(shape), attrs.get("value", 0.0),
                           dtype=attrs.get("dtype", "float32")))


@register_op("gaussian_random", no_grad=True, ref="operators/gaussian_random_op.cc")
def _gaussian_random(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", ()))
    dtype = attrs.get("dtype", "float32")
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    x = jax.random.normal(ctx.key(), shape, dtype=jnp.float32) * std + mean
    return single(x.astype(dtype))


@jax.jit(static_argnums=(0, 1, 2))
def hash_normal(shape, dtype, std, seed, salt):
    """Normal(0, std) values of ``shape`` in ``dtype``, each a pure
    function of (its flat index, ``seed``, ``salt``): two murmur-mixed
    uniforms through Box-Muller, elementwise — so XLA fuses the whole
    draw into the write of the result and a 3 B-parameter start-up needs
    no memory beside its outputs (``jax.random.normal`` keeps a
    threefry bit buffer per tensor: 10.5 GB of temporaries beside 9.4 GB
    of outputs for the hybrid model's start-up). ``seed`` and ``salt``
    are uint32 scalars, traced or not; at most 2**32 elements.

    Jitted with ``shape``, ``dtype`` and ``std`` static (hashable: a
    tuple, a dtype's name, a float), so that this body is traced once a
    (shape, dtype, std) a process, by JAX's own trace cache: shape
    inference and the start-up's lowering of a family's every view find
    the draw of each later parameter of a signature already traced. A
    nested ``jit`` is inlined before XLA fuses, so a start-up still
    writes each draw straight into its result."""
    n = 1
    for d in shape:
        n *= int(d)
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)

    def mix(x):
        x = x ^ (jnp.uint32(seed) * jnp.uint32(0x85EBCA6B))
        x = x ^ (jnp.uint32(salt) * jnp.uint32(0x27D4EB2F))
        for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35)):
            x = (x ^ (x >> shift)) * jnp.uint32(mul)
        x = x ^ (x >> 16)
        # uniform in (0, 1) from the 24 high bits; never exactly 0 or 1
        return ((x >> jnp.uint32(8)).astype(jnp.float32) + 0.5) \
            * (1.0 / (1 << 24))

    u1 = mix(idx * jnp.uint32(0x9E3779B9))
    u2 = mix(idx * jnp.uint32(0x9E3779B9) + jnp.uint32(0x7F4A7C15))
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * np.pi) * u2)
    return (z * std).astype(dtype)


@register_op("hash_normal_random", no_grad=True,
             ref="TPU-native initializer: Normal(0, std) as an "
                 "elementwise function of the element's index and the "
                 "op's rng key (no bit buffer: see hash_normal)")
def _hash_normal_random(ctx, ins, attrs):
    key = jax.random.key_data(ctx.key()).reshape(-1).astype(jnp.uint32)
    return single(hash_normal(tuple(attrs["shape"]), attrs["dtype"],
                              attrs.get("std", 1.0), key[0], key[-1]))


@register_op("uniform_random", no_grad=True, ref="operators/uniform_random_op.cc")
def _uniform_random(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", ()))
    dtype = attrs.get("dtype", "float32")
    lo = attrs.get("min", -1.0)
    hi = attrs.get("max", 1.0)
    x = jax.random.uniform(ctx.key(), shape, minval=lo, maxval=hi, dtype=jnp.float32)
    return single(x.astype(dtype))


@register_op("truncated_gaussian_random", no_grad=True,
             ref="operators/truncated_gaussian_random_op.cc")
def _truncated_gaussian_random(ctx, ins, attrs):
    shape = tuple(attrs.get("shape", ()))
    dtype = attrs.get("dtype", "float32")
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    x = jax.random.truncated_normal(ctx.key(), -2.0, 2.0, shape, dtype=jnp.float32)
    return single((x * std + mean).astype(dtype))


@register_op("assign", ref="operators/assign_op.cc")
def _assign(ctx, ins, attrs):
    return single(first(ins, "X"))


@register_op("assign_value", no_grad=True, ref="operators/assign_value_op.cc")
def _assign_value(ctx, ins, attrs):
    import numpy as np
    shape = tuple(attrs.get("shape", ()))
    dtype = attrs.get("dtype", "float32")
    vals = np.asarray(attrs.get("values", []), dtype=dtype).reshape(shape)
    return single(jnp.asarray(vals))


@register_op("sign", ref="operators/sign_op.cc")
def _sign(ctx, ins, attrs):
    return single(jnp.sign(first(ins, "X")))


@register_op("increment", no_grad=True, ref="operators/increment_op.cc")
def _increment(ctx, ins, attrs):
    x = first(ins, "X")
    return single(x + jnp.asarray(attrs.get("step", 1.0), dtype=x.dtype))


@register_op("shape", no_grad=True, ref="operators/shape_op.cc")
def _shape(ctx, ins, attrs):
    return single(jnp.asarray(first(ins, "Input").shape, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# elementwise binary ops with fluid's axis-broadcast convention
# (reference: operators/elementwise/elementwise_op.h — Y broadcast into X
# with Y's dims aligned at attr `axis`; axis=-1 means trailing alignment)
# ---------------------------------------------------------------------------

def _broadcast_y(x, y, axis):
    if y.ndim == 0 or x.shape == y.shape:
        return y
    if axis == -1 or axis is None:
        axis = x.ndim - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return y.reshape(new_shape)


def _match_low_precision(x, y):
    """When one side is a low-precision activation (bf16/fp16) and the
    other fp32, cast the fp32 side DOWN instead of letting promotion lift
    the result to fp32 — keeps pure-bf16 AMP programs bf16 through
    bias-adds AND full-size mixes like residual adds (an fp32 residual
    stream doubles the HBM traffic of every elementwise/norm op between
    matmuls; measured on Transformer-base bs128 v5e). Only applied to ops
    tagged __amp_match_dtype__ by rewrite_program_amp (pure mode): a
    non-AMP program's deliberate fp32 promotion is kept."""
    lowp = (jnp.bfloat16, jnp.float16)
    if x.dtype in lowp and y.dtype == jnp.float32:
        y = y.astype(x.dtype)
    elif y.dtype in lowp and x.dtype == jnp.float32:
        x = x.astype(y.dtype)
    return x, y


# float elementwise binaries (shared by contrib.mixed_precision dtype
# matching and contrib.layout broadcast analysis)
ELEMENTWISE_OPS = ("elementwise_add", "elementwise_sub", "elementwise_mul",
                   "elementwise_div", "elementwise_max", "elementwise_min")


def _register_elementwise(name, fn):
    @register_op(name, ref="operators/elementwise/" + name + "_op.cc")
    def _emit(ctx, ins, attrs, _fn=fn):
        x = first(ins, "X")
        y = first(ins, "Y")
        if attrs.get("__nhwc_bcast__") and y.ndim == 1:
            # contrib.layout NHWC region: the channel (axis=1) broadcast
            # re-aims at the physical last axis
            y = y.reshape((1,) * (x.ndim - 1) + (-1,))
        elif attrs.get("__nhwc_bcast_bc__") and y.ndim == 2:
            # [B, C] at axis=0 over an NHWC-resident X: batch leads,
            # channels re-aim at the physical last axis (SE gates)
            y = y.reshape((y.shape[0],) + (1,) * (x.ndim - 2)
                          + (y.shape[1],))
        else:
            y = _broadcast_y(x, y, attrs.get("axis", -1))
        if attrs.get("__amp_match_dtype__") \
                and jnp.issubdtype(x.dtype, jnp.floating) \
                and jnp.issubdtype(y.dtype, jnp.floating):
            x, y = _match_low_precision(x, y)
        return single(_fn(x, y))


_register_elementwise("elementwise_add", jnp.add)
_register_elementwise("elementwise_sub", jnp.subtract)
_register_elementwise("elementwise_mul", jnp.multiply)
_register_elementwise("elementwise_div", jnp.divide)
_register_elementwise("elementwise_max", jnp.maximum)
_register_elementwise("elementwise_min", jnp.minimum)
_register_elementwise("elementwise_pow", jnp.power)
_register_elementwise("elementwise_mod", jnp.mod)


# ---------------------------------------------------------------------------
# activations (reference: operators/activation_op.cc — 20+ registered there)
# ---------------------------------------------------------------------------

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "rsqrt": jax.lax.rsqrt,
    "square": jnp.square,
    "abs": jnp.abs,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "round": jnp.round,
    "reciprocal": jnp.reciprocal,
    "softsign": jax.nn.soft_sign,
    "softplus": jax.nn.softplus,
    "gelu": jax.nn.gelu,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "logsigmoid": jax.nn.log_sigmoid,
    "tanh_shrink": lambda x: x - jnp.tanh(x),
}

for _name, _fn in _ACTIVATIONS.items():
    def _emit_act(ctx, ins, attrs, _fn=_fn):
        return single(_fn(first(ins, "X")))
    register_op(_name, ref="operators/activation_op.cc")(_emit_act)


@register_op("leaky_relu", ref="operators/activation_op.cc")
def _leaky_relu(ctx, ins, attrs):
    return single(jax.nn.leaky_relu(first(ins, "X"), attrs.get("alpha", 0.02)))


@register_op("elu", ref="operators/activation_op.cc")
def _elu(ctx, ins, attrs):
    return single(jax.nn.elu(first(ins, "X"), attrs.get("alpha", 1.0)))


@register_op("relu6", ref="operators/activation_op.cc")
def _relu6(ctx, ins, attrs):
    t = attrs.get("threshold", 6.0)
    return single(jnp.clip(first(ins, "X"), 0.0, t))


@register_op("hard_sigmoid", ref="operators/activation_op.cc")
def _hard_sigmoid(ctx, ins, attrs):
    slope = attrs.get("slope", 0.2)
    offset = attrs.get("offset", 0.5)
    return single(jnp.clip(first(ins, "X") * slope + offset, 0.0, 1.0))


@register_op("pow", ref="operators/activation_op.cc")
def _pow(ctx, ins, attrs):
    return single(jnp.power(first(ins, "X"), attrs.get("factor", 1.0)))


@register_op("swish", ref="operators/activation_op.cc")
def _swish(ctx, ins, attrs):
    x = first(ins, "X")
    beta = attrs.get("beta", 1.0)
    return single(x * jax.nn.sigmoid(beta * x))


@register_op("prelu", ref="operators/prelu_op.cc")
def _prelu(ctx, ins, attrs):
    x = first(ins, "X")
    alpha = first(ins, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "channel" and alpha.size > 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return single(jnp.where(x > 0, x, alpha * x))


@register_op("clip", ref="operators/clip_op.cc")
def _clip(ctx, ins, attrs):
    return single(jnp.clip(first(ins, "X"), attrs.get("min"), attrs.get("max")))


# ---------------------------------------------------------------------------
# comparison / logical (reference: operators/controlflow/compare_op.cc,
# logical_op.cc)
# ---------------------------------------------------------------------------

def _register_compare(name, fn):
    @register_op(name, no_grad=True, ref="operators/controlflow/compare_op.cc")
    def _emit(ctx, ins, attrs, _fn=fn):
        x = first(ins, "X")
        y = _broadcast_y(x, first(ins, "Y"), attrs.get("axis", -1))
        return single(_fn(x, y))


_register_compare("equal", jnp.equal)
_register_compare("not_equal", jnp.not_equal)
_register_compare("less_than", jnp.less)
_register_compare("less_equal", jnp.less_equal)
_register_compare("greater_than", jnp.greater)
_register_compare("greater_equal", jnp.greater_equal)


@register_op("logical_and", no_grad=True, ref="operators/controlflow/logical_op.cc")
def _logical_and(ctx, ins, attrs):
    return single(jnp.logical_and(first(ins, "X"), first(ins, "Y")))


@register_op("logical_or", no_grad=True, ref="operators/controlflow/logical_op.cc")
def _logical_or(ctx, ins, attrs):
    return single(jnp.logical_or(first(ins, "X"), first(ins, "Y")))


@register_op("logical_not", no_grad=True, ref="operators/controlflow/logical_op.cc")
def _logical_not(ctx, ins, attrs):
    return single(jnp.logical_not(first(ins, "X")))


@register_op("logical_xor", no_grad=True, ref="operators/controlflow/logical_op.cc")
def _logical_xor(ctx, ins, attrs):
    return single(jnp.logical_xor(first(ins, "X"), first(ins, "Y")))


@register_op("select", ref="lax.select; capability of fluid's cond/switch "
             "(operators/controlflow) for elementwise choice")
def _select(ctx, ins, attrs):
    cond = first(ins, "Condition")
    x = first(ins, "X")
    y = first(ins, "Y")
    return single(jnp.where(cond, x, y))


@register_op("isfinite", no_grad=True, ref="operators/isfinite_op.cc")
def _isfinite(ctx, ins, attrs):
    x = first(ins, "X")
    return single(jnp.all(jnp.isfinite(x)).reshape(1))
