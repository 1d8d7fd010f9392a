"""Mixed-precision training decorator (capability successor of the
reference's fp16 direction: the reference era shipped fp16 *inference*
(contrib/float16); this adds the training half the way later fluid did —
loss scaling + overflow-safe updates — expressed dataflow-style for XLA).

On TPU the compute dtype is bfloat16, whose fp32-equal exponent range
makes loss scaling unnecessary for most models; `decorate` exists for
capability parity and true-fp16 experiments. Semantics:

  scaled_loss = loss * scale;  grads = backward(scaled_loss)
  finite      = all(isfinite(g))
  g'          = g * finite / scale      # zeroed on overflow -> update is
                                        # skipped in effect (divergence:
                                        # adaptive moments see a zero grad
                                        # instead of no op at all)
  dynamic: scale grows by incr_ratio after incr_every_n_steps clean steps,
  shrinks by decr_ratio on overflow — all on-device (XLA select), no host
  round-trip per step."""

from __future__ import annotations

from paddle_tpu.fluid import framework
from paddle_tpu.fluid.initializer import ConstantInitializer
from paddle_tpu.fluid.layer_helper import LayerHelper


def _emit(op_type, inputs, n_out=1, attrs=None, dtype="float32",
          out_slot="Out"):
    helper = LayerHelper(op_type)
    outs = [helper.create_variable_for_type_inference(dtype)
            for _ in range(n_out)]
    helper.append_op(op_type, inputs=inputs, outputs={out_slot: outs},
                     attrs=attrs or {})
    return outs[0] if n_out == 1 else outs


def _const(value):
    from paddle_tpu.fluid import layers
    return layers.fill_constant([1], "float32", float(value))


def _finite_flag(grads):
    """all(isfinite(g)) over every gradient, as a float32 [1] tensor."""
    from paddle_tpu.fluid import layers
    flags = []
    for g in grads:
        fin = _emit("isfinite", {"X": [g]}, dtype="bool")
        flags.append(layers.cast(fin, "float32"))
    prod = flags[0]
    for f in flags[1:]:
        prod = layers.elementwise_mul(prod, f)
    return layers.reshape(prod, shape=[1])


def decorate(optimizer, init_loss_scaling=2.0 ** 15,
             use_dynamic_loss_scaling=True, incr_every_n_steps=1000,
             decr_every_n_nan_or_inf=2, incr_ratio=2.0, decr_ratio=0.5):
    """reference: fluid.contrib.mixed_precision.decorate(optimizer, ...)
    -> optimizer whose minimize() trains under loss scaling."""
    return OptimizerWithMixedPrecision(
        optimizer, init_loss_scaling, use_dynamic_loss_scaling,
        incr_every_n_steps, incr_ratio, decr_ratio,
        decr_every_n_nan_or_inf)


class OptimizerWithMixedPrecision:
    def __init__(self, optimizer, init_scale, dynamic, incr_every,
                 incr_ratio, decr_ratio, decr_every=2):
        self._opt = optimizer
        self._init_scale = float(init_scale)
        self._dynamic = dynamic
        self._incr_every = float(incr_every)
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._decr_every = float(decr_every)

    @property
    def loss_scaling_name(self):
        return "loss_scaling@AMP"

    def backward(self, *a, **kw):
        return self._opt.backward(*a, **kw)

    def apply_gradients(self, params_grads):
        return self._opt.apply_gradients(params_grads)

    def _persistable(self, name, value):
        main = framework.default_main_program()
        startup = framework.default_startup_program()
        v = main.global_block().create_var(
            name=name, shape=[1], dtype="float32", persistable=True,
            stop_gradient=True)
        sv = startup.global_block().create_var(
            name=name, shape=[1], dtype="float32", persistable=True)
        ConstantInitializer(float(value))(sv, startup.global_block())
        return v

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from paddle_tpu.fluid import layers

        scale_var = self._persistable(self.loss_scaling_name,
                                      self._init_scale)
        good_steps = self._persistable("good_steps@AMP", 0.0)

        scaled_loss = layers.elementwise_mul(loss, scale_var)
        params_grads = self._opt.backward(scaled_loss, startup_program,
                                          parameter_list, no_grad_set)

        finite = _finite_flag([g for _, g in params_grads])
        # g' = g * (finite / scale): [1] broadcasts against any grad shape
        mult = layers.elementwise_div(finite, scale_var)
        safe = [(p, layers.elementwise_mul(g, mult))
                for p, g in params_grads]
        opt_ops = self._opt.apply_gradients(safe)

        if self._dynamic:
            bad_steps = self._persistable("bad_steps@AMP", 0.0)
            one = _const(1.0)
            not_finite = layers.elementwise_sub(one, finite)
            inc = layers.elementwise_mul(
                layers.elementwise_add(good_steps, one), finite)
            reached = layers.cast(
                _ge(inc, _const(self._incr_every)), "float32")
            grown = layers.elementwise_mul(
                scale_var,
                layers.elementwise_add(
                    one, layers.elementwise_mul(
                        reached, _const(self._incr_ratio - 1.0))))
            # shrink only after decr_every consecutive nan/inf steps
            # (reference: decr_every_n_nan_or_inf semantics)
            bad_inc = layers.elementwise_mul(
                layers.elementwise_add(bad_steps, one), not_finite)
            decr_reached = layers.cast(
                _ge(bad_inc, _const(self._decr_every)), "float32")
            shrunk_overflow = layers.elementwise_add(
                layers.elementwise_mul(
                    layers.elementwise_mul(scale_var,
                                           _const(self._decr_ratio)),
                    decr_reached),
                layers.elementwise_mul(
                    scale_var, layers.elementwise_sub(one, decr_reached)))
            new_scale = layers.elementwise_add(
                layers.elementwise_mul(grown, finite),
                layers.elementwise_mul(shrunk_overflow, not_finite))
            layers.assign(new_scale, scale_var)
            keep = layers.elementwise_mul(
                inc, layers.elementwise_sub(one, reached))
            layers.assign(keep, good_steps)
            keep_bad = layers.elementwise_mul(
                bad_inc, layers.elementwise_sub(one, decr_reached))
            layers.assign(keep_bad, bad_steps)

        return opt_ops, params_grads


def _ge(a, b):
    """a >= b as a float-friendly bool tensor via the compare ops."""
    from paddle_tpu.fluid import layers
    return layers.greater_equal(a, b)


AMP_OP_TYPES = ("conv2d", "depthwise_conv2d", "conv2d_fusion", "conv3d",
                "mul", "matmul", "conv2d_transpose", "fc",
                "fused_linear_ce", "fused_attention_block",
                # the hybrid block's ops (ops/math_ops.py:amp_dtypes):
                # bfloat16 products over float32 master weights, router
                # scores, norms, rotation and softmax float32
                "dense", "mla_full", "swiglu_ffn", "expert_ffn_held")


RECURRENT_OPS = ("dynamic_lstm", "dynamic_gru", "dynamic_lstmp", "while",
                 "gru_unit", "lstm_unit")
# a top-k router picks by comparison: behind bfloat16 op edges its picks
# flip where two scores lie within the rounding (on the chip, PR 47: 2-3 %
# of a 256-wide router's load signs differed from the float32 reference's
# and a held expert's weight gradient 16-39 % entry by entry, its norm
# within 0.1 %; the gradients that reach the first layers through the
# bfloat16 residual stream read 2 % low). A program that routes keeps
# float32 edges.
ROUTED_OPS = ("expert_ffn_held",)


def rewrite_program_amp(program=None, op_types=AMP_OP_TYPES, pure=None):
    """bf16 compute rewrite: tag every MXU op so its emitter casts float
    inputs to bfloat16 (master weights stay fp32 in the Scope — the
    later-fluid pure-bf16 AMP capability, done at the op level so autodiff
    re-traces see the same cast).

    pure=True additionally keeps the tagged ops' OUTPUTS bf16, so
    activations stay half-width through the whole elementwise/norm tail
    between MXU ops (batch/layer norm compute fp32 statistics and
    bias-adds cast parameters down rather than promoting — see
    ops/nn_ops.py, ops/basic.py); the loss boundary
    (softmax_with_cross_entropy) upcasts to fp32. pure=False restores
    fp32 at every op edge (the conservative per-op mode).

    pure=None (default) auto-selects: pure bf16 unless the program
    contains recurrent-scan ops (RECURRENT_OPS) — scan steps are small
    and latency-bound, where bf16 activation edges add per-step converts
    instead of saving bandwidth (measured: machine_translation GRU 772k
    words/s conservative vs 650k pure on v5e; ResNet-50 the reverse,
    2530 pure vs 1890 conservative img/s) — or a top-k routed expert
    layer (ROUTED_OPS), whose picks flip behind bfloat16 edges.

    bf16's fp32-equal exponent range makes loss scaling unnecessary
    (module docstring), so this composes with — but does not require —
    `decorate`."""
    from paddle_tpu.fluid import framework
    program = program or framework.default_main_program()
    from paddle_tpu.ops.basic import ELEMENTWISE_OPS as elementwise
    if pure is None:
        pure = not any(op.type in RECURRENT_OPS + ROUTED_OPS
                       for block in program.desc.blocks
                       for op in block.ops)
    n = 0
    for block in program.desc.blocks:        # sub-blocks too (while/cond)
        for op in block.ops:
            if op.type in op_types:
                op.attrs["__amp_bf16__"] = True
                if pure:
                    op.attrs["__amp_keep_bf16__"] = True
                n += 1
            elif pure and op.type in elementwise:
                # bias/scale adds after tagged ops: cast the fp32 param
                # operand down instead of promoting the bf16 activation up
                op.attrs["__amp_match_dtype__"] = True
            elif pure and op.type == "lookup_table":
                # the embedding STARTS the residual stream: keep it bf16
                # or every downstream elementwise/norm runs fp32 (2x HBM)
                op.attrs["__amp_keep_bf16__"] = True
                n += 1
            elif op.type == "__vjp__":
                # backward ops re-trace a SNAPSHOT of the forward op
                # (grad_ops.py fwd_op dict) — tag it too so rewrites after
                # minimize() keep the backward in bf16
                fwd = op.attrs.get("fwd_op", {})
                if fwd.get("type") in op_types:
                    fwd.setdefault("attrs", {})["__amp_bf16__"] = True
                    if pure:
                        fwd["attrs"]["__amp_keep_bf16__"] = True
                    n += 1
                elif pure and fwd.get("type") in elementwise:
                    fwd.setdefault("attrs", {})["__amp_match_dtype__"] = True
                elif pure and fwd.get("type") == "lookup_table":
                    fwd.setdefault("attrs", {})["__amp_keep_bf16__"] = True
    program.desc.bump_version()
    return n
