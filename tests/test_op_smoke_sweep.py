"""Executable smoke sweep over the ENTIRE op registry.

Round-1 verdict item 6: the registry-closure test asserted only
registration (`r in OPS`), so a gutted op would stay green. This sweep
EXECUTES every registered op's emitter with minimal synthetic inputs and
asserts real arrays come out. Accounting is total: every op in the
registry must be exactly one of
  - SPECS        — executed here with concrete inputs/attrs,
  - REDIRECTS    — the documented NotImplementedError redirect set,
                   asserted EXACTLY (machine-checked __redirect__ marker),
  - CONTEXT_OPS  — needs program context (sub-blocks, feed/fetch plumbing,
                   host IO); each maps to the test file that executes it
                   end-to-end, and the sweep verifies that file exists and
                   names the op.
A new op that lands in none of the buckets fails the sweep.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (registers all emitters)
from paddle_tpu.core.registry import OPS, EmitContext


def f(*shape, seed=0, lo=-0.5, hi=0.5):
    rng = np.random.RandomState(seed)
    return jnp.asarray((rng.rand(*shape) * (hi - lo) + lo)
                       .astype(np.float32))


def pos(*shape, seed=0):
    return f(*shape, seed=seed, lo=0.1, hi=0.9)


def ints(*shape, hi=4, seed=0, dtype=np.int64):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, hi, shape).astype(dtype))


def lens(*vals):
    return jnp.asarray(np.array(vals, np.int32))


B, T, D = 2, 4, 8
X23 = {"X": [f(2, 3)]}
XY = {"X": [f(2, 3)], "Y": [f(2, 3, seed=1)]}
SEQ = {"X": [f(B, T, D)], "SeqLens": [lens(3, 4)]}
IMG = {"Input": [f(1, 3, 8, 8)]}


# ---------------------------------------------------------------------------
# the documented redirect set — EXACTLY these raise NotImplementedError
# (ops/infra_ops.py _register_redirect)
REDIRECTS = {
    "send", "recv", "send_barrier", "fetch_barrier", "prefetch",
    "listen_and_serv", "checkpoint_notify", "gen_nccl_id", "nccl", "go",
    "tensorrt_engine", "read", "create_custom_reader",
}

# ops that only execute inside a full program (sub-blocks, TensorArray
# state threaded by the lowering, feed/fetch plumbing, host IO callbacks)
# -> the test file that drives them end-to-end
CONTEXT_OPS = {
    "while": "test_control_flow.py",
    "cond": "test_control_flow.py",
    "scan": "test_control_flow.py",
    "conditional_block": ("test_control_flow.py", "IfElse"),
    "recurrent": "test_lod_ops.py",     # alias of scan (ops/lod_ops.py)
    "lod_tensor_to_array": "test_lod_ops.py",
    "array_to_lod_tensor": "test_lod_ops.py",
    "tensor_array_to_tensor": "test_lod_ops.py",
    "feed": "test_executor_basic.py",
    "fetch": "test_executor_basic.py",
    "__vjp__": "test_op_grads.py",
    "beam_search": "test_beam_search.py",
    "beam_search_decode": "test_beam_search.py",
    # emitted by models.machine_translation.build(is_train=False), driven
    # end-to-end by test_machine_translation_train_and_beam_decode
    "attention_gru_beam_decode": ("test_beam_search.py",
                                  "machine_translation"),
    # pp/ep sections: sub-block + mesh context (fluid.layers.Pipeline /
    # switch_moe), trained end-to-end over a pp x ep mesh
    "pipeline": "test_parallel_layers.py",
    "moe_ffn": "test_parallel_layers.py",
    # paged KV attention reads/writes a PagePool-owned page table whose
    # geometry (page rows, sentinel clamps, scale planes) only exists in
    # a full paged engine build; driven end-to-end vs the wave oracle
    "kv_attention_prefill_paged": ("test_kv_pool.py", "prefill_paged"),
    "kv_attention_decode_paged": ("test_kv_pool.py", "decode_paged"),
    # the paged verify window resolves its write rows through the same
    # PagePool-owned table; driven end-to-end by the speculative-decode
    # parity + rollback tests
    "kv_attention_verify_paged": ("test_spec_decode.py",
                                  "decode_verify_paged"),
}


def _adam_like(n_moments=2, pows=("Beta1Pow", "Beta2Pow")):
    ins = {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
           "LearningRate": [pos(1)]}
    for i in range(n_moments):
        ins[f"Moment{i + 1}"] = [pos(3, 4, seed=2 + i)]
    for p in pows:
        ins[p] = [pos(1)]
    return ins


SPECS = {}


def spec(name, ins, attrs=None):
    SPECS[name] = (ins, attrs or {})


# --- basic: unary elementwise ---------------------------------------------
for op in ("abs ceil cos exp floor gelu hard_sigmoid leaky_relu log "
           "logsigmoid reciprocal relu relu6 round rsqrt sigmoid sign sin "
           "softplus softsign sqrt square swish tanh tanh_shrink elu "
           "isfinite brelu stanh selu soft_shrink hard_shrink "
           "thresholded_relu logical_not").split():
    spec(op, {"X": [pos(2, 3)]})
spec("clip", X23, {"min": -0.2, "max": 0.2})
spec("prelu", {"X": [f(2, 3)], "Alpha": [pos(1)]}, {"mode": "all"})
spec("assign", X23)
spec("pow", X23, {"factor": 2.0})
spec("assign_value", {}, {"shape": [2, 2], "dtype": "float32",
                          "values": [1.0, 2.0, 3.0, 4.0]})
spec("fill_constant", {}, {"shape": [2, 2], "dtype": "float32",
                           "value": 3.0})
spec("fill_zeros_like", X23)
spec("fill_constant_batch_size_like",
     {"Input": [f(5, 3)]},
     {"shape": [-1, 2], "dtype": "float32", "value": 1.0})
spec("increment", {"X": [f(1)]}, {"step": 1.0})
spec("shape", {"Input": [f(2, 3)]})
spec("gaussian_random", {}, {"shape": [2, 3], "dtype": "float32"})
spec("uniform_random", {}, {"shape": [2, 3], "dtype": "float32"})
spec("truncated_gaussian_random", {}, {"shape": [2, 3],
                                       "dtype": "float32"})
spec("select", {"Condition": [ints(2, 3, hi=2).astype(jnp.bool_)],
                "X": [f(2, 3)], "Y": [f(2, 3, seed=1)]})

# --- basic: binary ---------------------------------------------------------
for op in ("elementwise_add elementwise_sub elementwise_mul "
           "elementwise_div elementwise_max elementwise_min "
           "elementwise_pow elementwise_mod equal not_equal less_than "
           "less_equal greater_than greater_equal logical_and logical_or "
           "logical_xor").split():
    if op == "elementwise_mod":
        spec(op, {"X": [ints(2, 3, hi=9)], "Y": [ints(2, 3, hi=3) + 1]})
    elif op.startswith("logical"):
        spec(op, {"X": [ints(2, 3, hi=2).astype(jnp.bool_)],
                  "Y": [ints(2, 3, hi=2, seed=1).astype(jnp.bool_)]})
    elif op in ("elementwise_div", "elementwise_pow"):
        spec(op, {"X": [pos(2, 3)], "Y": [pos(2, 3, seed=1)]})
    else:
        spec(op, XY)

# --- math_ops --------------------------------------------------------------
spec("argmax", X23, {"axis": 1})
spec("argmin", X23, {"axis": 1})
spec("arg_max", X23, {"axis": 1})
spec("arg_min", X23, {"axis": 1})
spec("cast", X23, {"out_dtype": "float32"})
spec("concat", {"X": [f(2, 3), f(2, 2, seed=1)]}, {"axis": 1})
spec("cumsum", X23, {"axis": 1})
spec("expand", X23, {"expand_times": [2, 1]})
spec("gather", {"X": [f(4, 3)], "Index": [ints(2, hi=4)]})
spec("matmul", {"X": [f(2, 3)], "Y": [f(3, 4, seed=1)]})
spec("mean", X23)
spec("mul", {"X": [f(2, 3)], "Y": [f(3, 4, seed=1)]})
spec("norm", X23, {"axis": 1})
spec("one_hot", {"X": [ints(3, 1, hi=4)]}, {"depth": 5})
spec("range", {"Start": [jnp.asarray(0.0)], "End": [jnp.asarray(4.0)],
               "Step": [jnp.asarray(1.0)]})
for op in ("reduce_max", "reduce_mean", "reduce_min", "reduce_prod",
           "reduce_sum"):
    spec(op, X23, {"dim": [1]})
spec("reshape", X23, {"shape": [3, 2]})
spec("reshape2", X23, {"shape": [3, 2]})
spec("scale", X23, {"scale": 2.0})
spec("scatter", {"X": [f(4, 3)], "Ids": [ints(2, hi=4)],
                 "Updates": [f(2, 3, seed=1)]})
spec("slice", {"Input": [f(4, 5)]},
     {"axes": [0, 1], "starts": [1, 0], "ends": [3, 4]})
spec("split", {"X": [f(2, 6)]}, {"axis": 1, "num": 2})
spec("squared_l2_norm", X23)
spec("squeeze", {"X": [f(2, 1, 3)]}, {"axes": [1]})
spec("squeeze2", {"X": [f(2, 1, 3)]}, {"axes": [1]})
spec("stack", {"X": [f(2, 3), f(2, 3, seed=1)]}, {"axis": 0})
spec("sum", {"X": [f(2, 3), f(2, 3, seed=1)]})
spec("top_k", X23, {"k": 2})
spec("transpose", X23, {"axis": [1, 0]})
spec("transpose2", X23, {"axis": [1, 0]})
spec("unsqueeze", X23, {"axes": [1]})
spec("unsqueeze2", X23, {"axes": [1]})

# --- nn_ops ----------------------------------------------------------------
spec("attention", {"Q": [f(1, 2, 4, 4)], "K": [f(1, 2, 4, 4, seed=1)],
                   "V": [f(1, 2, 4, 4, seed=2)]}, {"causal": True})
spec("fused_attention_block",
     {"Xq": [f(2, 4, 8)], "Xkv": [f(2, 4, 8, seed=1)],
      "Wq": [f(8, 8, seed=2)], "Wk": [f(8, 8, seed=3)],
      "Wv": [f(8, 8, seed=4)], "Wo": [f(8, 8, seed=5)]},
     {"n_head": 2, "causal": True})
# serving KV-cache family (ops/kv_attention.py): token_sample picks next
# tokens on-device (the paged attention ops are CONTEXT_OPS above)
spec("token_sample",
     {"Logits": [f(2, 16)], "Temperature": [f(2, 1, lo=0.0, hi=1.0)],
      "TopK": [ints(2, 1, hi=5)], "Seed": [ints(2, 1, hi=100, seed=4)],
      "StepIdx": [ints(2, 1, hi=4, seed=5)]})
# the hybrid sparse block (ops/kda.py, ops/expert_ffn.py): two slots,
# two heads of four channels, a conv of four taps, rank-3 gates; two
# held experts (1 and 2) of a router six wide
_KDA = {"Wq": [f(8, 8, seed=2)], "Wk": [f(8, 8, seed=3)],
        "Wv": [f(8, 8, seed=4)], "Wo": [f(8, 8, seed=5)],
        "ConvW": [f(4, 24, seed=6)], "ALog": [f(2, seed=7)],
        "DtBias": [f(8, seed=8)], "WaDown": [f(8, 3, seed=9)],
        "WaUp": [f(3, 8, seed=10)], "WBeta": [f(8, 2, seed=11)],
        "WgDown": [f(8, 3, seed=12)], "WgUp": [f(3, 8, seed=13)],
        "ONorm": [pos(4)], "State": [f(2, 2, 4, 4, seed=14)],
        "Conv": [f(2, 3, 24, seed=15)]}
spec("kda_decode", {"X": [f(2, 1, 8)], **_KDA,
                    "Active": [ints(2, 1, hi=2, seed=3)]},
     {"n_head": 2, "head_dim": 4})
spec("kda_prefill", {"X": [f(1, 5, 8)], **_KDA,
                     "SeqLen": [lens(3).reshape(1, 1)],
                     "Slot": [lens(1).reshape(1, 1)]},
     {"n_head": 2, "head_dim": 4})
# Gated DeltaNet (ops/gdn.py): two slots, two heads with keys of four
# and values of six, a conv of four taps, a chunk of three
_GDN = {"Wq": [f(8, 8, seed=2)], "Wk": [f(8, 8, seed=3)],
        "Wv": [f(8, 12, seed=4)], "Wz": [f(8, 12, seed=5)],
        "Wo": [f(12, 8, seed=6)], "ConvW": [f(4, 28, seed=7)],
        "ALog": [f(2, seed=8)], "DtBias": [f(2, seed=9)],
        "Wa": [f(8, 2, seed=10)], "Wb": [f(8, 2, seed=11)],
        "ONorm": [pos(6)], "State": [f(2, 2, 4, 6, seed=12)],
        "Conv": [f(2, 3, 28, seed=13)]}
_GDN_ATTRS = {"n_head": 2, "key_dim": 4, "value_dim": 6}
spec("gdn_decode", {"X": [f(2, 1, 8)], **_GDN,
                    "Active": [ints(2, 1, hi=2, seed=3)]}, _GDN_ATTRS)
spec("gdn_prefill", {"X": [f(1, 6, 8)], **_GDN,
                     "SeqLen": [lens(4).reshape(1, 1)],
                     "Slot": [lens(1).reshape(1, 1)]},
     {**_GDN_ATTRS, "chunk": 3})
# the state-space mixer (ops/ssd.py): two slots, two heads of four
# channels, a state of eight, one group, a conv of four taps with bias
_SSD = {"WIn": [f(8, 34, seed=2)], "WOut": [f(8, 8, seed=3)],
        "ConvW": [f(4, 24, seed=4)], "ConvB": [f(1, 24, seed=5)],
        "ALog": [f(2, seed=6)], "DtBias": [f(2, seed=7)], "D": [pos(2)],
        "Norm": [pos(8)], "State": [f(2, 8, 8, seed=8)],
        "Conv": [f(2, 3, 24, seed=9)]}
_SSD_ATTRS = {"n_head": 2, "head_dim": 4, "d_state": 8, "n_groups": 1}
spec("ssd_decode", {"X": [f(2, 1, 8)], **_SSD,
                    "Active": [ints(2, 1, hi=2, seed=3)]}, _SSD_ATTRS)
spec("ssd_prefill", {"X": [f(1, 6, 8)], **_SSD,
                     "SeqLen": [lens(4).reshape(1, 1)],
                     "Slot": [lens(1).reshape(1, 1)]},
     {**_SSD_ATTRS, "chunk": 3})
# Mamba-1's selective scan (ops/s6.py): two slots, twelve channels, a
# state of four, a step rank of three, a conv of four taps with bias
_S6 = {"WIn": [f(8, 24, seed=2)], "WOut": [f(12, 8, seed=3)],
       "ConvW": [f(4, 12, seed=4)], "ConvB": [f(1, 12, seed=5)],
       "WX": [f(12, 11, seed=6)], "WDt": [f(3, 12, seed=7)],
       "DtNorm": [pos(3)], "BNorm": [pos(4)], "CNorm": [pos(4)],
       "DtBias": [f(12, seed=8)], "ALog": [f(48, seed=9)], "D": [pos(12)],
       "State": [f(2, 4, 12, seed=10)], "Conv": [f(2, 3, 12, seed=11)]}
spec("s6_decode", {"X": [f(2, 1, 8)], **_S6,
                   "Active": [ints(2, 1, hi=2, seed=3)]})
spec("s6_prefill", {"X": [f(1, 6, 8)], **_S6,
                    "SeqLen": [lens(4).reshape(1, 1)],
                    "Slot": [lens(1).reshape(1, 1)]}, {"chunk": 3})
# the gated short convolution (ops/shortconv.py): two slots, eight
# channels, three taps
_SHORTCONV = {"WIn": [f(8, 24, seed=2)], "ConvW": [f(3, 8, seed=3)],
              "WOut": [f(8, 8, seed=4)], "Conv": [f(2, 2, 8, seed=5)]}
spec("shortconv_decode", {"X": [f(2, 1, 8)], **_SHORTCONV,
                          "Active": [ints(2, 1, hi=2, seed=3)]})
spec("shortconv_prefill", {"X": [f(1, 6, 8)], **_SHORTCONV,
                           "SeqLen": [lens(4).reshape(1, 1)],
                           "Slot": [lens(1).reshape(1, 1)]})
spec("expert_ffn_held",
     {"X": [f(1, 4, 8)], "RouterW": [f(8, 6, seed=1)],
      "WGate": [f(2, 8, 5, seed=2)], "WUp": [f(2, 8, 5, seed=3)],
      "WDown": [f(2, 5, 8, seed=4)], "SGate": [f(8, 5, seed=5)],
      "SUp": [f(8, 5, seed=6)], "SDown": [f(5, 8, seed=7)],
      "SeqLen": [lens(3).reshape(1, 1)]},
     {"top_k": 2, "held_start": 1})
spec("swiglu_ffn", {"X": [f(1, 4, 8)], "WGate": [f(8, 5, seed=1)],
                    "WUp": [f(8, 5, seed=2)], "WDown": [f(5, 8, seed=3)]})
spec("router_bias_update",
     {"Bias": [f(1, 6)], "Load": [ints(6, hi=9, seed=2)],
      "LoadTotal": [ints(6, hi=9, seed=3)]}, {"gamma": 0.001})
# latent attention WITHOUT an indexer over whole sequences, the
# trainer's view (ops/mla.py: mla_full): two heads (nope 4, rope 4, v 4)
# over a latent of 8, two sequences of six tokens
spec("mla_full",
     {"X": [f(2, 6, 8)], "Wdq": [f(8, 6, seed=1)], "QNorm": [pos(6)],
      "Wuq": [f(6, 16, seed=2)], "Wdkv": [f(8, 12, seed=3)],
      "KvNorm": [pos(8)], "Wuk": [f(8, 8, seed=4)],
      "Wuv": [f(8, 8, seed=5)], "Wo": [f(8, 8, seed=6)]},
     {"n_head": 2, "kv_lora_rank": 8, "qk_nope_head_dim": 4,
      "qk_rope_head_dim": 4, "v_head_dim": 4, "rope_theta": 10000.0})
# latent attention with the DSA indexer (ops/mla.py): two heads (nope 4,
# rope 4, v 4) over a latent of 8, an indexer of two heads of 8 that
# keeps 4 rows; a pool of 6 pages of 2 rows, the latent row padded to
# 128 lanes
_MLA = {"Wdq": [f(8, 6, seed=2)], "QNorm": [pos(6)],
        "Wuq": [f(6, 16, seed=3)], "Wdkv": [f(8, 12, seed=4)],
        "KvNorm": [pos(8)], "Wuk": [f(8, 8, seed=5)],
        "Wuv": [f(8, 8, seed=6)], "Wo": [f(8, 8, seed=7)],
        "Wiq": [f(6, 16, seed=8)], "Wik": [f(8, 8, seed=9)],
        "IkScale": [pos(8)], "IkBias": [f(8, seed=10)],
        "Wiw": [f(8, 2, seed=11)], "PageC": [f(6, 2, 128, seed=12)],
        "PageI": [f(6, 2, 8, seed=13)]}
_MLA_ATTRS = {"n_head": 2, "kv_lora_rank": 8, "qk_nope_head_dim": 4,
              "qk_rope_head_dim": 4, "v_head_dim": 4, "index_n_heads": 2,
              "index_head_dim": 8, "index_topk": 4, "rope_theta": 1e4}
spec("mla_prefill_paged",
     {"X": [f(1, 6, 8)], **_MLA, "Rows": [np.arange(6).reshape(6, 1)]},
     _MLA_ATTRS)
spec("mla_decode_paged",
     {"X": [f(2, 1, 8)], **_MLA,
      "PageTable": [np.asarray([[0, 1, 2], [3, 4, 5]])],
      "Pos": [np.asarray([[5], [4]])], "SeqLen": [np.asarray([[3], [2]])],
      "GenStart": [np.asarray([[4], [4]])],
      "Active": [np.asarray([[1], [0]])],
      "Position": [np.asarray([[4], [2]])]}, _MLA_ATTRS)
spec("rms_norm", {"X": [f(2, 3)], "Scale": [pos(3)]})
spec("dense", {"X": [f(2, 3)], "W": [f(3, 4, seed=1)]},
     {"out_dtype": "float32"})
spec("hash_normal_random", {},
     {"shape": [2, 3], "dtype": "float32", "std": 0.5})
spec("batch_norm", {"X": [f(2, 3, 4, 4)], "Scale": [pos(3)],
                    "Bias": [f(3, seed=1)], "Mean": [f(3, seed=2)],
                    "Variance": [pos(3, seed=3)]}, {"is_test": False})
spec("conv2d", {"Input": [f(1, 3, 8, 8)], "Filter": [f(4, 3, 3, 3)]},
     {"strides": [1, 1], "paddings": [1, 1]})
spec("conv3d", {"Input": [f(1, 2, 4, 6, 6)],
                "Filter": [f(3, 2, 3, 3, 3)]},
     {"strides": [1, 1, 1], "paddings": [1, 1, 1]})
spec("conv2d_transpose", {"Input": [f(1, 3, 4, 4)],
                          "Filter": [f(3, 2, 3, 3)]},
     {"strides": [2, 2], "paddings": [0, 0]})
spec("depthwise_conv2d", {"Input": [f(1, 3, 8, 8)],
                          "Filter": [f(3, 1, 3, 3)]},
     {"strides": [1, 1], "paddings": [1, 1], "groups": 3})
spec("cross_entropy", {"X": [pos(3, 4)], "Label": [ints(3, 1, hi=4)]})
spec("dropout", X23, {"dropout_prob": 0.3})
spec("fused_linear_ce", {"X": [f(8, 8)], "W": [f(8, 16, seed=1)],
                         "Label": [ints(8, hi=16)]},
     {"label_smoothing": 0.1})
spec("group_norm", {"X": [f(2, 4, 4, 4)], "Scale": [pos(4)],
                    "Bias": [f(4, seed=1)]}, {"groups": 2})
spec("huber_loss", {"X": [f(3, 1)], "Y": [f(3, 1, seed=1)]},
     {"delta": 1.0})
spec("im2sequence", {"X": [f(1, 3, 8, 8)]},
     {"kernels": [2, 2], "strides": [2, 2], "paddings": [0, 0, 0, 0]})
spec("label_smooth", {"X": [pos(3, 4)]}, {"epsilon": 0.1})
spec("layer_norm", {"X": [f(2, 6)], "Scale": [pos(6)],
                    "Bias": [f(6, seed=1)]}, {"begin_norm_axis": 1})
spec("log_softmax", X23)
spec("lookup_table", {"W": [f(10, 4)], "Ids": [ints(3, 1, hi=10)]})
spec("lrn", {"X": [f(1, 4, 4, 4)]}, {"n": 3})
spec("pad", X23, {"paddings": [0, 1, 1, 0], "pad_value": 0.0})
spec("pool2d", {"X": [f(1, 2, 4, 4)]},
     {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
      "paddings": [0, 0]})
spec("pool3d", {"X": [f(1, 2, 4, 4, 4)]},
     {"pooling_type": "avg", "ksize": [2, 2, 2], "strides": [2, 2, 2],
      "paddings": [0, 0, 0]})
spec("sigmoid_cross_entropy_with_logits",
     {"X": [f(3, 4)], "Label": [pos(3, 4, seed=1)]})
spec("smooth_l1_loss", {"X": [f(3, 4)], "Y": [f(3, 4, seed=1)]})
spec("softmax", X23)
spec("softmax_with_cross_entropy",
     {"Logits": [f(3, 5)], "Label": [ints(3, 1, hi=5)]})
spec("square_error_cost", {"X": [f(3, 1)], "Y": [f(3, 1, seed=1)]})

# --- sequence / lod (padded [B, T, ...] + SeqLens redesign) ---------------
spec("sequence_concat", {"X": [f(B, T, D), f(B, 3, D, seed=1)],
                         "SeqLens": [lens(3, 4), lens(2, 3)]})
spec("sequence_conv", {"X": [f(B, T, D)], "Filter": [f(3 * D, 5)],
                       "SeqLens": [lens(3, 4)]},
     {"contextLength": 3, "contextStart": -1})
spec("sequence_enumerate", {"X": [ints(B, T, hi=9)],
                            "SeqLens": [lens(3, 4)]},
     {"win_size": 2, "pad_value": 0})
spec("sequence_erase", {"X": [ints(B, T, hi=5)], "SeqLens": [lens(3, 4)]},
     {"tokens": [1]})
spec("sequence_expand", {"X": [f(B, 1, D)], "Y": [f(B, T, D, seed=1)],
                         "SeqLensX": [lens(1, 1)],
                         "SeqLensY": [lens(3, 4)]})
spec("sequence_expand_as", {"X": [f(B, 1, D)], "Y": [f(B, T, D, seed=1)],
                            "SeqLens": [lens(3, 4)]})
spec("sequence_mask", {"X": [lens(2, 4)]}, {"maxlen": T})
spec("sequence_pad", {"X": [f(B, T, D)], "PadValue": [f(1, lo=0, hi=0)],
                      "SeqLens": [lens(3, 4)]}, {"padded_length": T + 1})
spec("sequence_pool", SEQ, {"pooltype": "SUM"})
spec("sequence_reshape", {"X": [f(B, T, D)], "SeqLens": [lens(2, 4)]},
     {"new_dim": D * 2})
spec("sequence_reverse", SEQ)
spec("sequence_slice", {"X": [f(B, T, D)], "Offset": [lens(0, 1)],
                        "Length": [lens(2, 2)], "SeqLens": [lens(3, 4)]})
spec("sequence_softmax", {"X": [f(B, T)], "SeqLens": [lens(3, 4)]})
spec("sequence_unpad", {"X": [f(B, T, D)], "Length": [lens(3, 4)]})
spec("sequence_scatter", {"X": [f(B, 6)], "Ids": [ints(B, 3, hi=6)],
                          "Updates": [f(B, 3, seed=1)],
                          "SeqLens": [lens(2, 3)]})
spec("edit_distance", {"Hyps": [ints(B, T, hi=5)],
                       "Refs": [ints(B, T, hi=5, seed=1)],
                       "HypsLens": [lens(3, 4)], "RefsLens": [lens(4, 3)]})
spec("lod_reset", {"X": [f(B, T, D)], "Y": [lens(2, 4)]})
spec("lod_rank_table", {"X": [f(B, T, D)], "SeqLens": [lens(3, 4)]})
spec("reorder_lod_tensor_by_rank",
     {"X": [f(B, T, D)], "RankTable": [lens(1, 0)]})
spec("split_lod_tensor", {"X": [f(4, 3)],
                          "Mask": [ints(4, 1, hi=2).astype(jnp.bool_)]})
spec("merge_lod_tensor",
     {"X": [f(4, 3)], "Mask": [ints(4, 1, hi=2).astype(jnp.bool_)],
      "InTrue": [f(4, 3, seed=1)], "InFalse": [f(4, 3, seed=2)]})

# --- fused / rnn -----------------------------------------------------------
spec("gru", {"Input": [f(B, T, 3 * D)], "Weight": [f(D, 3 * D)],
             "Bias": [f(1, 3 * D, seed=1)], "SeqLens": [lens(3, 4)]})
spec("lstm", {"Input": [f(B, T, 4 * D)], "Weight": [f(D, 4 * D)],
              "Bias": [f(1, 4 * D, seed=1)], "SeqLens": [lens(3, 4)]})
spec("lstmp", {"Input": [f(B, T, 4 * D)], "Weight": [f(4, 4 * D)],
               "ProjWeight": [f(D, 4)], "Bias": [f(1, 4 * D, seed=1)],
               "SeqLens": [lens(3, 4)]})
spec("dynamic_lstm", {"Input": [f(B, T, 4 * D)], "Weight": [f(D, 4 * D)],
                      "Bias": [f(1, 4 * D, seed=1)],
                      "SeqLens": [lens(3, 4)]})
spec("dynamic_gru", {"Input": [f(B, T, 3 * D)], "Weight": [f(D, 3 * D)],
                     "Bias": [f(1, 3 * D, seed=1)],
                     "SeqLens": [lens(3, 4)]})
spec("gru_unit", {"Input": [f(B, 3 * D)], "HiddenPrev": [f(B, D)],
                  "Weight": [f(D, 3 * D)], "Bias": [f(1, 3 * D, seed=1)]})
spec("lstm_unit", {"X": [f(B, 4 * D)], "C_prev": [f(B, D)]})
spec("cudnn_lstm", {"Input": [f(T, B, D)], "InitH": [f(1, B, D)],
                    "InitC": [f(1, B, D)],
                    "W": [f(4 * D * (2 * D + 2), seed=1)]},
     {"hidden_size": D, "is_bidirec": False})
spec("attention_lstm",
     {"X": [f(B, T, D)], "C0": [f(B, D, seed=1)],
      "AttentionWeight": [f(2 * D, 1)],
      "LSTMWeight": [f(2 * D, 4 * D, seed=2)],
      "LSTMBias": [f(1, 4 * D, seed=3)], "SeqLens": [lens(3, 4)]})
spec("fusion_gru", {"X": [f(B, T, D)], "WeightX": [f(D, 3 * D)],
                    "WeightH": [f(D, 3 * D, seed=1)],
                    "Bias": [f(1, 3 * D, seed=2)],
                    "SeqLens": [lens(3, 4)]})
spec("fusion_lstm", {"X": [f(B, T, D)], "WeightX": [f(D, 4 * D)],
                     "WeightH": [f(D, 4 * D, seed=1)],
                     "Bias": [f(1, 4 * D, seed=2)],
                     "SeqLens": [lens(3, 4)]})
spec("fused_embedding_fc_lstm",
     {"Ids": [ints(B, T, hi=10)], "Embeddings": [f(10, 4 * D)],
      "WeightH": [f(D, 4 * D, seed=1)], "Bias": [f(1, 4 * D, seed=2)],
      "SeqLens": [lens(3, 4)]})
spec("fused_embedding_seq_pool",
     {"W": [f(10, D)], "Ids": [ints(B, T, 1, hi=10)],
      "SeqLens": [lens(3, 4)]}, {"combiner": "sum"})
spec("fusion_seqconv_eltadd_relu",
     {"X": [f(B, T, D)], "Filter": [f(3 * D, 5)], "Bias": [f(1, 5)],
      "SeqLens": [lens(3, 4)]},
     {"contextLength": 3, "contextStart": -1})
spec("fusion_seqexpand_concat_fc",
     {"X": [f(B, T, D), f(B, D, seed=1)], "FCWeight": [f(2 * D, 5)],
      "SeqLens": [lens(3, 4)]})
spec("fusion_seqpool_concat",
     {"X": [f(B, T, D), f(B, T, D, seed=1)], "SeqLens": [lens(3, 4)]},
     {"pooltype": "SUM"})
spec("fusion_transpose_flatten_concat",
     {"X": [f(2, 3, 4), f(2, 3, 4, seed=1)]},
     {"trans_axis": [0, 2, 1], "flatten_axis": 1})
spec("fused_elemwise_activation", XY,
     {"functor_list": ["elementwise_add", "relu"]})
spec("conv2d_fusion", {"Input": [f(1, 3, 8, 8)],
                       "Filter": [f(4, 3, 3, 3)], "Bias": [f(4)]},
     {"strides": [1, 1], "paddings": [1, 1], "activation": "relu"})
spec("conv2d_inception_fusion",
     {"Input": [f(1, 4, 8, 8)],
      "Filter": [f(2, 4, 1, 1), f(2, 4, 3, 3), f(2, 4, 5, 5),
                 f(2, 4, 1, 1)]})

# --- image_ops -------------------------------------------------------------
spec("affine_channel", {"X": [f(1, 3, 4, 4)], "Scale": [pos(3)],
                        "Bias": [f(3, seed=1)]})
spec("affine_grid", {"Theta": [f(1, 2, 3)]}, {"output_shape": [1, 1, 4, 4]})
spec("bilinear_interp", {"X": [f(1, 3, 8, 8)]}, {"out_h": 4, "out_w": 4})
spec("nearest_interp", {"X": [f(1, 3, 8, 8)]}, {"out_h": 4, "out_w": 4})
spec("conv3d_transpose", {"Input": [f(1, 2, 3, 3, 3)],
                          "Filter": [f(2, 2, 2, 2, 2)]},
     {"strides": [2, 2, 2], "paddings": [0, 0, 0]})
spec("depthwise_conv2d_transpose", {"Input": [f(1, 3, 4, 4)],
                                    "Filter": [f(3, 1, 3, 3)]},
     {"strides": [2, 2], "paddings": [0, 0], "groups": 3})
spec("grid_sampler", {"X": [f(1, 2, 4, 4)], "Grid": [f(1, 4, 4, 2)]})
spec("max_pool2d_with_index", {"X": [f(1, 2, 4, 4)]},
     {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]})
spec("max_pool3d_with_index", {"X": [f(1, 2, 4, 4, 4)]},
     {"ksize": [2, 2, 2], "strides": [2, 2, 2], "paddings": [0, 0, 0]})
spec("psroi_pool", {"X": [f(1, 8, 6, 6)],
                    "ROIs": [jnp.asarray([[0.0, 0.0, 4.0, 4.0]])],
                    "RoisBatchId": [lens(0)]},
     {"output_channels": 2, "pooled_height": 2, "pooled_width": 2,
      "spatial_scale": 1.0})
spec("roi_align", {"X": [f(1, 2, 6, 6)],
                   "ROIs": [jnp.asarray([[0.0, 0.0, 4.0, 4.0]])],
                   "RoisBatchId": [lens(0)]},
     {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0})
spec("roi_pool", {"X": [f(1, 2, 6, 6)],
                  "ROIs": [jnp.asarray([[0.0, 0.0, 4.0, 4.0]])],
                  "RoisBatchId": [lens(0)]},
     {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0})
spec("roi_perspective_transform",
     {"X": [f(1, 2, 6, 6)],
      "ROIs": [jnp.asarray([[0.0, 0.0, 4.0, 0.0, 4.0, 4.0, 0.0, 4.0]])],
      "RoisBatchId": [lens(0)]},
     {"transformed_height": 2, "transformed_width": 2,
      "spatial_scale": 1.0})
spec("spp", {"X": [f(1, 2, 6, 6)]}, {"pyramid_height": 2})
spec("unpool", {"X": [f(1, 2, 2, 2)],
                "Indices": [ints(1, 2, 2, 2, hi=4, dtype=np.int32)]},
     {"unpooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
      "paddings": [0, 0]})

# --- detection / rpn -------------------------------------------------------
spec("anchor_generator", IMG,
     {"anchor_sizes": [32.0], "aspect_ratios": [1.0],
      "stride": [4.0, 4.0], "variances": [0.1, 0.1, 0.2, 0.2]})
spec("bipartite_match", {"DistMat": [pos(3, 4)]})
spec("box_coder", {"PriorBox": [pos(4, 4)],
                   "PriorBoxVar": [pos(4, 4, seed=1)],
                   "TargetBox": [pos(4, 4, seed=2)]},
     {"code_type": "encode_center_size"})
spec("density_prior_box", {"Input": [f(1, 2, 4, 4)],
                           "Image": [f(1, 3, 16, 16)]},
     {"densities": [2], "fixed_sizes": [4.0], "fixed_ratios": [1.0],
      "variances": [0.1, 0.1, 0.2, 0.2]})
spec("detection_map",
     {"DetectRes": [jnp.asarray([[[1.0, 0.9, 0.1, 0.1, 0.4, 0.4]]])],
      "Label": [jnp.asarray([[[1.0, 0.1, 0.1, 0.4, 0.4]]])]},
     {"class_num": 2, "background_label": 0})
spec("iou_similarity", {"X": [pos(3, 4)], "Y": [pos(2, 4, seed=1)]})
spec("mine_hard_examples",
     {"ClsLoss": [pos(1, 4)], "MatchIndices": [ints(1, 4, hi=2,
                                                    dtype=np.int32)],
      "LocLoss": [pos(1, 4, seed=1)], "MatchDist": [pos(1, 4, seed=2)]},
     {"neg_pos_ratio": 3.0, "mining_type": "max_negative"})
spec("multiclass_nms",
     {"BBoxes": [pos(1, 4, 4)], "Scores": [pos(1, 3, 4)]},
     {"background_label": 0, "score_threshold": 0.01, "nms_top_k": 4,
      "nms_threshold": 0.5, "keep_top_k": 4})
spec("polygon_box_transform", {"Input": [f(1, 4, 4, 4)]})
spec("prior_box", {"Input": [f(1, 2, 4, 4)], "Image": [f(1, 3, 16, 16)]},
     {"min_sizes": [4.0], "aspect_ratios": [1.0],
      "variances": [0.1, 0.1, 0.2, 0.2]})
spec("target_assign",
     {"X": [f(1, 3, 4)], "MatchIndices": [ints(1, 2, hi=3,
                                               dtype=np.int32)]},
     {"mismatch_value": 0.0})
spec("generate_proposals",
     {"Scores": [pos(1, 2, 4, 4)], "BboxDeltas": [f(1, 8, 4, 4)],
      "ImInfo": [jnp.asarray([[16.0, 16.0, 1.0]])],
      "Anchors": [pos(4, 4, 2, 4)], "Variances": [pos(4, 4, 2, 4,
                                                      seed=1)]},
     {"pre_nms_topN": 8, "post_nms_topN": 4, "nms_thresh": 0.5,
      "min_size": 0.5})
spec("rpn_target_assign",
     {"Anchor": [pos(8, 4)], "GtBoxes": [pos(2, 4, seed=1)]},
     {"rpn_batch_size_per_im": 4})
spec("yolov3_loss",
     {"X": [f(1, 18, 4, 4)], "GTBox": [pos(1, 2, 4)],
      "GTLabel": [ints(1, 2, hi=2, dtype=np.int32)]},
     {"anchors": [10, 13, 16, 30, 33, 23], "anchor_mask": [0, 1, 2],
      "class_num": 1, "ignore_thresh": 0.5, "downsample_ratio": 4})
spec("generate_proposal_labels",
     {"RpnRois": [pos(1, 4, 4)], "GtClasses": [ints(1, 2, hi=3,
                                                    dtype=np.int32)],
      "IsCrowd": [ints(1, 2, hi=1, dtype=np.int32)],
      "GtBoxes": [pos(1, 2, 4, seed=1)],
      "ImInfo": [jnp.asarray([[16.0, 16.0, 1.0]])]},
     {"batch_size_per_im": 4, "fg_fraction": 0.5, "fg_thresh": 0.2,
      "bg_thresh_hi": 0.5, "bg_thresh_lo": 0.0,
      "bbox_reg_weights": [0.1, 0.1, 0.2, 0.2], "class_nums": 3})

# --- loss / metric ---------------------------------------------------------
spec("cos_sim", {"X": [f(3, 4)], "Y": [f(3, 4, seed=1)]})
spec("crf_decoding", {"Emission": [f(B, T, 3)],
                      "Transition": [f(5, 3, seed=1)],
                      "SeqLens": [lens(3, 4)]})
spec("linear_chain_crf", {"Emission": [f(B, T, 3)],
                          "Transition": [f(5, 3, seed=1)],
                          "Label": [ints(B, T, hi=3)],
                          "SeqLens": [lens(3, 4)]})
spec("hierarchical_sigmoid",
     {"X": [f(3, 4)], "W": [f(5, 4, seed=1)], "Label": [ints(3, 1, hi=6)],
      "Bias": [f(5, seed=2)]}, {"num_classes": 6})
spec("nce", {"Input": [f(3, 4)], "Label": [ints(3, 1, hi=6)],
             "Weight": [f(6, 4, seed=1)]},
     {"num_total_classes": 6, "num_neg_samples": 2})
spec("accuracy", {"Out": [pos(3, 2)], "Indices": [ints(3, 2, hi=4)],
                  "Label": [ints(3, 1, hi=4)]})
spec("auc", {"Predict": [pos(3, 2)], "Label": [ints(3, 1, hi=2)],
             "StatPos": [jnp.zeros(201, jnp.int64)],
             "StatNeg": [jnp.zeros(201, jnp.int64)]},
     {"num_thresholds": 200})
spec("chunk_eval", {"Inference": [ints(B, T, hi=5)],
                    "Label": [ints(B, T, hi=5, seed=1)]},
     {"num_chunk_types": 2, "chunk_scheme": "IOB"})
spec("precision_recall",
     {"MaxProbs": [pos(3, 1)], "Indices": [ints(3, 1, hi=2)],
      "Labels": [ints(3, 1, hi=2, seed=1)],
      "StatesInfo": [jnp.zeros((2, 4), jnp.float32)]},
     {"class_number": 2})
spec("mean_iou", {"Predictions": [ints(6, hi=3, dtype=np.int32)],
                  "Labels": [ints(6, hi=3, seed=1, dtype=np.int32)]},
     {"num_classes": 3})

# --- misc_ops --------------------------------------------------------------
spec("add_position_encoding", {"X": [f(B, T, D)]},
     {"alpha": 1.0, "beta": 1.0})
spec("argsort", X23, {"axis": 1})
spec("bilinear_tensor_product",
     {"X": [f(3, 4)], "Y": [f(3, 5, seed=1)], "Weight": [f(2, 4, 5,
                                                           seed=2)]})
spec("bpr_loss", {"X": [pos(3, 4)], "Label": [ints(3, 1, hi=4)]})
spec("conv_shift", {"X": [f(3, 8)], "Y": [f(3, 3, seed=1)]})
spec("crop", {"X": [f(4, 5)]}, {"offsets": [1, 1], "shape": [2, 3]})
spec("data_norm", {"X": [f(3, 4)],
                   "BatchSize": [pos(4)], "BatchSum": [f(4, seed=1)],
                   "BatchSquareSum": [pos(4, seed=2)]})
spec("fc", {"Input": [f(3, 4)], "W": [f(4, 5, seed=1)],
            "Bias": [f(5, seed=2)]})
spec("fill", {}, {"shape": [2, 2], "dtype": "float32",
                  "value": [1.0, 2.0, 3.0, 4.0]})
spec("flatten", {"X": [f(2, 3, 4)]}, {"axis": 1})
spec("flatten2", {"X": [f(2, 3, 4)]}, {"axis": 1})
spec("hinge_loss", {"Logits": [f(3, 1)],
                    "Labels": [ints(3, 1, hi=2).astype(jnp.float32)]})
spec("is_empty", X23)
spec("l1_norm", X23)
spec("log_loss", {"Predicted": [pos(3, 1)],
                  "Labels": [ints(3, 1, hi=2).astype(jnp.float32)]},
     {"epsilon": 1e-4})
spec("margin_rank_loss", {"X1": [f(3, 1)], "X2": [f(3, 1, seed=1)],
                          "Label": [jnp.ones((3, 1), jnp.float32)]},
     {"margin": 0.1})
spec("maxout", {"X": [f(1, 4, 3, 3)]}, {"groups": 2})
spec("minus", {"X": [f(2, 3)], "Y": [f(2, 3, seed=1)]})
spec("modified_huber_loss", {"X": [f(3, 1)],
                             "Y": [jnp.ones((3, 1), jnp.float32)]})
spec("multiplex", {"Ids": [ints(3, 1, hi=2, dtype=np.int32)],
                   "X": [f(3, 4), f(3, 4, seed=1)]})
spec("pad2d", {"X": [f(1, 2, 3, 3)]},
     {"paddings": [1, 1, 1, 1], "mode": "constant"})
spec("pad_constant_like", {"X": [f(4, 5)], "Y": [f(2, 3, seed=1)]},
     {"pad_value": 0.0})
spec("random_crop", {"X": [f(1, 3, 8, 8)], "Seed": [lens(7)]},
     {"shape": [3, 4, 4]})
spec("rank_loss", {"Label": [jnp.ones((3, 1), jnp.float32)],
                   "Left": [f(3, 1)], "Right": [f(3, 1, seed=1)]})
spec("reverse", X23, {"axis": [1]})
spec("row_conv", {"X": [f(B, T, D)], "Filter": [f(3, D, seed=1)],
                  "SeqLens": [lens(3, 4)]})
spec("sampling_id", {"X": [pos(3, 4)]})
spec("selu", X23)
spec("similarity_focus", {"X": [f(1, 2, 3, 3)]},
     {"axis": 1, "indexes": [0]})
spec("space_to_depth", {"X": [f(1, 2, 4, 4)]}, {"blocksize": 2})
spec("squared_l2_distance", {"X": [f(3, 4)], "Y": [f(3, 4, seed=1)]})
spec("teacher_student_sigmoid_loss",
     {"X": [f(3, 1)], "Label": [pos(3, 1, seed=1)]})
spec("unstack", {"X": [f(3, 4)]}, {"axis": 0, "num": 3})

# --- optimizer_ops ---------------------------------------------------------
spec("sgd", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
             "LearningRate": [pos(1)]})
spec("momentum", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                  "Velocity": [f(3, 4, seed=2)],
                  "LearningRate": [pos(1)]}, {"mu": 0.9})
spec("adam", _adam_like())
spec("adamax", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                "Moment": [f(3, 4, seed=2)],
                "InfNorm": [pos(3, 4, seed=3)],
                "LearningRate": [pos(1)], "Beta1Pow": [pos(1)]})
spec("adagrad", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                 "Moment": [pos(3, 4, seed=2)], "LearningRate": [pos(1)]})
spec("adadelta", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                  "AvgSquaredGrad": [pos(3, 4, seed=2)],
                  "AvgSquaredUpdate": [pos(3, 4, seed=3)]})
spec("decayed_adagrad", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                         "Moment": [pos(3, 4, seed=2)],
                         "LearningRate": [pos(1)]})
spec("ftrl", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
              "SquaredAccumulator": [pos(3, 4, seed=2)],
              "LinearAccumulator": [f(3, 4, seed=3)],
              "LearningRate": [pos(1)]})
spec("rmsprop", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                 "MeanSquare": [pos(3, 4, seed=2)],
                 "Moment": [f(3, 4, seed=3)], "LearningRate": [pos(1)],
                 "MeanGrad": [f(3, 4, seed=4)]})
spec("proximal_gd", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                     "LearningRate": [pos(1)]})
spec("proximal_adagrad", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                          "Moment": [pos(3, 4, seed=2)],
                          "LearningRate": [pos(1)]})
spec("lars_momentum", {"Param": [f(3, 4)], "Grad": [f(3, 4, seed=1)],
                       "Velocity": [f(3, 4, seed=2)],
                       "LearningRate": [pos(1)]}, {"mu": 0.9})
spec("clip_by_norm", X23, {"max_norm": 1.0})
spec("global_norm_clip_apply",
     {"X": [f(2, 3)], "GlobalNorm": [pos(1)]}, {"max_norm": 1.0})
spec("ema_accumulate", {"Param": [f(3, 4)], "Ema": [f(3, 4, seed=1)]},
     {"decay": 0.99})
spec("average_accumulates",
     {"param": [f(4)], "in_sum_1": [jnp.zeros(4)],
      "in_sum_2": [jnp.zeros(4)], "in_sum_3": [jnp.zeros(4)],
      "in_num_accumulates": [jnp.zeros(1, jnp.int64)],
      "in_old_num_accumulates": [jnp.zeros(1, jnp.int64)],
      "in_num_updates": [jnp.zeros(1, jnp.int64)]},
     {"average_window": 2.0, "max_average_window": 10, "min_average_window": 1})

# --- quant ----------------------------------------------------------------
spec("fake_quantize_abs_max", X23, {"bit_length": 8})
spec("fake_quantize_range_abs_max",
     {"X": [f(2, 3)], "InScale": [pos(1)], "Iter": [jnp.zeros(1,
                                                              jnp.int64)]},
     {"bit_length": 8, "window_size": 10})
spec("fake_dequantize_max_abs", {"X": [f(2, 3)], "Scale": [pos(1)]},
     {"max_range": 127.0})
spec("quantize", {"Input": [f(2, 3)]}, {"scale": 127.0})
spec("dequantize", {"Input": [ints(2, 3, hi=100, dtype=np.int32)
                              .astype(jnp.int8)]}, {"scale": 127.0})
spec("fake_init", {}, {"shape": [2, 3], "dtype": "float32"})

# --- ctc ------------------------------------------------------------------
spec("ctc_align", {"Input": [ints(B, T, hi=4, dtype=np.int32)],
                   "SeqLens": [lens(3, 4)]},
     {"blank": 0, "merge_repeated": True})
spec("warpctc", {"Logits": [f(B, T, 5)],
                 "Label": [ints(B, 2, hi=4, dtype=np.int32)],
                 "LogitsLens": [lens(4, 4)], "LabelLens": [lens(2, 2)]},
     {"blank": 0})

# --- infra / distributed ---------------------------------------------------
spec("split_ids", {"Ids": [ints(6, 1, hi=20)]}, {"n_parts": 2})
spec("merge_ids",
     {"Ids": [ints(4, 1, hi=20)],
      "X": [f(4, 3)], "Rows": [ints(4, hi=20)]})
spec("split_selected_rows", {"X": [f(4, 3)], "Rows": [ints(4, hi=8)]},
     {"height_sections": [4, 4]})
spec("merge_selected_rows", {"X": [f(4, 3)], "Rows": [ints(4, hi=4)]})
spec("split_byref", {"X": [f(4, 6)]}, {"num": 2})
spec("get_tensor_from_selected_rows",
     {"X": [f(4, 3)], "Rows": [ints(4, hi=8)]}, {"height": 8})
spec("delete_var", X23)

# --- tensor arrays / rnn memory / host IO ---------------------------------
_ARR = {"Array": [f(3, 2, 2)]}
spec("array_write", {"Array": [f(3, 2, 2)], "X": [f(2, 2, seed=1)],
                     "I": [lens(1)]})
spec("array_read", {"Array": [f(3, 2, 2)], "I": [lens(1)]})
spec("array_length", dict(_ARR))
spec("write_to_array", {"Array": [f(3, 2, 2)], "X": [f(2, 2, seed=1)],
                        "I": [lens(1)]})
spec("read_from_array", {"Array": [f(3, 2, 2)], "I": [lens(1)]})
spec("lod_array_length", dict(_ARR))
spec("max_sequence_len", {"RankTable": [lens(3, 4)]})
spec("shrink_rnn_memory", {"X": [f(2, 3)], "I": [lens(1)],
                           "RankTableLens": [lens(3, 1)]})
spec("rnn_memory_helper", X23)
spec("get_places", {})
spec("print", {"In": [f(2, 2)]}, {"message": "smoke: "})
spec("hash", {"X": [ints(4, 2, hi=100)]}, {"mod_by": 1000, "num_hash": 2})
spec("adaptive_pool2d", {"X": [f(1, 2, 6, 6)]},
     {"pooled_size": [3, 3], "pooling_type": "avg"})
spec("adaptive_pool3d", {"X": [f(1, 2, 4, 6, 6)]},
     {"pooled_size": [2, 3, 3], "pooling_type": "max"})
spec("has_inf", X23)
spec("has_nan", X23)
spec("uniform_random_batch_size_like", {"Input": [f(3, 2)]}, {"shape": [0, 5]})
spec("gaussian_random_batch_size_like", {"Input": [f(3, 2)]}, {"shape": [0, 5]})
spec("py_func", {"X": [f(2, 3)]},
     {"func": lambda a: np.asarray(a) * 2.0,
      "out_shapes": [[2, 3]], "out_dtypes": ["float32"]})
spec("lookup_sparse_table", {"W": [f(10, 4)], "Ids": [ints(3, 1, hi=10)]})

import tempfile as _tempfile
_IO_DIR = _tempfile.mkdtemp(prefix="paddle_tpu_smoke_")
np.save(os.path.join(_IO_DIR, "load_src.npy"),
        np.ones((2, 3), np.float32))
np.savez(os.path.join(_IO_DIR, "loadc_src.npz"),
         v0=np.ones((2,), np.float32), v1=np.zeros((3,), np.float32))
spec("save", X23, {"file_path": os.path.join(_IO_DIR, "save_dst.npy")})
spec("save_combine", {"X": [f(2), f(3, seed=1)]},
     {"file_path": os.path.join(_IO_DIR, "savec_dst")})
spec("load", {}, {"file_path": os.path.join(_IO_DIR, "load_src.npy")})
spec("load_combine", {},
     {"file_path": os.path.join(_IO_DIR, "loadc_src.npz"),
      "var_names": ["v0", "v1"]})

# documented no-output ops (delete_var: buffer lifetime is XLA liveness)
EMPTY_OUTPUT_OK = {"delete_var"}


# ---------------------------------------------------------------------------

def _ctx():
    return EmitContext(base_key=jax.random.key(0),
                       step_base_key=jax.random.key(1), op_index=0)


def test_redirect_set_is_exactly_documented():
    actual = {name for name, s in OPS.items()
              if getattr(s.emit, "__redirect__", False)}
    assert actual == REDIRECTS


def test_every_op_is_accounted_for():
    """SPECS ∪ REDIRECTS ∪ CONTEXT_OPS covers the registry exactly."""
    all_ops = set(OPS)
    buckets = set(SPECS) | REDIRECTS | set(CONTEXT_OPS)
    unaccounted = sorted(all_ops - buckets)
    assert not unaccounted, f"ops missing from the sweep: {unaccounted}"
    phantom = sorted(set(SPECS) - all_ops)
    assert not phantom, f"specs for unregistered ops: {phantom}"
    overlap = (set(SPECS) & REDIRECTS) | (set(SPECS) & set(CONTEXT_OPS))
    assert not overlap, f"ops in two buckets: {sorted(overlap)}"


def test_context_ops_have_covering_tests():
    here = os.path.dirname(os.path.abspath(__file__))
    for op, target in CONTEXT_OPS.items():
        fname, needle = (target if isinstance(target, tuple)
                         else (target, op.strip("_")))
        path = os.path.join(here, fname)
        assert os.path.exists(path), f"{op}: covering test {fname} missing"
        text = open(path).read()
        assert re.search(re.escape(needle), text), \
            f"{op}: {fname} does not mention {needle!r}"


@pytest.mark.parametrize("op_name", sorted(SPECS))
def test_op_executes(op_name):
    ins, attrs = SPECS[op_name]
    outs = OPS[op_name].emit(_ctx(), dict(ins), dict(attrs))
    assert isinstance(outs, dict), f"{op_name}: no output dict"
    if op_name in EMPTY_OUTPUT_OK:
        return
    arrays = [v for vals in outs.values() if vals is not None
              for v in vals if v is not None]
    assert arrays, f"{op_name}: no output arrays"
    for v in arrays:
        assert hasattr(v, "shape"), f"{op_name}: non-array output {v!r}"
        a = np.asarray(v)
        if np.issubdtype(a.dtype, np.floating):
            assert np.isfinite(a).all(), f"{op_name}: non-finite output"


@pytest.mark.parametrize("op_name", sorted(REDIRECTS))
def test_redirect_raises_with_pointer(op_name):
    with pytest.raises(NotImplementedError, match="capability"):
        OPS[op_name].emit(_ctx(), {}, {})
