"""Recompute / gradient-checkpointing rewrite (TPU-first addition; the
reference era's closest capability is the gradient-accumulation
multi_batch_merge_pass — ir/multi_batch_merge_pass.cc — which trades
throughput for memory at the batch level. Here the trade is per op:
`jax.checkpoint` on tagged ops makes the backward re-run their forward
instead of keeping their internals as residuals, so e.g. attention
probability matrices [B, H, T, T], projected q / k / v or wide FFN
activations never persist between the forward and backward passes — the
standard long-context memory lever on TPU).

What a recomputed op keeps: its INPUTS, and the values a kernel inside
it has NAMED with one of ``KEPT`` (`jax.ad_checkpoint.checkpoint_name`
in the kernel's forward rule) — values that cost a kernel to remake and
no more than the op's inputs to hold. Today that is flash attention's
output and log-sum-exp: the two residuals its backward kernels read
beside q, k, v. Everything else is made again. The rule is the
checkpoint's policy (`save_only_these_names(*KEPT)`), the same for
every tagged op: an op in which nothing is named keeps its inputs
alone and lowers as under a bare `jax.checkpoint`.
``paddle_recompute_kept_values_total{op}`` / ``_bytes_total{op}`` count
what each lowering kept (ops/grad_ops.py).

Attr-only, like contrib.mixed_precision / contrib.layout: tagging sets
`__remat__` on forward ops AND their `__vjp__` snapshots. The lowering
loop emits such a pair from ONE trace (ops/grad_ops.py
``emit_with_backward``: the forward op under its backward's
``jax.vjp(jax.checkpoint(...))``), so the kept values are the forward
op's own — a re-traced copy of a Mosaic call would not merge with the
forward op's, and the kernel would run twice.
"""

from __future__ import annotations

# memory-heavy ops whose internals dominate activation footprints
# ("attention" is the fused scaled_dot_product_attention op)
DEFAULT_REMAT_OPS = ("attention", "softmax", "matmul", "fc", "mul")

# what a recomputed op KEEPS beside its inputs: the values a kernel's
# forward rule has named (jax.ad_checkpoint.checkpoint_name) with one of
# these. The rule for a new name: the value is no larger than the op's
# own inputs (which recomputation keeps anyway) and remaking it costs a
# kernel — flash attention's output and log-sum-exp, the two residuals
# its backward kernels read beside q, k, v
# (ops/pallas/flash_attention.py:_named).
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"
KEPT = (FLASH_OUT, FLASH_LSE)


def rewrite_program_recompute(program=None, op_types=DEFAULT_REMAT_OPS):
    """Tag `op_types` for backward rematerialization. Apply after
    minimize() (the `__vjp__` snapshots must exist) or before (forward
    tags propagate when backward is appended later). Returns #ops
    tagged."""
    from paddle_tpu.fluid import framework
    program = program or framework.default_main_program()
    n = 0
    for block in program.desc.blocks:
        for op in block.ops:
            if op.type in op_types:
                op.attrs["__remat__"] = True
                n += 1
            elif op.type == "__vjp__":
                fwd = op.attrs.get("fwd_op", {})
                if fwd.get("type") in op_types:
                    fwd.setdefault("attrs", {})["__remat__"] = True
                    n += 1
    program.desc.bump_version()
    return n
