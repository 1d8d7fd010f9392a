"""Benchmark-driven pass pipeline over the verified IR.

A compiler-layer subsystem with three pieces (ROADMAP: "Benchmark-driven
pass pipeline over the verified IR"; TVM arXiv:1802.04799 and the XLA
fusion study arXiv:2301.13062 both argue program-level rewrites should
be *selected by measurement*, not heuristics):

1. **TPU-semantic rewrite passes** (:mod:`.fusion`, :mod:`.layout`)
   registered into the existing ``fluid/ir_pass.py`` registry — every
   pass either ``grad_aware`` (safe on post-minimize programs, merges
   the member ops' ``__vjp__`` backward) or ``inference_only``
   (numeric folds over trained statistics);
2. **a persistent autotuning cache** (:mod:`.autotune`) — the
   committed-table discipline ``tools/flash_autotune.py`` proved on one
   kernel, generalized: winners measured offline by ``tools/
   autotune.py``, committed to a versioned JSON table, looked up at
   build time with ZERO measurement in CI paths;
3. **observability** — pass-application counters, per-pass duration
   histograms, and cache hit/miss counters, preregistered in the
   exporter catalog; ``bench.py`` records which passes fired per row.

:func:`apply_pipeline` is the one driver: select passes (explicit list,
committed per-model winner, or the defaults), apply them over the
global block, then RE-VERIFY the rewritten program with
``paddle_tpu.analysis`` — a pass bug surfaces as a named diagnostic at
build time, not as silently wrong training.

Registration is lazy (:func:`register_all`) so importing the leaf
:mod:`.autotune` module (e.g. from the Pallas kernels) never drags the
fluid stack in.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from paddle_tpu.passes import autotune  # noqa: F401  (leaf module)

# grad-aware passes, applicable to training programs in this order
TRAIN_PIPELINE = ("layout_assignment_pass", "conv_block_fuse_pass")
# inference programs additionally fold trained statistics: region
# fusion FIRST (it absorbs the conv's separate bias add into
# conv2d_fusion), then the BN fold (which handles conv2d_fusion heads
# and absorbs the trailing activation), then layout canonicalization
INFER_PIPELINE = ("conv_block_fuse_pass", "conv_bn_fold_pass",
                  "layout_assignment_pass")

_registered = False


def register_all():
    """Idempotently import the pass modules so their ``register_pass``
    decorators run; returns the registered TPU pass names."""
    global _registered
    if not _registered:
        from paddle_tpu.passes import fusion, layout  # noqa: F401
        _registered = True
    return list(TRAIN_PIPELINE) + ["conv_bn_fold_pass"]


def pin_op_indices(block) -> None:
    """Stamp every op with its current index (`__op_index__`) before a
    pass pipeline mutates the block. The lowering salts per-op rng by
    this pinned index when present (core/lowering.py emit_op_seq), so
    removing/fusing ops does NOT shift every later dropout's mask — the
    rewritten program draws the identical random stream, and pass
    parity is exact even on models with dropout. Idempotent (setdefault:
    a second pipeline run keeps the original pins)."""
    for i, op in enumerate(block.ops):
        op.attrs.setdefault("__op_index__", i)


def run_pass(p, name: str, block, scope=None) -> int:
    """Apply one instantiated pass to a block, the way every
    application path does (BuildStrategy and apply_pipeline). Returns
    the number of ops removed/replaced."""
    from paddle_tpu.fluid import ir_pass as irp
    if hasattr(p, "scope"):
        p.scope = scope
    n_before = len(block.ops)
    p(irp.Graph(block))
    return max(n_before - len(block.ops), 0)


def pipeline_for(program=None, is_test: Optional[bool] = None,
                 model: Optional[str] = None,
                 batch_size: Optional[int] = None) -> List[str]:
    """Pass selection, measurement-first: when a committed
    ``pass_pipeline`` winner exists for (model, bs bucket), use it;
    otherwise the static default for the program kind. The committed
    entry is itself the product of a ``tools/autotune.py --kind
    pass_pipeline`` A/B run — pass on/off is a tuned variant, exactly
    like a kernel block size."""
    if model is not None:
        entry = autotune.lookup("pass_pipeline", {
            "model": model,
            "bs": autotune.bucket_pow2(batch_size or 1)})
        if entry and isinstance(entry.get("passes"), list):
            return list(entry["passes"])
    if is_test is None and program is not None:
        is_test = bool(getattr(program, "_is_test", False))
    return list(INFER_PIPELINE if is_test else TRAIN_PIPELINE)


def apply_pipeline(program, scope=None, names: Optional[Sequence[str]] = None,
                   is_test: Optional[bool] = None,
                   model: Optional[str] = None,
                   batch_size: Optional[int] = None,
                   verify: bool = True,
                   feed_names=None, fetch_names=None) -> List[str]:
    """Apply the selected passes to ``program``'s global block and
    re-verify the result. Returns the names actually applied (a pass
    that is not grad-aware is SKIPPED on a differentiated program, with
    a warning — same contract as ``BuildStrategy``).

    ``verify=True`` re-runs the build-time program verifier post-pass
    and raises ``ProgramVerificationError`` on any ERROR diagnostic —
    the "every rewritten program re-verified" guarantee."""
    register_all()
    from paddle_tpu.fluid import ir_pass as irp

    if names is None:
        names = pipeline_for(program, is_test=is_test, model=model,
                             batch_size=batch_size)
    block = program.desc.global_block
    pin_op_indices(block)
    has_vjp = any(op.type == "__vjp__" for op in block.ops)
    applied: List[str] = []
    for name in names:
        p = irp.get_pass(name)
        if has_vjp and not getattr(p, "grad_aware", False):
            import warnings
            warnings.warn(
                f"pass pipeline: {name!r} is not grad-aware and the "
                f"program has backward ops — skipped.", stacklevel=2)
            continue
        if getattr(p, "inference_only", False) and scope is None:
            # statistics folds need materialized params; silently
            # correct to skip (the composed form stays)
            continue
        run_pass(p, name, block, scope=scope)
        applied.append(name)
    if applied:
        program.desc.bump_version()
        if verify:
            from paddle_tpu import analysis
            analysis.verify_program(program, feed_names=feed_names,
                                    fetch_names=fetch_names,
                                    is_test=bool(is_test))
    return applied
