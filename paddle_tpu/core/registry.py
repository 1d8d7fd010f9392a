"""Operator registry: the TPU-native replacement for the reference's kernel
registry + dispatch machinery (reference: paddle/fluid/framework/op_registry.h:197
REGISTER_OPERATOR, operator.cc:912 OperatorWithKernel::RunImpl).

Where the reference registers per-(place, dtype, layout) kernel functors and
dispatches at every step, we register one *emitter* per op: a pure function
that receives traced JAX values and returns traced JAX values. The whole
block's emitters are traced once and fused/compiled by XLA — there is no
per-op dispatch at run time, and dtype/layout specialization is XLA's job.

Emitter signature::

    def emit(ctx: EmitContext, ins: Dict[slot, List[Array]], attrs: Dict) \
            -> Dict[slot, List[Array]]

following the reference's multi-slot input/output convention
(e.g. ins["X"][0], returns {"Out": [y]}).

Grad ops are not registered per-op: reverse-mode rules come from `jax.vjp`
over the forward emitter (see paddle_tpu.core.backward), replacing the
reference's hand-written GradOpDescMaker classes
(reference: framework/grad_op_desc_maker.h).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax


@dataclass
class EmitContext:
    """Per-op emission context.

    Two rng streams, both deterministic given (program seed, op index) so a
    re-emission of the same op inside a vjp recompute sees identical
    randomness (the functional replacement for the reference's per-op `seed`
    attrs, e.g. dropout_op.cc):

    - key():      program-level — initializers; re-running the startup
                  program reproduces the same parameters.
    - step_key(): per-execution — dropout/sampling vary across steps.
    """

    base_key: Any              # key(program.random_seed)
    step_base_key: Any = None  # fold_in(base_key, step_seed)
    op_index: int = 0
    is_test: bool = False
    # set during multi-device lowering: the DistributeConfig (mesh + dp/tp/
    # sp axes) for ops that partition themselves, e.g. ring attention over
    # the sp axis. mesh/data_axis are views into it — single source of
    # truth, so every context constructor (lowering, grad re-trace, shape
    # inference) only has to thread one field.
    dist: Any = None

    @property
    def mesh(self):
        return getattr(self.dist, "mesh", None)

    @property
    def data_axis(self) -> Optional[str]:
        return getattr(self.dist, "data_axis", None)
    # the enclosing ProgramDesc — control-flow emitters (while/cond/scan)
    # recursively lower their sub-blocks through this handle
    # (reference: sub-blocks interpreted with child scopes, while_op.cc:64)
    program: Any = None
    # the OpDesc being emitted (set by the lowering loop; None for direct
    # emitter calls) — lets emitters read their own var NAMES, e.g. the
    # sparse-apply telemetry site needs the Param name
    op: Any = None
    # shared by the ops of one lowered sequence (lowering.emit_op_seq): a
    # recomputed forward op emitted under its backward's jax.vjp leaves
    # the transpose here for its `__vjp__` op (ops/grad_ops.py)
    linked: Any = None

    def key(self, salt: int = 0):
        return jax.random.fold_in(
            jax.random.fold_in(self.base_key, self.op_index), salt)

    def step_key(self, salt: int = 0):
        base = self.step_base_key if self.step_base_key is not None else self.base_key
        return jax.random.fold_in(
            jax.random.fold_in(base, self.op_index), salt)


@dataclass
class OpSpec:
    type: str
    emit: Callable
    # ops excluded from autodiff (optimizer updates, metrics, rng state...)
    no_grad: bool = False
    # flat input indices (slot order) that can never carry gradient
    # (integer ids, labels); autodiff skips them without tracing
    nondiff_inputs: tuple = ()
    # docstring-level reference citation
    ref: str = ""
    # per-slot recurrent state of a serving op: (the kind of state, the
    # output slots that carry it) — what the slot engine finds a model's
    # fixed-size state by (serving/engine.py:_discover_state), whatever
    # mixer keeps it and whatever its variables are called
    slot_state: Optional[tuple] = None


OPS: Dict[str, OpSpec] = {}


def register_op(op_type: str, *, no_grad: bool = False, ref: str = "",
                slot_state: Optional[tuple] = None):
    """Register an emitter for `op_type` (capability parity with
    REGISTER_OPERATOR / REGISTER_OP_CUDA_KERNEL, op_registry.h:197,237)."""

    def deco(fn: Callable) -> Callable:
        if op_type in OPS:
            raise ValueError(f"op {op_type!r} registered twice")
        OPS[op_type] = OpSpec(type=op_type, emit=fn, no_grad=no_grad, ref=ref,
                              slot_state=slot_state)
        return fn

    return deco


def get_op(op_type: str) -> OpSpec:
    spec = OPS.get(op_type)
    if spec is None:
        raise KeyError(
            f"no emitter registered for op {op_type!r}; registered: "
            f"{sorted(OPS)[:40]}..."
        )
    return spec


def has_op(op_type: str) -> bool:
    return op_type in OPS


def slot_state_vars(block) -> Dict[str, Dict[str, List[str]]]:
    """{kind of state: {output slot: its variables' names, sorted}} of
    the per-slot recurrent state that the ops of ``block`` (a BlockDesc)
    carry: the outputs an op's registration declares as ``slot_state``
    (``StateOut``: the recurrence's own state; ``ConvOut``: the conv
    window's last rows)."""
    found: Dict[str, Dict[str, set]] = {}
    for op in block.ops:
        spec = OPS.get(op.type)
        if spec is not None and spec.slot_state:
            kind, slots = spec.slot_state
            for slot in slots:
                found.setdefault(kind, {}).setdefault(slot, set()).update(
                    op.output(slot))
    return {kind: {slot: sorted(names) for slot, names in slots.items()}
            for kind, slots in sorted(found.items())}


# -- helpers for emitters ---------------------------------------------------

def first(ins: Dict[str, List[Any]], slot: str, default=None):
    vals = ins.get(slot) or []
    return vals[0] if vals else default


def single(x) -> Dict[str, List[Any]]:
    return {"Out": [x]}
