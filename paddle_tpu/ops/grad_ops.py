"""The universal gradient op.

The reference synthesizes one hand-written grad op per forward op via
GradOpDescMaker classes (reference: framework/grad_op_desc_maker.h, invoked
from python backward.py:394 through core.get_grad_op_desc). TPU-native
re-design: a single `__vjp__` op whose emitter re-traces the forward
emitter under `jax.vjp` — every op's backward rule is derived automatically
and XLA's CSE merges the re-traced forward with the original, so there is no
duplicate compute in the compiled executable. (A Mosaic call is the
exception: its serialized body carries the trace's call stack and two
traces never compare equal — a kernel's backward takes (inputs,
cotangent) alone so that the re-traced forward call is dead code, as
ops/attention_block.py does, or the op is tagged for recomputation.)

An op tagged `__remat__` (contrib/recompute.py) is not re-traced: the
lowering loop emits the forward op itself under the backward's
``jax.vjp(jax.checkpoint(..., policy=KEPT))`` (``emit_with_backward``)
and the `__vjp__` op calls the transpose that trace left. The backward
keeps the op's inputs and the values a kernel has named, and those are
the forward op's own.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import ir
from paddle_tpu.core import selected_rows as sr
from paddle_tpu.core.registry import EmitContext, get_op, register_op
from paddle_tpu.observability import metrics as _metrics


def _slot_layout(slots: Dict[str, List[str]]) -> List[Tuple[str, int]]:
    return [(slot, len(names)) for slot, names in sorted(slots.items())]


# ---------------------------------------------------------------------------
# row-sparse embedding VJP fast path (core/selected_rows.py)
# ---------------------------------------------------------------------------

# fwd op types whose W-gradient is a pure row gather transpose: instead of
# scattering B*T rows into a dense [V, D] zeros (the reference's
# is_sparse=False lookup_table_grad kernel), emit the (rows, values) pair
# directly (the is_sparse=True SelectedRows kernel, lookup_table_op.cc:85).
# lookup_sparse_table delegates to the lookup_table emitter with the same
# slots (infra_ops.py), so it shares the fast path.
SPARSE_EMB_OPS = ("lookup_table", "lookup_sparse_table",
                  "fused_embedding_seq_pool")


def og_matches_single(og_mask, pos) -> bool:
    """True when exactly one output cotangent is provided and it is the
    flat output at `pos` (the embedding ops' single 'Out')."""
    return bool(og_mask[pos]) and sum(1 for m in og_mask if m) == 1


def _sparse_embedding_vjp(fwd_op, ins_by_slot, grads_by_slot):
    """RowSparseGrad of W for the embedding-family ops, or None when the
    pattern doesn't apply (caller falls back to the generic re-trace).

    ins_by_slot: {slot: [vals]} forward inputs; grads_by_slot: {slot:
    cotangent or None} for the forward outputs. Returns the W gradient
    only — the remaining inputs (Ids, SeqLens) are integer-typed and never
    differentiable."""
    w = (ins_by_slot.get("W") or [None])[0]
    ids = (ins_by_slot.get("Ids") or [None])[0]
    g = grads_by_slot.get("Out")
    if w is None or ids is None or g is None or w.ndim != 2:
        return None
    v, d = w.shape
    ids = ids.astype(jnp.int32)
    if fwd_op.type != "fused_embedding_seq_pool":   # lookup_table family
        rows = ids.reshape(-1)
        if g.size != rows.shape[0] * d:
            return None
        vals = g.reshape(rows.shape[0], d)
        padding_idx = fwd_op.attrs.get("padding_idx", -1)
        if padding_idx is not None and padding_idx >= 0:
            # forward zeroes padding rows, so their cotangent is dead
            vals = jnp.where((rows == padding_idx)[:, None], 0.0, vals)
    else:  # fused_embedding_seq_pool: Out [B, D] fans out over T gathers
        if ids.ndim == 3:
            ids = ids[..., 0]
        if ids.ndim != 2 or g.shape != (ids.shape[0], d):
            return None
        b, t = ids.shape
        vals = jnp.broadcast_to(g[:, None, :], (b, t, d))
        lens = (ins_by_slot.get("SeqLens") or [None])[0]
        if lens is not None:
            from paddle_tpu.ops.sequence_ops import _mask_bt
            mask = _mask_bt(lens, b, t)
            vals = vals * mask[:, :, None].astype(vals.dtype)
        rows = ids.reshape(-1)
        vals = vals.reshape(b * t, d)
    return sr.RowSparseGrad(rows, vals.astype(w.dtype), height=v)


def _flatten(d: Dict[str, List[Any]], layout) -> List[Any]:
    out = []
    for slot, n in layout:
        vals = d.get(slot) or []
        if len(vals) < n:
            raise ValueError(f"slot {slot} produced {len(vals)} values, expected {n}")
        out.extend(vals[:n])
    return out


def _unflatten(vals: List[Any], layout) -> Dict[str, List[Any]]:
    d = {}
    i = 0
    for slot, n in layout:
        d[slot] = list(vals[i:i + n])
        i += n
    return d


# exporter-catalog families (docs/observability.md). Count LOWERINGS:
# each time a recomputed op's backward is traced into a program, the
# values its checkpoint keeps beside the op's inputs — what a kernel of
# the op has named (contrib/recompute.py:KEPT) — and their bytes, by the
# forward op's type. Read off the vjp's residuals at trace time: no host
# work in a step. An op in which nothing is named counts 0.
KEPT_VALUES = _metrics.counter(
    "paddle_recompute_kept_values_total",
    "Named values a recomputed op's backward keeps beside its inputs, "
    "by lowering", labelnames=("op",))
KEPT_BYTES = _metrics.counter(
    "paddle_recompute_kept_bytes_total",
    "Bytes of the named values a recomputed op's backward keeps, by "
    "lowering", labelnames=("op",))


def _count_kept(op_type, vjp_fn, flat_in):
    """A checkpoint's residuals are its function's inputs and what its
    policy saved: the latter are the vjp's array leaves that are none of
    the op's inputs (a schedule table is a numpy constant, no array of
    the trace)."""
    given = {id(v) for v in flat_in}
    kept = [r for r in jax.tree_util.tree_leaves(vjp_fn)
            if isinstance(r, jax.Array) and id(r) not in given]
    KEPT_VALUES.labels(op=op_type).inc(len(kept))
    KEPT_BYTES.labels(op=op_type).inc(
        sum(r.size * r.dtype.itemsize for r in kept))


def _traced_vjp(fwd_ctx: EmitContext, fwd_op, flat_in, diff_idx):
    """The forward op traced under ``jax.vjp`` in its differentiable
    inputs -> (outs, float_out, primals, vjp_fn): every declared output
    flat (what ``emit_with_backward`` hands on as the forward op's), the
    flat positions of those that carry cotangents, their values, and the
    transpose over them."""
    spec = get_op(fwd_op.type)
    in_layout = _slot_layout(fwd_op.inputs)
    out_layout = _slot_layout(fwd_op.outputs)
    diff_vals = tuple(flat_in[i] for i in diff_idx)

    def forward_flat(diff_vals, ctx=fwd_ctx):
        vals = list(flat_in)
        for i, v in zip(diff_idx, diff_vals):
            vals[i] = v
        outs = spec.emit(ctx, _unflatten(vals, in_layout), fwd_op.attrs)
        return tuple(_flatten(outs, out_layout))

    # determine which declared outputs are float (can carry cotangents);
    # a probe, not the op's emission (no `op`: lowering counters pass)
    out_avals = jax.eval_shape(
        functools.partial(forward_flat,
                          ctx=dataclasses.replace(fwd_ctx, op=None)),
        diff_vals)
    float_out = [k for k, a in enumerate(out_avals)
                 if jnp.issubdtype(a.dtype, jnp.inexact)]

    def forward_float_only(diff_vals):
        outs = forward_flat(diff_vals)
        return tuple(outs[k] for k in float_out), outs

    remat = bool(fwd_op.attrs.get("__remat__"))
    if remat:
        # contrib.recompute: the backward keeps this op's INPUTS and the
        # values a kernel of it has named (contrib/recompute.py:KEPT —
        # dear to remake, no larger than the inputs: flash attention's
        # output and log-sum-exp) and re-runs the rest of the forward
        # (jax.checkpoint) — trades FLOPs for activation memory (e.g.
        # projections, the broadcast key, attention probs [B,H,T,T]
        # never persist between fwd and bwd). An op in which nothing is
        # named keeps its inputs alone.
        from paddle_tpu.contrib.recompute import KEPT
        forward_float_only = jax.checkpoint(
            forward_float_only,
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT))

    primals, vjp_fn, outs = jax.vjp(forward_float_only, diff_vals,
                                    has_aux=True)
    if remat and fwd_ctx.step_base_key is not None:
        _count_kept(fwd_op.type, vjp_fn, flat_in)
    return outs, float_out, primals, vjp_fn


def recomputed_pairs(block: ir.BlockDesc, indices) -> Dict[int, Any]:
    """{position of a forward op tagged for recomputation: its `__vjp__`
    op} over the ops at `indices` — paired by the snapshot's identity
    (``ir_pass.vjp_snapshot_key``) and the same inputs, the forward op
    first. The lowering loop emits such a pair from ONE trace
    (``emit_with_backward``)."""
    tagged = {}
    for i in indices:
        op = block.ops[i]
        if op.type != "__vjp__" and op.attrs.get("__remat__"):
            tagged[i] = op
    if not tagged:
        return {}
    from paddle_tpu.fluid.ir_pass import vjp_snapshot_key
    at = {vjp_snapshot_key(op.type, op.outputs): i
          for i, op in tagged.items()}
    pairs = {}
    for i in indices:
        op = block.ops[i]
        if op.type != "__vjp__":
            continue
        snap = op.attrs.get("fwd_op", {})
        j = at.get(vjp_snapshot_key(snap.get("type"), snap.get("outputs")))
        if (j is not None and j < i
                and snap.get("attrs", {}).get("__remat__")
                and op.inputs.get("FwdIn") == _flatten(
                    tagged[j].inputs, _slot_layout(tagged[j].inputs))):
            pairs[j] = op
    return pairs


def emit_with_backward(ctx: EmitContext, op, vjp_op, ins, attrs):
    """Emit forward `op` under the ``jax.vjp`` of its `__vjp__` op and
    leave the transpose in ``ctx.linked`` for it: the op's outputs and
    its backward's residuals come from one trace, so what the checkpoint
    keeps IS what the forward op computed. (Two traces of one Mosaic call
    do not merge: the serialized kernel carries each trace's call stack,
    and XLA's CSE compares it byte for byte.)"""
    fwd_op = ir.OpDesc(type=op.type, inputs=op.inputs, outputs=op.outputs,
                       attrs=attrs)
    flat_in = _flatten(ins, _slot_layout(op.inputs))
    diff_idx = [i for i, m in enumerate(vjp_op.attrs["in_grad_mask"]) if m]
    outs, *traced = _traced_vjp(ctx, fwd_op, flat_in, diff_idx)
    ctx.linked[id(vjp_op)] = traced
    return _unflatten(outs, _slot_layout(op.outputs))


@register_op("__vjp__", no_grad=True, ref="framework/grad_op_desc_maker.h (capability)")
def _vjp_emit(ctx: EmitContext, ins, attrs):
    fwd_op = ir.OpDesc.from_dict(attrs["fwd_op"])
    in_layout = _slot_layout(fwd_op.inputs)
    out_layout = _slot_layout(fwd_op.outputs)
    flat_in = ins.get("FwdIn", [])
    diff_mask = attrs["in_grad_mask"]      # per flat fwd input
    og_mask = attrs["out_grad_mask"]       # per flat fwd output: grad provided?
    diff_idx = [i for i, m in enumerate(diff_mask) if m]

    def flat_pos(layout, slot):
        pos = 0
        out = []
        for s, n in layout:
            for _ in range(n):
                if s == slot:
                    out.append(pos)
                pos += 1
        return out

    if fwd_op.type in SPARSE_EMB_OPS and sr.sparse_grads_enabled():
        # fast path: W is the only differentiable input, so the whole VJP
        # is the gather transpose — emit it as a static-shape RowSparseGrad
        # instead of re-tracing the forward under jax.vjp (whose transpose
        # scatters into a dense [V, D] zeros)
        w_pos = flat_pos(in_layout, "W")
        out_pos = flat_pos(out_layout, "Out")
        if (len(w_pos) == 1 and diff_idx == w_pos and len(out_pos) == 1
                and og_matches_single(attrs["out_grad_mask"], out_pos[0])):
            g = ins.get("OutGrad", [])[0]
            wgrad = _sparse_embedding_vjp(
                fwd_op, _unflatten(flat_in, in_layout), {"Out": g})
            if wgrad is not None:
                return {"InGrad": [wgrad]}

    # a recomputed op's forward was emitted under this vjp already
    # (emit_with_backward); every other op is re-traced here, and XLA's
    # CSE merges the copy with the forward op
    traced = ctx.linked.pop(id(ctx.op), None) if ctx.linked else None
    if traced is None:
        # propagate dist: the backward re-trace must partition exactly
        # like the forward (e.g. ring attention stays sequence-parallel
        # in its vjp)
        fwd_ctx = EmitContext(base_key=ctx.base_key,
                              step_base_key=ctx.step_base_key,
                              op_index=attrs["fwd_op_index"],
                              is_test=ctx.is_test,
                              program=ctx.program, dist=ctx.dist)
        _, *traced = _traced_vjp(fwd_ctx, fwd_op, flat_in, diff_idx)
    float_out, primals, vjp_fn = traced
    ograds = ins.get("OutGrad", [])
    og_by_flat: Dict[int, Any] = {}
    j = 0
    for k, present in enumerate(og_mask):
        if present:
            og_by_flat[k] = ograds[j]
            j += 1
    cotangents = []
    for pos, k in enumerate(float_out):
        g = og_by_flat.get(k)
        p = primals[pos]
        if g is None:
            cotangents.append(jnp.zeros_like(p))
        else:
            cotangents.append(g.reshape(p.shape).astype(p.dtype))
    (gin,) = vjp_fn(tuple(cotangents))
    return {"InGrad": list(gin)}


GRAD_SUFFIX = "@GRAD"


def append_backward_desc(block: ir.BlockDesc, loss_name: str,
                         no_grad_set=None) -> Dict[str, str]:
    """Reverse-mode autodiff over the block's op list.

    Capability parity with `append_backward` (reference:
    python/paddle/fluid/backward.py:394; op walk :252; sum-aggregation
    insertion :148,195): walks ops in reverse, appends one `__vjp__` op per
    relevant forward op, inserts `sum` ops where a var's gradient fans in
    from several consumers, and returns {var_name: grad_var_name}.
    """
    no_grad_set = set(no_grad_set or ())

    def var_stops(n: str) -> bool:
        if n in no_grad_set:
            return True
        if block.has_var(n):
            v = block.var(n)
            if v.stop_gradient:
                return True
            if not v.dtype.startswith(("float", "bfloat")):
                return True
        return False

    # relevance: ops backward-reachable from the loss
    n_fwd = len(block.ops)
    needed = {loss_name}
    relevant = [False] * n_fwd
    for i in range(n_fwd - 1, -1, -1):
        op = block.ops[i]
        if op.type in ("feed", "fetch") or get_op(op.type).no_grad:
            continue
        if set(op.output_names()) & needed:
            relevant[i] = True
            needed.update(op.input_names())

    # loss@GRAD = ones
    loss_var = block.var(loss_name)
    loss_grad = loss_name + GRAD_SUFFIX
    block.append_op(ir.OpDesc(
        type="fill_constant",
        outputs={"Out": [loss_grad]},
        attrs={"shape": list(loss_var.shape or []), "value": 1.0,
               "dtype": loss_var.dtype},
    ))
    _add_grad_var(block, loss_grad, loss_var)

    # pending[v] = list of partial-grad var names awaiting aggregation
    pending: Dict[str, List[str]] = {loss_name: [loss_grad]}
    finalized: Dict[str, str] = {}

    def finalize(v: str) -> str:
        if v in finalized:
            return finalized[v]
        parts = pending.get(v, [])
        if not parts:
            return ""
        gname = v + GRAD_SUFFIX
        if len(parts) == 1:
            gname = parts[0]
        else:
            block.append_op(ir.OpDesc(type="sum", inputs={"X": list(parts)},
                                      outputs={"Out": [gname]}))
            _add_grad_var(block, gname, block.var(v) if block.has_var(v) else None)
        finalized[v] = gname
        return gname

    for i in range(n_fwd - 1, -1, -1):
        if not relevant[i]:
            continue
        op = block.ops[i]
        in_layout = _slot_layout(op.inputs)
        out_layout = _slot_layout(op.outputs)
        flat_in = _flatten({s: list(ns) for s, ns in op.inputs.items()}, in_layout)
        flat_out = _flatten({s: list(ns) for s, ns in op.outputs.items()}, out_layout)

        og_names, og_mask = [], []
        for o in flat_out:
            g = finalize(o)
            og_mask.append(bool(g))
            if g:
                og_names.append(g)
        if not any(og_mask):
            continue

        in_grad_mask = [not var_stops(n) for n in flat_in]
        if not any(in_grad_mask):
            continue

        grad_out_names = []
        for n, m in zip(flat_in, in_grad_mask):
            if not m:
                continue
            parts = pending.setdefault(n, [])
            gname = n + GRAD_SUFFIX if not parts else f"{n}{GRAD_SUFFIX}@RENAME@{len(parts)}"
            parts.append(gname)
            grad_out_names.append(gname)
            _add_grad_var(block, gname, block.var(n) if block.has_var(n) else None)

        block.append_op(ir.OpDesc(
            type="__vjp__",
            inputs={"FwdIn": list(flat_in), "OutGrad": og_names},
            outputs={"InGrad": grad_out_names},
            attrs={
                "fwd_op": op.to_dict(),
                "fwd_op_index": i,
                "in_grad_mask": in_grad_mask,
                "out_grad_mask": og_mask,
            },
        ))

    # finalize remaining grads (parameters are usually leaves)
    grad_map: Dict[str, str] = {}
    for v in list(pending):
        g = finalize(v)
        if g:
            grad_map[v] = g
    return grad_map


def _add_grad_var(block: ir.BlockDesc, gname: str, base: "ir.VarDesc | None"):
    if block.has_var(gname):
        return
    block.add_var(ir.VarDesc(
        name=gname,
        shape=list(base.shape) if base is not None and base.shape else None,
        dtype=base.dtype if base is not None else "float32",
        stop_gradient=True,
    ))
