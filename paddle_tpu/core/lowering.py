"""Block lowering: ProgramDesc block → one pure JAX function → XLA.

This replaces the reference's entire interpreter stack: where
`Executor::RunPreparedContext` loops `op->Run(scope, place)` per step with
per-call kernel dispatch and runtime InferShape
(reference: framework/executor.cc:413-456, operator.cc:912-966), we walk the
block ONCE at trace time, emitting each op's JAX computation into a single
function that XLA compiles and fuses. Parameters are threaded functionally
(state-in/state-out) with buffer donation so optimizer updates stay in-place
in HBM — the functional equivalent of the reference's mutable Scope.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import ir
from paddle_tpu.core import selected_rows as sr
from paddle_tpu.core.registry import EmitContext, get_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.observability import runtime as _obs_runtime

# ensure all builtin emitters are registered on import
import paddle_tpu.ops  # noqa: F401


@dataclass(frozen=True)
class BlockSignature:
    """Static analysis of a block: which names are feeds, which come from the
    scope (split into mutated state vs read-only consts), which are fetched,
    and which ops are live for this (feed, fetch) signature."""

    feed_names: Tuple[str, ...]
    fetch_names: Tuple[str, ...]
    state_names: Tuple[str, ...]       # scope vars read and/or (re)written
    const_names: Tuple[str, ...]       # scope vars only read
    created_persistable: Tuple[str, ...]  # persistables first created here
    live_ops: Tuple[int, ...]          # indices of ops that execute


def analyze_block(block: ir.BlockDesc, feed_names: Sequence[str],
                  fetch_names: Sequence[str]) -> BlockSignature:
    def is_persistable(n: str) -> bool:
        return block.has_var(n) and block.var(n).persistable

    # Liveness: an op executes if it contributes to a fetch or writes
    # persistable state. The reference interprets every op in the block
    # (executor.cc:448) and errors on un-fed inputs; here dead subgraphs
    # (e.g. the loss ops of a clone(for_test) program when only the
    # prediction is fetched) are pruned at trace time, so their feeds are
    # not required.
    needed = set(fetch_names)
    live_rev: List[int] = []
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        if op.type in ("feed", "fetch"):
            continue
        outs = op.output_names()
        if (set(outs) & needed) or any(is_persistable(n) for n in outs):
            live_rev.append(i)
            needed.update(op.input_names())
    live = tuple(reversed(live_rev))

    defined = set(feed_names)
    from_scope: List[str] = []
    written: set = set()
    for i in live:
        op = block.ops[i]
        for name in op.input_names():
            if name not in defined and name not in from_scope:
                from_scope.append(name)
        for name in op.output_names():
            defined.add(name)
            written.add(name)

    state, const, created = [], [], []
    for n in from_scope:
        if n in written and is_persistable(n):
            state.append(n)
        else:
            const.append(n)
    for n in written:
        if is_persistable(n) and n not in from_scope:
            created.append(n)

    # fetches not produced by the block must come from the scope
    for n in fetch_names:
        if n not in defined and n not in from_scope and n not in const:
            const.append(n)

    return BlockSignature(
        feed_names=tuple(feed_names),
        fetch_names=tuple(fetch_names),
        state_names=tuple(state),
        const_names=tuple(const),
        created_persistable=tuple(sorted(created)),
        live_ops=live,
    )


# lookup ops whose W may be a __sharded__-marked table (ISSUE 14): when
# the hot-rows cache is enabled the runtime array under the table's name
# is the [capacity + 1, D] cache and the executor feeds SLOT ids, so a
# site's original vocab-space padding_idx must be rewritten to the
# cache's pinned-zero pad slot — forward zeroing AND the row-sparse
# VJP's padding-gradient drop then hold in slot space exactly.
_SHARDED_LOOKUP_OPS = ("lookup_table", "fused_embedding_seq_pool")


def _sharded_attrs(program: ir.ProgramDesc, op) -> dict:
    """op.attrs, with padding_idx patched to the cache pad slot for
    lookup sites over a __sharded__ table (and for their __vjp__ ops,
    whose fwd_op payload carries the attrs the backward emitter reads).
    Identity when no table is sharded — zero cost on the common path."""
    pads = getattr(program, "_sharded_pad_slots", None)
    if not pads:
        return op.attrs

    def patch(op_type, inputs, attrs):
        if op_type in _SHARDED_LOOKUP_OPS:
            w = (inputs.get("W") or [None])[0]
            if w in pads:
                gvar = program.global_block.vars.get(w)
                if gvar is not None and gvar.attrs.get("__sharded__"):
                    pidx = attrs.get("padding_idx", -1)
                    if pidx is not None and int(pidx) >= 0:
                        out = dict(attrs)
                        out["padding_idx"] = pads[w]
                        return out
        return attrs

    if op.type == "__vjp__":
        fwd = op.attrs.get("fwd_op") or {}
        patched = patch(fwd.get("type"), fwd.get("inputs", {}),
                        fwd.get("attrs", {}))
        if patched is not fwd.get("attrs", {}):
            out = dict(op.attrs)
            f2 = dict(fwd)
            f2["attrs"] = patched
            out["fwd_op"] = f2
            return out
        return op.attrs
    return patch(op.type, op.inputs, op.attrs)


def emit_op_seq(program: ir.ProgramDesc, block: ir.BlockDesc,
                indices, env: Dict[str, Any], base_key, step_base,
                is_test: bool, dist=None) -> None:
    """Emit the ops at `indices` of `block` into `env` (mutated in place).
    This is the single trace-time interpreter loop; control-flow emitters
    call back into it for their sub-blocks (replacing the reference's
    per-iteration child-scope interpretation, while_op.cc:64-70)."""
    from paddle_tpu.ops import grad_ops          # ops import core
    # a forward op tagged for recomputation and its `__vjp__` come from
    # one trace: what the backward keeps is what the forward op computed
    with_backward = grad_ops.recomputed_pairs(block, indices)
    linked: Dict[int, Any] = {}
    for i in indices:
        op = block.ops[i]
        spec = get_op(op.type)
        # salt rng per (block, op) so sub-block ops never collide with
        # parent-block ops at the same index. Ops carry a pinned
        # `__op_index__` once an IR pass has rewritten the block
        # (paddle_tpu/passes pin_op_indices): random ops keep their
        # pre-rewrite salt, so a pass that removes ops does not shift
        # every later dropout's mask — rewrites preserve the random
        # stream, which is what makes pass/no-pass parity testable
        op_salt = op.attrs.get("__op_index__", i)
        ctx = EmitContext(base_key=base_key, step_base_key=step_base,
                          op_index=block.idx * 100_000 + op_salt,
                          is_test=is_test,
                          program=program, dist=dist, op=op, linked=linked)
        ins = {}
        for slot, names in op.inputs.items():
            try:
                ins[slot] = [env[n] for n in names]
            except KeyError as e:
                raise KeyError(
                    f"op {op.type!r} input {slot} references undefined var "
                    f"{e.args[0]!r}; did you run the startup program?") from e
        # row-sparse grad plumbing (core/selected_rows.py): the sparse-apply
        # optimizer ops consume the (rows, values) pair natively; the linear
        # plumbing ops (sum/scale/isfinite/...) rewrite sparsely; everything
        # else gets an exact densify — a consumer can never observe the
        # difference, only the fast path's cost profile
        attrs = _sharded_attrs(program, op)
        # every lowered op in a scope of its own type: XLA carries it
        # into each instruction's op_name, which is how the device's
        # time is read under the program's names (device_scopes.py)
        with jax.named_scope(_device_scopes.op_scope(op)):
            if any(sr.is_sparse(v) for vals in ins.values() for v in vals) \
                    and op.type not in sr.SPARSE_APPLY_OPS:
                outs = sr.try_sparse_emit(op.type, ins, attrs)
                if outs is None:
                    outs = spec.emit(ctx, sr.densify_ins(ins), attrs)
            elif i in with_backward:
                outs = grad_ops.emit_with_backward(ctx, op, with_backward[i],
                                                   ins, attrs)
            else:
                outs = spec.emit(ctx, ins, attrs)
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                env[n] = v


def emit_subblock(ctx: EmitContext, block_idx: int, env: Dict[str, Any],
                  key_salt=None) -> None:
    """Recursively lower sub-block `block_idx` into `env` under the caller's
    trace (used by while/cond/scan emitters). `key_salt` is a (possibly
    traced) iteration counter folded into the rng keys so random ops draw
    fresh randomness each loop iteration (the reference re-interprets the
    sub-block per step with fresh seeds, while_op.cc:64-70)."""
    base, step_base = ctx.base_key, ctx.step_base_key
    if key_salt is not None:
        base = jax.random.fold_in(base, key_salt)
        if step_base is not None:
            step_base = jax.random.fold_in(step_base, key_salt)
    sub = ctx.program.block(block_idx)
    emit_op_seq(ctx.program, sub, range(len(sub.ops)), env,
                base, step_base, ctx.is_test, dist=ctx.dist)


def build_block_fn(program: ir.ProgramDesc, block_idx: int,
                   sig: BlockSignature, is_test: bool = False, dist=None):
    """Returns fn(state: dict, consts: dict, feeds: dict, step_seed) ->
    (fetches: list, new_state: dict). Pure — safe to jit/pjit/shard_map."""

    block = program.block(block_idx)
    seed0 = program.random_seed

    def fn(state: Dict[str, Any], consts: Dict[str, Any],
           feeds: Dict[str, Any], step_seed):
        env: Dict[str, Any] = {}
        env.update(consts)
        env.update(state)
        env.update(feeds)
        # Randomness semantics mirror the reference's seed convention
        # (python/paddle/fluid/framework.py Program.random_seed): a nonzero
        # program seed makes every run reproducible (interpreter semantics —
        # fixed per-op seeds); seed 0 draws fresh randomness each step.
        if seed0 != 0:
            base_key = jax.random.key(seed0)
        else:
            base_key = jax.random.fold_in(jax.random.key(0), step_seed)
        step_base = base_key
        emit_op_seq(program, block, sig.live_ops, env, base_key, step_base,
                    is_test, dist=dist)
        fetches = []
        for n in sig.fetch_names:
            v = env[n]
            if sr.is_sparse(v):
                # a fetched @GRAD var densifies at the boundary — users
                # (and the numeric-grad checker) see the dense gradient
                v = v.densify()
            # contrib.layout NHWC-resident intermediates come back to the
            # user in the declared NCHW layout
            if (getattr(v, "ndim", 0) == 4 and block.has_var(n)
                    and block.var(n).attrs.get("__nhwc__")):
                v = jnp.transpose(v, (0, 3, 1, 2))
            fetches.append(v)
        new_state = {n: env[n] for n in sig.state_names if n in env}
        for n in sig.created_persistable:
            if n in env:
                new_state[n] = env[n]
        return fetches, new_state

    return fn


class _Executables:
    """The jitted fns of one block, the arguments each compiled for and
    the executable of each once something asked for it. Kept apart from
    the block, and referring to none of its arrays, so that
    ``observability.device_scopes`` can hold it while a trace is taken
    and read the names of a block that is gone by then."""

    def __init__(self):
        # (iterations, stacked names) -> the jitted fn, and the name
        # jax.monitoring's compile events give it -> that key
        self.jitted: Dict[Tuple, Any] = {}
        self.names: Dict[str, Tuple] = {}
        # (iterations, stacked names, feed shapes) -> the argument specs
        # of the NEWEST compile (a second one, for the layouts the
        # step's own outputs carry, replaces the first), and the
        # executable once asked for
        self.ran: Dict[Tuple, Any] = {}
        self.compiled: Dict[Tuple, Any] = {}
        _device_scopes.register(self)

    def note(self, fun_name: str, args) -> None:
        """The compile listener's call (``runtime.dispatching``): a
        jitted fn called ``fun_name`` compiled inside a dispatch of
        ``args``. Shapes, dtypes and where each argument was committed
        are kept, so that a later ``.lower(*specs)`` finds the very
        executable the dispatch made in JAX's own caches, without a
        trace, a lowering or a compile. Runs per COMPILE, never per
        dispatch."""
        mine = self.names.get(fun_name)
        if mine is None:
            return

        def spec(x):
            aval = jax.typeof(x)
            committed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(
                aval.shape, aval.dtype, weak_type=aval.weak_type,
                sharding=x.sharding if committed else None)
        self.ran[(*mine, _feed_sig(args[2]))] = \
            jax.tree_util.tree_map(spec, args)

    def executable(self, iterations: int, snames, args):
        """THE lower / compile round trip, once per jitted fn and feed
        shapes."""
        key = (iterations, snames, _feed_sig(args[2]))
        exe = self.compiled.get(key)
        if exe is None:
            exe = self.compiled[key] = \
                self.jitted[(iterations, snames)].lower(*args).compile()
        return exe

    def device_executables(self) -> list:
        """The executable of every signature the block's jitted fns
        compiled for: what the block has run."""
        return [self.executable(iterations, snames, specs)
                for (iterations, snames, _sig), specs
                in list(self.ran.items())]


def _feed_sig(feeds: Dict[str, Any]):
    return tuple(sorted((n, tuple(getattr(v, "shape", ()) or ()))
                        for n, v in feeds.items()))


class CompiledBlock:
    """A compiled executable for (program block, feed/fetch signature) —
    the analogue of the reference's per-program executor cache
    (reference: executor.py:222 _get_program_cache_key / use_program_cache),
    except the cached object is an XLA executable, not a list of op objects.

    With a DistributeConfig, this is also the ParallelExecutor replacement
    (reference: parallel_executor.cc:191): feeds shard over the mesh's data
    axis, params replicate (or shard per param_axes), and XLA emits the
    gradient reduction over ICI that the reference ran as NCCL allreduce
    op-handles (details/all_reduce_op_handle.cc:103)."""

    # monotonic instance tag for observability caches (id() would be
    # reused after GC and inherit a dead block's FLOPs; itertools.count
    # is atomic under concurrent construction)
    _SEQ = itertools.count(1)

    def __init__(self, program: ir.ProgramDesc, block_idx: int,
                 feed_names: Sequence[str], fetch_names: Sequence[str],
                 is_test: bool = False, donate: bool = True, dist=None,
                 feed_transform=None):
        # ``feed_transform(feeds) -> feeds``, traced INSIDE the
        # executable before the block reads its feeds: the caller may
        # then hand over entries the program does not name and have them
        # folded into ones it does, with no dispatch of their own (the
        # slot engine's token feed: serving/engine.py). Not under a mesh.
        self._obs_tag = next(CompiledBlock._SEQ)
        _obs_runtime.install_compile_listener()
        # build-time program verification (FLAGS_verify_program or a
        # BuildStrategy.verify_program request): reject malformed
        # programs with rule + op provenance BEFORE tracing, where the
        # same defect would surface as an opaque JAX error (or not at
        # all). Errors raise ProgramVerificationError; warnings land in
        # paddle_analysis_diagnostics_total (docs/static_analysis.md).
        from paddle_tpu import flags as _flags
        if _flags.get("verify_program") \
                or getattr(program, "_verify_requested", False):
            from paddle_tpu import analysis
            analysis.verify_program(program, feed_names=feed_names,
                                    fetch_names=fetch_names,
                                    is_test=is_test)
        block = program.block(block_idx)
        self.sig = analyze_block(block, feed_names, fetch_names)
        self.block = block
        self.dist = dist
        self._program_desc = program
        self._donate = bool(donate)
        # resolve every tunable region's autotune-cache lookup at BUILD
        # time: deterministic (committed table only — zero timing
        # measurements on this path, enforced by autotune.measure_ms's
        # forbid guard) and recorded in the hit/miss counters so CI can
        # assert the executable's selection never depended on a
        # measurement (paddle_tpu/passes/autotune.py)
        try:
            from paddle_tpu.passes import autotune as _autotune
            self.autotune_lookups = _autotune.note_block_build(program,
                                                               block)
        except Exception:
            self.autotune_lookups = {"hit": 0, "miss": 0}
        # HBM-budget-aware sharding selection: with FLAGS_hbm_bytes set,
        # a plan whose per-device state footprint exceeds the budget
        # walks the dp -> ZeRO -> tp fallback ladder BEFORE the specs
        # freeze (docs/performance.md "SPMD execution"). The decision —
        # every rung's estimate and which one was chosen — is recorded
        # on self.hbm_plan for tooling (tools/spmd_bench.py,
        # tools/proglint.py --sharding).
        self.hbm_plan = None
        if dist is not None and dist.mesh is not None:
            budget = float(_flags.get("hbm_bytes") or 0.0)
            if budget > 0:
                self._plan_under_budget(budget)
                dist = self.dist
            try:
                from paddle_tpu.observability import spmd as _obs_spmd
                _obs_spmd.note_mesh(dist.mesh.size)
            except Exception:
                pass
        fn = build_block_fn(program, block_idx, self.sig, is_test=is_test,
                            dist=dist)
        if feed_transform is not None:
            if dist is not None and dist.mesh is not None:
                raise ValueError("feed_transform is not supported under "
                                 "a mesh (feeds are sharded by name)")
            block_fn = fn

            def fn(state, consts, feeds, step_seed):
                return block_fn(state, consts, feed_transform(feeds),
                                step_seed)
        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnums"] = (0,)
        self._shardings = None
        if dist is not None and dist.mesh is not None:
            shardings = self._input_shardings()
            self._shardings = shardings
            jit_kwargs["in_shardings"] = shardings
            # pin state *outputs* to the same layout as the state inputs —
            # otherwise XLA propagates e.g. a ZeRO-sharded moment's layout
            # into the updated param, and the next step's in_shardings
            # reject the scope array
            state_sh = shardings[0]
            out_sh = dict(state_sh)
            for n in self.sig.created_persistable:
                out_sh[n] = self._param_sharding_fn(n)
            base_fn = fn

            def fn(state, consts, feeds, step_seed):
                fetches, new_state = base_fn(state, consts, feeds, step_seed)
                new_state = {
                    n: (jax.lax.with_sharding_constraint(v, out_sh[n])
                        if n in out_sh else v)
                    for n, v in new_state.items()}
                return fetches, new_state
        # donate the mutated-state dict: optimizer updates reuse the same HBM
        # buffers (reference keeps params in-place in the Scope; we get the
        # same via XLA input_output_aliasing)
        self._step_fn = fn            # un-jitted (dist-wrapped) single step
        self._jit_kwargs = jit_kwargs
        self._exes = _Executables()
        self.fn = self._jit(fn, 1, False, jit_kwargs)
        # key: (iterations, True | tuple of stacked feed names)
        self._multi_cache: Dict[Tuple[int, Any], Any] = {}
        # device-resident training state: after a dispatch the (sharded)
        # output jax.Arrays are cached here keyed by the scope's mutation
        # clock, so the steady-state step loop never walks the scope —
        # state stays in HBM across steps and _gather_state runs only on
        # the first dispatch or after an EXTERNAL scope write (a
        # checkpoint restore, a user set_var). gather_state_calls is the
        # witness counter (tests/test_spmd_exec.py).
        self._resident = None   # (scope, scope.version(), state, consts)
        self.gather_state_calls = 0

    def _jit(self, fn, iterations: int, snames, jit_kwargs):
        """``fn`` (a closure of this block's own) jitted under the
        block's name, so that XLA calls the module ``jit_<name>``
        (``device_scopes.module_name``) and the profiler's "XLA Modules"
        line names the program's blocks. A scan of N steps is another
        executable than the single step and gets another name, so that
        an instruction name means one thing under each: ``_x<N>`` at the
        END of it, behind the digest of a block that holds phased ops
        (the trace readers' step pattern is ``jit_\\w+_x\\d+``)."""
        name = _device_scopes.module_name(
            self.obs_label,
            (key for b in self._program_desc.blocks for op in b.ops
             for key in _device_scopes.scope_keys(op))) \
            + (f"_x{iterations}" if iterations > 1 else "")
        fn.__name__ = fn.__qualname__ = name
        jitted = self._exes.jitted[(iterations, snames)] = jax.jit(
            fn, **jit_kwargs)
        self._exes.names[f"jit({name})"] = (iterations, snames)
        return jitted

    def _multi_fn(self, iterations: int, stacked):
        """jitted N-step executable: scans the single-step fn over donated
        state in ONE dispatch — the TPU analogue of the reference's C++
        interpreter hot loop (framework/executor.cc:448 runs the op list
        per step host-side; here the whole loop lives on-device, so the
        per-dispatch host cost — which scales with the number of param
        buffers — is paid once per N steps, not once per step).

        `stacked` is True (every feed carries a leading [iterations] axis,
        one batch per step), False (one resident batch reused), or an
        iterable of feed NAMES — only those scan per-step while the rest
        stay resident (e.g. fresh labels over a resident image batch).
        Fetches come back stacked per step ([iterations, ...])."""
        snames = (frozenset() if isinstance(stacked, bool)
                  else frozenset(stacked))
        key = (iterations, stacked if isinstance(stacked, bool)
               else tuple(sorted(snames)))
        cached = self._multi_cache.get(key)
        if cached is not None:
            return cached
        step_fn = self._step_fn
        all_stacked = stacked is True
        # (not through ``self``: the jitted fn must not keep the block,
        # and with it the state arrays, alive)
        created = self.sig.created_persistable

        def fn(state, consts, feeds, seed0):
            sf = {n: v for n, v in feeds.items()
                  if all_stacked or n in snames}
            rf = {n: v for n, v in feeds.items() if n not in sf}
            # the step fn returns state_names ∪ created_persistable; the
            # scan carry must have the same structure, so seed the carry
            # with zero placeholders for persistables first CREATED by this
            # block (they're written before read, so the zeros never leak)
            if created:
                feeds0 = {**rf, **jax.tree_util.tree_map(
                    lambda x: x[0], sf)}
                _, out_sd = jax.eval_shape(step_fn, state, consts, feeds0,
                                           seed0)
                state = dict(state)
                for n in created:
                    if n in out_sd and n not in state:
                        state[n] = jnp.zeros(out_sd[n].shape,
                                             out_sd[n].dtype)

            def body(carry, xs):
                i, sf_i = xs
                fetches, new_state = step_fn(carry, consts,
                                             {**rf, **sf_i}, seed0 + i)
                return new_state, tuple(fetches)
            idx = jnp.arange(iterations, dtype=jnp.uint32)
            new_state, fetches = jax.lax.scan(body, state, (idx, sf))
            return list(fetches), new_state

        jit_kwargs = dict(self._jit_kwargs)
        if "in_shardings" in jit_kwargs:
            state_sh, const_sh, feed_sh, repl = jit_kwargs["in_shardings"]
            if stacked:
                from jax.sharding import NamedSharding, PartitionSpec as P
                mesh = self.dist.mesh
                feed_sh = {
                    n: (NamedSharding(mesh, P(None, *sh.spec))
                        if (all_stacked or n in snames) else sh)
                    for n, sh in feed_sh.items()}
            jit_kwargs["in_shardings"] = (state_sh, const_sh, feed_sh, repl)
        jitted = self._jit(fn, iterations, key[1], jit_kwargs)
        self._multi_cache[key] = jitted
        return jitted

    def _plan_under_budget(self, budget: float) -> None:
        """Walk the dp -> ZeRO -> tp fallback ladder until the analytic
        per-device state footprint fits `budget` bytes, replacing
        self.dist with the chosen (copied) config. Rungs:

        1. the plan as configured (dp-replicated params/moments unless
           the user already sharded them);
        2. ZeRO: ``reduce_strategy="reduce_scatter"`` reduce-scatters
           the optimizer accumulators over the data axis;
        3. tp: turn on graph-derived tensor-parallel placement
           (``auto_shard``) over the model axis, when the mesh has one.

        When no rung fits, the cheapest plan is kept and
        ``hbm_plan["fits"]`` is False — tools/proglint.py --sharding
        turns that into a lint error naming the replicated vars."""
        import dataclasses
        import warnings
        from paddle_tpu.observability import memory as obs_memory

        configured = self.dist
        rungs = [("as-configured", configured)]
        d = configured
        dp_active = (d.data_axis and d.data_axis in d.mesh.axis_names
                     and d.mesh.shape[d.data_axis] > 1)
        if d.reduce_strategy != "reduce_scatter" and dp_active:
            d = dataclasses.replace(d, reduce_strategy="reduce_scatter")
            rungs.append(("zero", d))
        tp_possible = (configured.model_axis
                       and configured.model_axis in configured.mesh.axis_names
                       and configured.mesh.shape[configured.model_axis] > 1)
        if tp_possible and not configured.auto_shard:
            rungs.append(("tp", dataclasses.replace(d, auto_shard=True)))

        ladder, chosen, best = [], None, None
        for name, cand in rungs:
            state_sh, const_sh, _, _ = self._input_shardings(dist=cand)
            est = obs_memory.sharded_state_bytes(
                self.block, {**state_sh, **const_sh})
            fits = est <= budget
            ladder.append({"rung": name, "per_device_state_bytes": est,
                           "fits": fits})
            if best is None or est < best[1]:
                best = (name, est, cand)
            if fits and chosen is None:
                chosen = (name, est, cand)
                break
        if chosen is None:
            chosen = best
            warnings.warn(
                f"FLAGS_hbm_bytes={budget:.4g}: no sharding plan fits "
                f"the per-device budget (cheapest rung "
                f"{chosen[0]!r} needs {chosen[1]:.4g} state bytes/"
                f"device); keeping it — expect OOM or add mesh axes")
        # vars the budget forces off replication: replicated under the
        # configured plan, sharded under the chosen one
        must_shard = []
        if chosen[2] is not configured:
            base_sh, base_csh, _, _ = self._input_shardings(dist=configured)
            new_sh, new_csh, _, _ = self._input_shardings(dist=chosen[2])
            base = {**base_sh, **base_csh}
            new = {**new_sh, **new_csh}
            for n, sh in new.items():
                old = base.get(n)
                if (old is not None and not tuple(old.spec)
                        and tuple(sh.spec)):
                    must_shard.append(n)
        self.hbm_plan = {
            "budget_bytes": budget,
            "ladder": ladder,
            "chosen": chosen[0],
            "per_device_state_bytes": chosen[1],
            "fits": bool(chosen[1] <= budget),
            "must_shard": sorted(must_shard),
        }
        self.dist = chosen[2]

    def _gather_state(self, scope) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """(state, consts) dicts pulled from the scope — the argument
        prefix every executable (single- and multi-step, and the
        observability cost-analysis lowering) shares. Dispatch paths go
        through :meth:`_resident_state`, which skips this walk entirely
        once the state is device-resident."""
        self.gather_state_calls += 1
        state = {}
        for n in self.sig.state_names:
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"variable {n!r} not initialized in scope — run the "
                    f"startup program first (reference: two-program "
                    f"convention, framework.py default_startup_program)")
            state[n] = v
        consts = {}
        for n in self.sig.const_names:
            v = scope.find_var(n)
            if v is None:
                if self.block.has_var(n) and not self.block.var(n).persistable:
                    raise RuntimeError(
                        f"variable {n!r} is neither fed nor initialized — "
                        f"add it to the feed dict (an op in the program "
                        f"consumes it)")
                raise RuntimeError(
                    f"persistable variable {n!r} not found in scope — run "
                    f"the startup program first")
            consts[n] = v
        return state, consts

    def _resident_state(self, scope):
        """(state, consts) for a dispatch: the device-resident cache when
        the scope's mutation clock is unchanged since our last writeback,
        else a fresh scope gather. A cache hit costs two comparisons —
        no scope walk, no host round trip."""
        res = self._resident
        if (res is not None and res[0] is scope
                and res[1] == scope.version()):
            return res[2], res[3]
        state, consts = self._gather_state(scope)
        if self._shardings is not None:
            self._note_resharding(state, consts)
        return state, consts

    def _finish_dispatch(self, scope, new_state, consts) -> None:
        """Write updated state back to the scope (fetch/checkpoint
        coherence — the scope keeps holding device arrays) and re-arm
        the device-resident cache with the step's OUTPUT arrays (the
        inputs were just donated). The version snapshot is taken after
        our own set_var calls, so only an external write invalidates."""
        for n, v in new_state.items():
            scope.set_var(n, v)
        state = {n: new_state[n] for n in self.sig.state_names
                 if n in new_state}
        if len(state) == len(self.sig.state_names):
            self._resident = (scope, scope.version(), state, consts)
        else:
            self._resident = None

    def _note_resharding(self, state, consts) -> None:
        """Count bytes of dispatch inputs that arrive in a different
        layout than the program's NamedSharding — jit reshards them on
        entry (the startup->training-layout move on the first dispatch).
        Steady state takes the resident-cache path and never gets here,
        so paddle_spmd_resharding_bytes_total staying flat IS the
        device-resident witness."""
        state_sh, const_sh = self._shardings[0], self._shardings[1]
        total = 0
        for vals, shs in ((state, state_sh), (consts, const_sh)):
            for n, v in vals.items():
                want = shs.get(n)
                if want is None or not isinstance(v, jax.Array):
                    continue
                try:
                    same = v.sharding.is_equivalent_to(want, v.ndim)
                except Exception:
                    same = v.sharding == want
                if not same:
                    total += int(getattr(v, "nbytes", 0) or 0)
        if total:
            try:
                from paddle_tpu.observability import spmd as obs_spmd
                obs_spmd.note_resharding(self.obs_label, total)
            except Exception:
                pass

    def run_steps(self, scope, feeds: Dict[str, Any], step_seed0: int,
                  iterations: int, stacked=False, marks=None):
        """Run `iterations` training steps in one device-side loop.
        `feeds` maps name -> array (resident batch, reused every step) or,
        with stacked=True (or the name listed in a stacked iterable),
        name -> array with a leading [iterations] axis.
        Returns per-step stacked fetches. Reference capability: amortized
        multi-step execution (executor.cc:448 interpreter loop,
        threaded_ssa_graph_executor.cc)."""
        state, consts = self._resident_state(scope)
        fn = self._multi_fn(iterations, stacked)
        if marks is not None:
            marks.append(time.perf_counter())
        args = (state, consts, feeds, np.uint32(step_seed0))
        with _obs_runtime.dispatching(self.obs_label, self._exes.note,
                                      args):
            fetches, new_state = fn(*args)
        self._finish_dispatch(scope, new_state, consts)
        return fetches

    def _signature(self, feeds, stacked):
        """(stacked names, feed shapes) of a dispatch: feed shapes
        belong in a cache key — jit retraces per shape behind one jitted
        fn, so a partial tail batch must not be served the full batch's
        numbers."""
        snames = (stacked if isinstance(stacked, bool)
                  else tuple(sorted(stacked)))
        return snames, _feed_sig(feeds)

    def _compile_thunk(self, scope, feeds, iterations, snames):
        """A callable that gives the one executable ``analyzed_flops``,
        ``analyzed_memory``, ``donation_audit`` and
        ``observability.device_scopes`` all read for this jitted fn and
        these feed shapes (``_Executables.executable``): none of them
        lowers or compiles on its own. Call it AFTER a real dispatch."""
        def compiled():
            if iterations > 1:
                self._multi_fn(iterations, snames)
            state, consts = self._resident_state(scope)
            return self._exes.executable(
                iterations, snames, (state, consts, feeds, np.uint32(0)))
        return compiled

    def analyzed_flops(self, scope, feeds: Dict[str, Any],
                       iterations: int = 1, stacked=False):
        """Per-step FLOPs of this executable from XLA's compiled-cost
        analysis (observability MFU numerator), cached per (iterations,
        stacked) jit signature. The lower/compile round trip runs once
        per signature (``_compile_thunk``) — call AFTER a real dispatch
        so jax's executable caches are warm. None when the backend
        reports no FLOPs (the caller falls back to utils/flops.py's
        analytic walk)."""
        from paddle_tpu.observability import runtime as obs_runtime
        snames, feed_sig = self._signature(feeds, stacked)
        key = (self._obs_tag, iterations, snames, feed_sig)
        hit, val = obs_runtime.cost_cache_peek(key)
        if hit:
            # resolved signature: skip the scope walk / fn lookup — this
            # runs once per dispatch on the telemetry path
            return val
        return obs_runtime.compiled_flops(
            None, cache_key=key, per_call_steps=iterations,
            compiled=self._compile_thunk(scope, feeds, iterations, snames))

    @property
    def obs_label(self) -> str:
        """Bounded-cardinality program label for memory metrics: the
        name a caller pinned on the desc (bench/serving/mem_probe set
        ``_obs_name``) or this block's build tag."""
        return (getattr(self._program_desc, "_obs_name", None)
                or f"block{self._obs_tag}")

    def analyzed_memory(self, scope, feeds: Dict[str, Any],
                        iterations: int = 1, stacked=False):
        """Compiled memory breakdown of this executable (argument/
        output/temp/alias/generated_code/peak bytes) from XLA's
        memory_analysis(), cached per jit signature exactly like
        :meth:`analyzed_flops`. None when the backend reports nothing."""
        from paddle_tpu.observability import memory as obs_memory
        snames, feed_sig = self._signature(feeds, stacked)
        key = ("mem", self._obs_tag, iterations, snames, feed_sig)
        hit, val = obs_memory.memory_cache_peek(key)
        if hit:
            return val
        return obs_memory.compiled_memory(
            None, cache_key=key,
            compiled=self._compile_thunk(scope, feeds, iterations, snames))

    def donation_audit(self, scope, feeds: Dict[str, Any]) -> dict:
        """Verify every mutated state var this block donates actually
        aliases in the compiled executable's input_output_alias header
        (jit-pruned vars are skipped, not flagged). Cached per feed
        signature; counts paddle_donation_violations_total on the first
        resolution. {program, expected, aliased, violations, skipped}."""
        from paddle_tpu.observability import memory as obs_memory
        key = ("audit", self._obs_tag, _feed_sig(feeds))
        hit, val = obs_memory.memory_cache_peek(key)
        if hit:
            return val
        compiled = self._compile_thunk(scope, feeds, 1, False)
        return obs_memory.donation_audit(
            lambda: compiled().as_text(), self.sig.state_names,
            program=self.obs_label, cache_key=key)

    def _input_shardings(self, dist=None):
        from jax.sharding import NamedSharding, PartitionSpec as P
        dist = dist if dist is not None else self.dist
        mesh = dist.mesh
        repl = NamedSharding(mesh, P())
        block = self.block

        # params (and embedding tables) sharded by explicit regex, by the
        # dist hint the embedding(is_distributed=True) layer recorded, or
        # by graph-derived role (DistributeConfig auto_shard: matmul/fc
        # weights column-parallel, lookup tables row-sharded)
        param_specs = {}
        all_params = set()
        names = tuple(self.sig.state_names) + tuple(self.sig.const_names)
        if hasattr(dist, "check_param_axes_matched"):
            dist.check_param_axes_matched(names)
        for n in names:
            axes = dist._axes_for(n, block)
            if axes is not None:
                param_specs[n] = axes
            if block.has_var(n) and block.var(n).is_parameter:
                all_params.add(n)

        def acc_base_param(name):
            """Optimizer accumulators are named '<param>_<kind>_N'
            (optimizer.py _add_accumulator) — find the owning param so
            moments shard exactly like their parameter."""
            best = None
            for p in all_params:
                if name != p and name.startswith(p + "_"):
                    if best is None or len(p) > len(best):
                        best = p
            return best

        zero_style = (dist.reduce_strategy == "reduce_scatter"
                      and dist.data_axis in mesh.axis_names)

        def param_sharding(name):
            axes = param_specs.get(name)
            if axes is None:
                base = acc_base_param(name)
                if base is not None and base in param_specs:
                    v = block.var(name) if block.has_var(name) else None
                    pv = block.var(base) if block.has_var(base) else None
                    if (v is not None and pv is not None
                            and v.shape == pv.shape):
                        axes = param_specs[base]
            if axes is not None:
                return NamedSharding(mesh, P(*axes))
            if zero_style and block.has_var(name):
                # kReduce/ZeRO parity: shard optimizer state over the data
                # axis (each dp shard owns a slice of the moments, like each
                # pserver owned a param block — distribute_transpiler.py:368
                # slice_var_up)
                v = block.var(name)
                is_acc = acc_base_param(name) is not None or \
                    (v.attrs or {}).get("optimizer_state", False)
                if (is_acc and v.shape and len(v.shape) >= 1 and v.shape[0]
                        and v.shape[0] > 0
                        and v.shape[0] % mesh.shape[dist.data_axis] == 0):
                    return NamedSharding(
                        mesh, P(dist.data_axis,
                                *([None] * (len(v.shape) - 1))))
            return repl

        def feed_sharding(name):
            axis = dist.data_axis
            if axis is None or axis not in mesh.axis_names:
                return repl
            v = self.block.var(name) if self.block.has_var(name) else None
            if v is not None and v.shape and len(v.shape) >= 1:
                d0 = v.shape[0]
                if d0 == -1 or d0 > 0:
                    # the batch dim shards whether declared dynamic (-1)
                    # or concrete. A non-divisible batch is no longer
                    # silently replicated (every device computing the
                    # full batch): the executor feed path pads the batch
                    # to the next data-axis multiple and slices the
                    # padded rows back off row-shaped fetches
                    # (utils/padding.py pad_feeds_to_multiple).
                    ndim = len(v.shape)
                    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))
            return repl

        state_sh = {n: param_sharding(n) for n in self.sig.state_names}
        const_sh = {n: param_sharding(n) for n in self.sig.const_names}
        feed_sh = {n: feed_sharding(n) for n in self.sig.feed_names}
        self._param_sharding_fn = param_sharding
        return (state_sh, const_sh, feed_sh, repl)

    def feed_dtype(self, name: str) -> Optional[str]:
        if self.block.has_var(name):
            return self.block.var(name).dtype
        return None

    def feed_sharding(self, name: str):
        if self.dist is None or self.dist.mesh is None:
            return None
        if not hasattr(self, "_feed_sh_cache"):
            self._feed_sh_cache = self._input_shardings()[2]
        return self._feed_sh_cache.get(name)

    def param_sharding(self, name: str):
        """Target sharding this compiled step assigns to a persistable —
        the ``sharding_fn`` for restore-with-resharding
        (fluid.sharded_io.load_sharded): restore a checkpoint directly
        into the layout the next mesh will train with."""
        if self.dist is None or self.dist.mesh is None:
            return None
        if not hasattr(self, "_param_sharding_fn"):
            self._input_shardings()
        return self._param_sharding_fn(name)

    def __call__(self, scope, feeds: Dict[str, Any], step_seed: int,
                 marks=None):
        """One step. ``marks`` (the executor's, while tracing) gets the
        perf_counter reading between the state gather and the jitted
        call: where ``executor.prepare`` ends and ``executor.dispatch``
        starts."""
        state, consts = self._resident_state(scope)
        if marks is not None:
            marks.append(time.perf_counter())
        args = (state, consts, feeds, np.uint32(step_seed))
        with _obs_runtime.dispatching(self.obs_label, self._exes.note,
                                      args):
            fetches, new_state = self.fn(*args)
        self._finish_dispatch(scope, new_state, consts)
        return fetches
