"""Executor: the user-facing run(program, feed, fetch_list) engine.

Capability parity with the reference's `fluid.Executor`
(reference: python/paddle/fluid/executor.py:260 class, :447 run;
C++ framework/executor.cc:203 Executor::Run) — but where the reference
interprets the block op-by-op per call, this executor compiles the block
once per (program version, feed signature, fetch list) and replays the XLA
executable. Feed/fetch are native jit arguments/results rather than injected
feed_op/fetch_op pairs (executor.py:315) — the ops are still accepted in
programs for parity and skipped at lowering.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import numpy as np
import jax

from paddle_tpu import observability
from paddle_tpu.core import ir
from paddle_tpu.core.lowering import CompiledBlock
from paddle_tpu.core.scope import Scope, global_scope
from paddle_tpu.observability import memory as _obs_memory
from paddle_tpu.observability import tracing as _obs_tracing
from paddle_tpu.utils import faults as _faults


class Place:
    """Device tag (reference: platform/place.h Place variant)."""

    def __repr__(self):
        return type(self).__name__ + "()"


class CPUPlace(Place):
    pass


class TPUPlace(Place):
    """The new first-class place: BASELINE.json north star
    `fluid.Executor(place=TPUPlace())`."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id


class CUDAPlace(Place):  # accepted for API parity; maps to default backend
    def __init__(self, device_id: int = 0):
        self.device_id = device_id


class EOFException(Exception):
    """Raised by exe.run when an attached py_reader's epoch is exhausted
    (reference: fluid.core.EOFException from the reader ops' blocking
    queue — operators/reader/blocking_queue.h). Catch it, call
    reader.reset(), and continue to the next epoch."""


def _resolve_device(place: Optional[Place]):
    """The jax device a place names. Never a substitute: a place the
    host cannot honour raises (``jax.devices("cpu")`` itself raises when
    the process has no CPU backend)."""
    if isinstance(place, CPUPlace):
        return jax.devices("cpu")[0]
    devs = jax.devices()
    idx = getattr(place, "device_id", 0)
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"{type(place).__name__}({idx}): this process has "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[idx]


class Executor:
    """reference: executor.py:260. One instance per place; caches compiled
    executables keyed the way executor.py:222 keys its program cache."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place if place is not None else TPUPlace()
        self.device = _resolve_device(self.place)
        # programs without feeds (startup) have nothing committed to
        # follow: they are pinned to the place's device when it is not
        # the process default (CPUPlace on a TPU host, TPUPlace(k > 0))
        self._pin = self.device != jax.devices()[0]
        self._cache: Dict[Any, CompiledBlock] = {}
        self._step = 0

    def close(self):
        self._cache.clear()

    @staticmethod
    def _dist_key(dist):
        # key by content, not identity: a user mutating the (mutable)
        # DistributeConfig between runs must get a fresh compile
        if dist is None:
            return None
        return (dist.mesh, dist.data_axis, dist.model_axis, dist.sp_axis,
                getattr(dist, "pp_axis", None),
                getattr(dist, "ep_axis", None),
                tuple(sorted((k, tuple(v))
                             for k, v in (dist.param_axes or {}).items())),
                dist.reduce_strategy, getattr(dist, "auto_shard", True))

    def _compiled(self, program, feed_names, fetch_names, is_test: bool):
        desc = program.desc if hasattr(program, "desc") else program
        dist = getattr(program, "dist_config", None)
        # the HBM budget participates in sharding selection (the
        # dp->ZeRO->tp ladder runs at CompiledBlock build), so a changed
        # budget must recompile, not replay a plan chosen under the old one
        from paddle_tpu import flags as _flags
        budget = _flags.get("hbm_bytes") if dist is not None else None
        key = (desc.version_token, tuple(feed_names), tuple(fetch_names),
               is_test, self._dist_key(dist), budget)
        cb = self._cache.get(key)
        if cb is None:
            cb = CompiledBlock(desc, 0, feed_names, fetch_names,
                               is_test=is_test, dist=dist)
            self._cache[key] = cb
        return cb

    def run(self, program=None, feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[List[Any]] = None,
            feed_var_name: str = "feed", fetch_var_name: str = "fetch",
            scope: Optional[Scope] = None, return_numpy: bool = True,
            use_program_cache: bool = True, iterations: int = 1,
            stacked_feed=False):
        """reference: executor.py:447 — same signature contract.

        iterations > 1 runs that many steps in ONE device-side loop
        (lax.scan over donated state) — the amortized analogue of the
        reference's C++ interpreter hot loop (executor.cc:448), which on
        TPU removes the per-dispatch host cost that otherwise scales
        with the number of parameter buffers. `feed` is either one
        batch dict (resident batch reused each step) or a list of
        `iterations` batch dicts (stacked and scanned). Fetches come back
        stacked with a leading [iterations] axis.

        stacked_feed=True declares that `feed` is a DICT whose arrays
        already carry the leading [iterations] axis (e.g. a device-built
        batch-per-step tensor) — no host-side stacking. A LIST of feed
        names stacks only those (fresh per-step labels/ids over a
        resident image batch — avoids both the memorize-the-batch
        training artifact and the cost of stacking large float feeds).
        NOTE for stateless (inference) programs: a RESIDENT batch reused
        across the scan is loop-invariant and XLA computes the step once;
        benchmark such programs with per-step data (stacked feeds)."""
        # the ONE tracing check of a dispatch: every span below is
        # recorded from timestamps taken only when it was true
        trace_on = _obs_tracing.active()
        t_run = time.perf_counter() if trace_on else 0.0
        if program is None:
            from paddle_tpu.fluid import framework as fw
            program = fw.default_main_program()
        scope = scope or global_scope()
        fetch_list = fetch_list or []

        # attached py_readers supply the feed when none is given (the
        # reference's in-graph reader ops pulling their blocking queue;
        # raises EOFException at epoch end — fluid/layers/io.py PyReader)
        readers = getattr(program, "_py_readers", None)
        if not feed and readers:
            started = [r for r in readers if r._queue is not None]
            if started:
                def pull_one():
                    # pull a batch from every reader; if one fails
                    # midway (EOF or a provider error), push the
                    # already-pulled parts back so no batch is lost
                    pulled = []
                    try:
                        for r in started:
                            pulled.append((r, r._next_feed()))
                    except BaseException:
                        for r, fd in pulled:
                            r._push_back(fd)
                        raise
                    f = {}
                    for _, fd in pulled:
                        f.update(fd)
                    return f

                if iterations > 1:
                    # one fresh batch per scanned step; a short epoch
                    # tail shrinks the window (EOF only when empty)
                    feeds, eof = [], None
                    for _ in range(iterations):
                        try:
                            feeds.append(pull_one())
                        except EOFException as e:
                            eof = e
                            break
                    if not feeds:
                        raise eof
                    feed, iterations = feeds, len(feeds)
                else:
                    feed = pull_one()

        # BuildStrategy IR passes run once, right before compilation —
        # the reference's BuildStrategy::Apply moment (CompiledProgram
        # carries the strategy; the pass pipeline bumps the program
        # version so the executable cache recompiles)
        apply_bs = getattr(program, "_apply_build_strategy", None)
        if apply_bs is not None:
            apply_bs(scope)

        stacked = isinstance(feed, (list, tuple))
        if stacked:
            if len(feed) != iterations:
                raise ValueError(
                    f"feed list has {len(feed)} batches but iterations="
                    f"{iterations}")
            if iterations == 1:
                # single-step with a 1-element feed list: unwrap, no
                # stacking (the single-step executable takes plain batches)
                feed, stacked = feed[0], False
            else:
                feed = {n: np.stack([np.asarray(b[n]) for b in feed])
                        for n in feed[0]}
        elif stacked_feed:
            if iterations <= 1:
                raise ValueError("stacked_feed requires iterations>1")
            if stacked_feed is True:
                check = (feed or {}).items()
            else:
                if isinstance(stacked_feed, str):
                    stacked_feed = [stacked_feed]
                missing = [n for n in stacked_feed if n not in (feed or {})]
                if missing:
                    raise ValueError(
                        f"stacked_feed names {missing} are not in the "
                        f"feed dict (feeds: {sorted(feed or {})})")
                check = [(n, feed[n]) for n in stacked_feed]
            for n, v in check:
                shape = np.shape(v)
                if not shape or shape[0] != iterations:
                    raise ValueError(
                        f"stacked_feed: {n!r} leading dim "
                        f"{shape[0] if shape else '<scalar>'} != "
                        f"iterations {iterations}")
            stacked = True if stacked_feed is True else \
                sorted(set(stacked_feed))
        feed = feed or {}

        fetch_names = [v if isinstance(v, str) else v.name for v in fetch_list]
        feed_names = sorted(feed)
        is_test = bool(getattr(program, "_is_test", False))

        # sharded-table id translation (ops/embed_cache.py): feeds that
        # carry vocab ids into a __sharded__-marked table are rewritten
        # to cache SLOT ids host-side, after the cache pulls any cold
        # rows from their owning shard — the jitted step below only ever
        # sees in-range slots over the static-shape cache array (the
        # zero-steady-state-recompile construction)
        _caches = getattr(getattr(program, "desc", None),
                          "_embed_caches", None)
        if _caches and feed:
            translated = None
            for fname, cache in _caches.items():
                if fname in feed:
                    if translated is None:
                        translated = dict(feed)
                    translated[fname] = cache.translate(
                        feed[fname], train=not is_test)
            if translated is not None:
                feed = translated

        cb = self._compiled(program, feed_names, fetch_names, is_test)

        feeds = {}
        dist_mode = cb.dist is not None and cb.dist.mesh is not None
        multi_host = dist_mode and jax.process_count() > 1

        def stacked_sharding(name):
            """Per-step feed sharding with the [iterations] axis
            prepended (matches CompiledBlock._multi_fn's in_shardings)."""
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = cb.feed_sharding(name)
            return NamedSharding(cb.dist.mesh, P(None, *sh.spec))

        def is_stacked(name):
            return stacked is True or (isinstance(stacked, list)
                                       and name in stacked)

        # pad-and-slice for the data axis: a batch whose (per-step) batch
        # dim is not divisible by the mesh data axis used to be silently
        # replicated to every device (the old feed_sharding fallback);
        # now the batch pads to the next multiple by repeating the last
        # row (always-valid inputs), shards normally, and the padded rows
        # are sliced back off row-shaped fetches below. Batch-REDUCED
        # fetches (a mean loss) see the padded rows — exactness there
        # needs a divisible batch (utils/padding.py).
        pad_plan = None
        if dist_mode:
            axis = cb.dist.data_axis
            axis_size = (cb.dist.mesh.shape[axis]
                         if axis in cb.dist.mesh.axis_names else 1)
            if axis_size > 1:
                from paddle_tpu.utils import padding as _padding
                plan = _padding.PadPlan()
                padded_feed = None
                for name in feed_names:
                    sh = cb.feed_sharding(name)
                    spec = getattr(sh, "spec", None) or ()
                    if not len(spec) or spec[0] != axis:
                        continue
                    bdim = 1 if is_stacked(name) else 0
                    shape = np.shape(feed[name])
                    if len(shape) <= bdim or shape[bdim] % axis_size == 0:
                        continue
                    arr = np.asarray(feed[name])
                    n = arr.shape[bdim]
                    target = _padding.next_multiple(n, axis_size)
                    pads = [(0, 0)] * arr.ndim
                    pads[bdim] = (0, target - n)
                    if padded_feed is None:
                        padded_feed = dict(feed)
                    padded_feed[name] = np.pad(arr, pads, mode="edge")
                    plan.note(n, target)
                if padded_feed is not None:
                    feed = padded_feed
                    pad_plan = plan
                    import warnings
                    warnings.warn(
                        f"batch dim not divisible by data axis "
                        f"{axis!r} (size {axis_size}); padding "
                        f"{dict(plan.pairs)} by repeating the last row "
                        f"— row-shaped fetches are sliced back, but "
                        f"batch-REDUCED fetches (a mean loss) and state "
                        f"updates see the padded rows; feed a divisible "
                        f"batch for exactness")

        for name in feed_names:
            val = feed[name]
            want = cb.feed_dtype(name)
            if is_stacked(name) and multi_host:
                sh = stacked_sharding(name)
                if isinstance(val, jax.Array):
                    # mirror the single-step global-array contract below:
                    # pass through when correctly sharded, refuse a
                    # cross-host reshard, host-copy only addressable
                    # committed arrays
                    if want is not None and str(val.dtype) != want:
                        val = val.astype(want)
                    if val.sharding == sh:
                        feeds[name] = val
                        continue
                    if not val.is_fully_addressable:
                        raise ValueError(
                            f"stacked feed {name!r} is a global jax.Array "
                            f"with a different sharding than the program "
                            f"expects ({val.sharding} vs {sh}); reshard "
                            f"it on the producer side")
                # every process feeds the same stacked global batch; the
                # callback slices this host's shard (same convention as
                # the single-step multi-host path below)
                arr = np.asarray(val)
                if want is not None and str(arr.dtype) != want:
                    arr = arr.astype(want)
                feeds[name] = jax.make_array_from_callback(
                    arr.shape, sh, lambda idx, a=arr: a[idx])
                continue
            if isinstance(val, jax.Array) and multi_host:
                want_sh = cb.feed_sharding(name)
                if want is not None and str(val.dtype) != want:
                    # dtype-only mismatch: astype is sharding-preserving,
                    # so fix it device-side even for global arrays
                    val = val.astype(want)
                if val.sharding == want_sh:
                    # correctly-sharded global array (prefetched pipeline
                    # batch) — pass straight through
                    feeds[name] = val
                    continue
                if not val.is_fully_addressable:
                    raise ValueError(
                        f"feed {name!r} is a global jax.Array with a "
                        f"different sharding than the program expects "
                        f"({val.sharding} vs {want_sh}); reshard it on the "
                        f"producer side — cross-host resharding inside "
                        f"exe.run is not supported")
                # host-local committed array: round-trip through the host
                # copy and take the global-array path below
                val = np.asarray(val)
            if isinstance(val, jax.Array):
                # already on device (e.g. a prefetched pipeline batch or a
                # benchmark-resident tensor) — keep it device-side, but
                # still honour the declared dtype and, under a mesh,
                # reshard (device-to-device) to the stacked-aware feed
                # sharding so a committed single-device array doesn't
                # clash with in_shardings
                if want is not None and str(val.dtype) != want:
                    val = val.astype(want)
                sh = None
                if dist_mode:
                    sh = (stacked_sharding(name) if is_stacked(name)
                          else cb.feed_sharding(name))
                if sh is not None:
                    try:
                        same = val.sharding.is_equivalent_to(sh, val.ndim)
                    except Exception:
                        same = val.sharding == sh
                    if not same:
                        # committed single-device (or differently-sharded)
                        # feed moving to the program's layout: a real
                        # device-to-device reshard, counted
                        try:
                            from paddle_tpu.observability import (
                                spmd as _obs_spmd)
                            _obs_spmd.note_resharding(
                                cb.obs_label,
                                int(getattr(val, "nbytes", 0) or 0))
                        except Exception:
                            pass
                    val = jax.device_put(val, sh)
                feeds[name] = val
                continue
            arr = np.asarray(val)
            if want is not None and str(arr.dtype) != want:
                arr = arr.astype(want)
            if dist_mode:
                if multi_host:
                    # multi-host: jit refuses numpy with non-trivial
                    # shardings — build the global jax.Array here. Every
                    # process feeds the same global batch (the reference's
                    # nccl2-mode convention: same program, rank-split
                    # happens inside), so the callback slices the local
                    # shard out of the host copy.
                    sh = cb.feed_sharding(name)
                    feeds[name] = jax.make_array_from_callback(
                        arr.shape, sh, lambda idx, a=arr: a[idx])
                else:
                    # jit's in_shardings places/shards the host array itself
                    feeds[name] = arr
            else:
                feeds[name] = jax.device_put(arr, self.device)

        from paddle_tpu import flags
        bench = flags.get("benchmark")
        obs_on = observability.enabled()
        # HBM telemetry shares the step sampler's contract: this call is
        # the subsystem's ENTIRE cost when off (one flag lookup)
        mem_on = _obs_memory.enabled()
        if obs_on:
            # flags asked for telemetry: idempotently bring up the dump
            # thread / scrape endpoint (no-op bool check after the first)
            from paddle_tpu.observability import exporters as _obs_exp
            _obs_exp.ensure_started()
        if bench:
            t0 = time.time()
        t_dispatch = time.perf_counter()
        # span recorded only under an active profiler or telemetry —
        # the flags-unset hot path pays nothing here (<2% overhead
        # contract on the bench step loop)
        span = (_obs_tracing.span("executor.run", iterations=iterations)
                if (obs_on or trace_on)
                else contextlib.nullcontext())
        on_place = (jax.default_device(self.device) if self._pin
                    else contextlib.nullcontext())
        # the block stamps the end of its state gather here
        marks = [] if trace_on else None
        try:
            with span, on_place:
                # chaos site: the OOM-forensics test arms
                # 'executor.dispatch:raise@1:exc=MemoryError' here
                _faults.inject("executor.dispatch")
                if iterations > 1:
                    seed0 = self._step + 1
                    self._step += iterations
                    outs = cb.run_steps(scope, feeds, seed0, iterations,
                                        stacked=stacked, marks=marks)
                else:
                    self._step += 1
                    outs = cb(scope, feeds, self._step, marks=marks)
                if marks:
                    # executor.prepare: entry of run() to the jitted
                    # call (feed conversion and placement, the state
                    # gather); executor.dispatch: the call and the
                    # write-back, until the async dispatch returns
                    tracer = _obs_tracing.default_tracer()
                    tracer.record("executor.prepare", t_run, marks[0])
                    tracer.record("executor.dispatch", marks[0],
                                  time.perf_counter())
        except Exception as e:
            # RESOURCE_EXHAUSTED forensics: write the memdump (top live
            # buffers + the failing program's compiled breakdown)
            # through the flight-recorder path, then let the OOM
            # propagate. oom_dump gates itself and never raises.
            if _obs_memory.is_oom_error(e):
                _obs_memory.oom_dump(cb, scope, e, feeds=feeds,
                                     iterations=iterations,
                                     stacked=stacked)
            raise
        if bench:
            # dispatch wall time (async: device completion lands later;
            # reference capability: FLAGS_benchmark per-run executor timing)
            print(f"[FLAGS_benchmark] run dispatch {time.time() - t0:.4f}s "
                  f"iterations={iterations} feeds={len(feed_names)} "
                  f"fetches={len(fetch_names)}")
        if _check_nan_inf_enabled():
            # FLAGS_check_nan_inf capability (reference: operator.cc:978-990
            # scans every op output per step). Here outputs are fused, so
            # the debug scan covers fetches + every updated state var —
            # the observable surface of the compiled step.
            for name, o in zip(fetch_names, outs):
                _assert_finite(name, o)
            for name in cb.sig.state_names:
                v = scope.find_var(name)
                if v is not None:
                    _assert_finite(name, v)
        if pad_plan is not None:
            # slice the padded rows back off batch-shaped fetches (batch
            # dim is axis 1 for stacked multi-step fetches). Only fetches
            # whose DECLARED leading dim is dynamic (-1) are sliced — a
            # fetch whose fixed extent coincidentally equals the padded
            # batch (a [8, D] weight under a padded-to-8 batch) must
            # come back untouched
            bdim = 1 if iterations > 1 else 0
            sliced = []
            for name, o in zip(fetch_names, outs):
                shape = np.shape(o)
                v = cb.block.var(name) if cb.block.has_var(name) else None
                batch_shaped = (v is not None and v.shape
                                and len(v.shape) >= 1 and v.shape[0] == -1)
                orig = (pad_plan.pairs.get(shape[bdim])
                        if batch_shaped and len(shape) > bdim else None)
                if orig is not None:
                    o = o[(slice(None),) * bdim + (slice(0, orig),)]
                sliced.append(o)
            outs = sliced
        if return_numpy:
            outs = [np.asarray(o) for o in outs]   # D2H sync point
        else:
            outs = list(outs)
        if obs_on and return_numpy:
            # step-time sample covers dispatch + the D2H fetch — the
            # per-step wall time a training loop sees. return_numpy=
            # False hands back ASYNC device handles: elapsed would be
            # dispatch-only (microseconds) and the steps/s / MFU gauges
            # would read garbage (>1 MFU), so those dispatches are not
            # sampled — callers that fence themselves (bench.py) publish
            # their own measured window instead.
            self._record_telemetry(
                cb, program, scope, feeds, feed_names, iterations,
                stacked, time.perf_counter() - t_dispatch)
        if mem_on:
            self._record_memory(cb, scope, feeds, iterations, stacked)
        return outs

    def _record_memory(self, cb, scope, feeds, iterations, stacked):
        """Per-dispatch HBM telemetry (observability.memory): compiled
        breakdown gauges, live-buffer census + watermark, and a one-time
        donation audit per compiled block. Every compiled query is
        cached per jit signature, so steady state is gauge sets plus one
        scope walk. Never raises."""
        try:
            _obs_memory.set_compiled_gauges(
                cb.obs_label,
                cb.analyzed_memory(scope, feeds, iterations, stacked))
        except Exception:
            pass
        try:
            if not getattr(cb, "_mem_params_noted", False):
                cb._mem_params_noted = True
                _obs_memory.note_params(
                    n for n in (tuple(cb.sig.state_names)
                                + tuple(cb.sig.const_names))
                    if cb.block.has_var(n)
                    and cb.block.var(n).is_parameter)
            _obs_memory.record_census(scope)
        except Exception:
            pass
        if cb._donate:
            try:
                cb.donation_audit(scope, feeds)
            except Exception:
                pass

    def _record_telemetry(self, cb, program, scope, feeds, feed_names,
                          iterations, stacked, elapsed_s):
        """One step-stats sample per dispatch (observability.runtime):
        step time, examples inferred from the feed batch dim, and the
        MFU numerator from compiled-cost analysis with the analytic
        model-FLOP walk as fallback. Never raises."""
        from paddle_tpu.observability import runtime as obs_runtime
        # batch size = the most common leading dim across feeds (data +
        # label share it; a stray lr scalar or lengths vector can't win
        # the vote the way first-feed-wins would let it)
        votes: Dict[int, int] = {}
        for name in feed_names:
            shape = getattr(feeds.get(name), "shape", None)
            if not shape:
                continue
            is_st = stacked is True or (isinstance(stacked, list)
                                        and name in stacked)
            dim = (shape[1] if len(shape) > 1 else None) if is_st \
                else shape[0]
            if dim:
                votes[int(dim)] = votes.get(int(dim), 0) + 1
        examples = max(votes, key=votes.get) if votes else None
        flops = None
        try:
            flops = cb.analyzed_flops(scope, feeds, iterations, stacked)
        except Exception:
            flops = None
        if flops is None and examples:
            # analytic fallback, cached on the compiled block — the IR
            # walk over every op must not run once per dispatch
            cache = getattr(cb, "_analytic_flops", None)
            if cache is None:
                cache = cb._analytic_flops = {}
            flops = cache.get(int(examples), "miss")
            if flops == "miss":
                try:
                    from paddle_tpu.utils import flops as flops_mod
                    flops = flops_mod.program_flops(
                        program, int(examples)) or None
                except Exception:
                    flops = None
                cache[int(examples)] = flops
        try:
            obs_runtime.record_dispatch(
                elapsed_s / max(iterations, 1), steps=iterations,
                examples=int(examples) if examples else None,
                flops_per_step=flops)
        except Exception:
            pass
        # sparse-apply sites registered at trace time by the row-sparse
        # optimizer path (core/selected_rows.record_sparse_apply):
        # rows-touched counts advance once per dispatched step
        try:
            desc = program.desc if hasattr(program, "desc") else program
            sites = getattr(desc, "_sparse_sites", None)
            if sites:
                from paddle_tpu.observability import metrics as obs_metrics
                fam = obs_metrics.counter(
                    "paddle_sparse_rows_touched_total",
                    "embedding-table rows (incl. duplicates) carried by "
                    "row-sparse gradients into the sparse optimizer "
                    "apply, per param", ("param",))
                for pname, (k, _height) in sites.items():
                    fam.labels(param=pname).inc(k * iterations)
        except Exception:
            pass


# convenience used by tests and io
def run_startup(startup_program, scope: Optional[Scope] = None,
                place: Optional[Place] = None):
    exe = Executor(place)
    exe.run(startup_program, scope=scope)
    return exe


def _check_nan_inf_enabled() -> bool:
    """FLAGS_check_nan_inf via the unified registry (paddle_tpu.flags;
    reference gflags re-export convention, python __init__.py:125)."""
    from paddle_tpu import flags
    return flags.get("check_nan_inf")


def _assert_finite(name: str, arr):
    a = np.asarray(arr)
    if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
        n_nan = int(np.isnan(a).sum())
        n_inf = int(np.isinf(a).sum())
        raise FloatingPointError(
            f"check_nan_inf: variable {name!r} has {n_nan} NaN / {n_inf} "
            f"Inf values (shape {a.shape})")
