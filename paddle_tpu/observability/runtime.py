"""Per-compiled-step runtime stats: step-time ring buffer → steps/s,
examples/s, tokens/s gauges, plus an MFU gauge.

The executor records one sample per *dispatch* (a dispatch covers
``iterations`` device-side steps under the lax.scan hot loop, so the
per-sample overhead amortizes to nothing); the ring buffer holds the
last ``window`` samples and the throughput gauges are recomputed from
the window on every record — an operator scraping /metrics sees a
moving-average rate, not a lifetime mean.

Compile stages are program counters too: ONE process-wide
``jax.monitoring`` duration listener (:func:`install_compile_listener`)
feeds ``paddle_compile_seconds_total`` / ``paddle_compile_events_total``
by ``{stage, program}``, where ``program`` is the compiled block being
dispatched on the listening thread (:func:`dispatching`), ``other``
outside any dispatch.

MFU comes from XLA's own compiled-computation cost analysis
(``jit_fn.lower(...).compile().cost_analysis()['flops']``, the
per-signature truth about what the compiler actually emitted), cached
per jit signature; when the backend reports no FLOPs the analytic
model-FLOP walk (``utils/flops.py``, 2 FLOPs/MAC, backward = 2x
forward) is the fallback. The peak-FLOP/s denominator is the attached
chip's spec-sheet number (``utils.flops.device_peak_flops``) or the
``FLAGS_peak_flops`` override (how CPU runs and tests get a real MFU
value instead of null).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, Optional

from paddle_tpu.observability import metrics
from paddle_tpu.observability import trace_context as _tctx

STEPS_TOTAL = metrics.counter(
    "paddle_steps_total", "Training/executor steps dispatched")
STEP_TIME = metrics.gauge(
    "paddle_step_time_seconds", "Wall time per step, last dispatch "
    "(dispatch time / iterations; includes D2H sync when the caller "
    "fetched numpy)")
STEPS_PER_S = metrics.gauge(
    "paddle_steps_per_second", "Steps/s over the ring-buffer window")
EXAMPLES_PER_S = metrics.gauge(
    "paddle_examples_per_second", "Examples/s over the ring-buffer window")
TOKENS_PER_S = metrics.gauge(
    "paddle_tokens_per_second", "Tokens/s over the ring-buffer window "
    "(0 until a caller declares tokens-per-example)")
MFU = metrics.gauge(
    "paddle_mfu_ratio", "Model FLOPs Utilization in [0,1]: achieved "
    "FLOP/s over peak (FLAGS_peak_flops or the chip spec sheet); 0 when "
    "no peak is known")

COMPILE_SECONDS = metrics.counter(
    "paddle_compile_seconds_total",
    "Wall seconds jax spent per compile stage: trace (the Python of "
    "the lowering rules), lower (jaxpr to MLIR), backend_compile (XLA, "
    "or the persistent cache's load when it hits), cache_load (the "
    "cache retrieval alone, inside backend_compile). Nested events "
    "count once, so a stage never exceeds the wall time it ran in",
    labelnames=("stage", "program"))
COMPILE_EVENTS = metrics.counter(
    "paddle_compile_events_total",
    "jax compile-stage events by the program being dispatched "
    "(CompiledBlock.obs_label; 'other' outside any dispatch). "
    "stage=backend_compile counts jit-cache misses",
    labelnames=("stage", "program"))


class StepStats:
    """Ring buffer of (step_time_s, steps, examples, tokens, flops)
    samples; recomputes the throughput/MFU gauges on every record."""

    def __init__(self, window: int = 256):
        self._lock = threading.Lock()
        self._ring = deque(maxlen=int(window))
        self._peak_flops: Optional[float] = None
        self._peak_resolved = False
        self.total_steps = 0

    # -- peak-FLOPs denominator -----------------------------------------
    def _peak(self) -> Optional[float]:
        from paddle_tpu import flags
        override = flags.get("peak_flops")
        if override:
            return float(override)
        if not self._peak_resolved:
            self._peak_resolved = True
            from paddle_tpu.utils import flops as flops_mod
            self._peak_flops = flops_mod.device_peak_flops()
        return self._peak_flops

    # -- recording -------------------------------------------------------
    def record(self, step_time_s: float, steps: int = 1,
               examples: Optional[int] = None,
               tokens: Optional[int] = None,
               flops_per_step: Optional[float] = None) -> dict:
        """Record one dispatch of ``steps`` device steps that took
        ``step_time_s`` seconds *per step*. Returns the snapshot dict the
        step-JSONL exporter appends (one line per dispatch)."""
        with self._lock:
            self._ring.append((float(step_time_s), int(steps),
                               examples, tokens, flops_per_step))
            self.total_steps += int(steps)
            secs = sum(r[0] * r[1] for r in self._ring)
            n = sum(r[1] for r in self._ring)
            ex = sum((r[2] or 0) * r[1] for r in self._ring)
            tok = sum((r[3] or 0) * r[1] for r in self._ring)
            total = self.total_steps
        steps_s = n / secs if secs > 0 else 0.0
        examples_s = ex / secs if secs > 0 else 0.0
        tokens_s = tok / secs if secs > 0 else 0.0
        STEPS_TOTAL.inc(steps)
        STEP_TIME.set(step_time_s)
        STEPS_PER_S.set(steps_s)
        EXAMPLES_PER_S.set(examples_s)
        TOKENS_PER_S.set(tokens_s)
        mfu = None
        peak = self._peak()
        if peak and flops_per_step and step_time_s > 0:
            mfu = flops_per_step / step_time_s / peak
            MFU.set(mfu)
        return {"step": total, "step_time_s": step_time_s,
                "steps_per_s": round(steps_s, 4),
                "examples_per_s": round(examples_s, 2),
                "tokens_per_s": round(tokens_s, 2), "mfu": mfu}

    def reset(self):
        with self._lock:
            self._ring.clear()
            self.total_steps = 0


_DEFAULT = StepStats()


def step_stats() -> StepStats:
    return _DEFAULT


def record_dispatch(step_time_s: float, steps: int = 1,
                    examples: Optional[int] = None,
                    tokens: Optional[int] = None,
                    flops_per_step: Optional[float] = None):
    """Record into the process-default :class:`StepStats` and hand the
    per-dispatch record to the step-JSONL exporter (no-op unless the
    dump thread is running)."""
    rec = _DEFAULT.record(step_time_s, steps, examples=examples,
                          tokens=tokens, flops_per_step=flops_per_step)
    from paddle_tpu.observability import exporters
    exporters.offer_step_record(rec)
    return rec


# -- compiled-cost FLOPs (cached per jit signature) -----------------------

_COST_CACHE: Dict[Any, Optional[float]] = {}
_COST_LOCK = threading.Lock()
_COST_CACHE_MAX = 4096     # bound: long-lived processes churning
# compiled blocks (per-shape serving compiles) must not grow this
# forever — dicts iterate in insertion order, so eviction is FIFO


def cost_cache_peek(key: Any):
    """(hit, value) for a compiled-cost cache key — lets callers skip
    argument gathering entirely once a signature is resolved."""
    with _COST_LOCK:
        if key in _COST_CACHE:
            return True, _COST_CACHE[key]
    return False, None


def compiled_flops(jit_fn, *args, cache_key: Any = None,
                   per_call_steps: int = 1,
                   compiled=None) -> Optional[float]:
    """Per-step FLOPs of ``jit_fn`` specialized to ``args``, from XLA's
    compiled-cost analysis. ``cache_key`` identifies the jit signature
    (callers pass their executable-cache key); the lower/compile round
    trip runs once per key — jax's internal caches make it cheap when
    the signature was already compiled by a real call. A caller that
    keeps its executables (``CompiledBlock._compile_thunk``) passes
    ``compiled``, a callable that returns the one for this key, in place
    of ``jit_fn`` and ``args``. Returns None when the backend reports no
    FLOPs (callers fall back to the analytic walk in
    ``utils/flops.py``)."""
    key = cache_key if cache_key is not None else id(jit_fn)
    with _COST_LOCK:
        if key in _COST_CACHE:
            return _COST_CACHE[key]
    flops: Optional[float] = None
    try:
        exe = compiled() if compiled is not None \
            else jit_fn.lower(*args).compile()
        cost = exe.cost_analysis()
        if isinstance(cost, (list, tuple)):   # older jax: one per device
            cost = cost[0] if cost else {}
        raw = float(cost.get("flops", 0.0) or 0.0)
        # some backends report -1/0 for "unknown"
        if raw > 0:
            flops = raw / max(int(per_call_steps), 1)
    except Exception:
        flops = None
    with _COST_LOCK:
        while len(_COST_CACHE) >= _COST_CACHE_MAX:
            _COST_CACHE.pop(next(iter(_COST_CACHE)))
        _COST_CACHE[key] = flops
    return flops


def mfu_ratio(flops_per_step: Optional[float], step_time_s: float,
              device=None) -> Optional[float]:
    """MFU in [0,1] from per-step FLOPs + step time, against
    FLAGS_peak_flops (override) or the attached chip's spec-sheet peak.
    None when either side is unknown."""
    if not flops_per_step or step_time_s <= 0:
        return None
    from paddle_tpu import flags
    peak = float(flags.get("peak_flops")) or None
    if peak is None:
        from paddle_tpu.utils import flops as flops_mod
        peak = flops_mod.device_peak_flops(device)
    if not peak:
        return None
    return flops_per_step / step_time_s / peak


# -- compile stages (one jax.monitoring listener) -------------------------

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
OTHER_PROGRAM = "other"
# per thread and stage. One traced program fires ~10 000 nested trace
# events before its own arrives and absorbs them, so the bound is far
# above that: a guard on memory, not a working size. Past it the older
# half collapses into one interval, and only an event that starts inside
# that half (a caller of over 65 000 nested jits) would be miscounted
_MAX_INTERVALS = 1 << 17

# per thread: the program being dispatched, and per stage the disjoint
# (start, end, seconds counted inside) intervals already seen
_compile_local = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False
# (stage, program) -> (events child, seconds child): labels() costs a
# lock and a key build, and one traced program fires tens of thousands
# of nested trace events
_children: Dict[Any, Any] = {}


class dispatching:
    """``with dispatching(cb.obs_label, note, args): jitted(*args)`` —
    names the program for the compile events of this thread, and, where
    a backend compile happens inside, hands ``note(fun_name, args)`` the
    arguments it compiled for (``CompiledBlock`` keeps their shapes and
    placement: ``lowering._Executables.note``). A handful of attribute
    stores per dispatch, nothing else; the previous dispatch comes back
    on exit, so a jit outside any counts under ``other``."""

    __slots__ = ("program", "note", "args", "_prev")

    def __init__(self, program: str, note=None, args=None):
        self.program = program
        self.note = note
        self.args = args

    def __enter__(self):
        self._prev = getattr(_compile_local, "dispatch", None)
        _compile_local.dispatch = self

    def __exit__(self, *exc):
        _compile_local.dispatch = self._prev


def _own_seconds(stage: str, start: float, end: float) -> float:
    """Seconds of [start, end) not yet counted for ``stage`` on this
    thread. jax fires a nested jit's event before its caller's and
    inside the caller's duration, so events arrive ordered by their end
    and nest like calls: when a caller's event arrives, the intervals
    that started inside it are its callees', they leave the list, and
    the caller is reduced by the seconds counted in them. A stage's
    total is then wall time, counted once. The list holds what no caller
    has absorbed yet, each interval with the seconds counted inside."""
    by_stage = getattr(_compile_local, "intervals", None)
    if by_stage is None:
        by_stage = _compile_local.intervals = {}
    done = by_stage.get(stage)
    if done is None:
        done = by_stage[stage] = []
    own = end - start
    held = 0.0                  # all the seconds of what is absorbed
    while done and done[-1][1] > start:
        a, b, counted = done.pop()
        held += counted
        if a < start:
            # straddles the start (two clock readings apart): only what
            # lies inside reduces the caller; intervals stay disjoint
            counted = min(counted, b - start)
            start = a
        own -= counted
    own = max(own, 0.0)
    done.append((start, end, held + own))
    if len(done) > _MAX_INTERVALS:
        half = len(done) // 2
        done[:half] = [(done[0][0], done[half - 1][1],
                        sum(c for _a, _b, c in done[:half]))]
    return own


def _on_compile_event(event: str, duration: float, **kw) -> None:
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None:
        return
    now = time.perf_counter()
    dispatch = getattr(_compile_local, "dispatch", None)
    key = (stage, OTHER_PROGRAM if dispatch is None else dispatch.program)
    if stage == "backend_compile" and dispatch is not None \
            and dispatch.note is not None:
        dispatch.note(kw.get("fun_name"), dispatch.args)
    pair = _children.get(key)
    if pair is None:
        pair = _children[key] = (COMPILE_EVENTS.labels(*key),
                                 COMPILE_SECONDS.labels(*key))
    pair[0].inc()
    pair[1].inc(_own_seconds(stage, now - duration, now))
    if _tctx.active():
        # retroactive: lands inside the serving.prefill@P or
        # executor.run span whose dispatch compiled
        _tctx.record_span("compile." + stage, now - duration, now,
                          ctx=_tctx.current(), program=key[1],
                          fun=kw.get("fun_name"))


def install_compile_listener() -> None:
    """Register the process's one compile listener (idempotent; jax has
    no way to unregister a single listener, so it stays)."""
    global _listener_installed
    if _listener_installed:
        return
    with _listener_lock:
        if _listener_installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        _listener_installed = True


def backend_compile_count() -> int:
    """jit-cache misses (``backend_compile_duration`` events) seen
    process-wide since the listener was installed; installs it. Flat
    across a window means no recompile in it."""
    install_compile_listener()
    return int(sum(child.value for (stage, _program), child
                   in COMPILE_EVENTS.children().items()
                   if stage == "backend_compile"))
