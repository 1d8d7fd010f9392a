"""Per-compiled-step runtime stats: step-time ring buffer → steps/s,
examples/s, tokens/s gauges, plus an MFU gauge.

The executor records one sample per *dispatch* (a dispatch covers
``iterations`` device-side steps under the lax.scan hot loop, so the
per-sample overhead amortizes to nothing); the ring buffer holds the
last ``window`` samples and the throughput gauges are recomputed from
the window on every record — an operator scraping /metrics sees a
moving-average rate, not a lifetime mean.

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
from collections import deque
from typing import Any, Dict, Optional

from paddle_tpu.observability import metrics

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
                   per_call_steps: int = 1) -> Optional[float]:
    """Per-step FLOPs of ``jit_fn`` specialized to ``args``, from XLA's
    compiled-cost analysis. ``cache_key`` identifies the jit signature
    (callers pass their executable-cache key); the lower/compile round
    trip runs once per key — jax's internal caches make it cheap when
    the signature was already compiled by a real call. Returns None when
    the backend reports no FLOPs (callers fall back to the analytic walk
    in ``utils/flops.py``)."""
    key = cache_key if cache_key is not None else id(jit_fn)
    with _COST_LOCK:
        if key in _COST_CACHE:
            return _COST_CACHE[key]
    flops: Optional[float] = None
    try:
        cost = jit_fn.lower(*args).compile().cost_analysis()
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
