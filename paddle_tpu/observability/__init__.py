"""Runtime observability: metrics registry, structured tracing, step
telemetry, exporters.

The chaos-hardened control plane (retries, circuit breakers,
heartbeats, CRC-verified checkpoints — docs/robustness.md) is provable
in tests but was invisible in production. This package makes it
watchable:

- :mod:`~paddle_tpu.observability.metrics` — thread-safe registry of
  labeled Counter/Gauge/Histogram families, Prometheus-text + JSON
  rendering, one process-default registry;
- :mod:`~paddle_tpu.observability.tracing` — lock-protected,
  thread-id-aware span recorder (context manager / decorator) with
  chrome-trace/Perfetto export; ``fluid.profiler`` delegates here;
- :mod:`~paddle_tpu.observability.runtime` — per-compiled-step stats:
  step-time ring buffer → steps/s, examples/s, tokens/s gauges, and an
  MFU gauge from XLA's compiled-cost analysis (analytic-FLOPs
  fallback);
- :mod:`~paddle_tpu.observability.exporters` — background JSONL step
  log + Prometheus text file (``FLAGS_metrics_dump_path`` /
  ``FLAGS_metrics_dump_interval``) and an optional stdlib http scrape
  endpoint (``FLAGS_metrics_port``, with ``/healthz``);
- :mod:`~paddle_tpu.observability.trace_context` — W3C-traceparent
  style cross-process trace context (inject/extract on every JSON wire
  format) so spans parent correctly across processes;
- :mod:`~paddle_tpu.observability.spool` — crash-tolerant per-process
  span spool (``FLAGS_trace_spool_dir``), merged by
  ``tools/trace_collect.py`` into one Perfetto trace;
- :mod:`~paddle_tpu.observability.flight_recorder` — black-box ring of
  recent spans / metric deltas / fault fires, dumped on crash signals
  (``FLAGS_flight_recorder_dir``);
- :mod:`~paddle_tpu.observability.lock_witness` — runtime lock-order
  witness (``FLAGS_lock_witness``): ``ObservedLock`` validates the
  global lock DAG per acquisition, counting inversions and dumping
  both offending stacks through the flight recorder — the dynamic twin
  of the static ``ccy-lock-order-cycle`` lint;
- :mod:`~paddle_tpu.observability.pause_watch` — one thread that names
  the host's pauses (span ``host.pause``,
  ``paddle_host_pause*_total{cause}``); it exists only while a tracer,
  a span sink, step telemetry or an exporter listens.

Everything is off by default; with no observability flag set the hot
path pays one flag lookup per executor dispatch. Metric catalog and
label conventions: docs/observability.md.
"""

from __future__ import annotations

from paddle_tpu.observability import metrics  # noqa: F401
from paddle_tpu.observability import tracing  # noqa: F401
from paddle_tpu.observability import trace_context  # noqa: F401
from paddle_tpu.observability import runtime  # noqa: F401
from paddle_tpu.observability import exporters  # noqa: F401
from paddle_tpu.observability import spool  # noqa: F401
from paddle_tpu.observability import flight_recorder  # noqa: F401
from paddle_tpu.observability import lock_witness  # noqa: F401
from paddle_tpu.observability import memory  # noqa: F401
from paddle_tpu.observability import pause_watch  # noqa: F401
from paddle_tpu.observability.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, counter, default_registry,
    gauge, histogram)
from paddle_tpu.observability.tracing import (  # noqa: F401
    Tracer, default_tracer, span, trace)
from paddle_tpu.observability.trace_context import (  # noqa: F401
    TraceContext, extract, inject, new_trace)

_force_enabled = False


def enable():
    """Programmatically switch step telemetry on for this process (the
    flag-free path tests and bench use)."""
    global _force_enabled
    _force_enabled = True
    pause_watch.hold("telemetry")


def disable():
    global _force_enabled
    _force_enabled = False
    pause_watch.release("telemetry")


def enabled() -> bool:
    """True when step telemetry should be recorded: an observability
    flag is set (dump path / scrape port) or :func:`enable` was called.
    The executor checks this once per dispatch — with everything off
    the whole subsystem costs two flag lookups."""
    if _force_enabled:
        return True
    from paddle_tpu import flags
    return bool(flags.get("metrics_dump_path")) \
        or flags.get("metrics_port") >= 0
