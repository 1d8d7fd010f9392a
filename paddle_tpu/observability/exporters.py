"""Telemetry exporters: background file dumper + Prometheus scrape
endpoint.

- :class:`MetricsDumper` — a daemon thread that, every
  ``FLAGS_metrics_dump_interval`` seconds, appends the step records the
  runtime produced since the last tick to ``<dump_path>/steps.jsonl``
  (one JSON object per dispatch: step, step_time_s, steps/s,
  examples/s, tokens/s, mfu) and atomically rewrites
  ``<dump_path>/metrics.prom`` with the full registry in Prometheus
  text format. ``stop()``/``flush()`` force a final write, and an
  atexit hook flushes on interpreter exit — a short training run never
  loses its tail to the interval.
- :class:`MetricsServer` — an optional stdlib ``http.server`` scrape
  endpoint (``GET /metrics``) on ``FLAGS_metrics_port``. The server
  socket binds at construction (port 0 = ephemeral, read ``.port``
  back), so there is no pick-a-port-then-rebind TOCTOU window — same
  discipline as ``utils/net.bound_listener``.

:func:`ensure_started` is the one idempotent entry point the executor
pokes when observability flags are set; it also pre-imports every
instrumented module so the exported catalog is complete from the first
scrape (master-lease, pserver-retry, checkpoint-CRC counters render at
zero instead of popping into existence at their first event).
"""

from __future__ import annotations

import atexit
import json
import os
import threading
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from paddle_tpu.observability import metrics
from paddle_tpu.observability import pause_watch

STEP_LOG_NAME = "steps.jsonl"
PROM_NAME = "metrics.prom"

# step records offered by runtime.record_dispatch, drained by the dump
# thread; bounded so a run without a dumper (or a stalled disk) cannot
# grow memory — oldest records drop first
_STEP_QUEUE: deque = deque(maxlen=65536)
_lock = threading.Lock()
_dumper: Optional["MetricsDumper"] = None
_server: Optional["MetricsServer"] = None
_started_from_flags = False
_ready_probe = None


def set_ready_probe(fn) -> None:
    """Register the process's readiness callable for ``GET /readyz``
    (``None`` clears it). Distinct from ``/healthz`` the same way the
    replica wire protocol splits them (docs/serving.md): healthz says
    "this process serves HTTP", readyz says "send me traffic" — false
    during warmup and while draining. With no probe registered /readyz
    answers 200 like /healthz (a process with no warmup phase is ready
    the moment it serves). A probe that returns falsy OR raises answers
    503 — a broken probe must read as not-ready, never as ready."""
    global _ready_probe
    _ready_probe = fn


def offer_step_record(rec: dict):
    """Called by ``runtime.record_dispatch`` for every dispatch; cheap
    append (the dump thread serializes to disk). Dropped when no dumper
    exists — scrape-endpoint-only mode must not retain 65k records for
    a consumer that will never drain them."""
    if _dumper is not None:
        _STEP_QUEUE.append(rec)


class MetricsDumper:
    """Background JSONL-step-log + Prometheus-text-file writer."""

    def __init__(self, dump_dir: str, interval_s: float = 10.0,
                 registry: Optional[metrics.MetricsRegistry] = None):
        self.dump_dir = dump_dir
        self.interval_s = max(float(interval_s), 0.05)
        self.registry = registry or metrics.default_registry()
        os.makedirs(dump_dir, exist_ok=True)
        self._stop = threading.Event()
        self._wlock = threading.Lock()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="paddle-metrics-dump")
        self._thread.start()

    @property
    def step_log_path(self) -> str:
        return os.path.join(self.dump_dir, STEP_LOG_NAME)

    @property
    def prom_path(self) -> str:
        return os.path.join(self.dump_dir, PROM_NAME)

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.flush()
            except OSError:
                pass          # disk trouble must not kill the thread

    def flush(self):
        """Drain pending step records to the JSONL log and rewrite the
        Prometheus snapshot (atomic tmp+rename, so a scraper of the
        file never reads a torn snapshot). A failed write re-queues the
        drained records — a transient disk error costs a delay, not an
        interval of telemetry."""
        with self._wlock:
            lines = []
            while True:
                try:
                    lines.append(_STEP_QUEUE.popleft())
                except IndexError:
                    break
            try:
                if lines:
                    # one buffered write: a failure requeues the whole
                    # batch (at-least-once — a duplicate line is only
                    # possible if the OS partially persisted the single
                    # write, which beats silently losing the interval)
                    buf = "".join(json.dumps(rec) + "\n" for rec in lines)
                    with open(self.step_log_path, "a") as f:
                        f.write(buf)
                    lines = []
                tmp = self.prom_path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(self.registry.render_prometheus())
                os.replace(tmp, self.prom_path)
            finally:
                for rec in reversed(lines):   # failed write: requeue,
                    # without evicting newer records from a full deque
                    if len(_STEP_QUEUE) >= (_STEP_QUEUE.maxlen or 0):
                        break
                    _STEP_QUEUE.appendleft(rec)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        try:
            self.flush()
        except OSError:
            pass


class _ScrapeHandler(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 - http.server API
        route = self.path.split("?")[0]
        if route == "/healthz":
            # liveness probe for process-launch tests / orchestrators:
            # no registry render, just "this process serves HTTP"
            body = b"ok\n"
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if route == "/readyz":
            probe = _ready_probe
            try:
                ready = True if probe is None else bool(probe())
            except Exception:
                ready = False
            body = b"ready\n" if ready else b"not ready\n"
            self.send_response(200 if ready else 503)
            self.send_header("Content-Type", "text/plain; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if route == "/memory":
            # on-demand HBM snapshot (observability.memory): census
            # families, top buffers, watermark history — JSON, so an
            # operator can jq it without a Prometheus stack
            try:
                from paddle_tpu.observability import memory
                body = json.dumps(memory.snapshot(), default=str,
                                  sort_keys=True).encode()
            except Exception as e:
                self.send_error(500, str(e))
                return
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if route not in ("/metrics", "/"):
            self.send_error(404)
            return
        body = self.server.registry.render_prometheus().encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # quiet: no per-scrape stderr spam
        pass


class MetricsServer:
    """Prometheus scrape endpoint on a socket bound AT CONSTRUCTION
    (port 0 picks an ephemeral port; read ``.port`` back) — no TOCTOU
    window between choosing the port and serving on it."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 registry: Optional[metrics.MetricsRegistry] = None):
        self._httpd = ThreadingHTTPServer((host, port), _ScrapeHandler)
        self._httpd.daemon_threads = True
        # __lint_suppress__: ccy-unlocked-shared-write -- writes to the just-constructed HTTPServer before its serve thread starts (the lint matches .registry to MetricsDumper by attr name)
        self._httpd.registry = (registry  # type: ignore[attr-defined]
                                or metrics.default_registry())
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            kwargs={"poll_interval": 0.1}, daemon=True,
            name="paddle-metrics-http")
        self._thread.start()

    @property
    def endpoint(self) -> str:
        return f"{self.host}:{self.port}"

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def _preregister_catalog():
    """Import every instrumented module so its metric families exist in
    the registry before the first snapshot — the operator's scrape shows
    the full catalog at zero, and the acceptance contract (master-lease
    / pserver-retry / checkpoint-CRC counters present in the text
    snapshot of ANY observed run) holds without those paths firing."""
    import importlib
    for mod in ("paddle_tpu.observability.runtime",
                # the pause watch's two (paddle_host_pauses_total,
                # paddle_host_pause_seconds_total{cause})
                "paddle_tpu.observability.pause_watch",
                # HBM memory families (paddle_hbm_*, paddle_donation_*,
                # paddle_oom_*): compiled breakdowns, census gauges,
                # donation violations, OOM events
                "paddle_tpu.observability.memory",
                # the tracer's ring-overflow counter
                # (paddle_trace_dropped_spans_total) — silent span loss
                # is a lying timeline, so it's part of the catalog
                "paddle_tpu.observability.tracing",
                # SPMD families (paddle_spmd_*): mesh size and the
                # entry-reshard byte counter that witnesses
                # device-resident state (docs/performance.md)
                "paddle_tpu.observability.spmd",
                "paddle_tpu.distributed.resilience",
                "paddle_tpu.distributed.async_pserver",
                "paddle_tpu.data.master_service",
                "paddle_tpu.data.pipeline",
                "paddle_tpu.fluid.sharded_io",
                "paddle_tpu.fluid.io",
                # the model-server families (paddle_serving_*): request
                # latency/outcomes, queue depth, batch occupancy, the
                # zero-steady-state compile counter, and the predictor's
                # AOT-fallback counter — import-light (docs/serving.md);
                # the page pool's too, the full layers' rows attended
                # and gathered and paddle_kv_row_bytes{group} among them
                "paddle_tpu.serving.metrics",
                # sharded embedding tables: hot-rows cache hit/miss/
                # eviction/occupancy and per-shard wire bytes
                # (docs/performance.md 'Sharded embedding tables')
                "paddle_tpu.ops.embed_cache",
                # which tier the paged K/V gather was lowered to
                # (paddle_kv_gather_lowered_total{path}), what attends
                # a grouped-KV prefill
                # (paddle_gqa_prefill_attend_lowered_total{path}) and
                # how a full layer's decode attends
                # (paddle_kv_decode_attend_lowered_total{path})
                "paddle_tpu.ops.kv_attention",
                # which way a latent-attention decode layer attends
                # (paddle_mla_decode_lowered_total{path})
                "paddle_tpu.ops.mla",
                # whose weights an expert layer's dense way streams
                # (paddle_expert_dense_lowered_total{path}) and what
                # runs its grouped way's products
                # (paddle_expert_grouped_lowered_total{path})
                "paddle_tpu.ops.expert_ffn",
                # which tier advances a KDA decode layer's state
                # (paddle_kda_decode_lowered_total{path})
                "paddle_tpu.ops.kda",
                # what walks the rows of a Mamba-1 layer's prefill
                # (paddle_s6_scan_lowered_total{path}) and what updates
                # its decode step's state
                # (paddle_s6_state_lowered_total{path})
                "paddle_tpu.ops.s6",
                # what runs a fused attention block's core
                # (paddle_attention_block_lowered_total{path, d_head})
                "paddle_tpu.ops.nn_ops",
                # what a causal flash kernel's grid visits, computes and
                # masks (paddle_flash_causal_blocks_total{kernel, kind})
                "paddle_tpu.ops.pallas.flash_attention",
                # what a recomputed op's backward keeps beside its inputs
                # (paddle_recompute_kept_values_total{op}, _bytes_total)
                "paddle_tpu.ops.grad_ops",
                "paddle_tpu.distributed.sharded_table"):
        try:
            importlib.import_module(mod)
        except Exception:     # a broken optional module must not kill
            pass              # telemetry for the rest
    try:
        # analyzer families (paddle_analysis_*) declare lazily per run;
        # force them into the catalog so a scrape shows them at zero
        from paddle_tpu.analysis import rules as _analysis_rules
        _analysis_rules.declare_metrics()
    except Exception:
        pass
    try:
        # cross-view program-contract checks (paddle_analysis_contract_
        # checks_total): each validate_geometry / verify_family run
        # counts here — zero on a scrape means the verifier never ran
        from paddle_tpu.analysis import contracts as _contracts
        _contracts.declare_metrics()
    except Exception:
        pass
    try:
        # runtime lock-order witness (paddle_lock_witness_violations_
        # total): the chaos suites assert this stays zero; a non-zero
        # scrape in prod is a latent-deadlock page
        from paddle_tpu.observability import lock_witness as _lock_witness
        _lock_witness.declare_metrics()
    except Exception:
        pass
    try:
        # autotune-cache families (paddle_autotune_*): cache hit/miss
        # per region kind, and the measurement counter whose zero-ness
        # IS the CI determinism contract
        from paddle_tpu.passes import autotune as _autotune
        _autotune.declare_metrics()
    except Exception:
        pass


def ensure_started() -> bool:
    """Idempotently start the exporters the flags ask for
    (FLAGS_metrics_dump_path / FLAGS_metrics_dump_interval /
    FLAGS_metrics_port). Called by the executor when observability is
    enabled; safe to call every step (one attribute check once running).
    Never raises — a misconfigured exporter (port in use, unwritable
    dump dir) warns once and latches off instead of failing every
    training step. With no exporter flag set nothing latches, so flags
    set later in the process are still honored. Returns True once
    anything is running."""
    global _dumper, _server, _started_from_flags
    if _dumper is not None or _server is not None:
        return True
    if _started_from_flags:       # a prior attempt failed: stay off
        return False              # (shutdown() un-latches)
    from paddle_tpu import flags
    dump_path = flags.get("metrics_dump_path")
    port = flags.get("metrics_port")
    if not dump_path and port < 0:
        # nothing requested: don't latch (flags set later are honored)
        # and don't take the lock — the enable()-without-flags path hits
        # this every dispatch and must stay two env lookups, no lock
        return False
    with _lock:
        if _dumper is not None or _server is not None:
            return True
        if _started_from_flags:
            return False
        _preregister_catalog()
        import warnings
        if dump_path:
            try:
                _dumper = MetricsDumper(
                    dump_path, flags.get("metrics_dump_interval"))
            except Exception as e:
                warnings.warn(f"metrics dump thread disabled: cannot "
                              f"start on {dump_path!r}: {e!r}")
        if port >= 0:
            try:
                _server = MetricsServer(port=port,
                                        host=flags.get("metrics_host"))
            except Exception as e:
                warnings.warn(f"metrics scrape endpoint disabled: "
                              f"cannot bind port {port}: {e!r}")
        _started_from_flags = True
        if _dumper is not None or _server is not None:
            pause_watch.hold("exporters")
            return True
        return False


def active_dumper() -> Optional[MetricsDumper]:
    return _dumper


def active_server() -> Optional[MetricsServer]:
    return _server


def flush():
    """Force the dump files current (tests; end-of-run hooks)."""
    if _dumper is not None:
        _dumper.flush()


def shutdown():
    """Stop the flag-started exporters and allow a later
    :func:`ensure_started` to re-read the flags (tests toggle the flags
    between runs)."""
    global _dumper, _server, _started_from_flags
    with _lock:
        if _dumper is not None:
            _dumper.stop()
            _dumper = None
        if _server is not None:
            _server.stop()
            _server = None
        _started_from_flags = False
    pause_watch.release("exporters")


@atexit.register
def _flush_at_exit():        # pragma: no cover - interpreter teardown
    try:
        flush()
    except Exception:
        pass
