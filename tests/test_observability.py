"""Observability subsystem (paddle_tpu.observability): registry
concurrency, histogram bucket semantics, Prometheus/JSON golden
formats, exporter round-trip, scrape endpoint, the MFU gauge, and the
end-to-end acceptance contract — a CPU train run with
FLAGS_metrics_dump_path set produces a step JSONL (step_time,
examples/s, MFU) and a Prometheus text snapshot carrying the
master-lease / pserver-retry / checkpoint-CRC counters."""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from paddle_tpu.observability import exporters, metrics, runtime, tracing


@pytest.fixture(autouse=True)
def _clean_exporters():
    """Exporter state (dump thread, scrape server) is process-global and
    flag-driven; every test here starts and ends with it torn down."""
    exporters.shutdown()
    yield
    exporters.shutdown()


# -- registry -------------------------------------------------------------

def test_counter_concurrency_exact():
    """N threads incrementing labeled counters lose no update."""
    reg = metrics.MetricsRegistry()
    fam = reg.counter("t_conc_total", "c", labelnames=("op",))
    threads, per = 8, 2000

    def work(op):
        child = fam.labels(op=op)
        for _ in range(per):
            child.inc()

    ts = [threading.Thread(target=work, args=("a" if i % 2 else "b",))
          for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert fam.labels(op="a").value == per * threads / 2
    assert fam.labels(op="b").value == per * threads / 2


def test_family_get_or_create_and_conflicts():
    reg = metrics.MetricsRegistry()
    a = reg.counter("t_fam_total", "x", labelnames=("k",))
    assert reg.counter("t_fam_total", "x", labelnames=("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("t_fam_total", "x", labelnames=("k",))
    with pytest.raises(ValueError):
        reg.counter("t_fam_total", "x")          # different label set
    h = reg.histogram("t_fam_seconds", "h", buckets=(0.1, 1.0))
    assert reg.histogram("t_fam_seconds", "h", buckets=(0.1, 1.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("t_fam_seconds", "h", buckets=(60.0, 300.0))
    with pytest.raises(ValueError):
        a.labels(wrong="v")
    with pytest.raises(ValueError):
        a.inc()                                  # labeled family: no proxy
    with pytest.raises(ValueError):
        a.labels(k="v").inc(-1)                  # counters only go up


def test_histogram_bucket_semantics():
    """Cumulative 'le' buckets: an exact-bound observation counts in
    that bucket; overflow lands only in +Inf."""
    reg = metrics.MetricsRegistry()
    h = reg.histogram("t_h_seconds", "h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 99.0):
        h.observe(v)
    buckets = dict(h.labels().cumulative_buckets())
    assert buckets[0.01] == 2          # 0.005 and the exact 0.01
    assert buckets[0.1] == 3
    assert buckets[1.0] == 4
    assert buckets[float("inf")] == 5
    assert h.labels().count == 5
    assert abs(h.labels().sum - 99.565) < 1e-9


def test_prometheus_render_golden():
    reg = metrics.MetricsRegistry()
    c = reg.counter("t_req_total", "requests", labelnames=("code",))
    c.labels(code="200").inc(3)
    g = reg.gauge("t_depth", "queue depth")
    g.set(2)
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    text = reg.render_prometheus()
    assert text == (
        "# HELP t_depth queue depth\n"
        "# TYPE t_depth gauge\n"
        "t_depth 2\n"
        "# HELP t_lat_seconds latency\n"
        "# TYPE t_lat_seconds histogram\n"
        't_lat_seconds_bucket{le="0.1"} 1\n'
        't_lat_seconds_bucket{le="1"} 1\n'
        't_lat_seconds_bucket{le="+Inf"} 1\n'
        "t_lat_seconds_sum 0.05\n"
        "t_lat_seconds_count 1\n"
        "# HELP t_req_total requests\n"
        "# TYPE t_req_total counter\n"
        't_req_total{code="200"} 3\n')


def test_json_snapshot_shape():
    reg = metrics.MetricsRegistry()
    reg.counter("t_c_total", "c", labelnames=("op",)).labels(op="x").inc()
    reg.gauge("t_g", "g").set(1.25)
    snap = json.loads(reg.snapshot_json())
    assert snap["t_c_total"]["type"] == "counter"
    assert snap["t_c_total"]["samples"] == [
        {"labels": {"op": "x"}, "value": 1}]
    assert snap["t_g"]["samples"][0]["value"] == 1.25


def test_histogram_timer():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("t_timer_seconds", "t")
    with h.time():
        pass
    assert h.labels().count == 1 and h.labels().sum >= 0


# -- tracing + the profiler thread-safety fix -----------------------------

def test_tracer_concurrent_spans_carry_real_tids():
    """Satellite: concurrent record_event calls are race-free and spans
    carry real thread ids, so the chrome trace no longer stacks every
    thread on tid 0."""
    from paddle_tpu.fluid import profiler
    profiler.reset_profiler()
    profiler.start_profiler()
    threads, per = 6, 300
    barrier = threading.Barrier(threads)   # all alive at once, so
    # thread idents are guaranteed distinct (idents recycle after exit)

    def work():
        barrier.wait()
        for _ in range(per):
            with profiler.record_event("concurrent_ev"):
                pass

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stats = tracing.default_tracer().event_stats()
    assert stats["concurrent_ev"]["calls"] == threads * per
    trace = tracing.default_tracer().to_chrome_trace()
    tids = {e["tid"] for e in trace["traceEvents"]
            if e["name"] == "concurrent_ev"}
    assert len(tids) == threads, f"expected {threads} tids, got {tids}"
    profiler.stop_profiler(profile_path=os.devnull)
    profiler.reset_profiler()


def test_profiler_export_spans_tid_column(tmp_path):
    """export_spans rows carry the tid in column 4 and round-trip
    through spans_to_chrome_trace (tools/trace_collect.py --profile_path input format)."""
    import csv
    from paddle_tpu.fluid import profiler
    profiler.reset_profiler()
    profiler.start_profiler()
    with profiler.record_event("tid_ev"):
        pass
    path = str(tmp_path / "spans.csv")
    profiler.export_spans(path)
    profiler.stop_profiler(profile_path=os.devnull)
    rows = [r for r in csv.reader(open(path))]
    assert rows and len(rows[0]) == 4
    assert int(rows[0][3]) == threading.get_ident()
    trace = profiler.spans_to_chrome_trace(rows)
    assert trace["traceEvents"][0]["tid"] == threading.get_ident()
    profiler.reset_profiler()


def test_span_decorator_and_args():
    tracer = tracing.Tracer()
    tracer.start()

    @tracer.trace("labeled")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    with tracer.span("with_args", step=3):
        pass
    spans = {s.name: s for s in tracer.spans()}
    assert set(spans) == {"labeled", "with_args"}
    assert spans["with_args"].args == {"step": 3}
    assert tracer.to_chrome_trace()["traceEvents"][1]["args"] == {"step": 3}


def test_tracer_span_cap():
    tracer = tracing.Tracer(max_spans=3)
    tracer.start()
    for _ in range(5):
        with tracer.span("s"):
            pass
    assert len(tracer.spans()) == 3 and tracer.dropped_spans == 2
    assert tracer.event_stats()["s"]["calls"] == 5   # aggregates keep all


# -- exporters ------------------------------------------------------------

def test_dumper_roundtrip(tmp_path, monkeypatch):
    reg = metrics.MetricsRegistry()
    reg.counter("t_dump_total", "d").inc(7)
    d = exporters.MetricsDumper(str(tmp_path), interval_s=30.0,
                                registry=reg)
    # records are dropped unless a dumper is active (scrape-only mode
    # must not retain an undrained queue) — register this one
    monkeypatch.setattr(exporters, "_dumper", d)
    exporters.offer_step_record({"step": 1, "step_time_s": 0.5})
    exporters.offer_step_record({"step": 2, "step_time_s": 0.25})
    d.flush()
    lines = [json.loads(l) for l in
             open(d.step_log_path).read().splitlines()]
    assert [l["step"] for l in lines] == [1, 2]
    assert "t_dump_total 7" in open(d.prom_path).read()
    # a second flush appends nothing (queue drained) and keeps the file
    d.stop()
    assert len(open(d.step_log_path).read().splitlines()) == 2


def test_scrape_endpoint_ephemeral_port():
    """The scrape server binds its socket AT construction (port 0 →
    ephemeral, read .port back) — the bound_listener discipline, no
    pick-a-port-then-rebind TOCTOU window."""
    reg = metrics.MetricsRegistry()
    reg.gauge("t_scrape", "s").set(42)
    srv = exporters.MetricsServer(port=0, registry=reg)
    try:
        assert srv.port > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "t_scrape 42" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.stop()


def test_scrape_endpoint_readyz_probe():
    """GET /readyz reflects the registered readiness probe (200/503),
    defaults to ready with no probe, and a RAISING probe reads as
    not-ready — the replica/router lifecycle split (readyz distinct
    from healthz) surfaced to HTTP orchestrators."""
    srv = exporters.MetricsServer(port=0)
    url = f"http://127.0.0.1:{srv.port}/readyz"
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200          # no probe: ready once serving
        ready = [False]
        exporters.set_ready_probe(lambda: ready[0])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=5)
        assert ei.value.code == 503
        ready[0] = True
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200 and r.read() == b"ready\n"

        def boom():
            raise RuntimeError("probe crashed")
        exporters.set_ready_probe(boom)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=5)
        assert ei.value.code == 503, "a broken probe must read not-ready"
        # /healthz stays liveness-only: up even while readyz is 503
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            assert r.status == 200
    finally:
        exporters.set_ready_probe(None)
        srv.stop()


# -- runtime: step stats + MFU --------------------------------------------

def test_step_stats_rates_and_ring():
    st = runtime.StepStats(window=4)
    rec = None
    for _ in range(6):
        rec = st.record(0.1, steps=2, examples=32, tokens=640)
    # 0.1 s/step → 10 steps/s; 32 examples & 640 tokens per step
    assert rec["steps_per_s"] == pytest.approx(10.0)
    assert rec["examples_per_s"] == pytest.approx(320.0)
    assert rec["tokens_per_s"] == pytest.approx(6400.0)
    assert st.total_steps == 12


def test_mfu_gauge_on_tiny_jitted_matmul():
    """MFU sanity: the compiled-cost-analysis FLOPs of a jitted matmul
    match the analytic 2*M*K*N within 2x, and the gauge lands in (0, 1]
    against the FLAGS_peak_flops denominator."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import flags

    m = k = n = 64
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    f(a, b).block_until_ready()
    flops = runtime.compiled_flops(f, a, b, cache_key="test_matmul")
    analytic = 2.0 * m * k * n
    assert flops is not None and 0.5 * analytic <= flops <= 2 * analytic
    # cached per signature: second call returns the same object fast
    assert runtime.compiled_flops(f, a, b,
                                  cache_key="test_matmul") == flops
    flags.set("peak_flops", 1e9)
    try:
        mfu = runtime.mfu_ratio(flops, step_seconds := flops / 1e9)
        assert mfu == pytest.approx(1.0)
        st = runtime.StepStats()
        rec = st.record(step_seconds, steps=1, examples=m,
                        flops_per_step=flops)
        assert rec["mfu"] == pytest.approx(1.0)
        assert runtime.MFU.value == pytest.approx(1.0)
    finally:
        flags.reset("peak_flops")
    assert runtime.mfu_ratio(None, 1.0) is None
    assert runtime.mfu_ratio(1e9, 0.0) is None


# -- acceptance: end-to-end CPU train run ---------------------------------

def test_e2e_train_run_dumps_step_jsonl_and_prom(tmp_path):
    """Acceptance: a single CPU train run with FLAGS_metrics_dump_path
    set produces a step JSONL (step_time, examples/s, MFU) and a
    Prometheus text snapshot containing the master-lease, pserver-retry,
    and checkpoint-CRC counters — plus a live scrape endpoint."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags

    dump = str(tmp_path / "telemetry")
    flags.set("metrics_dump_path", dump)
    flags.set("metrics_dump_interval", 30.0)   # flush() drives the files
    flags.set("metrics_port", 0)
    flags.set("peak_flops", 1e12)              # real MFU value on CPU
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, 8))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed={"x": np.ones((16, 4), np.float32)},
                    fetch_list=[loss])
        # checkpoint through the instrumented save path too
        fluid.io.save_persistables(exe, str(tmp_path / "ckpt"), main)
        exporters.flush()

        lines = [json.loads(l) for l in
                 open(os.path.join(dump, "steps.jsonl"))
                 .read().splitlines()]
        assert len(lines) >= 3
        train_recs = [l for l in lines if l["examples_per_s"] > 0]
        assert train_recs, lines
        for rec in train_recs:
            assert rec["step_time_s"] > 0
        assert any(r["mfu"] is not None and r["mfu"] > 0
                   for r in train_recs)

        prom = open(os.path.join(dump, "metrics.prom")).read()
        for name in ("paddle_master_leases_granted_total",      # lease
                     "paddle_master_leases_failed_back_total",
                     "paddle_pserver_rpc_retries_total",        # retry
                     "paddle_retry_attempts_total",
                     "paddle_checkpoint_crc_failures_total",    # CRC
                     "paddle_checkpoint_save_seconds",
                     "paddle_steps_total", "paddle_mfu_ratio"):
            assert name in prom, name
        # the save above moved the checkpoint histograms
        snap = metrics.default_registry().snapshot()
        save = snap["paddle_checkpoint_save_seconds"]["samples"]
        assert any(s["labels"].get("layout") == "plain"
                   and s["count"] >= 1 for s in save)

        srv = exporters.active_server()
        assert srv is not None and srv.port > 0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert "paddle_steps_total" in body
    finally:
        for f in ("metrics_dump_path", "metrics_dump_interval",
                  "metrics_port", "peak_flops"):
            flags.reset(f)


def test_disabled_flags_record_nothing(tmp_path):
    """With observability flags unset the executor records no step
    samples (the <2% overhead contract: one enabled() check per
    dispatch, nothing else)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability

    assert not observability.enabled()
    before = runtime.step_stats().total_steps
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(x, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[y])
    assert runtime.step_stats().total_steps == before
    assert exporters.active_dumper() is None
    assert exporters.active_server() is None
