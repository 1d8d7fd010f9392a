"""Distributed tracing (ISSUE 12): W3C-style trace-context propagation
through every JSON/tuple wire format the repo owns, lifecycle spans in
serving, the span spool + tools/trace_collect.py merge, latency
exemplars, the dropped-span counter, and the percentile/scrape edge
cases the observability suite did not cover."""

import json
import math
import os
import threading
import time
import urllib.request
import warnings

import numpy as np
import pytest

from paddle_tpu.observability import exporters, metrics
from paddle_tpu.observability import spool
from paddle_tpu.observability import trace_context as tctx
from paddle_tpu.observability import tracing


@pytest.fixture(autouse=True)
def _clean_tracer():
    """The default tracer (ring + sinks) is process-global; every test
    here starts and ends with a clean one."""
    t = tracing.default_tracer()
    t.stop()
    t.reset()
    yield
    t.stop()
    t.reset()
    t._sinks.clear()
    spool.shutdown()


class _capture:
    """Attach a list-collecting sink for the with-block (spans are
    captured without enabling the in-memory ring)."""

    def __enter__(self):
        self.spans = []
        tracing.add_sink(self.spans.append)
        return self.spans

    def __exit__(self, *exc):
        tracing.remove_sink(self.spans.append)


# -- trace context / wire format -----------------------------------------

def test_traceparent_roundtrip_and_malformed():
    ctx = tctx.new_trace()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    back = tctx.from_traceparent(ctx.to_traceparent())
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    # a hostile/stale peer never breaks parsing
    for bad in ("", "garbage", "00-zz-xx-01", "00-abc-def-01",
                "00-" + "a" * 32 + "-" + "b" * 16, None, 7):
        assert tctx.from_traceparent(bad) is None


def test_inject_extract_wire_discipline():
    msg = {"method": "ping"}
    tctx.inject(msg)
    assert "traceparent" not in msg      # wire unchanged when off
    assert tctx.extract(msg) is None
    ctx = tctx.new_trace()
    with tctx.activate(ctx):
        tctx.inject(msg)
    got = tctx.extract(msg)
    assert got.trace_id == ctx.trace_id
    assert got.span_id == ctx.span_id
    assert tctx.current() is None        # activate restored


def test_span_autoparenting_chain():
    with _capture() as spans:
        with tctx.span("outer") as octx:
            assert tctx.current() is octx
            with tctx.span("inner") as ictx:
                assert ictx.parent_id == octx.span_id
                assert ictx.trace_id == octx.trace_id
        assert tctx.current() is None
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["inner"].trace_id == by_name["outer"].trace_id


def test_tracer_span_parents_under_active_context():
    """tracing.span (the Tracer API used by executor/master internals)
    parents under the thread's activated TraceContext."""
    ctx = tctx.new_trace()
    with _capture() as spans:
        with tctx.activate(ctx):
            with tracing.span("executor.run"):
                pass
    (s,) = spans
    assert s.trace_id == ctx.trace_id
    assert s.parent_id == ctx.span_id


def test_span_is_noop_when_tracing_off():
    with tctx.span("nothing") as ctx:
        assert ctx is None
    assert tracing.default_tracer().spans() == []


def test_sink_captures_without_filling_ring():
    with _capture() as spans:
        with tctx.span("only_sinks"):
            pass
    assert [s.name for s in spans] == ["only_sinks"]
    assert tracing.default_tracer().spans() == []   # ring stays empty


# -- dropped spans (silent-loss fix) -------------------------------------

def test_dropped_spans_counter_and_one_time_warning():
    t = tracing.Tracer(max_spans=2)
    t.start()
    c0 = tracing.DROPPED_SPANS.value
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for i in range(5):
            t.record(f"s{i}", 0.0, 1.0)
    assert len(t.spans()) == 2
    assert t.dropped_spans == 3
    assert tracing.DROPPED_SPANS.value - c0 == 3
    warned = [x for x in w if "tracer ring full" in str(x.message)]
    assert len(warned) == 1              # one-time, not per span
    assert issubclass(warned[0].category, RuntimeWarning)


# -- exemplars ------------------------------------------------------------

def test_histogram_exemplars_and_lookup():
    from paddle_tpu.serving import metrics as smetrics
    reg = metrics.MetricsRegistry()
    h = reg.histogram("t_ex_seconds", "h", buckets=(0.1, 1.0),
                      labelnames=("model",))
    h.labels(model="m").observe(0.05)            # no exemplar
    assert h.labels(model="m").exemplars() == {}
    assert smetrics.histogram_exemplar(h, model="m") is None
    h.labels(model="m").observe(0.05, exemplar="t-fast")
    h.labels(model="m").observe(5.0, exemplar="t-slow")
    ex = h.labels(model="m").exemplars()
    assert ex[0.1] == "t-fast"
    assert ex[float("inf")] == "t-slow"
    # the p99-outlier recipe: highest populated bucket wins
    assert smetrics.histogram_exemplar(h, model="m") == "t-slow"
    assert smetrics.histogram_exemplar(h, bucket="0.1",
                                       model="m") == "t-fast"
    # snapshot carries exemplars additively (shape unchanged otherwise)
    sample = reg.snapshot()["t_ex_seconds"]["samples"][0]
    assert sample["exemplars"]["inf"] == "t-slow"
    plain = reg.histogram("t_plain_seconds", "h", buckets=(1.0,))
    plain.observe(0.5)
    assert "exemplars" not in \
        reg.snapshot()["t_plain_seconds"]["samples"][0]


# -- percentile edge cases (satellite c) ---------------------------------

def test_percentile_edge_cases():
    from paddle_tpu.serving import metrics as smetrics
    reg = metrics.MetricsRegistry()
    h = reg.histogram("t_pct_seconds", "h", buckets=(0.1, 1.0),
                      labelnames=("model",))
    # empty: 0.0, not a crash
    assert smetrics.histogram_percentile(h, 0.5, model="m") == 0.0
    assert smetrics.histogram_percentile(h, 0.99, model="m") == 0.0
    # single populated bucket: every quantile is its upper bound
    h.labels(model="m").observe(0.05)
    assert smetrics.histogram_percentile(h, 0.01, model="m") == 0.1
    assert smetrics.histogram_percentile(h, 0.99, model="m") == 0.1
    # all-overflow: lands in +Inf only
    h2 = reg.histogram("t_pct2_seconds", "h", buckets=(0.1, 1.0))
    for _ in range(4):
        h2.observe(50.0)
    assert math.isinf(smetrics.histogram_percentile(h2, 0.5))


def test_latency_percentile_empty_is_zero():
    from paddle_tpu.serving import metrics as smetrics
    assert smetrics.latency_percentile("no_such_model", 0.99) == 0.0
    assert smetrics.queue_wait_percentile("no_such_model", 0.5) == 0.0


# -- scrape endpoint (satellites b/c/e) ----------------------------------

def test_scrape_healthz_and_dropped_spans_preregistered():
    exporters.shutdown()
    exporters._preregister_catalog()
    srv = exporters.MetricsServer(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            assert r.status == 200
            assert r.read() == b"ok\n"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            body = r.read().decode()
        # the silent-loss fix: visible at zero before any drop
        assert "paddle_trace_dropped_spans_total" in body
    finally:
        srv.stop()


def test_scrape_endpoint_mid_flush():
    """Scraping while observations hammer the registry returns a
    parseable, internally consistent exposition every time."""
    reg = metrics.MetricsRegistry()
    h = reg.histogram("t_flush_seconds", "h", buckets=(0.1, 1.0))
    srv = exporters.MetricsServer(port=0, registry=reg)
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            h.observe((i % 100) / 10.0)
            i += 1

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        for _ in range(20):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=5) as r:
                body = r.read().decode()
            counts = {}
            for line in body.splitlines():
                if line.startswith("t_flush_seconds_bucket"):
                    le = line.split('le="')[1].split('"')[0]
                    counts[le] = float(line.rsplit(" ", 1)[1])
                elif line.startswith("t_flush_seconds_count"):
                    counts["count"] = float(line.rsplit(" ", 1)[1])
            # cumulative buckets are monotone and +Inf == count
            assert counts["0.1"] <= counts["1"] <= counts["+Inf"]
            assert counts["+Inf"] == counts["count"]
    finally:
        stop.set()
        t.join(timeout=5)
        srv.stop()


# -- serving: queue-wait histogram, exemplars, RPC propagation -----------

def _clf_server(tmp_path, name):
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import layers
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        prob = layers.softmax(layers.fc(x, size=4))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / name)
    os.makedirs(d, exist_ok=True)
    fluid.io.save_inference_model(d, ["x"], [prob], exe,
                                  main_program=main)
    sm = serving.ServedModel(name, d, serving.BucketPolicy((1, 2)))
    server = serving.ModelServer()
    server.add_model(sm)
    return server


def test_queue_wait_histogram_and_lifecycle_spans(tmp_path):
    from paddle_tpu import serving  # noqa: F401 - built via _clf_server
    from paddle_tpu.serving import metrics as smetrics
    server = _clf_server(tmp_path, "clf_qw")
    qw = smetrics.QUEUE_WAIT.labels(model="clf_qw")
    count0 = qw.count
    x = np.ones((1, 8), np.float32)
    try:
        with _capture() as spans:
            server.infer("clf_qw", {"x": x}, timeout=60)
    finally:
        server.stop()
    assert qw.count - count0 == 1        # admission-to-dispatch observed
    assert smetrics.queue_wait_percentile("clf_qw", 0.5) > 0.0
    names = {s.name for s in spans}
    for expected in ("serving.admission", "serving.queue_wait",
                     "serving.coalesce", "serving.settle"):
        assert expected in names, names
    # the lifecycle spans of one request share one trace
    by_name = {s.name: s for s in spans}
    assert by_name["serving.queue_wait"].trace_id == \
        by_name["serving.settle"].trace_id
    # coalesce is a local (per-wave) span: no trace identity
    assert by_name["serving.coalesce"].trace_id is None


def test_rpc_roundtrip_returns_trace_id_and_exemplar(tmp_path):
    from paddle_tpu import serving
    from paddle_tpu.serving import metrics as smetrics
    server = _clf_server(tmp_path, "clf_rpc")
    endpoint = server.serve()
    client = serving.ServingClient(endpoint)
    x = np.ones((1, 8), np.float32)
    try:
        with _capture() as spans:
            client.infer("clf_rpc", {"x": x})
    finally:
        client.close()
        server.stop()
    # the server returned the request_id<->trace_id mapping
    tid = client.last_trace_id
    assert tid and len(tid) == 32
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, s)
    client_span = by_name["serving.infer"]
    handle = by_name["serving.handle"]
    assert client_span.trace_id == tid
    assert handle.trace_id == tid
    assert handle.parent_id == client_span.span_id
    # server-side lifecycle spans land on the same trace, inside the
    # client span's interval (containment = the acceptance property)
    settle = by_name["serving.settle"]
    assert settle.trace_id == tid
    assert client_span.start_s <= settle.start_s
    assert settle.end_s <= client_span.end_s
    # the latency histogram carries the trace_id as an exemplar
    assert smetrics.histogram_exemplar(
        smetrics.REQUEST_LATENCY, model="clf_rpc") == tid


def test_master_rpc_propagates_context():
    from paddle_tpu.data.master import Master
    from paddle_tpu.data.master_service import MasterClient, MasterServer
    srv = MasterServer(Master(timeout_s=10))
    client = MasterClient(srv.endpoint)
    try:
        with _capture() as spans:
            assert client.ping()
            # beat=false without a reaper — the RPC still crosses the
            # wire, which is all the propagation assertion needs
            client.heartbeat()
    finally:
        client.close()
        srv.stop()
    pings = [s for s in spans if s.name == "master.ping"]
    # client span + server handler span, causally linked
    assert len(pings) == 2
    child = next(p for p in pings if p.parent_id in
                 {q.span_id for q in pings})
    parent = next(p for p in pings if p.span_id == child.parent_id)
    assert child.trace_id == parent.trace_id
    # heartbeats ride the same propagation path
    hbs = [s for s in spans if s.name == "master.heartbeat"]
    assert len(hbs) == 2


def test_pserver_rpc_propagates_context():
    import paddle_tpu.fluid as fluid
    from _dist_utils import bound_listener, stop_pserver
    from paddle_tpu import models
    from paddle_tpu.distributed import AsyncPServer, AsyncTrainerClient
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.transpiler import DistributeTranspiler
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 3
    startup.random_seed = 3
    with unique_name.guard():
        with fluid.program_guard(main_p, startup):
            models.deepfm.build(is_train=True, num_fields=4,
                                vocab_size=64, embed_dim=8, lr=1e-2)
    listener, port = bound_listener()
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(0, program=main_p, pservers=ep, trainers=2,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog))
    ps.serve(listener=listener)
    g = t.send_vars[0]
    pname = next(p for p in t.params if g == p + "@GRAD")
    shape = ps.get_params([pname])[pname].shape
    client = AsyncTrainerClient(("127.0.0.1", port))
    try:
        with _capture() as spans:
            client.push_grad(g, np.ones(shape, np.float32) * 0.1)
            client.pull([pname])
    finally:
        client.close()
        stop_pserver(ps)
    for op in ("pserver.push", "pserver.pull"):
        pair = [s for s in spans if s.name == op]
        assert len(pair) == 2, [s.name for s in spans]
        child = next(p for p in pair if p.parent_id in
                     {q.span_id for q in pair})
        parent = next(p for p in pair if p.span_id == child.parent_id)
        assert child.trace_id == parent.trace_id


# -- spool + trace_collect ------------------------------------------------

def _tools():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_collect.py")
    spec = importlib.util.spec_from_file_location("trace_collect", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spool_format_and_trace_collect_merge(tmp_path):
    tc = _tools()
    d = str(tmp_path / "spools")
    client = spool.SpanSpool(d, role="client")
    tracing.add_sink(client)
    with tctx.client_span("rpc.call"):
        header = tctx.current().to_traceparent()
    tracing.remove_sink(client)
    client.close()
    server = spool.SpanSpool(d, role="server")
    tracing.add_sink(server)
    with tctx.activate(tctx.from_traceparent(header)):
        with tctx.span("server.handle"):
            with tctx.span("server.work"):
                time.sleep(0.001)
    tracing.remove_sink(server)
    server.close()

    paths = tc.find_spools(d)
    assert len(paths) == 2
    meta, spans, torn = tc.load_spool(paths[0])
    assert meta["role"] == "client" and torn == 0
    assert spans[0]["name"] == "rpc.call"
    assert len(spans[0]["trace_id"]) == 32

    assert tc.check(paths) == []         # the gate passes
    trace = tc.merge(paths)
    evs = trace["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    assert {e["name"] for e in xs} >= {"rpc.call", "server.handle",
                                       "server.work"}
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any(p.startswith("client") for p in procs)
    assert any(p.startswith("server") for p in procs)
    flows = [e for e in evs if e.get("ph") in ("s", "f")]
    assert len(flows) == 2               # one cross-process edge, paired
    assert {e["ph"] for e in flows} == {"s", "f"}


def test_trace_collect_check_catches_problems(tmp_path):
    tc = _tools()
    d = tmp_path / "bad"
    d.mkdir()
    lines = [
        {"k": "meta", "role": "r", "pid": 1, "start_wall_us": 0.0},
        {"k": "span", "name": "a", "ts": 100.0, "dur": 5.0, "tid": 1,
         "trace_id": "t" * 32, "span_id": "a" * 16,
         "parent_id": "f" * 16},          # parent never recorded
        {"k": "span", "name": "b", "ts": 100.0, "dur": -1.0, "tid": 1},
    ]
    with open(d / "r.1.jsonl", "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
        f.write('{"k": "span", "name": "torn"')     # torn final line
    problems = tc.check([str(d / "r.1.jsonl")])
    assert any("unresolved parent" in p for p in problems)
    assert any("bad ts/dur" in p for p in problems)
    # a single torn trailing line alone is tolerated (SIGKILL artifact)
    ok_lines = lines[:1] + [
        {"k": "span", "name": "a", "ts": 100.0, "dur": 5.0, "tid": 1}]
    with open(d / "ok.1.jsonl", "w") as f:
        for rec in ok_lines:
            f.write(json.dumps(rec) + "\n")
        f.write('{"k": "span"')
    assert tc.check([str(d / "ok.1.jsonl")]) == []


def test_spool_autostart_from_flags(tmp_path):
    """tracing.active() consults the spool flags once — the path a
    tools/launch.py child takes (env only, no API calls)."""
    from paddle_tpu import flags
    d = str(tmp_path / "auto")
    flags.set("trace_spool_dir", d)
    flags.set("trace_role", "autorole")
    prev = tracing._autostart_done
    tracing._autostart_done = False
    try:
        assert tctx.active()             # autostarts the spool sink
        with tctx.span("auto.span"):
            pass
        sp = spool.current()
        assert sp is not None and sp.role == "autorole"
    finally:
        spool.shutdown()
        tracing._autostart_done = prev
        flags.reset("trace_spool_dir")
        flags.reset("trace_role")
    files = os.listdir(d)
    assert any(f.startswith("autorole.") for f in files)
    with open(os.path.join(d, sorted(files)[0])) as f:
        recs = [json.loads(line) for line in f]
    assert recs[0]["k"] == "meta"
    assert any(r.get("name") == "auto.span" for r in recs[1:])
