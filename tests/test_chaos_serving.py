"""Chaos suite for the serving stack (ISSUE 8 satellite): the resilient
client vs a fault-injected server, on deterministic utils/faults
schedules — dropped connections, delayed responses, a mid-request kill
(reply lost after execution) — asserting the retry/breaker counters
match the injected schedule and that non-idempotent submits are applied
AT MOST ONCE (witness: paddle_serving_requests_applied_total).

Fault sites (docs/serving.md):
    serving.rpc.send   client, before a request hits the socket
    serving.rpc.recv   client, after send / before the reply read
    serving.handle     server, before dispatching a parsed request
    serving.reply      server, after execution / before the reply write
                       (a fault here IS the mid-request kill: work done,
                       ack lost)
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu import serving
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.distributed import resilience
from paddle_tpu.utils import faults

pytestmark = pytest.mark.chaos


def _clf_model_dir(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        prob = layers.softmax(layers.fc(x, size=4))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "clf")
    os.makedirs(d, exist_ok=True)
    fluid.io.save_inference_model(d, ["x"], [prob], exe,
                                  main_program=main)
    return d


@pytest.fixture
def served(tmp_path):
    d = _clf_model_dir(tmp_path)
    sm = serving.ServedModel("clf_chaos", d, serving.BucketPolicy((1, 2)))
    server = serving.ModelServer()
    server.add_model(sm)
    endpoint = server.serve()
    yield server, endpoint, sm
    faults.reset()
    server.stop()


def _applied():
    return smetrics.REQUESTS_APPLIED.labels(model="clf_chaos").value


def _retries(what):
    return resilience.RETRY_ATTEMPTS.labels(what=what).value


def test_client_rides_dropped_connections(served):
    """send faults on an exact schedule: the client retries with
    backoff, every request still succeeds, and the retry counter moves
    by exactly the number of injected faults."""
    server, endpoint, sm = served
    client = serving.ServingClient(endpoint)
    rng = np.random.RandomState(0)
    x = rng.rand(1, 8).astype(np.float32)
    ref = sm.infer({"x": x})[0]

    applied0, retries0 = _applied(), _retries("serving.infer")
    # fail the 2nd and 4th wire attempts at the client's send site
    with faults.active(
            "serving.rpc.send:raise@2,4:exc=ConnectionError"):
        for _ in range(3):
            (out,) = client.infer("clf_chaos", {"x": x})
            np.testing.assert_allclose(out, ref, rtol=1e-6)
        st = faults.stats()["serving.rpc.send"]
        assert st["fired"] == 2                 # schedule honored
    assert _retries("serving.infer") - retries0 == 2
    # a dropped SEND never reached the server: each logical request
    # executed exactly once
    assert _applied() - applied0 == 3
    client.close()


def test_lost_reply_is_applied_at_most_once(served):
    """The mid-request kill: the server EXECUTES the request, then the
    reply is lost. The client's retry carries the same request_id and is
    answered from the idempotency cache — applied moves ONCE."""
    server, endpoint, sm = served
    client = serving.ServingClient(endpoint)
    x = np.ones((1, 8), np.float32)
    ref = sm.infer({"x": x})[0]

    applied0 = _applied()
    with faults.active("serving.reply:raise@1:exc=ConnectionError"):
        (out,) = client.infer("clf_chaos", {"x": x})
        np.testing.assert_allclose(out, ref, rtol=1e-6)
        assert faults.stats()["serving.reply"]["fired"] == 1
    # two wire attempts, ONE execution — at-most-once for a
    # non-idempotent submit
    assert _applied() - applied0 == 1
    client.close()


def test_delayed_responses_ride_through(served):
    """Delay faults at the server's handle site slow requests down but
    break nothing; no retries fire (the socket just waits)."""
    server, endpoint, sm = served
    client = serving.ServingClient(endpoint)
    x = np.ones((1, 8), np.float32)
    retries0 = _retries("serving.infer")
    with faults.active("serving.handle:delay@1,2:s=0.05"):
        t0 = time.perf_counter()
        client.infer("clf_chaos", {"x": x})
        client.infer("clf_chaos", {"x": x})
        elapsed = time.perf_counter() - t0
        assert faults.stats()["serving.handle"]["fired"] == 2
    assert elapsed >= 0.1
    assert _retries("serving.infer") == retries0
    client.close()


def test_shed_is_not_retried(served):
    """A typed shed crosses the wire and is surfaced immediately — the
    retry counter must NOT move (admission control only works if
    clients back off instead of hammering)."""
    server, endpoint, sm = served
    hosted = server.model("clf_chaos")
    hosted.max_queue_depth = 0
    client = serving.ServingClient(endpoint)
    retries0 = _retries("serving.infer")
    with pytest.raises(serving.RequestShedError):
        client.infer("clf_chaos", {"x": np.ones((1, 8), np.float32)})
    assert _retries("serving.infer") == retries0
    hosted.max_queue_depth = 64
    client.close()


def test_breaker_opens_against_dead_server(tmp_path):
    """A killed server exhausts the retry budget once, trips the
    breaker, and subsequent calls fast-fail while it cools down."""
    d = _clf_model_dir(tmp_path)
    sm = serving.ServedModel("clf_dead", d, serving.BucketPolicy((1,)))
    server = serving.ModelServer()
    server.add_model(sm)
    endpoint = server.serve()
    server.stop()                      # kill it: connections now refuse

    breaker = resilience.CircuitBreaker(
        failure_threshold=3, reset_timeout_s=30.0, name="serving_chaos")
    opens0 = resilience.BREAKER_OPENS.labels(name="serving_chaos").value
    client = serving.ServingClient(
        endpoint,
        retry_policy=resilience.RetryPolicy(
            max_attempts=4, base_delay_s=0.005, max_delay_s=0.01,
            deadline_s=5.0,
            retryable=(ConnectionError, OSError)),
        breaker=breaker)
    with pytest.raises(serving.ServingUnavailableError) as ei:
        client.infer("clf_dead", {"x": np.ones((1, 8), np.float32)})
    assert ei.value.attempts == 4
    assert breaker.state == resilience.CircuitBreaker.OPEN
    assert resilience.BREAKER_OPENS.labels(
        name="serving_chaos").value - opens0 == 1
    # while open, attempts fast-fail with CircuitOpenError under the
    # hood — still surfaced as unavailable, with no socket dials
    t0 = time.perf_counter()
    with pytest.raises(serving.ServingUnavailableError):
        client.infer("clf_dead", {"x": np.ones((1, 8), np.float32)})
    assert time.perf_counter() - t0 < 2.0
    client.close()


def test_recv_fault_after_execution_dedups(served):
    """A recv-side drop AFTER the request was sent is indistinguishable
    from a lost reply: the retry must dedup server-side, not re-run."""
    server, endpoint, sm = served
    client = serving.ServingClient(endpoint)
    x = np.full((1, 8), 0.5, np.float32)
    applied0 = _applied()
    with faults.active("serving.rpc.recv:raise@1:exc=ConnectionError"):
        (out,) = client.infer("clf_chaos", {"x": x})
    assert out.shape == (1, 4)
    # the first attempt's request DID reach the server (fault fires
    # after send); its execution plus the deduped retry = ONE apply
    assert _applied() - applied0 == 1
    client.close()


# ---------------------------------------------------------------------------
# in-flight batching chaos (ISSUE 9): slot lifecycle under failure
# ---------------------------------------------------------------------------

_SLOT_CACHE = {}


def _slot_model():
    """A slot engine with a LONG decode budget so cancellation always
    races a generation that is genuinely mid-flight (the tiny model
    finishes short budgets in milliseconds)."""
    sgm = _SLOT_CACHE.get("sgm")
    if sgm is None:
        from paddle_tpu.models import transformer as T
        sgm = serving.make_slot_model(
            "lm_chaos_slot",
            T.build_decoder_lm_programs(
                prompt_len=8, max_new=512, vocab=32, d_model=16,
                d_inner=32, n_head=2, n_layer=2,
                modes=T.slot_modes(), n_slots=2))
        sgm.warmup()
        _SLOT_CACHE["sgm"] = sgm
    return sgm


def _evictions(model, cause):
    return smetrics.SLOT_EVICTIONS.labels(model=model,
                                          cause=cause).value


def test_cancel_frees_slot_within_one_step():
    """An explicit cancel of an in-flight generation frees its slot
    within one decode step: the future raises the typed error, the
    eviction counter moves with cause=cancelled, and the slot is free
    for the next admission."""
    sgm = _slot_model()
    server = serving.ModelServer()
    server.add_model(sgm)
    c0 = _evictions(sgm.name, "cancelled")
    try:
        fut = server.submit_generate(sgm.name, [np.arange(1, 6)],
                                     max_new=500, request_id="cancel-1")
        deadline = time.perf_counter() + 10
        while sgm.active_count() == 0 and time.perf_counter() < deadline:
            time.sleep(0.002)
        assert sgm.active_count() == 1
        assert server.cancel(sgm.name, "cancel-1")
        with pytest.raises(serving.RequestCancelledError):
            fut.result(10)
        # the future settles the moment the scheduler reaps — the slot
        # is already free
        assert sgm.active_count() == 0
        assert _evictions(sgm.name, "cancelled") - c0 == 1
        # the freed slot admits the next request immediately
        (toks,) = server.generate(sgm.name, [np.arange(1, 6)],
                                  max_new=4, timeout=30)
        assert len(toks) == 4
    finally:
        server.stop()


def test_killed_client_frees_slot_mid_generation():
    """The mid-generation client kill: a raw socket starts a long
    generation and dies; the RPC handler notices the hangup, cancels,
    and the slot frees within one step instead of burning to
    max-tokens."""
    import json
    import socket
    sgm = _slot_model()
    server = serving.ModelServer()
    server.add_model(sgm)
    endpoint = server.serve()
    host, port = endpoint.rsplit(":", 1)
    c0 = _evictions(sgm.name, "cancelled")
    try:
        s = socket.create_connection((host, int(port)), timeout=10)
        s.sendall((json.dumps(
            {"method": "generate", "model": sgm.name, "req_id": "kill-1",
             "prompts": [[1, 2, 3]], "max_new": 500}) + "\n").encode())
        deadline = time.perf_counter() + 10
        while sgm.active_count() == 0 and time.perf_counter() < deadline:
            time.sleep(0.002)
        assert sgm.active_count() == 1
        time.sleep(0.05)                       # genuinely mid-flight
        s.close()                              # the kill
        deadline = time.perf_counter() + 10
        while sgm.active_count() > 0 and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert sgm.active_count() == 0
        assert _evictions(sgm.name, "cancelled") - c0 == 1
    finally:
        server.stop()


def test_generate_retry_joins_inflight_stream():
    """At-most-once on the slot scheduler: a retried generate
    request_id JOINS the in-flight stream — same future, ONE slot
    admission, ONE application — instead of double-allocating a slot."""
    sgm = _slot_model()
    server = serving.ModelServer()
    server.add_model(sgm)
    adm0 = smetrics.SLOT_ADMISSIONS.labels(model=sgm.name).value
    app0 = smetrics.REQUESTS_APPLIED.labels(model=sgm.name).value
    try:
        f1 = server.submit_generate(sgm.name, [np.arange(1, 7)],
                                    max_new=40, request_id="retry-1")
        deadline = time.perf_counter() + 10
        while sgm.active_count() == 0 and time.perf_counter() < deadline:
            time.sleep(0.002)
        # the retry (lost-reply scenario) while the stream decodes
        f2 = server.submit_generate(sgm.name, [np.arange(1, 7)],
                                    max_new=40, request_id="retry-1")
        assert f1 is f2                        # joined, not re-queued
        (t1,) = f1.result(60)
        assert len(t1) == 40
        assert smetrics.SLOT_ADMISSIONS.labels(
            model=sgm.name).value - adm0 == 1
        assert smetrics.REQUESTS_APPLIED.labels(
            model=sgm.name).value - app0 == 1
        # a retry AFTER settlement answers from the idempotency cache
        (t2,) = server.generate(sgm.name, [np.arange(1, 7)],
                                max_new=40, request_id="retry-1")
        np.testing.assert_array_equal(t1, t2)
        assert smetrics.REQUESTS_APPLIED.labels(
            model=sgm.name).value - app0 == 1
    finally:
        server.stop()


def test_counters_match_full_fault_plan(served):
    """A combined plan across client and server sites: every counter
    (faults fired, retries, applies) matches the schedule exactly."""
    server, endpoint, sm = served
    client = serving.ServingClient(endpoint)
    rng = np.random.RandomState(1)
    n = 6
    applied0 = _applied()
    retries0 = _retries("serving.infer")
    plan = ("serving.rpc.send:raise@3:exc=ConnectionError;"
            "serving.reply:raise@2:exc=ConnectionError;"
            "serving.handle:delay@5:s=0.02")
    with faults.active(plan, seed_=7):
        for _ in range(n):
            (out,) = client.infer(
                "clf_chaos", {"x": rng.rand(1, 8).astype(np.float32)})
            assert out.shape == (1, 4)
        st = faults.stats()
        assert st["serving.rpc.send"]["fired"] == 1
        assert st["serving.reply"]["fired"] == 1
        assert st["serving.handle"]["fired"] == 1
    # send fault -> one retry; reply fault -> one retry; delay -> none
    assert _retries("serving.infer") - retries0 == 2
    # n logical requests; the reply-fault one deduped on retry: n applies
    assert _applied() - applied0 == n
    client.close()
