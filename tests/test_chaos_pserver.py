"""Chaos: the async-pserver trainer client under injected faults — a
connection drop before the push is sent is retried (and applied exactly
once), while a persistently dead pserver trips the circuit breaker into
fast-fail instead of hanging every training step.

The paddle_pserver_* / paddle_breaker_* counters are asserted against
the injected fault schedule — the telemetry is a second witness for the
retry/breaker behavior."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import AsyncPServer, AsyncTrainerClient
from paddle_tpu.distributed import async_pserver as aps
from paddle_tpu.distributed import resilience
from paddle_tpu.distributed.resilience import (CircuitBreaker,
                                               CircuitOpenError, RetryError,
                                               RetryPolicy)
from paddle_tpu.fluid.transpiler import DistributeTranspiler
from paddle_tpu.utils import faults
from _dist_utils import bound_listener as _bound_listener
from _dist_utils import stop_pserver

pytestmark = pytest.mark.chaos


def _server(lr=0.1):
    from paddle_tpu.fluid import unique_name
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 7
    startup.random_seed = 7
    with unique_name.guard():
        with fluid.program_guard(main_p, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, 1, bias_attr=False)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    t = DistributeTranspiler()
    ep = "127.0.0.1:0"
    t.transpile(0, program=main_p, pservers=ep, trainers=2,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog))
    g = t.send_vars[0]
    pname = next(p for p in t.params if g == p + "@GRAD")
    return ps, g, pname


def _fast_retry(max_attempts=5):
    return RetryPolicy(max_attempts=max_attempts, base_delay_s=0.001,
                       max_delay_s=0.004, deadline_s=5.0,
                       retryable=(ConnectionError, OSError, EOFError))


def test_push_retried_through_connect_fault_applies_exactly_once():
    ps, g, pname = _server()
    listener, port = _bound_listener()
    ps.serve(listener=listener)
    retries0 = aps.PS_RPC_RETRIES.labels(op="push").value
    applied0 = aps.PS_GRADS_APPLIED.value
    push_lat0 = aps.PS_RPC_SECONDS.labels(op="push").count
    try:
        c = AsyncTrainerClient(("127.0.0.1", port), trainer_id=0,
                               retry_policy=_fast_retry())
        w0 = c.pull([pname])[pname].copy()
        # the fault fires at the top of the first attempt — before the
        # request hits the wire — so the retry is safe and the gradient
        # applies exactly once
        with faults.active(
                "pserver.push_grad:raise@1:exc=ConnectionError"):
            c.push_grad(g, np.ones(w0.shape, np.float32))
        assert ps.n_applied == 1, "retried push must apply exactly once"
        # counters match the schedule: one injected drop → one recorded
        # push retry, one applied gradient, one latency sample
        assert aps.PS_RPC_RETRIES.labels(op="push").value \
            - retries0 == 1
        assert aps.PS_GRADS_APPLIED.value - applied0 == 1
        assert aps.PS_RPC_SECONDS.labels(op="push").count \
            - push_lat0 == 1
        w1 = c.pull([pname])[pname]
        np.testing.assert_allclose(w1, w0 - 0.1 * np.ones(w0.shape),
                                   rtol=1e-6)
        c.close()
    finally:
        stop_pserver(ps)


def test_pull_retried_through_transient_fault():
    ps, g, pname = _server()
    listener, port = _bound_listener()
    ps.serve(listener=listener)
    try:
        c = AsyncTrainerClient(("127.0.0.1", port), trainer_id=0,
                               retry_policy=_fast_retry())
        with faults.active("pserver.pull:raise@1:exc=ConnectionError"):
            params = c.pull([pname])
        assert pname in params
        c.close()
    finally:
        stop_pserver(ps)


def test_breaker_fast_fails_a_dead_pserver():
    ps, g, pname = _server()
    listener, port = _bound_listener()
    ps.serve(listener=listener)
    opens0 = resilience.BREAKER_OPENS.labels(name="chaos-ps").value
    exhausted0 = resilience.RETRY_EXHAUSTED.labels(what="push").value
    try:
        c = AsyncTrainerClient(
            ("127.0.0.1", port), trainer_id=0,
            retry_policy=_fast_retry(max_attempts=1),
            breaker=CircuitBreaker(failure_threshold=2,
                                   reset_timeout_s=60.0,
                                   name="chaos-ps"))
        with faults.active(
                "pserver.push_grad:raise@every1:exc=ConnectionError"):
            for _ in range(2):             # exhaust the breaker threshold
                with pytest.raises(RetryError):
                    c.push_grad(g, np.zeros((4, 1), np.float32))
            # circuit open: fast-fail without touching the retry budget
            with pytest.raises(CircuitOpenError):
                c.push_grad(g, np.zeros((4, 1), np.float32))
        assert ps.n_applied == 0
        # telemetry matches the schedule: two spent retry budgets, one
        # breaker trip, and the state gauge reads open (2)
        assert resilience.RETRY_EXHAUSTED.labels(what="push").value \
            - exhausted0 == 2
        assert resilience.BREAKER_OPENS.labels(
            name="chaos-ps").value - opens0 == 1
        assert resilience.BREAKER_STATE.labels(name="chaos-ps").value == 2
        c.close()
    finally:
        stop_pserver(ps)
