"""Process-level chaos for the autoscaling serving fleet (ISSUE 16):
the two closed-loop proofs the fast tier cannot stage.

1. **Load spike**: the same offered load that sheds >=5% of requests
   on a STATIC 2-replica fleet (shallow admission queues, fixed
   capacity) serves CLEAN under the autoscaled policy (deep queues
   absorbing while elastic capacity catches up) — zero sheds, zero
   client-visible failures — and the autoscaler's fleet-size trace
   shows the scale-up AND the drain-based scale-down in one run.

2. **Replica OOM under load**: an injected MemoryError mid-dispatch
   kills the replica WITHOUT acking (oom_exit), the supervisor finds
   the ``<role>.<pid>.memdump.json`` witness, classifies the death
   ``cause="oom"``, and REPLACES the slot with the registered
   smaller-footprint spec instead of re-entering the restart/
   quarantine loop — with zero acked-request loss (the router
   re-dispatches the unacked in-flight ids to the survivor).

Everything spawns real replica processes and compiles the tiny
decoder LM, so every test is ``slow``; the control law itself is
unit-proven in tests/test_autoscaler.py.
"""

import itertools
import json
import os
import threading
import time

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

_LM_PARAMS = {"prompt_len": 8, "max_new": 8, "vocab": 32, "d_model": 16,
              "d_inner": 32, "n_head": 2, "n_layer": 2}


def _lm_spec(max_queue_depth=64, n_slots=2, env=None):
    """The slot replica's tiny decoder LM (every dispatch of the slot
    engine goes through ``_launch``: the ``serving.dispatch`` chaos
    site the OOM injection needs)."""
    spec = {"model": {"kind": "decoder_lm", "name": "lm",
                      "params": dict(_LM_PARAMS, n_slots=int(n_slots))},
            "max_queue_depth": int(max_queue_depth)}
    if env:
        spec["env"] = dict(env)
    return spec


def _wait(pred, timeout, msg):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timeout waiting for {msg}")


def _spike_threads(endpoint, stop, results, sheds, errors, n=6):
    """The offered load of the spike: n generator threads issuing
    back-to-back greedy requests. A typed shed is COUNTED (the static
    arm's failure mode), any other client-visible failure is an
    error; every completed stream is recorded for the determinism
    audit."""
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.server import RequestShedError
    lock = threading.Lock()
    ids = itertools.count()

    def loop():
        cl = ServingClient(endpoint)
        try:
            while not stop.is_set():
                i = next(ids)
                rid = f"spike-{i}"
                prompt = (1 + (i % 5), 2, 3)
                try:
                    toks = cl.generate("lm", [prompt], max_new=4,
                                       request_id=rid)
                except RequestShedError:
                    with lock:
                        sheds.append(rid)
                    continue
                with lock:
                    results[rid] = (prompt, [int(x) for x in toks[0]])
        except Exception as e:          # audit, don't swallow
            errors.append(repr(e))
        finally:
            cl.close()

    threads = [threading.Thread(target=loop, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
    return threads


def _audit_streams(results):
    """Deterministic greedy: same prompt -> bit-identical stream,
    wherever (and however often, under failover) it executed."""
    by_prompt = {}
    for rid, (prompt, toks) in results.items():
        assert by_prompt.setdefault(prompt, toks) == toks, \
            f"stream diverged for {rid} (prompt {prompt})"


def test_load_spike_static_sheds_autoscaled_serves_clean(tmp_path):
    """The tentpole chaos proof, arm vs arm under the SAME offered
    load: static-2 with shallow queues sheds >=5%; the autoscaled
    fleet (deep queues + elastic capacity) sheds NOTHING and loses no
    acked request, while the fleet-size trace records a scale-up
    during the spike and a drain-based scale-down after it."""
    from paddle_tpu.serving import metrics as smetrics
    from paddle_tpu.serving.autoscaler import (Autoscaler,
                                               AutoscalePolicy)
    from paddle_tpu.serving.router import Router

    # -- arm 1: static-2, shallow queues --------------------------------
    shallow = _lm_spec(max_queue_depth=1)
    router = Router(spec=shallow, replicas=2,
                    workdir=str(tmp_path / "static"),
                    breaker_reset_s=0.5)
    router.start()
    assert router.wait_ready(timeout_s=600)
    ep = router.serve()
    stop = threading.Event()
    results, sheds, errors = {}, [], []
    threads = _spike_threads(ep, stop, results, sheds, errors, n=8)
    try:
        _wait(lambda: len(results) + len(sheds) >= 120, 120,
              "the static arm to absorb the spike")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        router.stop()
    assert not errors, f"static arm leaked non-shed failures: {errors}"
    total = len(results) + len(sheds)
    static_shed_ratio = len(sheds) / total
    assert static_shed_ratio >= 0.05, \
        (f"the spike must overwhelm static-2: only {len(sheds)}/{total} "
         f"shed ({static_shed_ratio:.1%}) — not a spike")

    # -- arm 2: the SAME spike, autoscaled ------------------------------
    deep = _lm_spec(max_queue_depth=512)
    router = Router(spec=deep, replicas=2,
                    workdir=str(tmp_path / "scaled"),
                    breaker_reset_s=0.5)
    router.start()
    assert router.wait_ready(timeout_s=600)
    ep = router.serve()
    policy = AutoscalePolicy(
        slo_queue_wait_p99_s=0.02, min_replicas=2, max_replicas=3,
        breach_window_s=0.5, clear_window_s=1.5, cooldown_s=2.0,
        window_s=4.0, poll_interval_s=0.25, scale_spec=deep)
    asc = Autoscaler(router=router, policy=policy).start()
    stop = threading.Event()
    results, sheds, errors = {}, [], []
    threads = _spike_threads(ep, stop, results, sheds, errors, n=8)
    try:
        # the saturated queue-wait p99 breaches the SLO -> the loop
        # scales to 3 and the new replica warms into the pool
        _wait(lambda: router.stats()["size"] >= 3, 120,
              "the breach to trigger a scale-up")
        _wait(lambda: router.stats()["ready"] >= 3, 600,
              "the scale-up replica to pass readyz")
        _wait(lambda: len(results) >= 120, 120,
              "the spike to keep flowing over the grown fleet")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    try:
        # the spike is over: the windowed signal clears and the loop
        # drains one replica back out — scale-DOWN rides the graceful
        # drain path, so it can never lose an acked request either
        _wait(lambda: any(d["action"] == "scale_down"
                          for d in asc.decisions), 120,
              "the clear signal to drain the fleet back down")
        _wait(lambda: router.stats()["size"] == 2, 60,
              "the pool to shrink to the floor")
    finally:
        asc.stop()
        trace = list(asc.fleet_trace)
        router.stop()

    assert not errors, f"autoscaled arm failures: {errors}"
    assert not sheds, \
        f"the autoscaled fleet shed {len(sheds)} requests (static " \
        f"shed {static_shed_ratio:.1%}); the loop failed to absorb"
    _audit_streams(results)
    sizes = [t["size"] for t in trace]
    assert max(sizes) >= 3, "no scale-up in the fleet-size trace"
    assert sizes[-1] == 2, "no scale-down in the fleet-size trace"
    down = [d for d in asc.decisions if d["action"] == "scale_down"]
    assert down and down[0].get("drained") is True, \
        "scale-down must be drain-based (graceful), not a kill"
    assert smetrics.AUTOSCALER_DECISIONS.labels(
        action="scale_up").value >= 1
    assert smetrics.AUTOSCALER_DECISIONS.labels(
        action="scale_down").value >= 1


def test_replica_oom_replaced_with_fallback_not_restart_looped(tmp_path):
    """OOM under load: the 10th ``serving.dispatch`` in slot 0's
    process (3 warmup dispatches, then under load) raises an
    injected MemoryError. The replica memdumps and dies WITHOUT
    acking; the supervisor classifies cause="oom" from the witness
    file and respawns the slot ONCE with the registered smaller
    fallback spec — no crash-loop accounting, no quarantine — while
    every client call completes on the survivor."""
    from paddle_tpu.serving import metrics as smetrics
    from paddle_tpu.serving.router import Router

    faulty = _lm_spec(env={
        "FLAGS_fault_plan":
            "serving.dispatch:raise@10:exc=MemoryError"})
    clean = _lm_spec()
    fallback = _lm_spec(n_slots=1)    # the smaller-footprint config
    router = Router(specs=[faulty, clean],
                    workdir=str(tmp_path), breaker_reset_s=0.5,
                    oom_fallback=fallback)
    router.start()
    assert router.wait_ready(timeout_s=600)
    ep = router.serve()
    oom0 = smetrics.ROUTER_RESTARTS.labels(cause="oom").value
    quar0 = smetrics.ROUTER_RESTARTS.labels(
        cause="quarantine_retry").value
    pid0 = router.stats()["replicas"][0]["pid"]
    stop = threading.Event()
    results, sheds, errors = {}, [], []
    threads = _spike_threads(ep, stop, results, sheds, errors, n=2)
    st0 = None
    try:
        _wait(lambda: (router.stats()["replicas"][0]["last_exit"]
                       or {}).get("cause") == "oom",
              180, "slot 0 to die of the injected OOM")
        # replaced, not restart-looped: fresh pid, READY again, and the
        # slot is NOT failed/quarantined
        _wait(lambda: (router.stats()["replicas"][0]["state"] == "ready"
                       and router.stats()["replicas"][0]["pid"]
                       not in (None, pid0)),
              600, "the fallback replacement to pass readyz")
        time.sleep(1.0)                    # load outlives the outage
        st0 = router.stats()["replicas"][0]
        replaced_spec = router._by_index[0].spec
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        router.stop()

    assert not errors, f"acked-request loss under the OOM: {errors}"
    assert not sheds
    _audit_streams(results)
    assert len(results) > 20, "load generator barely ran"

    # the memdump witness, where the supervisor promised to look
    ex = st0["last_exit"]
    assert ex["cause"] == "oom", ex
    assert ex["memdump"] and os.path.exists(ex["memdump"]), ex
    assert os.path.dirname(ex["memdump"]).endswith("replica0-flight")
    with open(ex["memdump"]) as f:
        dump = json.load(f)
    assert dump["exc_type"] == "MemoryError", dump
    assert dump["reason"] == "oom" and dump["role"] == "replica"
    import re
    assert re.fullmatch(r"replica\.\d+\.memdump\.json",
                        os.path.basename(ex["memdump"]))

    # classified + counted, and the slot took the FALLBACK config
    assert smetrics.ROUTER_RESTARTS.labels(
        cause="oom").value - oom0 >= 1
    assert replaced_spec == fallback, \
        "the OOM'd slot must come back on the smaller-footprint spec"
    assert st0["state"] == "ready"
    assert st0["restarts"] == 0 and st0["quarantines"] == 0, \
        f"an OOM replace must not enter crash-loop accounting: {st0}"
    assert smetrics.ROUTER_RESTARTS.labels(
        cause="quarantine_retry").value == quar0
