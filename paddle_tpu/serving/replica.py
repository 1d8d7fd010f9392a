"""One replica of the replicated serving deployment (docs/serving.md
"Deployment: router, replicas, drain, rolling restart"): a single-model
:class:`~paddle_tpu.serving.server.ModelServer` process built from a
JSON spec, with the lifecycle protocol the router supervises it by:

* the wire serves IMMEDIATELY (``readyz`` answers ``ready=false``
  while the engine warms / loads its AOT ladder), and the endpoint
  file is written atomically BEFORE warmup so the router can start
  polling readiness the moment the process binds a port;
* ``mark_ready()`` flips ``readyz`` true only after warmup completes —
  the router never routes traffic to a still-compiling replica;
* a ``drain`` RPC (or SIGTERM) stops admission, lets in-flight work
  settle, dumps the flight recorder, and exits CLEANLY (code 0) — the
  rolling-restart primitive; SIGKILL remains the crash the chaos suite
  proves at-most-once semantics against.

Spec format (``--spec`` file or ``--spec-json`` inline)::

    {"model": {"kind": "saved", "name": "clf",
               "model_dir": "/path", "buckets": [1, 2, 4],
               "aot_dir": null},
     "max_queue_depth": 64, "linger_s": 0.002,
     "oom_exit": true,
     "env": {"FLAGS_fault_plan": "..."}}

(``env`` is consumed by the SUPERVISOR — serving/router.py merges it
into the child environment at spawn, the chaos harness's per-slot
fault-plan hook; ``oom_exit`` selects the die-don't-ack OOM behavior
the router's replace path depends on.)

    {"model": {"kind": "decoder_lm", "name": "lm", "slots": true,
               "params": {"prompt_len": 8, "max_new": 8, "vocab": 32,
                          "d_model": 16, "d_inner": 32, "n_head": 2,
                          "n_layer": 2, "n_slots": 2}}}

Run as ``python -m paddle_tpu.serving.replica --spec spec.json
--endpoint-file ep.txt`` — exactly how ``serving.router.Router``
spawns its pool.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
from typing import Optional

from paddle_tpu import flags


def build_engine(model_spec: dict):
    """Spec dict -> a warmable serving engine (NOT yet warmed)."""
    from paddle_tpu.serving import bucketing, engine
    kind = model_spec.get("kind", "saved")
    name = model_spec.get("name", "model")
    if kind == "saved":
        buckets = model_spec.get("buckets") or (1,)
        return engine.ServedModel(
            name, model_spec["model_dir"],
            bucketing.BucketPolicy(tuple(int(b) for b in buckets)))
    if kind == "decoder_lm":
        from paddle_tpu.models import transformer as T
        params = dict(model_spec.get("params") or {})
        if not model_spec.get("slots", True):
            raise ValueError(
                f"replica spec of model {name!r} says \"slots\": false: "
                f"a decoder_lm is served by the slot engine alone")
        # page geometry the spec leaves out comes from
        # analysis.contracts.validate_geometry: page_size 4,
        # n_pages = every slot at full length
        params.setdefault("modes", T.slot_modes("paged"))
        params.setdefault("n_slots", 2)
        return engine.make_slot_model(
            name, T.build_decoder_lm_programs(name=name, **params))
    raise ValueError(f"unknown model kind {kind!r} in replica spec")


def _write_endpoint(path: str, endpoint: str):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(endpoint)
    os.replace(tmp, path)                 # atomic: never read half-written


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="one ModelServer replica behind serving.router")
    ap.add_argument("--spec", default=None,
                    help="path to the JSON replica spec")
    ap.add_argument("--spec-json", default=None,
                    help="the spec inline (wins over --spec)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (endpoint-file rendezvous)")
    ap.add_argument("--endpoint-file", default=None,
                    help="atomically write 'host:port' here once bound")
    ap.add_argument("--replica-id", default=None,
                    help="pool slot label (metrics / log prefix)")
    args = ap.parse_args(argv)

    if args.spec_json:
        spec = json.loads(args.spec_json)
    elif args.spec:
        with open(args.spec) as f:
            spec = json.load(f)
    else:
        ap.error("one of --spec / --spec-json is required")

    if not flags.get("trace_role"):
        flags.set("trace_role", "replica")

    # a respawned replica finds what its predecessor compiled
    from paddle_tpu.utils import chip
    chip.compile_cache_dir()

    from paddle_tpu.serving.server import ModelServer
    # oom_exit (default True): a dispatch OOM kills this process
    # WITHOUT acking errors — the supervising router finds the memdump,
    # classifies the death cause="oom", and replaces the replica with
    # its fallback spec (serving/autoscaler.py). Spec-gated so an
    # unsupervised replica can keep the settle-with-error behavior.
    server = ModelServer(
        linger_s=float(spec.get("linger_s", 0.002)),
        max_queue_depth=int(spec.get("max_queue_depth", 64)),
        oom_exit=bool(spec.get("oom_exit", True)))

    # serve FIRST (ready=False): readyz answers "not ready" during the
    # warmup below, and the endpoint file lands before the compiles so
    # the supervisor can poll instead of guessing at warmup time
    endpoint = server.serve(host=args.host, port=args.port, ready=False)
    if args.endpoint_file:
        _write_endpoint(args.endpoint_file, endpoint)

    # the HTTP scrape endpoint (FLAGS_metrics_port), when enabled,
    # answers GET /readyz with the SAME verdict as the wire readyz —
    # one readiness truth per process, whichever probe an orchestrator
    # speaks
    from paddle_tpu.observability import exporters
    exporters.set_ready_probe(lambda: server.ready)
    exporters.ensure_started()

    # SIGTERM -> drain, not drop: stop admission, settle in-flight,
    # dump the recorder, exit 0. SIGKILL stays the hard-crash arm.
    def _sigterm(signum, frame):
        threading.Thread(target=_drain_and_exit, daemon=True).start()

    def _drain_and_exit():
        server.drain(timeout_s=60.0)
        server.request_exit()

    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass                               # not the main thread (tests)

    engine = build_engine(spec["model"])
    aot_dir = spec["model"].get("aot_dir") or spec.get("aot_dir")
    server.add_model(engine, aot_dir=aot_dir if aot_dir else None)
    server.mark_ready()
    print(f"READY {endpoint}", flush=True)

    server.wait_exit()
    # let the drain reply (and any concurrent replies) flush before the
    # listener dies; then leave cleanly so the supervisor sees code 0
    import time
    time.sleep(0.3)
    server.stop()
    from paddle_tpu.observability import flight_recorder, spool
    spool.shutdown()
    flight_recorder.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
