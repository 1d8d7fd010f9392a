"""Two-process serving-acceptance body for tools/launch.py (ISSUE 12):
rank 0 hosts a slot-scheduled ModelServer, rank 1 drives one traced
generate through ServingClient. Each rank spools spans (and runs the
flight recorder) under its own role in the shared directory (argv[1]);
the parent test merges the spools with tools/trace_collect.py and
asserts the client's request span strictly CONTAINS the server's
admission -> prefill@bucket -> decode-step -> settle spans, stitched by
cross-process flow events. Rendezvous is file-based (endpoint.txt /
done.txt in the spool dir)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                                        # noqa: E402

from paddle_tpu import flags, serving                     # noqa: E402


def _await_file(path, deadline_s=180.0):
    deadline = time.time() + deadline_s
    while not os.path.exists(path):
        if time.time() > deadline:
            raise TimeoutError(f"timed out waiting for {path}")
        time.sleep(0.05)


def main():
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    share = sys.argv[1]
    role = "server" if rank == 0 else "client"
    flags.set("trace_spool_dir", share)
    flags.set("flight_recorder_dir", share)
    flags.set("trace_role", role)
    from paddle_tpu.observability import tracing
    assert tracing.active(), "spool autostart failed"

    ep_file = os.path.join(share, "endpoint.txt")
    done_file = os.path.join(share, "done.txt")
    if rank == 0:
        from paddle_tpu.models import transformer as T
        sgm = serving.make_slot_model(
            "lm", T.build_decoder_lm_programs(
                prompt_len=8, max_new=8, vocab=32, d_model=16,
                d_inner=32, n_head=2, n_layer=2,
                modes=T.slot_modes(), n_slots=2))
        sgm.warmup()
        server = serving.ModelServer()
        server.add_model(sgm)
        endpoint = server.serve()
        tmp = ep_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(endpoint)
        os.replace(tmp, ep_file)          # atomic: never read half-written
        print(f"READY {endpoint}", flush=True)
        _await_file(done_file)
        server.stop()
    else:
        _await_file(ep_file)
        with open(ep_file) as f:
            endpoint = f.read().strip()
        client = serving.ServingClient(endpoint, timeout_s=120)
        (toks,) = client.generate("lm", [np.arange(1, 6)], max_new=6)
        assert len(toks) == 6, f"expected 6 tokens, got {len(toks)}"
        print(f"TRACE_ID {client.last_trace_id}", flush=True)
        client.close()
        with open(done_file, "w") as f:
            f.write("ok")

    from paddle_tpu.observability import flight_recorder, spool
    spool.shutdown()
    flight_recorder.shutdown()


if __name__ == "__main__":
    main()
