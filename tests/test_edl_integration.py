"""Full EDL integration — the reference's v2 elastic-deep-learning story
in one test (reference: go/master task leasing over etcd + go/pserver
param service + N trainers; a trainer dies, the others absorb its
chunks, the model survives because its state lives on the pserver):

  data plane:  Master (csrc/master.cc) behind MasterServer (JSON/TCP)
  param plane: AsyncPServer (transpiled pserver program, barrier-free)
  trainers:    3 OS processes leasing chunks + pushing grads;
               one dies mid-lease (os._exit, unreported)

Asserted: every chunk trained exactly once across survivors, nothing
dropped, the pserver applied the survivors' gradients, and the final
held-out loss beats the initial parameters'."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid
from paddle_tpu import recordio
from _dist_utils import (build_deepfm_small, bound_listener,
                         eval_deepfm_loss, stop_pserver)
from paddle_tpu.core import native
from paddle_tpu.data.master import Master
from paddle_tpu.data.master_service import MASTER_ENV, MasterServer
from paddle_tpu.distributed.async_pserver import AsyncPServer
from paddle_tpu.fluid.transpiler import DistributeTranspiler

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native runtime unavailable")

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _make_dataset(tmp_path, n_files=3, chunks_per_file=10,
                  rows_per_chunk=32):
    """Learnable CTR records: label = f(ids)."""
    rng = np.random.RandomState(0)
    paths, n_chunks = [], 0
    for f in range(n_files):
        p = str(tmp_path / f"ctr-{f:03d}.recordio")
        with recordio.Writer(p, max_chunk_records=rows_per_chunk) as w:
            for _ in range(chunks_per_file * rows_per_chunk):
                ids = rng.randint(0, 64, size=4)
                label = int((ids[0] % 2) == 0)
                w.write(f"{','.join(map(str, ids))}:{label}".encode())
        paths.append(p)
        n_chunks += chunks_per_file
    return paths, n_chunks


def _eval_loss(scope):
    return eval_deepfm_loss(
        scope,
        label_fn=lambda ids: ((ids[:, 0, 0] % 2) == 0
                              ).astype(np.float32)[:, None])


def test_edl_master_plus_pserver_with_trainer_death(tmp_path):
    paths, n_chunks = _make_dataset(tmp_path)

    # data plane
    master = Master(timeout_s=6.0, failure_max=5)
    master.set_dataset(paths, chunks_per_task=1)
    srv = MasterServer(master)

    # param plane
    main_p, startup, loss = build_deepfm_small()
    listener, port = bound_listener()   # bound now; no rebind window
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(0, program=main_p, pservers=ep, trainers=3,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog))
    ps.serve(listener=listener)

    init_scope = fluid.Scope()
    for n in t.params:
        init_scope.set_var(n, np.asarray(ps.scope.find_var(n)))
    loss_before = _eval_loss(init_scope)

    bdir = str(tmp_path / "barrier")
    os.makedirs(bdir)
    env_base = {k: v for k, v in os.environ.items()
                if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_"))}
    workers = []
    try:
        for rank in range(3):
            env = dict(env_base)
            env[MASTER_ENV] = srv.endpoint
            env["PADDLE_PSERVER"] = ep
            env["PADDLE_TRAINER_ID"] = str(rank)
            env["PADDLE_TRAINERS_NUM"] = "3"
            env["MASTER_BARRIER_DIR"] = bdir
            env["TRAIN_SLEEP"] = "0.05"
            if rank == 0:
                # dies on its FIRST lease: always reached (the queue
                # cannot drain before every worker's first lease — the
                # others are still compiling their own first chunk), so
                # the death is deterministic; die_after=2 could let the
                # victim drain-exit rc=0 under first-compile skew
                env["DIE_AFTER_LEASES"] = "1"
            workers.append(subprocess.Popen(
                [sys.executable, os.path.join(TESTS_DIR, "edl_worker.py")],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                cwd=os.path.dirname(TESTS_DIR), env=env, text=True))
        deadline = time.time() + 120
        while len([f for f in os.listdir(bdir)
                   if f.startswith("ready_")]) < 3:
            assert time.time() < deadline, "workers never reached barrier"
            time.sleep(0.05)
        open(os.path.join(bdir, "go"), "w").close()

        outs = []
        for i, w in enumerate(workers):
            out, err = w.communicate(timeout=300)
            if i == 0:
                assert w.returncode == 17, f"victim survived:\n{err[-2000:]}"
            else:
                assert w.returncode == 0, f"worker {i} failed:\n{err[-3000:]}"
                outs.append(json.loads(
                    [l for l in out.splitlines()
                     if l.startswith("RESULT ")][-1][len("RESULT "):]))
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
        srv.stop()
        stop_pserver(ps)

    # exactly-once data plane: survivors completed every chunk except
    # those the victim landed before dying (0 or 1 — its first finish is
    # rejected if the first-step XLA compile outlives the lease, which is
    # exactly the timer semantics re-issuing correctly)
    completed = [tuple(c) for o in outs for c in o["completed"]]
    s = master.stats()
    assert s["dropped"] == 0 and s["todo"] == 0 and s["pending"] == 0
    assert s["done"] == n_chunks
    assert len(completed) == len(set(completed)), "a chunk trained twice"
    assert n_chunks - 1 <= len(completed) <= n_chunks
    # NOTE: no assertion that BOTH survivors completed work — under
    # first-compile skew one worker can legitimately drain the queue
    # while the other is still compiling; the system property is the
    # exactly-once accounting above, not scheduling fairness

    # param plane survived the death and learned: grads were applied and
    # the held-out loss improved over the initial parameters
    assert ps.n_applied > 0
    trained_scope = fluid.Scope()
    for n in t.params:
        trained_scope.set_var(n, np.asarray(ps.scope.find_var(n)))
    loss_after = _eval_loss(trained_scope)
    assert loss_after < loss_before, (loss_before, loss_after)
