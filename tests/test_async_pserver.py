"""Async-SGD pserver emulation (round-2 verdict item 7): the
RunAsyncLoop capability (reference listen_and_serv_op.cc:217-268) —
per-gradient optimizer subgraphs applied with NO trainer barriers —
behind the existing DistributeTranspiler split, exercised by a DeepFM
config across two real OS processes. DC-ASGD (delay compensation) is
covered by the tests at the bottom of this file."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import AsyncPServer, AsyncTrainerClient
from paddle_tpu.fluid.transpiler import DistributeTranspiler
from paddle_tpu import models
from _dist_utils import bound_listener as _bound_listener
from _dist_utils import stop_pserver


def _build_deepfm(seed=3):
    from paddle_tpu.fluid import unique_name
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = seed
    startup.random_seed = seed
    # identical param names on every build (the worker process builds the
    # same program): reset the unique-name counters per build
    with unique_name.guard():
        with fluid.program_guard(main_p, startup):
            loss, _, feed_specs = models.deepfm.build(
                is_train=True, num_fields=4, vocab_size=64, embed_dim=8,
                lr=1e-2)
    return main_p, startup, loss


def _batch(rng, n=16):
    from _dist_utils import noisy_deepfm_labels
    ids = rng.randint(0, 64, size=(n, 4, 1)).astype("int64")
    # ~5% label noise: keeps the separable task's loss floor away from 0
    # so async staleness can't blow up a saturated softmax (see
    # _dist_utils.noisy_deepfm_labels)
    return ids, noisy_deepfm_labels(rng, ids)


def test_async_apply_grad_updates_params_without_barrier():
    """In-process: one pushed gradient immediately moves the parameter —
    no second trainer, no barrier (RunAsyncLoop semantics)."""
    main_p, startup, loss = _build_deepfm()
    ep = "127.0.0.1:0"
    t = DistributeTranspiler()
    t.transpile(0, program=main_p, pservers=ep, trainers=2,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog))
    assert t.send_vars, "transpiler found no gradient send targets"
    g = t.send_vars[0]
    pname = next(p for p in t.params if g == p + "@GRAD")
    before = ps.get_params([pname])[pname].copy()
    gval = np.ones(before.shape, np.float32) * 0.5
    ps.apply_grad(g, gval)
    after = ps.get_params([pname])[pname]
    assert not np.allclose(before, after)
    assert ps.n_applied == 1


def test_deepfm_two_process_async_converges():
    """Two trainer OS processes hammer one AsyncPServer without barriers;
    the served parameters converge: the final evaluation loss lands
    within tolerance of a single-process synchronous run's."""
    steps = 40
    main_p, startup, loss = _build_deepfm()
    listener, port = _bound_listener()   # bound now; no rebind window
    ep = f"127.0.0.1:{port}"
    t = DistributeTranspiler()
    t.transpile(0, program=main_p, pservers=ep, trainers=2,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog))
    ps.serve(listener=listener)

    env_base = {k: v for k, v in os.environ.items()
                if not k.startswith(("PADDLE_", "XLA_FLAGS"))}
    workers = []
    for rank in range(2):
        env = dict(env_base)
        env["PADDLE_PSERVER"] = ep
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = "2"
        env["PADDLE_TEST_STEPS"] = str(steps)
        workers.append(subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "async_worker.py")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(__file__)), env=env,
            text=True))
    first_losses = {}
    try:
        for rank, w in enumerate(workers):
            out, err = w.communicate(timeout=420)
            assert w.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
            line = [l for l in out.splitlines()
                    if l.startswith("RESULT ")][-1]
            first_losses[rank] = json.loads(line[len("RESULT "):])["losses"]
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
        stop_pserver(ps)
    assert ps.n_applied >= 2 * steps * len(t.send_vars) * 0.9

    # evaluate the async-trained params vs a synchronous baseline
    def eval_loss(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(999)
        ids, label = _batch(rng, n=64)
        eval_p, eval_s, eval_l = _build_deepfm()
        (lv,) = exe.run(eval_p, feed={"feat_ids": ids, "label": label},
                        fetch_list=[eval_l], scope=scope)
        return float(np.asarray(lv).reshape(()))

    # async-served params -> fresh scope
    async_scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    m2, s2, _ = _build_deepfm()
    exe.run(s2, scope=async_scope)
    for n, v in ps.get_params(t.params).items():
        async_scope.set_var(n, v)
    async_loss = eval_loss(async_scope)

    # synchronous single-process baseline, same data distribution
    m3, s3, l3 = _build_deepfm()
    sync_scope = fluid.Scope()
    exe.run(s3, scope=sync_scope)
    rng = np.random.RandomState(100)
    init_loss = None
    for _ in range(steps):
        ids, label = _batch(rng)
        (lv,) = exe.run(m3, feed={"feat_ids": ids, "label": label},
                        fetch_list=[l3], scope=sync_scope)
        if init_loss is None:
            init_loss = float(np.asarray(lv).reshape(()))
    sync_loss = eval_loss(sync_scope)

    assert np.isfinite(async_loss)
    assert async_loss < init_loss, (async_loss, init_loss)
    # async staleness costs some quality; the tolerance bounds it
    assert abs(async_loss - sync_loss) < 0.25, (async_loss, sync_loss)


# -- DC-ASGD (delay-compensated async SGD) --------------------------------
# reference: distribute_transpiler.py:1595 _append_dc_asgd_ops (the
# sub/mul/mul/add compensation chain, unscaled), :977-985 (startup
# param->bak assign), request_handler_impl.cc:96-106 (GET refreshes
# param.trainer_%d_bak). This closes the last parallelism-table row that
# was previously a documented drop.


def _build_linear(seed=7, lr=0.1):
    from paddle_tpu.fluid import unique_name
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = seed
    startup.random_seed = seed
    with unique_name.guard():
        with fluid.program_guard(main_p, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, 1, bias_attr=False)
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    return main_p, startup


def _dc_server(lr=0.1):
    main_p, startup = _build_linear(lr=lr)
    t = DistributeTranspiler()
    t.config.enable_dc_asgd = True
    ep = "127.0.0.1:0"
    t.transpile(0, program=main_p, pservers=ep, trainers=2,
                sync_mode=False, startup_program=startup)
    ps_prog = t.get_pserver_program(ep)
    ps = AsyncPServer(ps_prog, t.get_startup_program(ep, ps_prog),
                      dc_asgd=t.config.enable_dc_asgd)
    g = t.send_vars[0]
    pname = next(p for p in t.params if g == p + "@GRAD")
    return ps, g, pname


def test_dc_asgd_compensation_exact():
    """One stale push reproduces w -= lr*(g + (w-w_bak)*g*g) bit-for-bit."""
    lr = 0.1
    ps, g, pname = _dc_server(lr=lr)
    # trainer 1 pulls -> its backup snapshots w0
    w0 = ps.get_params([pname], trainer_id=1)[pname].copy()
    # trainer 0 pushes while w == its backup (startup value): dc == g
    g1 = np.full(w0.shape, 0.5, np.float32)
    ps.apply_grad(g, g1, trainer_id=0)
    w1 = ps.get_params([pname])[pname].copy()
    np.testing.assert_allclose(w1, w0 - lr * g1, rtol=1e-6)
    # trainer 1's gradient is now stale by (w1 - w0): compensated
    g2 = np.full(w0.shape, -0.25, np.float32)
    ps.apply_grad(g, g2, trainer_id=1)
    dc = g2 + (w1 - w0) * g2 * g2
    w2 = ps.get_params([pname])[pname]
    np.testing.assert_allclose(w2, w1 - lr * dc, rtol=1e-5, atol=1e-7)


def test_dc_asgd_backup_refreshes_on_pull():
    """Pulling again re-snapshots the backup: an immediately-following
    push gets zero compensation (dc == g), per the reference GET handler."""
    lr = 0.1
    ps, g, pname = _dc_server(lr=lr)
    ps.apply_grad(g, np.full((4, 1), 1.0, np.float32), trainer_id=0)
    # trainer 1 pulls AFTER that update -> bak == current w
    w = ps.get_params([pname], trainer_id=1)[pname].copy()
    g2 = np.full(w.shape, 2.0, np.float32)
    ps.apply_grad(g, g2, trainer_id=1)
    w2 = ps.get_params([pname])[pname]
    np.testing.assert_allclose(w2, w - lr * g2, rtol=1e-6)


def test_dc_asgd_over_the_wire_trainer_id():
    """The connection protocol carries trainer_id: two clients with
    different ids get independent backups."""
    lr = 0.1
    ps, g, pname = _dc_server(lr=lr)
    listener, port = _bound_listener()
    ps.serve(listener=listener)
    try:
        c0 = AsyncTrainerClient(("127.0.0.1", port), trainer_id=0)
        c1 = AsyncTrainerClient(("127.0.0.1", port), trainer_id=1)
        w0 = c1.pull([pname])[pname].copy()         # bak(t1) = w0
        g1 = np.full(w0.shape, 0.5, np.float32)
        c0.push_grad(g, g1)                          # dc == g1 (t0 fresh)
        w1 = c0.pull([pname])[pname].copy()
        np.testing.assert_allclose(w1, w0 - lr * g1, rtol=1e-6)
        g2 = np.full(w0.shape, -0.25, np.float32)
        c1.push_grad(g, g2)                          # stale by w1-w0
        dc = g2 + (w1 - w0) * g2 * g2
        w2 = c0.pull([pname])[pname]
        np.testing.assert_allclose(w2, w1 - lr * dc, rtol=1e-5, atol=1e-7)
        c0.close()
        c1.stop_server()
        c1.close()
    finally:
        stop_pserver(ps)
