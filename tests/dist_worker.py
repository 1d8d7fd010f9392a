"""Worker script for the localhost multi-process distributed test — the
reference's test_dist_base.py trick (§4: fork real localhost processes,
each running the same model file with roles from env, pickle results over
stdout). Each process owns 2 virtual CPU devices; jax.distributed unifies
them into one 4-device global mesh and the dp training step all-reduces
gradients across PROCESSES (DCN capability), not just local devices."""

import json
import os
import sys

# launched as `python tests/dist_worker.py` — sys.path[0] is tests/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=2").strip()

import jax                                     # noqa: E402
jax.config.update("jax_platforms", "cpu")

import numpy as np                             # noqa: E402


def _build_mlp(fluid):
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 5
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    rng = np.random.RandomState(0)
    xs = rng.rand(16, 8).astype(np.float32)
    feed = {"x": xs, "y": xs.sum(axis=1, keepdims=True)
            .astype(np.float32) * 0.25}
    return main_p, startup, loss, feed


def _build_transformer(fluid):
    """Tiny Transformer (fused attention path, dropout 0 so local and
    sharded runs are bit-comparable) — the reference's dist_transformer
    model-parity subject (test_dist_base.py:257-286)."""
    from paddle_tpu import models
    V, T, B = 64, 8, 8
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 5
    with fluid.program_guard(main_p, startup):
        loss, _, feed_specs = models.transformer.build(
            is_train=True, src_vocab=V, tgt_vocab=V, max_len=T,
            d_model=16, d_inner=32, n_head=2, n_layer=2, dropout=0.0,
            lr=1e-3, label_smooth_eps=0.1, fused_attention=True)
    rng = np.random.RandomState(0)
    feed = {n: rng.randint(0, V, [B if d == -1 else d for d in sh])
            .astype("int64") for n, (sh, dt) in feed_specs.items()}
    return main_p, startup, loss, feed


def _build_sharded_table(fluid):
    """Embedding-table model: the table row-shards over a CROSS-PROCESS
    'tp' axis (auto_shard derives it from the lookup_table consumer) —
    the pserver-sharded-table capability exercised over the process
    boundary (SURVEY §2 #24/#27; reference test_dist_transpiler's
    sharded-table path)."""
    V, D, B = 64, 16, 16
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 5
    with fluid.program_guard(main_p, startup):
        ids = fluid.layers.data(name="ids", shape=[1], dtype="int64")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        emb = fluid.layers.embedding(
            ids, size=[V, D], param_attr=fluid.ParamAttr(name="big_table"))
        pred = fluid.layers.fc(emb, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    ids_v = rng.randint(0, V, (B, 1)).astype(np.int64)
    feed = {"ids": ids_v,
            "y": (ids_v % 5).astype(np.float32)}
    return main_p, startup, loss, feed


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    nprocs = int(os.environ["PADDLE_TRAINERS_NUM"])
    model = os.environ.get("PADDLE_TEST_MODEL", "mlp")
    steps = int(os.environ.get("PADDLE_TEST_STEPS", "12"))
    local_only = os.environ.get("PADDLE_LOCAL_BASELINE", "0") == "1"

    if not local_only:
        from paddle_tpu import distributed
        distributed.init_parallel_env(
            coordinator_address=os.environ["PADDLE_COORDINATOR"],
            num_processes=nprocs, process_id=rank)
        assert jax.process_count() == nprocs
        n_global = len(jax.devices())
        assert n_global == 2 * nprocs, n_global

    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import DistributeConfig, make_mesh

    build = {"mlp": _build_mlp, "transformer": _build_transformer,
             "sharded_table": _build_sharded_table}[model]
    main_p, startup, loss, feed = build(fluid)

    if local_only:
        # single-process, single-device reference run — the loss-curve
        # parity bar the distributed run must meet (test_dist_base.py
        # compares dist losses against the local model's)
        run_target = main_p
    elif model == "sharded_table":
        # tp × dp with tp MAJOR: the embedding table row-shards over a tp
        # axis that SPANS the two processes (device order [p0d0, p0d1,
        # p1d0, p1d1] reshaped (tp=2, dp=2) puts tp shard 0 on process 0
        # and shard 1 on process 1 — each process holds half the table
        # rows, the pserver placement); auto_shard derives the placement
        # from the lookup_table consumer
        n = len(jax.devices())
        mesh = make_mesh({"tp": 2, "dp": n // 2})
        run_target = fluid.CompiledProgram(main_p).with_sharding(
            DistributeConfig(mesh=mesh, data_axis="dp", model_axis="tp"))
    else:
        mesh = make_mesh({"dp": len(jax.devices())})
        run_target = fluid.CompiledProgram(main_p).with_sharding(
            DistributeConfig(mesh=mesh, data_axis="dp"))

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    # optional per-process span capture for the merged-timeline test
    # (tools/trace_collect.py --profile_path merges
    # trainer1=f1,trainer2=f2 as the reference's tools/timeline.py did)
    import contextlib
    spans_dir = os.environ.get("PADDLE_TEST_SPANS_DIR")
    if spans_dir:
        from paddle_tpu.fluid import profiler
        profiler.start_profiler()
        step_event = profiler.record_event
    else:
        step_event = lambda name: contextlib.nullcontext()  # noqa: E731

    # every process feeds the SAME global batch (jit with in_shardings
    # splits it over the dp axis; each process computes its shard)
    if model == "mlp" and not local_only:
        # exercise the multi-host MULTI-STEP path: the whole run is one
        # device-side scan over a stacked feed list (exe.run iterations=N
        # with global arrays built per process)
        with step_event(f"rank{rank}/train_scan_{steps}_steps"):
            (lvs,) = exe.run(run_target, feed=[feed] * steps,
                             fetch_list=[loss.name], iterations=steps)
        losses = [float(v) for v in np.asarray(lvs).reshape(-1)]
    else:
        losses = []
        for i in range(steps):
            with step_event(f"rank{rank}/step_{i}"):
                (lv,) = exe.run(run_target, feed=feed,
                                fetch_list=[loss.name])
            losses.append(float(np.asarray(lv).reshape(())))
    if spans_dir:
        profiler.export_spans(os.path.join(spans_dir,
                                           f"spans_rank{rank}.csv"))
    print("RESULT " + json.dumps({"rank": rank, "losses": losses}),
          flush=True)


if __name__ == "__main__":
    main()
