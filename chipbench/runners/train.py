"""Runner of training cells: the program as ``bench.py`` times its
train rows (model build, pass pipeline, amp rewrite; resident device
feeds, one multi-step dispatch per chunk, fenced by a D2H fetch of the
stacked loss), on one chip or over a data-parallel mesh.

Set-up is the same list of work for every seed: build two programs (the
trained one and its dropout-free twin for the comparison with the plain
reference), run start-up, draw the weights of ``--seed``, compare one
forward/backward with the reference, dispatch ``warm_dispatches`` chunks.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from chipbench import flops, harness, weights


def build_program(build: dict, amp: bool, pass_row: str, batch: int,
                  dropout: float):
    """(main, startup, loss): ``bench.build_train_program``'s steps, with
    parameter names that do not depend on what was built before."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import passes as tpu_passes
    from paddle_tpu.models import transformer as T
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, _, feed_specs = T.build(is_train=True,
                                      **{**build, "dropout": dropout})
        tpu_passes.apply_pipeline(
            main, names=None, model=pass_row, batch_size=batch,
            is_test=False, feed_names=sorted(feed_specs),
            fetch_names=[loss.name])
        if amp:
            from paddle_tpu.contrib.mixed_precision import \
                rewrite_program_amp
            rewrite_program_amp(main)
        from paddle_tpu.contrib.layout import rewrite_program_nhwc
        rewrite_program_nhwc(main)
    return main, startup, loss


def compare_with_reference(run, exe, scope, target, loss, params, feeds):
    """One dropout-free forward/backward through the executor against
    the plain reference on the same weights and sample: the loss and the
    gradient norms of a few matrices. Returns (ok, what was seen)."""
    cfg = run.config
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    roles = ref.param_shapes(cfg["build"])
    for p, (role, shape) in zip(params, roles):
        if tuple(p.shape) != tuple(shape):
            raise ValueError(f"parameter {p.name} has shape {p.shape}, the "
                             f"reference expects {role} {shape}")
    which = cfg["check"]["grad_params"]
    values = [scope.find_var(p.name) for p in params]     # before the step
    want_loss, want_norms = ref.loss_and_grad_norms(
        values, *(feeds[n][..., 0] for n in ("src_ids", "tgt_ids",
                                             "lbl_ids")),
        cfg["build"], which)
    out = exe.run(target, feed=feeds, scope=scope,
                  fetch_list=[loss.name]
                  + [params[i].name + "@GRAD" for i in which])
    got_loss = float(np.asarray(out[0]).reshape(()))
    got_norms = [float(np.sqrt(np.sum(np.square(
        np.asarray(g, np.float64))))) for g in out[1:]]
    tol = cfg["check"]["tolerance"]["amp" if cfg["amp"] else "fp32"]
    loss_err = abs(got_loss - want_loss) / abs(want_loss)
    norm_err = max(abs(g - w) / w for g, w in zip(got_norms, want_norms))
    ok = bool(np.isfinite(got_loss) and loss_err <= tol["loss_rel"]
              and norm_err <= tol["grad_norm_rel"])
    return ok, {"loss": [got_loss, want_loss], "loss_rel_err": loss_err,
                "grad_norms": [got_norms, want_norms],
                "grad_norm_rel_err": norm_err, "tolerance": tol}


def run(run: harness.Run) -> dict:
    import jax
    import paddle_tpu.fluid as fluid
    cfg, tr = run.config, run.traffic
    n_chips = run.cell["chips"]
    k = tr["steps_per_dispatch"]

    with run.phase("build"):
        data = harness.generator_of(tr).make(tr, cfg, run.seed, n_chips)
        main, startup, loss = build_program(
            cfg["build"], cfg["amp"], cfg["pass_table_row"], data["batch"],
            cfg["build"]["dropout"])
        twin, _, twin_loss = build_program(
            cfg["build"], cfg["amp"], cfg["pass_table_row"],
            tr["check"]["batch"], 0.0)
        target, twin_target = main, twin
        if tr.get("mesh"):
            from paddle_tpu.parallel import DistributeConfig, make_mesh
            dist = DistributeConfig(
                mesh=make_mesh(tr["mesh"], devices=run.devices),
                data_axis=tr["data_axis"])
            target = fluid.CompiledProgram(main).with_sharding(dist)
            twin_target = fluid.CompiledProgram(twin).with_sharding(dist)

    with run.phase("startup"):
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        params = main.global_block().all_parameters()
        weights.reseed(scope, weights.matrix_spec(
            {p.name: p.shape for p in params}, cfg["build"]["d_model"]),
            run.seed, exe.device)
        feed_sets = [{n: jax.device_put(v, exe.device)
                      for n, v in fs.items()} for fs in data["feed_sets"]]
        names = sorted(feed_sets[0])

    with run.phase("check"):
        correct, seen = compare_with_reference(
            run, exe, scope, twin_target, twin_loss, params, data["check"])

    n_dispatched = [0]

    def dispatch():
        feeds = feed_sets[n_dispatched[0] % len(feed_sets)]
        n_dispatched[0] += 1
        with run.span("chipbench.exe_run"):
            return exe.run(target, feed=feeds, fetch_list=[loss],
                           iterations=k, stacked_feed=names,
                           return_numpy=False, scope=scope)[0]

    def fence(handle):
        with run.span("chipbench.fetch_loss"):
            return np.asarray(handle).reshape(-1)

    with run.phase("warm"):
        # 1st compiles; 2nd is the program's known re-specialisation to
        # the layouts its own outputs carry; the 3rd must compile nothing
        warm_compiles = []
        for _ in range(tr["warm_dispatches"]):
            c0 = run.compiles.requests
            fence(dispatch())
            warm_compiles.append(run.compiles.requests - c0)

    run.open_window()
    limit = min(run.seconds, tr["trace_seconds"]) if run.trace \
        else run.seconds
    curves = []
    with run.traced() as win:
        t0 = time.perf_counter()
        pending = dispatch()
        while pending is not None:
            # one chunk in flight behind the one being fenced
            nxt = dispatch() if time.perf_counter() - t0 < limit else None
            curves.append(fence(pending))
            pending = nxt
    losses = np.concatenate(curves)
    steps = len(losses)
    finite = bool(np.all(np.isfinite(losses)))
    moving = bool(losses[-1] != losses[0])

    obs = {
        "correct": correct and finite and moving,
        "attempted": steps, "failed": int(np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s_chip":
                       data["tokens_per_step"] * steps / win.seconds
                       / n_chips},
        "window_s": win.seconds, "units": {"steps": steps},
        "phases": dict(run.phase_s), "compiles_in_window": win.compiles,
        "chips": n_chips, "config": cfg, "traffic": tr,
        "model_flops": getattr(flops, cfg["flops"])(
            data["batch"], cfg["build"]["max_len"], cfg["build"]["max_len"],
            cfg["build"]["d_model"], cfg["build"]["d_inner"],
            cfg["build"]["n_layer"], cfg["build"]["tgt_vocab"]) * steps,
        "notes": {"reference": seen, "warm_compiles": warm_compiles,
                  "loss_first_last": [float(losses[0]), float(losses[-1])],
                  "steps": steps, "window_s": win.seconds,
                  "compiles_in_window": win.compiles,
                  "phases": dict(run.phase_s)},
    }
    return harness.add_device_observations(run, win, obs)
