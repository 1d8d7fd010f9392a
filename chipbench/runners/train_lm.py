"""Runner of language-model training cells (``decoder_lm``'s hybrid
block through ``models/transformer.py:build_lm``): the program as
``runners/train.py`` times its cells — the pass pipeline, the
mixed-precision rewrite, resident device feeds, one multi-step dispatch
per chunk, fenced by a D2H fetch of the stacked loss — with what a
model of 0.7 B float32 parameters and a multi-token-prediction loss
needs besides:

- the weights of ``--seed`` are drawn a matrix at a time
  (``weights_chunked``: the device holds the model once, plus one
  matrix), by the model's own rule (``hybrid_weight_std``);
- recomputation (``contrib/recompute.py``) of the op types the
  configuration's ``recompute`` lists;
- the comparison with the plain reference runs on the TRAINED program
  itself (no dropout: no twin) at the timed length: one step's loss, the
  gradient norms of the matrices ``check.grad_params`` names and a
  strided sample of each entry by entry, how far those matrices MOVED in
  the step against the reference's Adam on the step's own gradient and
  the moments as they stood (a state left unchanged reads 1), each
  expert layer's load against the reference's, and the routers'
  correction biases after that step's update, exactly, from the step's
  own load. The reference reads the scope's float32 master arrays where
  they lie (no second copy);
- the expert layers' accumulated loads are read at the window's edges
  (``obs["moe_counts"]``: what ``moe_load_max_over_mean.train`` reads).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from chipbench import flops_mla_train, harness, weights_chunked

MODEL = "lm"


def build_program(cfg: dict, batch: int):
    """(main, startup, loss, the accumulated loads' names): build_lm,
    then the pass pipeline, the amp rewrite and the layout rewrite as
    ``runners/train.py`` applies them, then the recomputation tags."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import passes as tpu_passes
    from paddle_tpu.models import transformer as T
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, totals, feed_specs = T.build_lm(name=MODEL, **cfg["build"])
        tpu_passes.apply_pipeline(
            main, names=None, model=cfg["pass_table_row"], batch_size=batch,
            is_test=False, feed_names=sorted(feed_specs),
            fetch_names=[loss.name])
        if cfg["amp"]:
            from paddle_tpu.contrib.mixed_precision import \
                rewrite_program_amp
            rewrite_program_amp(main)
        from paddle_tpu.contrib.layout import rewrite_program_nhwc
        rewrite_program_nhwc(main)
        if cfg.get("recompute"):
            from paddle_tpu.contrib.recompute import \
                rewrite_program_recompute
            rewrite_program_recompute(main, tuple(cfg["recompute"]))
    return main, startup, loss, [t.name for t in totals]


def expert_layers(main) -> list:
    """[(tag, the bias parameter's name, the Load output's name)] of the
    program's expert layers, from its ``router_bias_update`` ops."""
    out = []
    for op in main.global_block().ops:
        if op.type == "router_bias_update":
            bias = op.desc.input("Bias")[0]
            tag = bias[len(MODEL) + 1:].split("_moe.")[0]
            out.append((tag, bias, op.desc.input("Load")[0]))
    return out


def adam_state(main) -> dict:
    """{parameter: the names of (Moment1, Moment2, Beta1Pow, Beta2Pow)}
    of the program's ``adam`` ops."""
    return {op.desc.input("Param")[0]: tuple(
        op.desc.input(k)[0] for k in ("Moment1", "Moment2", "Beta1Pow",
                                      "Beta2Pow"))
        for op in main.global_block().ops if op.type == "adam"}


def norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def compare_with_reference(run, exe, scope, main, loss, feeds, **faults):
    """One step of the trained program against the plain reference on
    the same weights and sequence. Returns (ok, what was seen).
    ``faults`` go to the reference alone (the builder's demonstrations:
    a lower precision, a fault built on purpose)."""
    cfg = run.config
    chk, build = cfg["check"], cfg["build"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    roles = [r for r, _ in ref.param_shapes(build)]
    params = {r: scope.find_var(f"{MODEL}_{r}") for r in roles}
    which = list(chk["grad_params"])
    layers = expert_layers(main)
    before = {tag: np.asarray(scope.find_var(bias))
              for tag, bias, _ in layers}
    # the checked matrices and their optimizer state as they stand, on
    # the sample (taken on the device: the step donates the arrays)
    state = adam_state(main)
    held = lambda n: np.asarray(ref.sample_of(scope.find_var(n)),  # noqa
                                np.float64)
    stood = {r: [held(n) for n in (f"{MODEL}_{r}",)
                 + state[f"{MODEL}_{r}"]] for r in which}
    want_loss, want_norms, want_bias, want_samples = ref.loss_and_grad_norms(
        params, *(np.asarray(feeds[n])[..., 0]
                  for n in ("ids", "lbl_ids", "lbl2_ids")), build, which,
        **faults)
    out = exe.run(main, feed=feeds, scope=scope,
                  fetch_list=[loss.name]
                  + [f"{MODEL}_{r}@GRAD" for r in which]
                  + [load for _, _, load in layers])
    got_loss = float(np.asarray(out[0]).reshape(()))
    grads = out[1:1 + len(which)]
    got_norms = [norm(g) for g in grads]
    # entry by entry on a sample: norms agree whatever the directions
    got_samples = [ref.sample_of(np.asarray(g)) for g in grads]
    sample_errs = [norm(g - w) / max(norm(w), 1e-30)
                   for g, w in zip(got_samples, want_samples)]
    # the step's update: what each matrix moved by against Adam on the
    # step's OWN gradient (judged: 1 where nothing moved, the share an
    # lr is off by) and on the reference's (seen: Adam's first step is
    # lr x sign(g), so every entry whose sign bfloat16 flips counts 2 lr)
    update_errs, update_ref_errs = {}, {}
    for r, g, w in zip(which, got_samples, want_samples):
        moved = held(f"{MODEL}_{r}") - stood[r][0]
        for errs, grad in ((update_errs, g), (update_ref_errs, w)):
            step = ref.adam_step(grad, *stood[r][1:], build)
            errs[r] = norm(moved - step) / max(norm(step), 1e-30)
    loads = [np.asarray(v) for v in out[1 + len(which):]]
    gamma = build["bias_update_gamma"]
    bias_exact, load_err = True, 0.0
    for (tag, bias, _), load in zip(layers, loads):
        # the update from the step's OWN load, exactly
        step = gamma * np.sign(load.mean(dtype=np.float64) - load)
        bias_exact &= bool(np.array_equal(
            np.asarray(scope.find_var(bias)),
            before[tag] + step.astype(np.float32).reshape(1, -1)))
        # and the load against the reference's: sign(mean - load) of
        # the two, the share of the experts on which they differ
        ref_step = (want_bias[tag] - before[tag]).reshape(-1)
        load_err = max(load_err, float(np.mean(
            np.sign(ref_step) != np.sign(step))))
    tol = chk["tolerance"]["amp" if cfg["amp"] else "fp32"]
    sample_errs = dict(zip(which, sample_errs))
    # judged entry by entry: the gradients no pick selects the rows of
    judged = max(sample_errs[r] for r in chk["sample_params"])
    loss_err = abs(got_loss - want_loss) / abs(want_loss)
    norm_errs = [abs(g - w) / max(w, 1e-30)
                 for g, w in zip(got_norms, want_norms)]
    ok = bool(np.isfinite(got_loss) and loss_err <= tol["loss_rel"]
              and max(norm_errs) <= tol["grad_norm_rel"]
              and judged <= tol["grad_sample_rel"]
              and max(update_errs.values()) <= tol["update_rel"]
              and load_err <= tol["bias_sign_share"] and bias_exact)
    return ok, {"loss": [got_loss, want_loss], "loss_rel_err": loss_err,
                "grad_norms": [got_norms, want_norms],
                "grad_norm_rel_errs": dict(zip(which, norm_errs)),
                "grad_norm_rel_err": max(norm_errs),
                "grad_sample_rel_errs": sample_errs,
                "grad_sample_rel_err": judged,
                "update_rel_errs": update_errs,
                "update_rel_err": max(update_errs.values()),
                "update_vs_reference_rel_errs": update_ref_errs,
                "bias_update_exact": bias_exact,
                "bias_sign_share": load_err, "tolerance": tol}


def start(run: harness.Run):
    """Build, start up, draw the weights of ``--seed``, place the feeds:
    (exe, scope, main, loss, totals, data, feed sets on the device)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.transformer import hybrid_weight_std
    cfg, tr = run.config, run.traffic
    with run.phase("build"):
        data = harness.generator_of(tr).make(tr, cfg, run.seed,
                                             run.cell["chips"])
        main, startup, loss, totals = build_program(cfg, data["batch"])
    with run.phase("startup"):
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup, scope=scope)
        params = main.global_block().all_parameters()
        weights_chunked.reseed(scope, weights_chunked.matrix_spec(
            {p.name: (p.shape, p.dtype) for p in params},
            hybrid_weight_std), run.seed, exe.device)
        feed_sets = [{n: jax.device_put(v, exe.device)
                      for n, v in fs.items()} for fs in data["feed_sets"]]
    return exe, scope, main, loss, totals, data, feed_sets


def run(run: harness.Run) -> dict:
    cfg, tr = run.config, run.traffic
    n_chips = run.cell["chips"]
    k = tr["steps_per_dispatch"]
    exe, scope, main, loss, totals, data, feed_sets = start(run)
    names = sorted(feed_sets[0])

    with run.phase("check"):
        correct, seen = compare_with_reference(
            run, exe, scope, main, loss, data["check"])

    n_dispatched = [0]

    def dispatch():
        feeds = feed_sets[n_dispatched[0] % len(feed_sets)]
        n_dispatched[0] += 1
        with run.span("chipbench.exe_run"):
            return exe.run(main, feed=feeds, fetch_list=[loss],
                           iterations=k, stacked_feed=names,
                           return_numpy=False, scope=scope)[0]

    def fence(handle):
        with run.span("chipbench.fetch_loss"):
            return np.asarray(handle).reshape(-1)

    def loads():
        return np.stack([np.asarray(scope.find_var(n)) for n in totals])

    with run.phase("warm"):
        warm_compiles = []
        for _ in range(tr["warm_dispatches"]):
            c0 = run.compiles.requests
            fence(dispatch())
            warm_compiles.append(run.compiles.requests - c0)
        loads0 = loads()

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
    # the window's picks per expert layer and expert (int32 totals that
    # wrap: differences), as ``moe_counts`` reads them: [layers, 2, E]
    given = (loads() - loads0).astype(np.int64) % (1 << 32)

    obs = {
        "correct": correct and finite and moving,
        "attempted": steps, "failed": int(np.sum(~np.isfinite(losses))),
        "end_to_end": {"train_tokens_per_s_chip":
                       data["tokens_per_step"] * steps / win.seconds
                       / n_chips},
        "window_s": win.seconds, "units": {"steps": steps},
        "phases": dict(run.phase_s), "compiles_in_window": win.compiles,
        "chips": n_chips, "config": cfg, "traffic": tr,
        "moe_counts": np.stack([given, (given > 0) * steps], axis=1),
        "moe_steps": steps,
        "model_flops": getattr(flops_mla_train, cfg["flops"])(
            cfg["build"], tr["seq_len"],
            tr["sequences_per_step"] * n_chips) * steps,
        "notes": {"reference": seen, "warm_compiles": warm_compiles,
                  "loss_first_last": [float(losses[0]), float(losses[-1])],
                  "steps": steps, "window_s": win.seconds,
                  "compiles_in_window": win.compiles,
                  "phases": dict(run.phase_s)},
    }
    return harness.add_device_observations(run, win, obs)
