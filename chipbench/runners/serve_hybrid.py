"""Runner of serving cells of a hybrid sparse model (``layer_kinds`` in
the configuration's ``build``): ``decoder_lm`` behind ``ModelServer`` on
the slot engine, the same normal path as ``runners/serve.py`` drives,
whose window loop, counters and generators' context it reuses. What
differs is set-up, and what is observed besides:

- the weights are drawn a matrix at a time (``weights_chunked``);
- the comparison with the plain reference is of LOGITS and of the
  recurrent STATE each slot was left with, not of tokens alone.
  ``check.prompt_lens`` requests are admitted TOGETHER and stepped
  together through ``engine.admit`` / ``engine.step`` — the
  executables, scope, pages and state the server then drives — with
  budgets that differ, so that several slots are live at once and some
  sit released while the others go on (``serve_together``). The logits
  are read by executables of the check's own over the same programs and
  the same scope, which return nothing else and write nothing
  (``LogitProbe``): the engine fetches tokens only. Then the same
  prompts go through ``ModelServer.submit_generate`` at once, and the
  server has to return the same tokens;
- the expert layers' device-side counters are read at the window's
  edges (``obs["moe_counts"]`` over ``obs["moe_steps"]`` decode steps:
  the program snapshots them every 32 steps, so the edges are that
  close to the window's).
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness, weights_chunked
from chipbench.runners import serve

MODEL = serve.MODEL


class LogitProbe:
    """The float32 logits a view of the served family chooses its token
    from (``<model>_logits``), for the dispatch the engine is about to
    make: an executable of the check's own per view, over the SAME
    program and the SAME scope as the engine's, fed what the engine
    feeds, that returns the logits and nothing else — no state comes
    back, so the views' writes (pages, recurrent state, counters) are
    dead code in it and the scope stays as the served path left it."""

    def __init__(self, engine):
        import jax
        from paddle_tpu.core.lowering import CompiledBlock
        self.engine = engine

        def reader(served):
            cb = CompiledBlock(served._program_desc, 0,
                               served.sig.feed_names,
                               [engine.name + "_logits"], is_test=True,
                               donate=False)
            return cb, jax.jit(lambda *args: cb._step_fn(*args)[0][0])
        self._decode = reader(engine._cb_decode)
        self._prefill = {p: reader(cb)
                         for p, cb in engine._cb_prefill.items()}

    def _read(self, reader, feeds):
        cb, fn = reader
        return fn(*self.engine._args(cb, feeds))

    def prefill(self, prompt) -> np.ndarray:
        """[V]: the row the prefill view chooses a prompt's first token
        from (the warm-up's feeds with the prompt in them: every page
        row and the state slot are sentinels, nothing is written)."""
        p_len = self.engine.prompt_bucket_for(len(prompt))
        feeds = self.engine._prefill_feeds(p_len)
        feeds["ids"][0, :len(prompt), 0] = prompt
        feeds["seq_len"][:] = len(prompt)
        return np.asarray(self._read(self._prefill[p_len], feeds))[0]

    def decode(self, slots) -> np.ndarray:
        """[len(slots), V]: the rows the NEXT decode step chooses the
        tokens of ``slots`` from. Call right before ``engine.step()``."""
        feeds = self.engine._decode_feeds()
        logits = self._read(self._decode, {
            k: feeds[k] for k in self._decode[0].sig.feed_names})
        return np.asarray(logits[np.asarray(slots)])


def serve_together(engine, probe, prompts, budgets) -> list:
    """Greedy requests admitted one after the other and then stepped
    TOGETHER, request i for ``budgets[i]`` tokens. Per request: (tokens
    [budget], the float32 logits row the served path chose each token
    from [budget, V], the recurrent state per KDA layer its slot was
    left with — read when ALL have finished, so a slot released early
    has sat through the others' steps)."""
    rows, live = {}, []
    for prompt, budget in zip(prompts, budgets):
        first = probe.prefill(prompt)
        slot, tok, done = engine.admit(prompt, max_new=budget)
        rows[slot] = ([tok], [first])
        if not done:
            live.append(slot)
    order = list(rows)
    while live:
        logits = dict(zip(live, probe.decode(live)))
        for slot, tok, done in engine.step():
            rows[slot][0].append(tok)
            rows[slot][1].append(logits[slot])
            if done:
                live.remove(slot)
    state_vars = [n for n in engine.state_vars if "_kda_state_" in n]
    return [(np.asarray(rows[s][0], np.int64), np.stack(rows[s][1]),
             [np.asarray(engine.scope.find_var(n)[s]) for n in state_vars])
            for s in order]


def serve_one(engine, prompt, max_new: int, probe=None):
    """One request through ``serve_together``."""
    return serve_together(engine, probe or LogitProbe(engine), [prompt],
                          [max_new])[0]


def compare_with_reference(cfg: dict, engine, rng) -> tuple:
    """``check.prompt_lens`` greedy requests of ``check.max_new`` tokens
    each (a list: one budget a request), live together (prefill, then
    decoding through pages AND state), against the reference's full
    forward on the same weights, under the limits the configuration's
    ``check`` gives with their reasons. Returns (correct, what was seen,
    the prompts and the served tokens: ``same_through_server`` sends them
    again)."""
    chk, build = cfg["check"], cfg["build"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    params = {n: engine.scope.find_var(n)
              for n in ref.param_names(build, MODEL)}
    prompts = [rng.randint(1, build["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    served = serve_together(engine, LogitProbe(engine), prompts,
                            chk["max_new"])
    seen = {"logit_err_median": 0.0, "logit_err_max": 0.0,
            "state_err_median": 0.0, "state_err_max": 0.0,
            "state_err_slow_median": 0.0, "state_bf16_share": 0.0,
            "margin_max_sd": 0.0}
    sized = all(str(engine.scope.find_var(n).dtype) == chk["state_dtype"]
                for n in engine.state_vars if "_kda_state_" in n)
    for prompt, budget, (toks, logits, states) in zip(
            prompts, chk["max_new"], served):
        sized &= len(toks) == budget
        logit_err, state_err, margin, slow = ref.compare(
            params, prompt, toks, logits, states, build, MODEL)
        for key, value in (("logit_err_median", np.median(logit_err)),
                           ("logit_err_max", logit_err.max()),
                           ("state_err_median", np.median(state_err)),
                           ("state_err_max", state_err.max()),
                           ("state_err_slow_median",
                            np.median(state_err[slow])),
                           ("state_bf16_share",
                            max(ref.bf16_share(s) for s in states)),
                           ("margin_max_sd", margin.max())):
            seen[key] = max(seen[key], float(value))
    ok = sized and all(seen[k] <= chk["limits"][k] for k in chk["limits"])
    return bool(ok), {**seen, "limits": chk["limits"],
                      "tokens_compared": int(sum(chk["max_new"]))}, \
        (prompts, [toks for toks, _logits, _states in served])


def same_through_server(server, cfg: dict, prompts, tokens) -> bool:
    """The compared requests again, all submitted at once through
    ``ModelServer.submit_generate`` (the scheduler's loop, its steps
    dispatched ahead, admissions between them): greedy decoding of the
    same weights has to return the tokens that were compared."""
    futures = [server.submit_generate(MODEL, [prompt], max_new=budget)
               for prompt, budget in zip(prompts, cfg["check"]["max_new"])]
    return all(np.array_equal(f.result(timeout=600)[0], toks)
               for f, toks in zip(futures, tokens))


def build_engine(cfg: dict, seed: int, device):
    """The program family and the slot engine with the weights of
    ``seed`` (start-up first, with its fixed seed)."""
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    build = cfg["build"]
    programs = T.build_decoder_lm_programs(
        name=MODEL, modes=T.slot_modes(cfg["kv_layout"]),
        kv_codec=cfg["kv_codec"],
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})
    engine = serving.make_slot_model(MODEL, programs)
    dec_main = programs[engine.DECODE][0]
    weights_chunked.reseed(engine.scope, weights_chunked.matrix_spec(
        {p.name: (p.shape, p.dtype)
         for p in dec_main.global_block().all_parameters()},
        T.hybrid_weight_std), seed, device)
    return engine


def bring_up(run: harness.Run):
    from paddle_tpu import serving
    with run.phase("build"):
        engine = build_engine(run.config, run.seed, run.devices[0])
    with run.phase("warm"):
        engine.warmup()
    with run.phase("check"):
        correct, seen, (prompts, tokens) = compare_with_reference(
            run.config, engine,
            np.random.RandomState((run.seed + 1) % 2 ** 32))
    server = serving.ModelServer()
    try:
        with run.phase("warm"):
            # warmed already: this resets only. A queue as deep as the
            # callers are many (one per slot; the server's default of 64
            # sheds half of 128 callers' first requests, whose clients
            # then spin through their rounds while the pool fills)
            hosted = server.add_model(
                engine, max_queue_depth=2 * engine.n_slots)
        with run.phase("check"):
            seen["same_through_server"] = same_through_server(
                server, run.config, prompts, tokens)
            correct &= seen["same_through_server"]
    except BaseException:
        server.stop()
        raise
    return server, engine, hosted, correct, seen


def run(run: harness.Run) -> dict:
    cfg, tr = run.config, run.traffic
    gen = harness.generator_of(tr)
    limit = min(run.seconds, tr["trace_seconds"]) if run.trace \
        else run.seconds
    with run.phase("build"):
        plan = gen.make(tr, cfg, run.seed, limit)
    server, engine, hosted, correct, seen = bring_up(run)
    try:
        ctx = serve.Ctx(run, server, plan)
        with run.phase("prime"):
            gen.prime(ctx)
        run.open_window()
        with run.traced() as win:
            m0 = engine.expert_token_counts()
            c0 = serve.counters(hosted)
            pool = serve.PoolWatch()
            pool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held = pool.close()
            c1 = serve.counters(hosted)
            m1 = engine.expert_token_counts()
        res = gen.finish(ctx, win.p0, win.p1)
    finally:
        server.stop()

    delta = {k: c1[k] - c0[k] for k in c0}
    e2e = {"serve_tokens_per_s": delta["tokens"] / win.seconds}
    # the program's token counter against what the clients were promised
    counted_ok = res.get("tokens_completed_inside", 0) <= delta["tokens"] \
        <= res.get("tokens_overlapping", delta["tokens"])
    # a request shed at ANY time of the run, set-up included, means the
    # callers' first round was not the staggered one (see bring_up)
    from paddle_tpu.serving import metrics as sm
    shed = sm.REQUESTS.labels(model=MODEL, outcome="shed").value
    clean = (delta["serving_compiles"] == 0 and delta["aot_fallbacks"] == 0
             and res["threads_left"] == 0 and shed == 0)
    obs = {
        "correct": bool(correct and counted_ok and clean
                        and res["failed"] == 0),
        "attempted": res["attempted"], "failed": res["failed"],
        "end_to_end": e2e, "window_s": win.seconds,
        "units": {"decode_steps": delta["decode_steps"],
                  "prefills": delta["prefills"]},
        "counters": delta, "phases": dict(run.phase_s),
        "compiles_in_window": win.compiles + delta["serving_compiles"],
        "slot_occupancy": (delta["sched_slot_steps"]
                           / (delta["sched_steps"] * engine.n_slots)
                           if delta["sched_steps"] else None),
        "kv_pages_held": held,
        # [layers, 2, n_held]: tokens per held expert and steps it was
        # hit, over the ``moe_steps`` decode steps between the program's
        # two snapshots nearest the window's edges
        "moe_counts": m1["counts"] - m0["counts"],
        "moe_steps": m1["steps"] - m0["steps"],
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    return harness.add_device_observations(run, win, obs)
