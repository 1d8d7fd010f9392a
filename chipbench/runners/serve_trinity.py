"""Runner of serving cells of a model whose attention layers are of two
kinds with different cache needs (``layer_kinds`` with ``swa`` in the
configuration's ``build``: sliding-window layers in the page pool's
window group beside full layers in its full group): ``decoder_lm``
behind ``ModelServer`` on the slot engine, the normal path that
``runners/serve.py`` and ``runners/serve_hybrid.py`` drive, whose window
loop, counters, weights and logit probe it reuses. What differs:

- the comparison with the plain reference is of LOGITS and of WHAT EACH
  WINDOW LAYER ATTENDED: ``check.prompt_lens`` requests are admitted
  together and stepped together through ``engine.admit`` /
  ``engine.step`` (the executables, scope and pages the server then
  drives; at least one slot returns window pages while it is checked:
  ``window_pages_released``); beside the float32 logits every token was
  chosen from, an executable of the check's own reads each window
  layer's ``Attended`` (the lowest key position the query attended and
  how many keys: ``AttendedProbe``; the engine fetches tokens only),
  which the reference gives in closed form. A window off by one key
  moves no logit by more than the rounding of bfloat16 does; it moves
  every one of these readings. Then the same prompts go through
  ``ModelServer.submit_generate`` at once and the server has to return
  the same tokens;
- the window group's own gauges are sampled beside the pool's
  (``obs["kv_window_pages_held"]``) and the program's
  ``paddle_kv_window_pages_released_total`` and
  ``paddle_kv_window_rows_attended_total`` are read at the window's
  edges (``obs["window_pages_released"]``, ``obs["window_rows"]``: what
  the window layers' roofline share counts its bytes from).
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness
from chipbench.runners import serve, serve_hybrid

MODEL = serve.MODEL


class AttendedProbe(serve_hybrid.LogitProbe):
    """``LogitProbe`` whose readers also return each window layer's
    ``<model>_l<i>_attn_attended``: per query (the lowest key position
    attended, the number of keys)."""

    def __init__(self, engine):
        import jax
        from paddle_tpu.core.lowering import CompiledBlock
        self.engine = engine

        def reader(served):
            gvars = served._program_desc.global_block.vars
            names = [engine.name + "_logits"] + sorted(
                (n for n in gvars if n.endswith("_attn_attended")),
                key=lambda n: int(n.split("_l")[-1].split("_")[0]))
            cb = CompiledBlock(served._program_desc, 0,
                               served.sig.feed_names, names, is_test=True,
                               donate=False)
            return cb, jax.jit(lambda *args: cb._step_fn(*args)[0])
        self._decode = reader(engine._cb_decode)
        self._prefill = {p: reader(cb)
                         for p, cb in engine._cb_prefill.items()}

    def prefill(self, prompt) -> tuple:
        """(logits [V], attended [window layers, 2]) of the row the
        prefill view chooses a prompt's first token from."""
        p_len = self.engine.prompt_bucket_for(len(prompt))
        feeds = self.engine._prefill_feeds(p_len)
        feeds["ids"][0, :len(prompt), 0] = prompt
        feeds["seq_len"][:] = len(prompt)
        out = self._read(self._prefill[p_len], feeds)
        return (np.asarray(out[0])[0],
                np.stack([np.asarray(a)[len(prompt) - 1] for a in out[1:]]))

    def decode(self, slots) -> tuple:
        """(logits [len(slots), V], attended [len(slots), window
        layers, 2]) of the NEXT decode step."""
        feeds = self.engine._decode_feeds()
        out = self._read(self._decode, {
            k: feeds[k] for k in self._decode[0].sig.feed_names})
        slots = np.asarray(slots)
        return (np.asarray(out[0])[slots],
                np.stack([np.asarray(a) for a in out[1:]], axis=1)[slots])


def serve_together(engine, probe, prompts, budgets) -> list:
    """Greedy requests admitted one after the other and then stepped
    TOGETHER, request i for ``budgets[i]`` tokens. Per request: (tokens
    [budget], the float32 logits row the served path chose each token
    from [budget, V], what each window layer attended when it did
    [budget, window layers, 2])."""
    rows, live = {}, []
    for prompt, budget in zip(prompts, budgets):
        first, seen = probe.prefill(prompt)
        slot, tok, done = engine.admit(prompt, max_new=budget)
        rows[slot] = ([tok], [first], [seen])
        if not done:
            live.append(slot)
    order = list(rows)
    while live:
        logits, seen = probe.decode(live)
        at = {s: i for i, s in enumerate(live)}
        for slot, tok, done in engine.step():
            rows[slot][0].append(tok)
            rows[slot][1].append(logits[at[slot]])
            rows[slot][2].append(seen[at[slot]])
            if done:
                live.remove(slot)
    return [(np.asarray(rows[s][0], np.int64), np.stack(rows[s][1]),
             np.stack(rows[s][2])) for s in order]


def serve_one(engine, prompt, max_new: int, probe=None):
    return serve_together(engine, probe or AttendedProbe(engine), [prompt],
                          [max_new])[0]


def window_counters() -> dict:
    from paddle_tpu.serving import metrics as sm
    return {"released": sm.KV_WINDOW_PAGES_RELEASED.labels(
                model=MODEL).value,
            "rows": sm.KV_WINDOW_ROWS_ATTENDED.labels(model=MODEL).value}


def serve_check(cfg: dict, engine, rng) -> tuple:
    """``check.prompt_lens`` greedy requests of ``check.max_new`` tokens
    each, live together: (the prompts, what ``serve_together`` read of
    each, the window pages the slots returned meanwhile)."""
    chk, build = cfg["check"], cfg["build"]
    prompts = [rng.randint(1, build["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    released0 = window_counters()["released"]
    served = serve_together(engine, AttendedProbe(engine), prompts,
                            chk["max_new"])
    return prompts, served, window_counters()["released"] - released0


def judge(cfg: dict, engine, prompts, served, released,
          **ref_kwargs) -> tuple:
    """What ``serve_check`` read against the reference's full forward on
    the same weights (``ref_kwargs``: a control's forward instead),
    under the limits the configuration's ``check`` gives with their
    reasons: (correct, what was seen)."""
    chk, build = cfg["check"], cfg["build"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    params = {n: engine.scope.find_var(n)
              for n in ref.param_names(build, MODEL)}
    seen = {"logit_err_median": 0.0, "logit_err_max": 0.0,
            "margin_max_sd": 0.0, "window_rows_wrong_share": 0.0,
            "window_pages_released": released}
    sized, wrong, read = True, 0, 0
    for prompt, budget, (toks, logits, attended) in zip(
            prompts, chk["max_new"], served):
        sized &= len(toks) == budget
        logit_err, margin, positions = ref.compare(
            params, prompt, toks, logits, build, MODEL, **ref_kwargs)
        # a logit that is no number is the largest error there is
        logit_err = np.where(np.isfinite(logit_err), logit_err, np.inf)
        seen["logit_err_median"] = max(seen["logit_err_median"],
                                       float(np.median(logit_err)))
        seen["logit_err_max"] = max(seen["logit_err_max"],
                                    float(logit_err.max()))
        seen["margin_max_sd"] = max(seen["margin_max_sd"],
                                    float(margin.max()))
        want = ref.attended(build, positions,
                            ref_kwargs.get("window"))[:, None, :]
        wrong += int(np.any(attended != want, axis=-1).sum())
        read += attended.shape[0] * attended.shape[1]
    seen["window_rows_wrong_share"] = wrong / max(read, 1)
    limits = chk["limits"]
    ok = sized and all(seen[k] <= limit for k, limit in limits.items()) \
        and released >= chk.get("min_released", 0)
    return bool(ok), {**seen, "limits": limits,
                      "tokens_compared": int(sum(chk["max_new"]))}


def compare_with_reference(cfg: dict, engine, rng) -> tuple:
    """(correct, what was seen, the prompts and the served tokens:
    ``same_through_server`` sends them again)."""
    prompts, served, released = serve_check(cfg, engine, rng)
    correct, seen = judge(cfg, engine, prompts, served, released)
    return correct, seen, (prompts, [toks for toks, _l, _s in served])


build_engine = serve_hybrid.build_engine


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
            hosted = server.add_model(
                engine, max_queue_depth=2 * engine.n_slots)
        with run.phase("check"):
            seen["same_through_server"] = serve_hybrid.same_through_server(
                server, run.config, prompts, tokens)
            correct &= seen["same_through_server"]
    except BaseException:
        server.stop()
        raise
    return server, engine, hosted, correct, seen


class WindowPoolWatch(serve.PoolWatch):
    """``serve.PoolWatch`` over the window group's own gauges: the mean
    share of the group's pages not free while the window was open."""

    def __init__(self):
        super().__init__()
        from paddle_tpu.serving import metrics as sm
        self._total = sm.KV_GROUP_PAGES_TOTAL.labels(model=MODEL,
                                                     group="window")
        self._free = sm.KV_GROUP_PAGES_FREE.labels(model=MODEL,
                                                   group="window")


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
            c0, w0 = serve.counters(hosted), window_counters()
            pool, wpool = serve.PoolWatch(), WindowPoolWatch()
            pool.start()
            wpool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held, wheld = pool.close(), wpool.close()
            c1, w1 = serve.counters(hosted), window_counters()
            m1 = engine.expert_token_counts()
        res = gen.finish(ctx, win.p0, win.p1)
    finally:
        server.stop()

    delta = {k: c1[k] - c0[k] for k in c0}
    released = w1["released"] - w0["released"]
    e2e = {"serve_tokens_per_s": delta["tokens"] / win.seconds}
    counted_ok = res.get("tokens_completed_inside", 0) <= delta["tokens"] \
        <= res.get("tokens_overlapping", delta["tokens"])
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
        "kv_window_pages_held": wheld,
        "window_pages_released": released,
        # cache rows the window layers attended over the window's decode
        # steps, all window layers
        "window_rows": w1["rows"] - w0["rows"],
        "moe_counts": m1["counts"] - m0["counts"],
        "moe_steps": m1["steps"] - m0["steps"],
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "kv_window_pages_held_share": wheld,
                  "window_pages_released": released,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    return harness.add_device_observations(run, win, obs)
