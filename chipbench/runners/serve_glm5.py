"""Runner of serving cells of a latent-attention model with the DSA
indexer (``layer_kinds`` ``mla`` in the configuration's ``build``):
``decoder_lm`` behind ``ModelServer`` on the slot engine, the normal
path that ``runners/serve.py`` and ``runners/serve_hybrid.py`` drive,
whose window loop, counters, weights and logit probe it reuses. What
differs:

- the comparison with the plain reference is of LOGITS and of the
  SELECTION: ``check.prompt_lens`` requests are admitted together and
  stepped together through ``engine.admit`` / ``engine.step`` (the
  executables, scope and pages the server then drives); beside the
  float32 logits every token was chosen from, an executable of the
  check's own reads which rows of the slot each latent-attention layer
  attended at each decode step (``PickProbe``; the engine fetches
  tokens only). Rows become positions on the host (a slot's generated
  rows start at its prompt's BUCKET) and are compared with the
  positions the reference's indexer selects
  (``reference.overlap``). Then the same prompts go through
  ``ModelServer.submit_generate`` at once and the server has to return
  the same tokens;
- the program's ``paddle_dsa_rows_scored_total`` and
  ``paddle_dsa_rows_selected_total`` are read at the window's edges
  (``obs["dsa_rows"]``): what the indexer's and the sparse attention's
  roofline shares count their bytes from;
- a configuration with ``router_balance`` has the held experts' entries
  of the router's correction bias balanced in set-up, after the warm-up
  and before the check (``balance_router_bias``): a decode step streams
  the HIT held experts' weights alone, so a step's time follows the
  load the router gives them, and a drawn bias leaves that to the seed.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness
from chipbench.runners import serve, serve_hybrid

MODEL = serve.MODEL


class PickProbe(serve_hybrid.LogitProbe):
    """``LogitProbe`` whose decode reader also returns, per
    latent-attention layer, the rows of each slot that the NEXT decode
    step attends (``<model>_l<i>_mla_selected``: -1 where fewer than
    index_topk are live)."""

    def __init__(self, engine):
        import jax
        from paddle_tpu.core.lowering import CompiledBlock
        super().__init__(engine)
        served = engine._cb_decode
        gvars = served._program_desc.global_block.vars
        names = [engine.name + "_logits"] + sorted(
            (n for n in gvars if n.endswith("_mla_selected")),
            key=lambda n: int(n.split("_l")[-1].split("_")[0]))
        cb = CompiledBlock(served._program_desc, 0, served.sig.feed_names,
                           names, is_test=True, donate=False)
        self._decode = (cb, jax.jit(lambda *args: cb._step_fn(*args)[0]))

    def decode(self, slots) -> tuple:
        """(logits [len(slots), V], rows [len(slots), layers, k])."""
        feeds = self.engine._decode_feeds()
        out = self._read(self._decode, {
            k: feeds[k] for k in self._decode[0].sig.feed_names})
        slots = np.asarray(slots)
        return (np.asarray(out[0])[slots],
                np.stack([np.asarray(r) for r in out[1:]], axis=1)[slots])


def rows_to_positions(rows, seq_len: int, gen_start: int):
    """A slot's rows as token positions: the prompt's rows are their
    positions, generated rows start at the bucket; -1 stays, and so
    does whatever lies between the prompt's end and its bucket — no
    position, which no reference set has (-2)."""
    rows = np.asarray(rows)
    out = np.where(rows >= gen_start, rows - gen_start + seq_len, rows)
    return np.where((rows >= seq_len) & (rows < gen_start), -2, out)


def serve_together(engine, probe, prompts, budgets) -> list:
    """Greedy requests admitted one after the other and then stepped
    TOGETHER, request i for ``budgets[i]`` tokens. Per request: (tokens
    [budget], the float32 logits row the served path chose each token
    from [budget, V], the POSITIONS each decode step attended per
    latent-attention layer [budget - 1, layers, k])."""
    rows, live, geometry = {}, [], {}
    for prompt, budget in zip(prompts, budgets):
        first = probe.prefill(prompt)
        slot, tok, done = engine.admit(prompt, max_new=budget)
        rows[slot] = ([tok], [first], [])
        geometry[slot] = (len(prompt), int(engine._gen0[slot]))
        if not done:
            live.append(slot)
    order = list(rows)
    while live:
        logits, picks = probe.decode(live)
        seen = {s: (logits[i], picks[i]) for i, s in enumerate(live)}
        for slot, tok, done in engine.step():
            rows[slot][0].append(tok)
            rows[slot][1].append(seen[slot][0])
            rows[slot][2].append(rows_to_positions(seen[slot][1],
                                                   *geometry[slot]))
            if done:
                live.remove(slot)
    return [(np.asarray(rows[s][0], np.int64), np.stack(rows[s][1]),
             np.asarray(rows[s][2])) for s in order]


def serve_one(engine, prompt, max_new: int, probe=None):
    return serve_together(engine, probe or PickProbe(engine), [prompt],
                          [max_new])[0]


def compare_with_reference(cfg: dict, engine, rng, **ref_kwargs) -> tuple:
    """``check.prompt_lens`` greedy requests of ``check.max_new`` tokens
    each, live together, against the reference's full forward on the
    same weights, under the limits the configuration's ``check`` gives
    with their reasons. Returns (correct, what was seen, the prompts and
    the served tokens: ``same_through_server`` sends them again)."""
    chk, build = cfg["check"], cfg["build"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    params = {n: engine.scope.find_var(n)
              for n in ref.param_names(build, MODEL)}
    prompts = [rng.randint(1, build["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    served = serve_together(engine, PickProbe(engine), prompts,
                            chk["max_new"])
    seen = {"logit_err_median": 0.0, "logit_err_max": 0.0,
            "select_overlap_min": 1.0, "select_overlap_median": 1.0,
            "margin_max_sd": 0.0}
    sized = True
    for prompt, budget, (toks, logits, picks) in zip(
            prompts, chk["max_new"], served):
        sized &= len(toks) == budget
        logit_err, overlaps, margin = ref.compare(
            params, prompt, toks, logits, picks, build, MODEL, **ref_kwargs)
        seen["logit_err_median"] = max(seen["logit_err_median"],
                                       float(np.median(logit_err)))
        seen["logit_err_max"] = max(seen["logit_err_max"],
                                    float(logit_err.max()))
        seen["margin_max_sd"] = max(seen["margin_max_sd"],
                                    float(margin.max()))
        if overlaps.size:
            seen["select_overlap_min"] = min(seen["select_overlap_min"],
                                             float(overlaps.min()))
            seen["select_overlap_median"] = min(
                seen["select_overlap_median"], float(np.median(overlaps)))
    limits = chk["limits"]
    # an error may not pass its limit, an overlap may not fall under it
    ok = sized and all(
        seen[k] >= limit if k.startswith("select_overlap")
        else seen[k] <= limit for k, limit in limits.items())
    return bool(ok), {**seen, "limits": limits,
                      "tokens_compared": int(sum(chk["max_new"]))}, \
        (prompts, [toks for toks, _logits, _picks in served])


build_engine = serve_hybrid.build_engine


def balance_router_bias(cfg: dict, engine, seed: int, device) -> dict:
    """The HELD experts' correction bias as a balanced deployment has
    it: the configuration's ``router_balance`` rounds of the published
    aux-loss-free rule (``b_e += gamma * sign(mean - load_e)``, gamma
    times ``decay`` a round) on ``bucket`` calibration tokens drawn from
    ``--seed``, through the served prefill view fed as its warm-up feeds
    it (every page row and the state slot a sentinel: nothing is
    written). A chip of the deployment sees its own experts' loads —
    the program's own per-expert counters beside each expert layer —
    and the known mean ``tokens x top_k / n_routed_experts``; the
    absent experts' entries stay the draw. Before the check, so that
    the check, the reference and the window read the one bias."""
    import jax
    rb, build = cfg["router_balance"], cfg["build"]
    p_len, lo = rb["bucket"], build.get("held_start", 0)
    cb = engine._cb_prefill[p_len]
    ops = [op for op in cb._program_desc.global_block.ops
           if op.type == "expert_ffn_held" and op.inputs.get("Counts")
           and op.inputs.get("RouterBias")]
    bias = {op.inputs["RouterBias"][0]: np.array(
        engine.scope.find_var(op.inputs["RouterBias"][0])) for op in ops}
    drawn = {n: b.copy() for n, b in bias.items()}

    def counts():
        return np.stack([np.asarray(engine.scope.find_var(
            op.inputs["Counts"][0]))[0] for op in ops]).astype(np.int64)

    rng = np.random.RandomState((seed + 2) % 2 ** 32)
    gamma, uneven = rb["gamma"], []
    for _ in range(rb["rounds"]):
        feeds = engine._prefill_feeds(p_len)
        feeds["ids"][0, :, 0] = rng.randint(1, build["vocab"], p_len)
        feeds["seq_len"][:] = p_len
        before = counts()
        engine._run(cb, (engine.PREFILL, p_len), feeds)
        engine._grouped_given_done += engine._grouped_given[p_len]
        load = (counts() - before) % (1 << 32)
        for op, held in zip(ops, load):
            name = op.inputs["RouterBias"][0]
            b = bias[name]
            mean = p_len * int(op.attrs["top_k"]) / b.shape[1]
            b[0, lo:lo + held.size] += (
                gamma * np.sign(mean - held)).astype(b.dtype)
            engine.scope.set_var(name, jax.device_put(b, device))
        uneven.append(float(np.mean(load.max(axis=1) / load.mean(axis=1))))
        gamma *= rb["decay"]
    moved = np.stack([bias[n] - drawn[n] for n in bias])
    return {"max_over_mean_by_round": uneven,
            "held_bias_moved_max": float(np.abs(moved).max()),
            "absent_bias_moved": float(np.abs(np.delete(
                moved, np.s_[lo:lo + load.shape[1]], axis=2)).max())}


def bring_up(run: harness.Run):
    from paddle_tpu import serving
    with run.phase("build"):
        engine = build_engine(run.config, run.seed, run.devices[0])
    with run.phase("warm"):
        engine.warmup()
        balanced = balance_router_bias(
            run.config, engine, run.seed, run.devices[0]) \
            if "router_balance" in run.config else None
    with run.phase("check"):
        correct, seen, (prompts, tokens) = compare_with_reference(
            run.config, engine,
            np.random.RandomState((run.seed + 1) % 2 ** 32))
        seen["router_balance"] = balanced
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


def dsa_rows() -> dict:
    from paddle_tpu.serving import metrics as sm
    return {"scored": sm.DSA_ROWS_SCORED.labels(model=MODEL).value,
            "selected": sm.DSA_ROWS_SELECTED.labels(model=MODEL).value}


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
            c0, d0 = serve.counters(hosted), dsa_rows()
            pool = serve.PoolWatch()
            pool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held = pool.close()
            c1, d1 = serve.counters(hosted), dsa_rows()
            m1 = engine.expert_token_counts()
        res = gen.finish(ctx, win.p0, win.p1)
    finally:
        server.stop()

    delta = {k: c1[k] - c0[k] for k in c0}
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
        "moe_counts": m1["counts"] - m0["counts"],
        "moe_steps": m1["steps"] - m0["steps"],
        # cache rows the indexer scored and the sparse attention
        # attended over the window's decode steps, all layers
        "dsa_rows": {k: d1[k] - d0[k] for k in d0},
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    # the program's counters over the window, as a traced run's
    # per-layer metrics read them, in an untraced run's notes too: a
    # step's time follows the held experts' hit share (PERF.md, PR 60)
    obs["notes"]["dsa_rows"] = obs["dsa_rows"]
    obs["notes"]["moe_steps"] = int(obs["moe_steps"])
    obs["notes"]["moe_counts"] = np.asarray(obs["moe_counts"]).tolist()
    return harness.add_device_observations(run, win, obs)
