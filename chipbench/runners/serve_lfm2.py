"""Runner of serving cells of a hybrid sparse model whose fixed-size
per-slot state is a gated short convolution's window (``conv`` in the
configuration's ``layer_kinds``): ``decoder_lm`` behind ``ModelServer``
on the slot engine, the normal path that ``runners/serve_hybrid.py``
drives, whose engine build, logit probe, server check and window loop it
reuses. ``serve_hybrid`` and ``serve_granite`` cannot be named as they
are: each finds the state it compares by its own mixer (``_kda_state_``
in a name; the kind ``ssd`` and its ``[N, H*P]`` layout). What differs:

- the window each slot was left with is found by its DECLARED role (the
  ``ConvOut`` of the ops registered with ``slot_state`` of kind
  ``shortconv``: ``core/registry.py:slot_state_vars``) and compared with
  the reference's last rows of ``B * x``;
- beside the float32 logits every token was chosen from, an executable
  of the check's own reads WHICH EXPERTS every expert layer picked for
  every token fed (``PicksProbe``, over copies of the served programs in
  which the expert ops declare their optional output ``Picks``: the
  served programs name none, the engine fetches tokens only). The
  reference weighs those experts and holds each served pick to its OWN
  float32 scores: with four picks of 32 and ten expert layers a
  near-tied pick flips under bfloat16 activations for most sequences
  somewhere, and a flipped pick moves a token's logits more than any
  fault the logits are there to find — so the served picks are weighed
  but not trusted: how far each lies under the reference's k-th best
  score + bias is judged, by the layers' mean (a layer that picks by
  other scores, say without its bias) and by the largest (one grossly
  wrong pick, which no logit could show once the reference adopts it);
- the program's ``paddle_shortconv_tokens_total`` is read at the
  window's edges (``obs["shortconv_tokens"]``, by view: the TRUE tokens
  the conv layers' roofline share counts its operations from).
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness
from chipbench.runners import serve, serve_hybrid

MODEL = serve.MODEL
build_engine = serve_hybrid.build_engine
same_through_server = serve_hybrid.same_through_server


def window_vars(engine) -> list:
    """The conv layers' window variables, by layer."""
    from paddle_tpu.core.registry import slot_state_vars
    block = engine._cb_decode._program_desc.global_block
    names = slot_state_vars(block).get("shortconv", {}).get("ConvOut", [])
    return sorted(names, key=lambda n: int(n.rsplit("_", 1)[1]))


def with_picks(program):
    """A copy of ``program`` in which every expert layer DECLARES its
    op's optional output ``Picks`` ([tokens, top_k] int32: the experts
    picked for every token), and the names given them in the layers'
    order. No builder declares that output — the served programs are
    what a deployment builds — and an op that does not declare it lowers
    as it did; a reader of routing decisions names it in its own copy."""
    from paddle_tpu.core import ir
    desc, names = program.clone(), []
    block = desc.global_block
    for op in block.ops:
        if op.type == "expert_ffn_held":
            names.append(op.output("Out")[0] + "@picks")
            block.add_var(ir.VarDesc(name=names[-1], dtype="int32"))
            op.outputs["Picks"] = [names[-1]]
    return desc, names


class PicksProbe(serve_hybrid.LogitProbe):
    """``LogitProbe`` whose readers also return each expert layer's
    picks, the experts picked for every token of the dispatch: its
    executables are over copies of the served programs in which the
    expert layers name that output (:func:`with_picks`)."""

    def __init__(self, engine):
        import jax
        from paddle_tpu.core.lowering import CompiledBlock
        self.engine = engine

        def reader(served):
            desc, picks = with_picks(served._program_desc)
            cb = CompiledBlock(desc, 0, served.sig.feed_names,
                               [engine.name + "_logits"] + picks,
                               is_test=True, donate=False)
            return cb, jax.jit(lambda *args: cb._step_fn(*args)[0])
        self._decode = reader(engine._cb_decode)
        self._prefill = {p: reader(cb)
                         for p, cb in engine._cb_prefill.items()}

    def prefill(self, prompt) -> tuple:
        """(logits [V] of the row the prefill view chooses a prompt's
        first token from, picks [expert layers, len(prompt), k])."""
        p_len = self.engine.prompt_bucket_for(len(prompt))
        feeds = self.engine._prefill_feeds(p_len)
        feeds["ids"][0, :len(prompt), 0] = prompt
        feeds["seq_len"][:] = len(prompt)
        out = self._read(self._prefill[p_len], feeds)
        return (np.asarray(out[0])[0],
                np.stack([np.asarray(a)[:len(prompt)] for a in out[1:]]))

    def decode(self, slots) -> tuple:
        """(logits [len(slots), V], picks [len(slots), expert layers, k])
        of the NEXT decode step."""
        feeds = self.engine._decode_feeds()
        out = self._read(self._decode, {
            k: feeds[k] for k in self._decode[0].sig.feed_names})
        slots = np.asarray(slots)
        return (np.asarray(out[0])[slots],
                np.stack([np.asarray(a) for a in out[1:]], axis=1)[slots])


def serve_together(engine, probe, prompts, budgets) -> list:
    """``serve_hybrid.serve_together`` with the conv layers' windows and
    the expert layers' picks: per request (tokens [budget], the float32
    logits row the served path chose each token from [budget, V], the
    window per conv layer its slot was left with, [taps - 1, M] each —
    read when ALL have finished, so a slot released early has sat
    through the others' steps —, the experts picked for every token fed
    [expert layers, len(prompt) + budget - 1, k])."""
    rows, live = {}, []
    for prompt, budget in zip(prompts, budgets):
        first, picks = probe.prefill(prompt)
        slot, tok, done = engine.admit(prompt, max_new=budget)
        rows[slot] = ([tok], [first], [picks])
        if not done:
            live.append(slot)
    order = list(rows)
    while live:
        logits, picks = probe.decode(live)
        at = {s: i for i, s in enumerate(live)}
        for slot, tok, done in engine.step():
            rows[slot][0].append(tok)
            rows[slot][1].append(logits[at[slot]])
            rows[slot][2].append(picks[at[slot]][:, None])
            if done:
                live.remove(slot)
    names = window_vars(engine)
    return [(np.asarray(rows[s][0], np.int64), np.stack(rows[s][1]),
             [np.asarray(engine.scope.find_var(n)[s], np.float32)
              for n in names],
             np.concatenate(rows[s][2], axis=1))
            for s in order]


def serve_one(engine, prompt, max_new: int, probe=None):
    """One request through ``serve_together``."""
    return serve_together(engine, probe or PicksProbe(engine), [prompt],
                          [max_new])[0]


def serve_check(cfg: dict, engine, rng) -> tuple:
    """``check.prompt_lens`` greedy requests of ``check.max_new`` tokens
    each, live together: (the prompts, what ``serve_together`` read)."""
    chk, build = cfg["check"], cfg["build"]
    prompts = [rng.randint(1, build["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    return prompts, serve_together(engine, PicksProbe(engine), prompts,
                                   chk["max_new"])


def judge(cfg: dict, engine, prompts, served, **ref_kwargs) -> tuple:
    """What ``serve_check`` read against the reference's full forward on
    the same weights (``ref_kwargs``: a control's forward instead), under
    the limits the configuration's ``check`` gives with their reasons:
    (correct, what was seen). A reading that is no number is the largest
    there is."""
    chk, build = cfg["check"], cfg["build"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    params = {n: engine.scope.find_var(n)
              for n in ref.param_names(build, MODEL)}
    seen = {"logit_err_median": 0.0, "logit_err_max": 0.0,
            "window_err_max": 0.0, "margin_max_sd": 0.0}
    sized = all(str(engine.scope.find_var(n).dtype) == chk["window_dtype"]
                for n in window_vars(engine))
    gaps = []
    for prompt, budget, (toks, logits, windows, picks) in zip(
            prompts, chk["max_new"], served):
        sized &= len(toks) == budget
        logit_err, window_err, margin, gap = ref.compare(
            params, prompt, toks, logits, windows, build, MODEL,
            served_picks=picks, **ref_kwargs)
        gaps.append(gap)
        for key, value in (("logit_err_median", np.median(logit_err)),
                           ("logit_err_max", logit_err.max()),
                           ("window_err_max", window_err.max()),
                           ("margin_max_sd", margin.max())):
            seen[key] = max(seen[key], value)
    # the picks, over every token fed of the four requests together
    # [expert layers, tokens]: the largest gap (no pick grossly wrong),
    # the largest of the layers' MEAN gaps (a layer that picks by other
    # scores moves its mean tenfold, and the largest gap hardly more
    # than a near tie the probe's and the engine's executables decided
    # differently does) and, not judged, the share of picks that are
    # not the reference's own
    gaps = np.concatenate(gaps, axis=1)
    seen.update(picks_gap_max=gaps.max(),
                picks_gap_layer_mean_max=gaps.mean(1).max(),
                picks_not_own_share=(gaps > 0).mean())
    seen = {k: float(v) if np.isfinite(v) else float("inf")
            for k, v in seen.items()}
    ok = sized and all(seen[k] <= chk["limits"][k] for k in chk["limits"])
    return bool(ok), {**seen, "limits": chk["limits"],
                      "tokens_compared": int(sum(chk["max_new"]))}


def compare_with_reference(cfg: dict, engine, rng) -> tuple:
    """(correct, what was seen, the prompts and the served tokens:
    ``same_through_server`` sends them again)."""
    prompts, served = serve_check(cfg, engine, rng)
    correct, seen = judge(cfg, engine, prompts, served)
    return correct, seen, (prompts, [s[0] for s in served])


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
            seen["same_through_server"] = same_through_server(
                server, run.config, prompts, tokens)
            correct &= seen["same_through_server"]
    except BaseException:
        server.stop()
        raise
    return server, engine, hosted, correct, seen


def shortconv_tokens() -> dict:
    """The conv layers' own counter by view ({} from a program that has
    none: a parent of PR 51)."""
    from paddle_tpu.serving import metrics as sm
    if not hasattr(sm, "SHORTCONV_TOKENS"):
        return {}
    return {view: sm.SHORTCONV_TOKENS.labels(model=MODEL, view=view).value
            for view in ("prefill", "decode")}


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
            c0, s0 = serve.counters(hosted), shortconv_tokens()
            pool = serve.PoolWatch()
            pool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held = pool.close()
            c1, s1 = serve.counters(hosted), shortconv_tokens()
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
        # true tokens through the conv layers over the window, summed
        # over those layers, by view
        "shortconv_tokens": {v: s1[v] - s0[v] for v in s0},
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    return harness.add_device_observations(run, win, obs)
