"""Runner of serving cells of a DENSE hybrid model whose fixed-size
per-slot state is a Mamba-1 selective-scan layer's (``s6`` in the
configuration's ``layer_kinds``): ``decoder_lm`` behind ``ModelServer``
on the slot engine, the normal path that ``runners/serve_hybrid.py``
drives, whose engine build, logit probe and server check it reuses.
``serve_granite`` and ``serve_olmo_hybrid`` cannot be named as they are:
each finds the state it compares by its own mixer's kind and layout.
What differs:

- the state each slot was left with is found by its DECLARED role (the
  ``StateOut`` of the ops registered with ``slot_state`` of kind ``s6``:
  ``core/registry.py:slot_state_vars``), read for the Mamba layers
  ``check.state_layers`` alone (a first, a middle, the last: 26 layers
  x 328 KB a slot otherwise) and turned from the layout the program
  keeps ([N, C] a slot) into the reference's [C, N];
- what the Mamba layers' metrics are computed from is observed besides:
  the program's ``paddle_s6_tokens_scanned_total`` and
  ``paddle_s6_chunk_rows_total`` at the window's edges
  (``obs["s6_tokens"]``, ``obs["s6_rows"]``), and the live slots summed
  over the window's decode steps (``obs["slot_steps"]``, the scheduler's
  own count: what a step's state bytes follow);
- the model has no expert layer: nothing is read of one;
- the logits are read by ``serve_hybrid.LogitProbe``: its decode reader
  donates nothing, so XLA copies the variables the view writes in place
  before it reads them — here the two attention layers' four planes,
  1.07 GB beside 9.5 GB of arguments; the Mamba layers' state is read,
  not scattered into, and is not copied.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import harness
from chipbench.runners import serve, serve_hybrid

MODEL = serve.MODEL
build_engine = serve_hybrid.build_engine
same_through_server = serve_hybrid.same_through_server
LogitProbe = serve_hybrid.LogitProbe


def state_vars(engine) -> list:
    """The Mamba layers' state variables, by layer."""
    from paddle_tpu.core.registry import slot_state_vars
    block = engine._cb_decode._program_desc.global_block
    names = slot_state_vars(block).get("s6", {}).get("StateOut", [])
    return sorted(names, key=lambda n: int(n.rsplit("_", 1)[1]))


def _judged_layers(cfg: dict, engine) -> list:
    """Indices, among the Mamba layers, of the ones whose state the
    check reads (``check.state_layers``; all of them without the key)."""
    return list(cfg["check"].get("state_layers")
                or range(len(state_vars(engine))))


def serve_together(engine, probe, prompts, budgets, layers=None) -> list:
    """``serve_hybrid.serve_together`` with the Mamba layers' state: per
    request (tokens [budget], the float32 logits row the served path
    chose each token from [budget, V], the state of the Mamba layers
    ``layers`` its slot was left with, [C, N] each — read when ALL have
    finished, so a slot released early has sat through the others'
    steps)."""
    ref = importlib.import_module("chipbench.reference.jamba2_3b")
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
    names = state_vars(engine)
    names = [names[j] for j in (range(len(names)) if layers is None
                                else layers)]
    return [(np.asarray(rows[s][0], np.int64), np.stack(rows[s][1]),
             [ref.served_state(engine.scope.find_var(n)[s]) for n in names])
            for s in order]


def serve_one(engine, prompt, max_new: int, probe=None):
    """One request through ``serve_together`` (every Mamba layer's
    state)."""
    return serve_together(engine, probe or LogitProbe(engine), [prompt],
                          [max_new])[0]


def serve_check(cfg: dict, engine, rng) -> tuple:
    """``check.prompt_lens`` greedy requests of ``check.max_new`` tokens
    each, live together: (the prompts, what ``serve_together`` read)."""
    chk, build = cfg["check"], cfg["build"]
    prompts = [rng.randint(1, build["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    return prompts, serve_together(
        engine, LogitProbe(engine), prompts, chk["max_new"],
        _judged_layers(cfg, engine))


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
            "state_err_median": 0.0, "state_err_max": 0.0,
            "state_err_slow_median": 0.0, "state_bf16_share": 0.0,
            "margin_max_sd": 0.0}
    sized = all(str(engine.scope.find_var(n).dtype) == chk["state_dtype"]
                for n in state_vars(engine))
    for prompt, budget, (toks, logits, states) in zip(
            prompts, chk["max_new"], served):
        sized &= len(toks) == budget
        logit_err, state_err, margin, slow = ref.compare(
            params, prompt, toks, logits, states, build, MODEL,
            state_layers=_judged_layers(cfg, engine), **ref_kwargs)
        for key, value in (("logit_err_median", np.median(logit_err)),
                           ("logit_err_max", logit_err.max()),
                           ("state_err_median", np.median(state_err)),
                           ("state_err_max", state_err.max()),
                           ("state_err_slow_median",
                            np.median(state_err[slow])),
                           ("state_bf16_share",
                            max(ref.bf16_share(s) for s in states)),
                           ("margin_max_sd", margin.max())):
            value = float(value) if np.isfinite(value) else float("inf")
            seen[key] = max(seen[key], value)
    ok = sized and all(seen[k] <= chk["limits"][k] for k in chk["limits"])
    return bool(ok), {**seen, "limits": chk["limits"],
                      "tokens_compared": int(sum(chk["max_new"]))}


def compare_with_reference(cfg: dict, engine, rng) -> tuple:
    """(correct, what was seen, the prompts and the served tokens:
    ``same_through_server`` sends them again)."""
    prompts, served = serve_check(cfg, engine, rng)
    correct, seen = judge(cfg, engine, prompts, served)
    return correct, seen, (prompts, [toks for toks, _l, _s in served])


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


def s6_counters() -> dict:
    """The Mamba layers' own counters ({} from a program that has none:
    a parent of PR 65)."""
    from paddle_tpu.serving import metrics as sm
    if not hasattr(sm, "S6_TOKENS_SCANNED"):
        return {}
    return {"tokens": sm.S6_TOKENS_SCANNED.labels(model=MODEL).value,
            "rows": sm.S6_CHUNK_ROWS.labels(model=MODEL).value}


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
            c0, s0 = serve.counters(hosted), s6_counters()
            pool = serve.PoolWatch()
            pool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held = pool.close()
            c1, s1 = serve.counters(hosted), s6_counters()
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
        # true prompt tokens through the selective scan, the rows it
        # walked (whole chunks), both summed over the Mamba layers; live
        # slots summed over the window's decode steps
        "s6_tokens": s1["tokens"] - s0["tokens"] if s0 else None,
        "s6_rows": s1["rows"] - s0["rows"] if s0 else None,
        "slot_steps": delta["sched_slot_steps"],
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    return harness.add_device_observations(run, win, obs)
