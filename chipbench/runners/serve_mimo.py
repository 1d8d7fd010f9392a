"""Runner of serving cells of a model whose grouped attention has a
geometry of its own per layer kind (MiMo-V2-Flash: full layers of 64
query heads over 4 KV heads beside window layers over 8, keys of 192
beside values of 128, a rotation of a head's first 64 values with a base
per kind, a window of 128 behind a learned sink): ``decoder_lm`` behind
``ModelServer`` on the slot engine, the normal path, through
``runners/serve_trinity.py``'s bring-up, window loop and comparison —
four greedy requests stepped together, float32 LOGITS of every position
and WHAT EACH WINDOW LAYER ATTENDED against the plain reference. What
differs:

- the window layers' sink logits are rank 1, which ``weights_chunked``
  leaves as start-up drew them: they are drawn Normal(0, 1) from
  ``--seed`` here, after the matrices;
- the full layers' decode is counted beside the window layers':
  ``paddle_kv_full_rows_attended_total`` (the LIVE rows a step's full
  layers attended) and ``paddle_kv_full_rows_gathered_total`` (the rows
  their gathers copied), read at the window's edges (``obs["full_rows"]``,
  ``obs["full_rows_gathered"]``), and the bytes a position costs in each
  page group (``paddle_kv_row_bytes{group}``, in the result's notes): what
  ``layer_metrics/grouped_kv_attn.py`` reckons the two contractions'
  roofline shares from.
"""

from __future__ import annotations

import numpy as np

from chipbench import harness, weights_chunked
from chipbench.runners import serve, serve_hybrid, serve_trinity

MODEL = serve.MODEL
SINK_SALT = 0x9E3779B9      # the sinks' stream beside the matrices'


def draw_sinks(engine, seed: int, device) -> None:
    """The window layers' sinks ([n_head] float32 each) drawn Normal(0,
    1) from ``seed``, in a stream of their own."""
    sinks = tuple(
        (n, tuple(np.shape(engine.scope.find_var(n))), "float32", 1.0)
        for n in sorted(engine._cb_decode.sig.const_names)
        if n.endswith(".sink"))
    weights_chunked.reseed(engine.scope, sinks, seed + SINK_SALT, device)


def build_engine(cfg: dict, seed: int, device):
    """``serve_hybrid.build_engine``, then the sinks from ``seed`` too."""
    engine = serve_hybrid.build_engine(cfg, seed, device)
    draw_sinks(engine, seed, device)
    return engine


def row_counters() -> dict:
    from paddle_tpu.serving import metrics as sm
    return {**serve_trinity.window_counters(),
            "full_rows": sm.KV_FULL_ROWS_ATTENDED.labels(model=MODEL).value,
            "full_rows_gathered": sm.KV_FULL_ROWS_GATHERED.labels(
                model=MODEL).value}


def bring_up(run: harness.Run):
    """``serve_trinity.bring_up`` over this module's engine."""
    from paddle_tpu import serving
    with run.phase("build"):
        engine = build_engine(run.config, run.seed, run.devices[0])
    with run.phase("warm"):
        engine.warmup()
    with run.phase("check"):
        correct, seen, (prompts, tokens) = \
            serve_trinity.compare_with_reference(
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
            c0, w0 = serve.counters(hosted), row_counters()
            pool, wpool = serve.PoolWatch(), serve_trinity.WindowPoolWatch()
            pool.start()
            wpool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held, wheld = pool.close(), wpool.close()
            c1, w1 = serve.counters(hosted), row_counters()
            m1 = engine.expert_token_counts()
        res = gen.finish(ctx, win.p0, win.p1)
    finally:
        server.stop()

    delta = {k: c1[k] - c0[k] for k in c0}
    rows = {k: w1[k] - w0[k] for k in w0}
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
        "window_pages_released": rows["released"],
        # cache rows the layers of each kind attended over the window's
        # decode steps (all layers of the kind), and what the full
        # layers' gathers copied
        "window_rows": rows["rows"],
        "full_rows": rows["full_rows"],
        "full_rows_gathered": rows["full_rows_gathered"],
        "moe_counts": m1["counts"] - m0["counts"],
        "moe_steps": m1["steps"] - m0["steps"],
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "kv_pages_held_share": held,
                  "kv_window_pages_held_share": wheld,
                  "kv_row_bytes": dict(engine.row_bytes),
                  "rows": rows,
                  "counted_ok": counted_ok, "clean": clean,
                  "requests_shed": shed},
    }
    return harness.add_device_observations(run, win, obs)
