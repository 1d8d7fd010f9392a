"""Runner of serving cells: ``decoder_lm`` behind ``ModelServer`` on the
slot engine, as ``chip_smoke.py`` brings it up, driven by the traffic
file's generator through ``submit_generate``.

Set-up is the same list of work for every seed: build the program
family, make the engine (its start-up runs once, with a fixed seed),
draw the weights of ``--seed``, ``add_model`` (the program warms every
bucket and the decode step), compare ``check.requests`` served requests
with the plain reference, prime by count, open the window.
"""

from __future__ import annotations

import importlib
import threading

import numpy as np

from chipbench import harness, weights

MODEL = "lm"
POOL_SAMPLE_S = 0.1


class Ctx:
    """What a generator's prime / drive / finish see."""

    def __init__(self, run, server, plan):
        from paddle_tpu.serving import metrics as sm
        self.run, self.server, self.plan = run, server, plan
        self.traffic, self.model = run.traffic, MODEL
        self._steps = sm.DECODE_STEPS.labels(model=MODEL)
        self._prefills = sm.PREFILLS.labels(model=MODEL)

    def decode_steps(self) -> int:
        return int(self._steps.value)

    def prefills(self) -> int:
        return int(self._prefills.value)


class PoolWatch(threading.Thread):
    """Reads the page pool's own gauges (pages total, pages free) every
    ``POOL_SAMPLE_S`` seconds while the window is open: the mean share
    of the pool's pages not free (leased to a request, or kept as
    evictable prefix cache after one). One light thread; None for
    a layout with no page pool."""

    def __init__(self):
        super().__init__(daemon=True, name="chipbench-poolwatch")
        from paddle_tpu.serving import metrics as sm
        self._total = sm.KV_PAGES_TOTAL.labels(model=MODEL)
        self._free = sm.KV_PAGES_FREE.labels(model=MODEL)
        self._done = threading.Event()
        self.shares = []

    def run(self):
        while not self._done.is_set():
            total = self._total.value
            if total > 0:
                self.shares.append(1.0 - self._free.value / total)
            self._done.wait(POOL_SAMPLE_S)

    def close(self):
        self._done.set()
        self.join(timeout=5)
        return float(np.mean(self.shares)) if self.shares else None


def counters(hosted) -> dict:
    """The program's counters the window's deltas are taken from."""
    from paddle_tpu.serving import metrics as sm
    out = {"tokens": sm.TOKENS_GENERATED.labels(model=MODEL).value,
           "decode_steps": sm.DECODE_STEPS.labels(model=MODEL).value,
           "prefills": sm.PREFILLS.labels(model=MODEL).value,
           "serving_compiles": sum(
               c.value for c in sm.COMPILATIONS.children().values()),
           "aot_fallbacks": sum(
               c.value for c in sm.AOT_FALLBACK.children().values()),
           "sched_steps": hosted.sched_steps,
           "sched_slot_steps": hosted.sched_slot_steps}
    for key, family in (("queue_wait", sm.QUEUE_WAIT),
                        ("inter_token", sm.INTER_TOKEN)):
        _buckets, total, count = family.labels(model=MODEL).snapshot()
        out[key + "_sum"], out[key + "_count"] = total, count
    return out


def compare_with_reference(run, server, engine, rng) -> tuple:
    """Greedy requests through the server (prefill, then decode through
    the paged cache) against the reference's full forward on the same
    weights: every served token must lie within the configuration's
    tolerance of the reference's best logit at its position."""
    cfg = run.config
    chk = cfg["check"]
    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    prompts = [rng.randint(1, cfg["build"]["vocab"], n).astype(np.int64)
               for n in chk["prompt_lens"]]
    outs = [server.generate(MODEL, [p], max_new=chk["max_new"],
                            timeout=600)[0] for p in prompts]
    params = {n: engine.scope.find_var(n)
              for n in ref.param_names(cfg["build"], MODEL)}
    margin = ref.worst_margin(params, prompts, outs, cfg["build"], MODEL)
    sized = all(len(o) == chk["max_new"] for o in outs)
    return bool(sized and margin <= chk["logit_margin_tol"]), {
        "worst_margin_in_logit_std": margin,
        "tolerance": chk["logit_margin_tol"],
        "tokens_compared": chk["max_new"] * len(prompts)}


def bring_up(run: harness.Run):
    """Build, start-up, weights, ``add_model`` and the comparison with
    the reference: (server, engine, hosted model, correct, what was
    seen). The caller stops the server."""
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    cfg = run.config
    build = cfg["build"]
    with run.phase("build"):
        programs = T.build_decoder_lm_programs(
            name=MODEL, modes=T.slot_modes(cfg["kv_layout"]),
            kv_codec=cfg["kv_codec"],
            **{**build, "prompt_buckets": tuple(build["prompt_buckets"])})
    with run.phase("startup"):
        engine = serving.make_slot_model(MODEL, programs)
        dec_main = programs[engine.DECODE][0]
        weights.reseed(engine.scope, weights.matrix_spec(
            {p.name: p.shape
             for p in dec_main.global_block().all_parameters()},
            build["d_model"]), run.seed, run.devices[0])
    server = serving.ModelServer()
    try:
        with run.phase("warm"):
            hosted = server.add_model(engine)
        with run.phase("check"):
            correct, seen = compare_with_reference(
                run, server, engine,
                np.random.RandomState((run.seed + 1) % 2 ** 32))
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
        ctx = Ctx(run, server, plan)
        with run.phase("prime"):
            gen.prime(ctx)
        run.open_window()
        with run.traced() as win:
            c0 = counters(hosted)
            pool = PoolWatch()
            pool.start()
            try:
                gen.drive(ctx, limit)
            finally:
                held = pool.close()
            c1 = counters(hosted)
        res = gen.finish(ctx, win.p0, win.p1)
    finally:
        server.stop()

    delta = {k: c1[k] - c0[k] for k in c0}
    e2e = {"serve_tokens_per_s": delta["tokens"] / win.seconds}
    if "ttft_s" in res and len(res["ttft_s"]):
        e2e["ttft_p50_ms"] = float(np.percentile(res["ttft_s"], 50)) * 1e3
        e2e["ttft_p95_ms"] = float(np.percentile(res["ttft_s"], 95)) * 1e3
    # the program's token counter against what the clients were promised
    counted_ok = res.get("tokens_completed_inside", 0) <= delta["tokens"] \
        <= res.get("tokens_overlapping", delta["tokens"])
    clean = (delta["serving_compiles"] == 0 and delta["aot_fallbacks"] == 0
             and res["threads_left"] == 0)
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
        "lateness_s": res.get("lateness_s"), "ttft_s": res.get("ttft_s"),
        "chips": 1, "config": cfg, "traffic": tr,
        "notes": {"reference": seen, "window_s": win.seconds,
                  "counters": delta, "phases": dict(run.phase_s),
                  "completed": res["completed"],
                  "backlog_at_last_due": res.get("backlog_at_last_due"),
                  "completions_per_s": res.get("completions_per_s"),
                  "kv_pages_held_share": held,
                  "counted_ok": counted_ok, "clean": clean},
    }
    return harness.add_device_observations(run, win, obs)
