"""Open loop: requests sent on a schedule whatever the system does
(copy of the idea of ``tools/serve_bench.py``'s open arm). A request is
timed from the instant it was DUE, so a stall shows in every request it
delays, and how late the generator itself ran is reported.

The schedule is the n-point multiset of exponential gaps at the file's
``rate_per_s`` in the order its ``schedule_seed`` fixes (n = rate x
seconds): Poisson arrivals, the same ones in every run. Measured on the
queue model in PERF.md, a schedule reshuffled by ``--seed`` moves the
95th percentile by 20-35 % between runs of unchanged code — more than
any bound could admit; the seed changes the prompts, not the arrivals.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from chipbench.generators import _multiset as ms


def make(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    build = config["build"]
    sched = traffic["schedule_seed"]
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = ms.fixed_order(
        ms.exponential_gaps(n, traffic["rate_per_s"]), sched, 0)
    lengths = ms.fixed_order(ms.quantiles(traffic["prompt_len"], n),
                             sched, 1)
    budgets = ms.fixed_order(ms.quantiles(traffic["max_new"], n), sched, 2)
    rng = np.random.RandomState(seed % 2 ** 32)
    lengths = ms.reorder_within_buckets(
        lengths, build["prompt_buckets"], rng)
    prompts = ms.prompts_for(lengths, build["vocab"], rng)
    k = traffic["prime_requests"]
    prime_lengths = ms.quantiles(traffic["prompt_len"], k)
    return {"due": np.cumsum(gaps) - gaps[0],
            "requests": list(zip(prompts, (int(b) for b in budgets))),
            "prime": list(zip(ms.prompts_for(prime_lengths, build["vocab"],
                                             rng),
                              (int(b) for b in ms.quantiles(
                                  traffic["max_new"], k))))}


def _send(ctx, due_abs, requests, tag: str):
    """Submit each request at its due time from this thread; one waiter
    thread per request stamps the resolution of its future."""
    rows, waiters = [], []

    def wait(fut, row, budget):
        try:
            out = fut.result(timeout=ctx.traffic["request_timeout_s"])[0]
            row[3] = bool(len(out) == budget and out.min() >= 0)
        except BaseException:
            row[3] = False
        row[2] = time.perf_counter()

    for i, (due, (prompt, budget)) in enumerate(zip(due_abs, requests)):
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        row = [due, time.perf_counter(), None, None]   # due, sent, done, ok
        rows.append(row)
        try:
            with ctx.run.span("chipbench.submit"):
                # no request id of ours: the server answers a repeated
                # id from its idempotency cache without running anything
                fut = ctx.server.submit_generate(
                    ctx.model, [prompt], max_new=budget)
        except Exception:       # shed at the queue's bound: a failure
            row[2], row[3] = time.perf_counter(), False
            continue
        w = threading.Thread(target=wait, args=(fut, row, budget),
                             daemon=True, name=f"chipbench-wait-{tag}{i}")
        w.start()
        waiters.append(w)
    return rows, waiters


def prime(ctx) -> None:
    """A fixed number of paced requests, all awaited."""
    reqs = ctx.plan["prime"]
    gap = 1.0 / ctx.traffic["rate_per_s"]
    t0 = time.perf_counter() + 0.01
    _rows, waiters = _send(ctx, [t0 + i * gap for i in range(len(reqs))],
                           reqs, "p")
    for w in waiters:
        w.join(timeout=ctx.traffic["request_timeout_s"])


def drive(ctx, seconds: float) -> None:
    """Send every request whose due time lies inside ``seconds``, then
    wait for all of them: the tail is the tail of all requests."""
    p0 = time.perf_counter()
    keep = [i for i, d in enumerate(ctx.plan["due"]) if d < seconds]
    ctx.rows, ctx.waiters = _send(
        ctx, [p0 + ctx.plan["due"][i] for i in keep],
        [ctx.plan["requests"][i] for i in keep], "r")
    ctx.last_due = p0 + (ctx.plan["due"][keep[-1]] if keep else 0.0)
    with ctx.run.span("chipbench.drain"):
        for w in ctx.waiters:
            w.join(timeout=ctx.traffic["request_timeout_s"])


def finish(ctx, p0: float, p1: float) -> dict:
    rows = ctx.rows
    done = [r for r in rows if r[3]]
    ttft = np.asarray([r[2] - r[0] for r in done])
    late = np.asarray([r[1] - r[0] for r in rows])
    # backlog when the last request was due: sent and not yet answered
    backlog = sum(1 for r in rows if r[2] is None or r[2] > ctx.last_due)
    return {"attempted": len(rows), "failed": len(rows) - len(done),
            "completed": len(done), "ttft_s": ttft, "lateness_s": late,
            "backlog_at_last_due": backlog,
            "completions_per_s": len(done) / (p1 - p0),
            "threads_left": sum(w.is_alive() for w in ctx.waiters)}
