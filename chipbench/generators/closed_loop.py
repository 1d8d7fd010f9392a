"""Closed loop: ``clients`` callers, each submitting its next request
when the future of its last resolves (copy of the idea of
``tools/serve_bench.py``'s closed arm, with client-side bookkeeping).

Priming is by count: every client submits its first request; when the
program's prefill counter says that all of them are admitted, the window
opens ``prime_decode_steps`` decode steps later — on a step boundary, as
it also closes, so the window holds a whole number of steps.

The first request of client c asks for only a share (c' + 0.5) / clients
of its budget (c' a fixed permutation of the clients): the slots then
finish at evenly staggered times from the window's start on, as they do
in a server that has been running, and not all together after the
shortest budget. Measured without it (PERF.md, PR 23): the first
completions bunched around the 30 s mark, and a window that closed one
decode step earlier or later held 3 or 6 prefills.

A traffic file with ``"sampling": {"temperature": t, "top_k": k}`` has
its requests SAMPLED by the server (on the device, each request keyed by
a seed of its own drawn from ``--seed``); without the key every request
is greedy, as before PR 60.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from chipbench.generators import _multiset as ms


def make(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    n, rounds = traffic["clients"], traffic["rounds"]
    build = config["build"]
    sched = traffic["schedule_seed"]
    lengths = ms.fixed_order(
        ms.quantiles(traffic["prompt_len"], n * rounds), sched, 1)
    budgets = ms.fixed_order(
        ms.quantiles(traffic["max_new"], n * rounds), sched, 2)
    rng = np.random.RandomState(seed % 2 ** 32)
    lengths = ms.reorder_within_buckets(
        lengths, build["prompt_buckets"], rng)
    prompts = ms.prompts_for(lengths, build["vocab"], rng)
    clients = [[(prompts[c * rounds + j], int(budgets[c * rounds + j]))
                for j in range(rounds)] for c in range(n)]
    shares = (ms.fixed_order(np.arange(n), sched, 3) + 0.5) / n
    for reqs, share in zip(clients, shares):
        prompt, budget = reqs[0]
        reqs[0] = (prompt, max(traffic["first_round_min"],
                               int(round(budget * share))))
    plan = {"clients": clients}
    if "sampling" in traffic:
        # drawn after everything else, so that a traffic file that gains
        # the key keeps its prompts and budgets: one seed a request, and
        # a sampled stream is keyed by it and the token's index alone —
        # the same --seed decodes the same tokens whatever the timing
        plan["sampling"] = {
            **traffic["sampling"],
            "seeds": rng.randint(0, 2 ** 31 - 1, (n, rounds)).tolist()}
    return plan


class _Client(threading.Thread):
    def __init__(self, idx, requests, server, model, stop, sampling=None):
        super().__init__(daemon=True, name=f"chipbench-client-{idx}")
        self.idx, self.requests = idx, requests
        self.server, self.model, self.stop = server, model, stop
        self.sampling = sampling   # None: greedy, as the server defaults
        self.log = []          # [submit_t, done_t | None, budget, ok]
        self.current = None    # request id in flight

    def run(self):
        j = 0
        while not self.stop.is_set():
            # round 0 (the staggered one) is served once, never again
            r = j if j < len(self.requests) \
                else 1 + (j - 1) % (len(self.requests) - 1)
            prompt, budget = self.requests[r]
            how = {} if self.sampling is None else {
                "temperature": self.sampling["temperature"],
                "top_k": self.sampling["top_k"],
                "seed": self.sampling["seeds"][self.idx][r]}
            rid = f"c{self.idx}-{j}"
            row = [time.perf_counter(), None, budget, None]
            self.log.append(row)
            self.current = rid
            try:
                out = self.server.submit_generate(
                    self.model, [prompt], max_new=budget,
                    request_id=rid, **how).result(timeout=3600)[0]
                row[3] = bool(len(out) == budget and out.min() >= 0)
            except BaseException:
                row[3] = False
            row[1] = time.perf_counter()
            j += 1


def prime(ctx) -> None:
    ctx.stop = threading.Event()
    admitted = ctx.prefills() + len(ctx.plan["clients"])
    ctx.clients = [_Client(i, reqs, ctx.server, ctx.model, ctx.stop,
                           ctx.plan.get("sampling"))
                   for i, reqs in enumerate(ctx.plan["clients"])]
    for c in ctx.clients:
        c.start()
    _wait_for(ctx.prefills, admitted)
    _wait_for_step(ctx, ctx.decode_steps() + ctx.traffic["prime_decode_steps"])


def _wait_for(counter, count: int, timeout: float = 600.0) -> None:
    t0 = time.perf_counter()
    while counter() < count:
        if time.perf_counter() - t0 > timeout:
            raise TimeoutError(f"{counter.__name__} did not reach {count} "
                               f"in {timeout} s")
        time.sleep(0.0005)


def _wait_for_step(ctx, count: int) -> None:
    _wait_for(ctx.decode_steps, count)


def drive(ctx, seconds: float) -> None:
    """Hold the window open for ``seconds``, then to the next decode
    step's end."""
    time.sleep(seconds)
    _wait_for_step(ctx, ctx.decode_steps() + 1)


def finish(ctx, p0: float, p1: float) -> dict:
    """After the window: stop the clients, cancel what is in flight, and
    count. A request in flight at the window's end is not a failure."""
    ctx.stop.set()
    for c in ctx.clients:
        if c.current is not None:
            ctx.server.cancel(ctx.model, c.current)
    for c in ctx.clients:
        c.join(timeout=30)
    rows = [r for c in ctx.clients for r in c.log if r[0] < p1]
    ended = [r for r in rows if r[1] is not None and r[1] <= p1]
    in_window = [r for r in ended if r[1] >= p0]
    return {"attempted": len([r for r in rows
                              if r[1] is None or r[1] >= p0]),
            "failed": len([r for r in in_window if not r[3]]),
            "completed": len(in_window),
            "tokens_completed_inside": sum(
                r[2] for r in in_window if r[0] >= p0),
            "tokens_overlapping": sum(
                r[2] for r in rows if r[1] is None or r[1] >= p0),
            "threads_left": sum(c.is_alive() for c in ctx.clients)}
