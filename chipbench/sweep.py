"""Find the knee of an open-loop cell: one process, one set-up, then
the cell's traffic at each of a few rates in turn.

    python3 -m chipbench.sweep --workload serve_prefill_open \\
        --rates 4,6,8,10,12 --seconds 20 --seed 1

Prints one JSON line per rate: arrivals and completions per second,
the backlog when the last request was due (requests sent and not yet
answered), and the tails. The knee is the highest rate at which
completions keep pace with arrivals and that backlog stays under one
second of arrivals; the cell's ``rate_per_s`` is written into its
traffic file as 0.8 x the knee (README.md). A tool for the builder:
the driver never runs it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import harness
from chipbench.runners import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic = harness.load_cell(bench, args.workload)
    run = harness.Run(bench, cell, config, traffic, args.seed, args.seconds,
                      False, harness.process_start_unix())
    gen = harness.generator_of(traffic)
    server, _engine, _hosted, correct, seen = serve.bring_up(run)
    print("[sweep] " + json.dumps({"correct": correct, "reference": seen,
                                   "phases": run.phase_s}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            run.traffic = {**traffic, "rate_per_s": rate}
            ctx = serve.Ctx(run, server, gen.make(
                run.traffic, config, args.seed, args.seconds))
            gen.prime(ctx)
            p0 = time.perf_counter()
            gen.drive(ctx, args.seconds)
            p1 = time.perf_counter()
            res = gen.finish(ctx, p0, p1)
            ttft = res["ttft_s"]
            print("[sweep] " + json.dumps({
                "rate_per_s": rate, "sent": res["attempted"],
                "failed": res["failed"],
                "arrivals_per_s": res["attempted"] / args.seconds,
                "completions_per_s_until_drained":
                    res["completions_per_s"],
                "drain_s_after_last_due": p1 - ctx.last_due,
                "backlog_at_last_due": res["backlog_at_last_due"],
                "backlog_in_seconds_of_arrivals":
                    res["backlog_at_last_due"] / rate,
                "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
                "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
                "lateness_p95_ms":
                    float(np.percentile(res["lateness_s"], 95)) * 1e3}),
                flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
