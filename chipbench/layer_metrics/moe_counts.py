"""The expert layers' load over the window's decode steps, from the
program's device-side counters (``obs["moe_counts"]``: per expert layer,
the tokens each held expert was given and the steps in which it was
given any, over ``obs["moe_steps"]`` decode steps; read at the
window's edges, off the step's path).

- ``hit_pct``: of the (step, layer, held expert) triples, the share in
  which the expert was given a token — the share of the held experts'
  weights a step has to read.
- ``max_over_mean``: the busiest held expert's tokens over the mean's,
  averaged over the layers — 1.0 is a perfectly even load.

None where the program has no such counters (a model with no expert
layer, or a parent without them)."""

import numpy as np


def read(obs, what):
    counts, steps = obs.get("moe_counts"), obs.get("moe_steps")
    if counts is None or not steps:
        return None
    tokens, hit = counts[:, 0].astype(float), counts[:, 1].astype(float)
    if what == "hit_pct":
        return 100.0 * hit.sum() / (hit.size * steps)
    if not tokens.sum():
        return None
    return float(np.mean(tokens.max(axis=1) / tokens.mean(axis=1)))
