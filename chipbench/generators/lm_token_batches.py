"""Language-model training batches: ``feed_sets`` sets of integer feeds,
each stacked over the steps of one dispatch. A sequence is ``seq_len +
2`` tokens drawn uniformly over the configuration's vocabulary slice:
``ids`` its first ``seq_len``, ``lbl_ids`` the next token of each
position and ``lbl2_ids`` the one after (the multi-token-prediction
module's target). The seed changes the token values; every shape is
fixed by the file."""

from __future__ import annotations

import numpy as np


def feeds_of(seq) -> dict:
    """{feed name: [..., T, 1] int64} of sequences [..., T + 2]."""
    t = seq.shape[-1] - 2
    return {name: seq[..., k:k + t, None].astype(np.int64)
            for k, name in enumerate(("ids", "lbl_ids", "lbl2_ids"))}


def make(traffic: dict, config: dict, seed: int, n_chips: int) -> dict:
    vocab = config["build"]["vocab"]
    steps, t = traffic["steps_per_dispatch"], traffic["seq_len"]
    batch = traffic["sequences_per_step"] * n_chips
    rng = np.random.RandomState(seed % (2 ** 32))
    sets = [feeds_of(rng.randint(0, vocab, size=(steps, batch, t + 2)))
            for _ in range(traffic["feed_sets"])]
    chk = traffic["check"]
    check = feeds_of(rng.randint(
        0, vocab, size=(chk["sequences"], chk["seq_len"] + 2)))
    return {"feed_sets": sets, "check": check, "batch": batch,
            "tokens_per_step": batch * t}
