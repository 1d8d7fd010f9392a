"""Training batches: ``feed_sets`` sets of integer feeds, each stacked
over the steps of one dispatch, values uniform over the vocabulary.
The seed changes the token values; every shape is fixed by the file."""

from __future__ import annotations

import numpy as np


def make(traffic: dict, config: dict, seed: int, n_chips: int) -> dict:
    build = config["build"]
    steps = traffic["steps_per_dispatch"]
    batch = traffic["batch_per_chip"] * n_chips
    rng = np.random.RandomState(seed % (2 ** 32))
    vocab = {"src_ids": build["src_vocab"], "tgt_ids": build["tgt_vocab"],
             "lbl_ids": build["tgt_vocab"]}
    shape = (steps, batch, build["max_len"], 1)
    sets = [{name: rng.randint(1, v, size=shape).astype(np.int64)
             for name, v in sorted(vocab.items())}
            for _ in range(traffic["feed_sets"])]
    cb = traffic["check"]["batch"]
    check = {name: rng.randint(1, v, size=(cb, build["max_len"], 1))
             .astype(np.int64) for name, v in sorted(vocab.items())}
    return {"feed_sets": sets, "check": check, "batch": batch,
            "tokens_per_step": batch * build["max_len"]}
