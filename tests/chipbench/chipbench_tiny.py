"""Tiny presets of the benchmark's configurations and traffic for the
CPU unit tests: the files as committed, with every size cut down.
Nothing measured with them is a device number."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

BENCH = {"workloads": [], "configs": [], "end_to_end": [], "per_layer": []}


def _load(kind, name):
    with open(os.path.join(ROOT, "chipbench", kind, name + ".json")) as f:
        return json.load(f)


def train_config():
    cfg = _load("configs", "transformer_big_wmt")
    cfg["amp"] = False          # XLA:CPU has no bf16 x bf16 -> f32 dot
    cfg["build"].update(n_layer=1, d_model=32, d_inner=64, n_head=2,
                        src_vocab=128, tgt_vocab=96, max_len=16)
    cfg["check"]["grad_params"] = [3, 32, 34]
    return cfg


def train_traffic(name="resident_feed"):
    tr = _load("traffic", name)
    tr.update(batch_per_chip=2, steps_per_dispatch=2)
    return tr


def serve_config():
    cfg = _load("configs", "gpt2_medium_d12")
    cfg["build"].update(n_layer=2, d_model=32, d_inner=64, n_head=2,
                        vocab=64, prompt_len=16, max_new=16,
                        prompt_buckets=[4, 8, 16], n_slots=4, page_size=4)
    cfg["check"].update(prompt_lens=[3, 12], max_new=6)
    return cfg


def serve_traffic(name):
    tr = _load("traffic", name)
    small = {"dist": "log_uniform", "lo": 2, "hi": 16}
    if tr["generator"] == "closed_loop":
        tr.update(clients=4, rounds=8, prompt_len=small, first_round_min=14,
                  prime_decode_steps=2,
                  max_new={"dist": "uniform", "lo": 14, "hi": 16})
    else:
        tr.update(rate_per_s=40.0, prompt_len=small)
    return tr


def run_cell(config, traffic, seed, seconds=0.5, chips=1):
    cell = {"name": "tiny", "chips": chips, "config": "-", "traffic": "-"}
    run = harness.Run(BENCH, cell, config, traffic, seed, seconds, False,
                      time.time(), allow_cpu=True)
    return run, harness.runner_of(config).run(run)
