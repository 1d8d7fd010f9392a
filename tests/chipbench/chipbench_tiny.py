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


class SetupLog(list):
    """A tiny run's engine dispatches up to the window's opening, in the
    scheduler's own order: ``(program key, shapes of its feeds)`` each;
    ``prompts[i]`` is the prompt that entry i prefilled (None for a
    decode step)."""

    prompts: list


def is_admission(entry) -> bool:
    return entry[0][0].startswith("prefill")


def admissions(setup) -> list:
    return [entry for entry in setup if is_admission(entry)]


def logged_run(monkeypatch, config, traffic, seed, seconds=0.4):
    """Run the tiny cell; (run, observations, the ``SetupLog``)."""
    import numpy as np
    from paddle_tpu.serving import engine as eng
    log, prompts, opened = [], [], []
    real_run = eng.GenerativeModel._run
    real_open = harness.Run.open_window

    def spy(self, cb, aot_key, feeds):
        log.append((aot_key, tuple(sorted(
            (k, tuple(np.shape(v))) for k, v in feeds.items()))))
        prompts.append(
            tuple(int(t) for t in np.asarray(feeds["ids"])[
                0, :int(np.asarray(feeds["seq_len"]).reshape(-1)[0]), 0])
            if is_admission(log[-1]) else None)
        return real_run(self, cb, aot_key, feeds)

    def open_window(self):
        opened.append(len(log))
        return real_open(self)

    for cls in (eng.GenerativeModel, eng.SlotGenerativeModel):
        monkeypatch.setattr(cls, "_run", spy)
    monkeypatch.setattr(harness.Run, "open_window", open_window)
    run, obs = run_cell(config, traffic, seed, seconds)
    setup = SetupLog(log[:opened[0]])
    setup.prompts = prompts[:opened[0]]
    return run, obs, setup


def priming(setup: SetupLog, config, traffic, seed, seconds, before: int):
    """A closed loop's set-up split at the scheduler's OWN events, never
    by the clock (under six loaded test workers the runner sees a count
    tens of decode steps late, and a client whose 14-token request has
    ended by then has had its next one admitted): ``head``, the log up
    to the first admission after the ``before`` that warm-up and check
    make — one list of dispatches whatever the load; ``first``, the
    admissions of the clients' first requests, known by their prompts
    (the plan is the seed's); ``later``, admissions of a client's next
    request; ``steps``, the decode dispatches after ``head`` (never
    fewer than the priming asks for, whatever the load).

    How far a priming may run PAST its count is bounded by events too:
    the window opens before ANY client's second further request is
    admitted (at most one ``later`` admission a client — the plan serves
    round 0 once and then repeats its later rounds, so a client's
    further requests are known by their prompts), and within two
    requests' budgets of decode steps after the last of the clients'
    first admissions, the event at which the count can be full. A
    priming that ran on for any number of steps would admit a client's
    third request and fail here; a tight count of steps would fail with
    the host's load (under ten busy loops a client's first request has
    ended before the last client's is admitted, and that one comes a
    step before the window opens)."""
    from chipbench.generators import closed_loop
    plan = closed_loop.make(traffic, config, seed, seconds)["clients"]
    firsts = [tuple(int(t) for t in reqs[0][0]) for reqs in plan]
    nexts = [tuple(int(t) for t in prompt)
             for reqs in plan for prompt, _budget in reqs[1:]]
    budget = max(b for reqs in plan for _prompt, b in reqs)
    cut = [i for i, entry in enumerate(setup) if is_admission(entry)][before]
    tail = range(cut, len(setup))
    first_at = [i for i in tail if setup.prompts[i] in firsts]
    later_at = [i for i in tail
                if is_admission(setup[i]) and i not in first_at]
    # every client's first request exactly once; whatever else was
    # admitted is a request of the plan's later rounds, once a client
    assert sorted(setup.prompts[i] for i in first_at) == sorted(firsts)
    later = [setup.prompts[i] for i in later_at]
    assert all(p in nexts for p in later)
    assert len(set(later)) == len(later) <= len(plan), later
    past = sum(1 for i in range(max(first_at) + 1, len(setup))
               if not is_admission(setup[i]))
    assert past <= traffic["prime_decode_steps"] + 2 * budget, past
    return (setup[:cut], [setup[i] for i in first_at],
            [setup[i] for i in later_at],
            len(tail) - len(first_at) - len(later_at))


def run_cell(config, traffic, seed, seconds=0.5, chips=1):
    cell = {"name": "tiny", "chips": chips, "config": "-", "traffic": "-"}
    run = harness.Run(BENCH, cell, config, traffic, seed, seconds, False,
                      time.time(), allow_cpu=True)
    return run, harness.runner_of(config).run(run)
