"""Model FLOP/s utilization: the operations the model's forward and
backward need for the window's steps (``chipbench/flops.py``; nothing
recomputed counts) over the window, the chips and the chip's peak."""


def read(obs):
    return 100.0 * obs["model_flops"] / obs["window_s"] \
        / (obs["chips"] * obs["peaks"]["bf16_flops"])
