"""Operations and bytes of three mechanisms of the hybrid block's decode
step (Kimi Delta Attention's state update, the held experts' gate and up
projections, the softmax layers' page gather), from shapes alone, beside
``flops.py`` and by its convention (2 FLOPs a multiply-accumulate; bytes
the algorithm NEEDS to move: each operand read once, each result written
once)."""

from __future__ import annotations


def kda_state_bytes_per_step(n_slots: int, n_kda_layers: int, heads: int,
                             head_dim: int, conv_taps: int,
                             conv_itemsize: int) -> float:
    """One decode step of every slot: the float32 recurrent state
    [n_slots, heads, head_dim, head_dim] and the conv window's
    [n_slots, conv_taps - 1, 3 * heads * head_dim] rows, each read once
    and written once, in every KDA layer."""
    state = n_slots * heads * head_dim * head_dim * 4
    conv = n_slots * (conv_taps - 1) * 3 * heads * head_dim * conv_itemsize
    return 2.0 * (state + conv) * n_kda_layers


def kda_state_flops_per_step(n_slots: int, n_kda_layers: int, heads: int,
                             head_dim: int) -> float:
    """k^T S, the rank-one update, S^T q: three multiply-accumulates an
    element of the state (on the VPU: the roofline's other bound, far
    under the bytes')."""
    return 2.0 * 3.0 * n_slots * heads * head_dim * head_dim * n_kda_layers


def expert_up_bytes_per_step(n_tokens: int, n_layers: int, n_held: int,
                             d_model: int, d_expert: int,
                             itemsize: int) -> float:
    """The held experts' gate and up projections with their activation,
    computed for every token of a decode step (the dense way of
    ``ops/expert_ffn.py``), summed over the expert layers: the two
    [n_held, d_model, d_expert] matrices read once (all of them: every
    held expert multiplies every token) and the tokens read twice. The
    products themselves ([n_tokens, n_held, d_expert]: 26 MB in float32
    at the cell's sizes) need not touch HBM — the v5e keeps them in its
    128 MiB of VMEM between the two ops and on to the down projection —
    so they are not bytes the algorithm needs to move."""
    weights = 2.0 * n_held * d_model * d_expert * itemsize
    tokens = 2.0 * n_tokens * d_model * itemsize
    return (weights + tokens) * n_layers


def expert_up_flops_per_step(n_tokens: int, n_layers: int, n_held: int,
                             d_model: int, d_expert: int) -> float:
    return 2.0 * 2.0 * n_tokens * n_held * d_model * d_expert * n_layers


def page_gather_bytes_per_step(n_slots: int, cache_len: int,
                               n_gqa_layers: int, row_width: int,
                               itemsize: int) -> float:
    """The softmax layers' page gather of one decode step: every cache
    row ``[n_kv_head * head_dim]`` of every slot, K and V, read from the
    pool once and written to the gathered copy once (what the kernel
    does today: it stops at no slot's length)."""
    return 2.0 * 2.0 * n_slots * cache_len * row_width * itemsize \
        * n_gqa_layers
