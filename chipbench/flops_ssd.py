"""Operations and bytes of the two mechanisms a state-space (Mamba-2,
SSD) layer adds to the slot server, from counts the program keeps,
beside ``flops.py`` and by its convention (2 FLOPs a multiply-accumulate;
bytes the algorithm NEEDS to move: each operand read once, each result
written once; a causal product counted over its triangle)."""

from __future__ import annotations


def state_bytes(slot_steps: float, n_layers: int, heads: int, head_dim: int,
                d_state: int) -> float:
    """The decode steps' state updates: the float32 state
    [heads, head_dim, d_state] of every LIVE slot, read once and written
    once, in every SSD layer. ``slot_steps`` is live slots summed over
    the steps (the scheduler's own count). The conv window, B, C, dt and
    x are a hundredth of it and are not counted."""
    return 2.0 * slot_steps * n_layers * heads * head_dim * d_state * 4


def state_flops(slot_steps: float, n_layers: int, heads: int, head_dim: int,
                d_state: int) -> float:
    """The decay, the rank-one update and ``S C``: three
    multiply-accumulates an element of the state (on the VPU: the
    roofline's other bound, far under the bytes')."""
    return 2.0 * 3.0 * slot_steps * n_layers * heads * head_dim * d_state


def scan_flops(chunk_rows: float, chunk: int, heads: int, head_dim: int,
               d_state: int, groups: int) -> float:
    """The chunked scan's matrix products over ``chunk_rows`` rows (whole
    chunks, summed over the SSD layers: what
    ``paddle_ssd_chunk_rows_total`` counts), at ``chunk`` rows a chunk.
    A row i of a chunk: ``C_i . B_j`` over the (chunk + 1) / 2 rows j <=
    i it may see, a group (d_state each); ``(L o C B^T)_ij (dt x)_j``
    over the same rows, a head (head_dim each); ``C_i . S`` against the
    state the chunk started from and the row's own ``dt x (x) B`` into
    the state it ends with, a head (head_dim * d_state each)."""
    seen = (chunk + 1) / 2.0
    inside = seen * (groups * d_state + heads * head_dim)
    across = 2.0 * heads * head_dim * d_state
    return 2.0 * chunk_rows * (inside + across)
