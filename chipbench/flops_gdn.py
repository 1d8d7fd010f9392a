"""Operations and bytes of the two mechanisms a Gated DeltaNet (``gdn``)
layer adds to the slot server, from counts the program keeps, beside
``flops.py`` and by its convention (2 FLOPs a multiply-accumulate; bytes
the algorithm NEEDS to move: each operand read once, each result written
once; a causal product counted over its triangle)."""

from __future__ import annotations


def state_bytes(slot_steps: float, n_layers: int, heads: int, key_dim: int,
                value_dim: int) -> float:
    """The decode steps' state updates: the float32 state
    [heads, key_dim, value_dim] of every LIVE slot, read once and written
    once, in every linear layer — whatever tier runs and however the
    device pads the tile. ``slot_steps`` is live slots summed over the
    steps (the scheduler's own count). The conv window, q, k, v, the
    decay and beta are a hundredth of it and are not counted."""
    return 2.0 * slot_steps * n_layers * heads * key_dim * value_dim * 4


def state_flops(slot_steps: float, n_layers: int, heads: int, key_dim: int,
                value_dim: int) -> float:
    """``S^T k``, ``S^T q`` and the decayed rank-one update: three
    multiply-accumulates an element of the state (on the VPU: the
    roofline's other bound, far under the bytes')."""
    return 2.0 * 3.0 * slot_steps * n_layers * heads * key_dim * value_dim


def scan_flops(tokens: float, chunk: int, heads: int, key_dim: int,
               value_dim: int) -> float:
    """The chunk algorithm's products over ``tokens`` TRUE prompt tokens
    (summed over the linear layers: what
    ``paddle_gdn_tokens_scanned_total`` counts), at ``chunk`` rows a
    chunk, a head. A row i of a chunk: ``k_i . k_j`` and ``q_i . k_j``
    over the (chunk + 1) / 2 rows j <= i it may see (key_dim each); its
    row of the triangular system ``(I + A) [W | U] = diag(beta) [K e^gamma
    | V]`` by substitution over the (chunk - 1) / 2 rows before it
    (key_dim + value_dim each); ``tril(Gamma * Q K^T)_ij v'_j`` over the
    rows it sees (value_dim each); ``w_i S``, ``q_i S`` against the state
    the chunk started from and the row's own ``k_i (x) v'_i`` into the
    state it ends with (key_dim * value_dim each). A padded row, a chunk
    computed past the prompt's end and an inverse formed in full count
    nothing: they show as lost share."""
    seen, before = (chunk + 1) / 2.0, (chunk - 1) / 2.0
    inside = seen * (2 * key_dim + value_dim) \
        + before * (key_dim + value_dim)
    across = 3.0 * key_dim * value_dim
    return 2.0 * tokens * heads * (inside + across)


def scan_bytes(tokens: float, heads: int, key_dim: int,
               value_dim: int) -> float:
    """What the scan has to move for ``tokens`` true tokens (summed over
    the linear layers): q, k, v, the decay and beta read once, o written
    once, in the float32 the algorithm is stated in; the state stays on
    the chip."""
    return 4.0 * tokens * heads * (2 * key_dim + 2 * value_dim + 2)
