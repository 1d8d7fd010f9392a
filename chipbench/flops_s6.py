"""Operations and bytes of the two mechanisms a Mamba-1 selective-scan
(``s6``) layer adds to the slot server, from counts the program keeps,
beside ``flops.py`` and by its convention (2 FLOPs a multiply-accumulate;
bytes the algorithm NEEDS to move: each operand read once, each result
written once). The recurrence is elementwise — it runs on the vector
units, whose peak ``peaks.json`` does not hold — so against the chip's
two peaks both mechanisms are bound by their BYTES; the operations are
counted for the record and for a later peak."""

from __future__ import annotations


def state_bytes(slot_steps: float, n_layers: int, d_inner: int,
                d_state: int) -> float:
    """The decode steps' state updates: the float32 state
    [d_state, d_inner] of every LIVE slot, read once and written once, in
    every Mamba layer — whatever runs the update. ``slot_steps`` is live
    slots summed over the steps (the scheduler's own count). The conv
    window, x, dt, B and C are a hundredth of it and are not counted."""
    return 2.0 * slot_steps * n_layers * d_inner * d_state * 4


def state_flops(slot_steps: float, n_layers: int, d_inner: int,
                d_state: int) -> float:
    """``dt A`` under the exponential, ``exp(dt A) h + dt x B`` and ``h
    C``: three multiply-accumulates an element of the state (the
    exponential itself is no FLOP by the convention)."""
    return 2.0 * 3.0 * slot_steps * n_layers * d_inner * d_state


def scan_flops(tokens: float, d_inner: int, d_state: int) -> float:
    """The recurrence over ``tokens`` TRUE prompt tokens (summed over the
    Mamba layers: what ``paddle_s6_tokens_scanned_total`` counts): the
    same three multiply-accumulates an element of the state a token. A
    padded row and a chunk walked past the prompt's end count nothing:
    they show as lost share."""
    return 2.0 * 3.0 * tokens * d_inner * d_state


def scan_bytes(tokens: float, d_inner: int, d_state: int) -> float:
    """What the scope ``s6_prefill/scan`` has to move for ``tokens`` true
    tokens (summed over the Mamba layers), in the float32 the recurrence
    is stated in: x and dt read once ([d_inner] each), B and C read once
    ([d_state] each), y written once ([d_inner]); the state stays on the
    chip and A is read once a prompt (not counted)."""
    return 4.0 * tokens * (3 * d_inner + 2 * d_state)
