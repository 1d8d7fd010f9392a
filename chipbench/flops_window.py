"""Operations and bytes of a sliding-window attention layer's decode
step, from a count of rows alone, beside ``flops.py`` and by its
convention (2 FLOPs a multiply-accumulate; bytes the algorithm NEEDS to
move: each operand read once).

Both take ROWS, summed over slots and window layers, as the program
counts them (``paddle_kv_window_rows_attended_total``: per step, slot
and window layer ``min(the slot's positions, the window)``): a step's
work follows the slots' lengths and the window, never the context."""

from __future__ import annotations


def window_bytes(rows_attended: float, n_kv_head: int, head_dim: int,
                 itemsize: int) -> float:
    """Each attended row's key and value read once: two rows of
    ``n_kv_head * head_dim`` values. (The query, the scores and the
    context are a few thousand values a slot; a page's rows outside the
    window are storage, not something the algorithm needs.)"""
    return rows_attended * 2 * n_kv_head * head_dim * itemsize


def window_flops(rows_attended: float, n_head: int, head_dim: int) -> float:
    """Every query head's score over a row's key and its share of the
    row's value: 2 * head_dim multiply-accumulates a head and row."""
    return 2.0 * rows_attended * n_head * 2 * head_dim
