"""Operations and bytes of a grouped-KV attention layer's decode step
whose key and value heads differ in size and whose KV head count is the
layer kind's own, from a count of rows alone, beside ``flops.py`` and by
its convention (2 FLOPs a multiply-accumulate; bytes the algorithm NEEDS
to move: each operand read once).

Both take ROWS, summed over slots and the layers of ONE kind, as the
program counts them (``paddle_kv_full_rows_attended_total``: per step,
slot and full layer the slot's live rows;
``paddle_kv_window_rows_attended_total``: ``min(the slot's positions,
the window)``) — never the rows a gather copied. (``flops_window.py``
reckons ``2 * n_kv * head_dim`` values a row: a fifth too many where a
value head is 128 beside a key head of 192.)"""

from __future__ import annotations


def kv_bytes(rows: float, n_kv: int, dk: int, dv: int,
             itemsize: int) -> float:
    """Each attended row's key (``n_kv * dk`` values) and value (``n_kv
    * dv``) read once. (The query, the scores, the sink and the context
    are a few thousand values a slot; a gathered row that no query
    attends is storage, not something the algorithm needs.)"""
    return rows * n_kv * (dk + dv) * itemsize


def attn_flops(rows: float, n_head: int, dk: int, dv: int) -> float:
    """Every query head's score over a row's key (``dk``
    multiply-accumulates) and its share of the row's value (``dv``)."""
    return 2.0 * rows * n_head * (dk + dv)
