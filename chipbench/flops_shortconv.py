"""Operations of the gated short convolution (LFM2's ``conv`` layers),
from the count the program keeps, beside ``flops.py`` and by its
convention (2 FLOPs a multiply-accumulate, matrix products only)."""

from __future__ import annotations


def mixer_flops(tokens: float, d_model: int) -> float:
    """The two projections of ``tokens`` TRUE tokens, summed over the
    conv layers (what ``paddle_shortconv_tokens_total`` counts): ``W_in``
    [M, 3M] and ``W_out`` [M, M]. The depthwise conv (3 taps a value) and
    the two elementwise gates are not matrix products and count nothing;
    a bucket's padded rows are not work the algorithm needs and count
    nothing either — whatever computes them shows as lost share."""
    return 2.0 * tokens * (d_model * 3 * d_model + d_model * d_model)
