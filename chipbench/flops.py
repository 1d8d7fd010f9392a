"""Operations and bytes the algorithm needs, from shapes alone (the
yardstick's copy of the convention of ``paddle_tpu/utils/flops.py``: 2
FLOPs per multiply-accumulate, matrix products only, causal attention
counted at half the square, backward = 2 x forward, recomputation not
counted)."""

from __future__ import annotations


def attention_fwd_flops(tokens_q: int, tokens_kv: int, len_kv: int,
                        d_model: int, causal: bool) -> float:
    """One multi-head attention block over ``tokens_q`` query positions
    attending to sequences of ``len_kv`` keys (``tokens_kv`` key/value
    positions are projected): q, k, v and output projections plus the
    QK^T and PV products, which sum over heads to d_model per pair."""
    proj = 2.0 * d_model * d_model * (2 * tokens_q + 2 * tokens_kv)
    dots = 2.0 * 2.0 * tokens_q * len_kv * d_model
    return proj + (dots * 0.5 if causal else dots)


def ffn_fwd_flops(tokens: int, d_model: int, d_inner: int) -> float:
    return 2.0 * 2.0 * tokens * d_model * d_inner


def encdec_train_flops_per_step(batch: int, src_len: int, tgt_len: int,
                                d_model: int, d_inner: int, n_layer: int,
                                tgt_vocab: int) -> float:
    """Forward + backward of the encoder-decoder Transformer of
    arXiv:1706.03762 on ``batch`` sentence pairs (embedding lookups and
    the optimizer are not matrix products and count nothing)."""
    src, tgt = batch * src_len, batch * tgt_len
    enc = attention_fwd_flops(src, src, src_len, d_model, False) \
        + ffn_fwd_flops(src, d_model, d_inner)
    dec = attention_fwd_flops(tgt, tgt, tgt_len, d_model, True) \
        + attention_fwd_flops(tgt, src, src_len, d_model, False) \
        + ffn_fwd_flops(tgt, d_model, d_inner)
    head = 2.0 * tgt * d_model * tgt_vocab
    return 3.0 * (n_layer * (enc + dec) + head)


def kv_gather_bytes_per_step(rows: int, d_model: int, itemsize: int,
                             n_layer: int) -> float:
    """Bytes the paged decode's row gather must move in one step: each
    of ``rows`` cache rows (slots x cache length) of ``d_model`` values
    read once and written once, for K and for V, in every layer."""
    return 2.0 * 2.0 * rows * d_model * itemsize * n_layer


def roofline_pct(flops: float, bytes_: float, seconds: float,
                 peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) as a share of the time
    taken, in percent."""
    least = max(flops / peaks["bf16_flops"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
