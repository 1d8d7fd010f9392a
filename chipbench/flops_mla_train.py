"""Operations a training step of a latent-attention (MLA) expert model
needs, from the configuration's sizes alone, beside ``flops.py`` and by
its convention: 2 FLOPs a multiply-accumulate, matrix products only,
causal attention counted at half the square, backward = 2 x forward,
nothing recomputed counted. Nothing here reads the implementation: a
later kernel is judged by the same work."""

from __future__ import annotations


def mla_attention_fwd_flops(seq_len: int, sequences: int, n_head: int,
                            d_qk: int, d_v: int) -> float:
    """One layer's causal attention, expanded: every query head's score
    over a key head of ``d_qk`` (nope + rotated) and its share of a
    value head of ``d_v``, over half the square of the sequence."""
    return 2.0 * sequences * 0.5 * seq_len * seq_len * n_head * (d_qk + d_v)


def mla_attention_train_flops(build: dict, seq_len: int,
                              sequences: int) -> float:
    """Forward and backward of the attention of every layer of a step:
    the main layers and the multi-token-prediction module's."""
    layers = build["n_layer"] + build.get("mtp_layers", 0)
    return 3.0 * layers * mla_attention_fwd_flops(
        seq_len, sequences, build["n_head"],
        build["qk_nope_head_dim"] + build["qk_rope_head_dim"],
        build["v_head_dim"])


def mla_attention_bytes(build: dict, seq_len: int, sequences: int) -> float:
    """What the attention of a step must move: q, k, v and the context
    of every head, bfloat16, read or written once forward, and they and
    their cotangents once more backward."""
    layers = build["n_layer"] + build.get("mtp_layers", 0)
    d_qk = build["qk_nope_head_dim"] + build["qk_rope_head_dim"]
    row = build["n_head"] * (2 * d_qk + 2 * build["v_head_dim"]) * 2
    return 3.0 * layers * sequences * seq_len * row


def layer_matrix_macs(build: dict, dense_ffn: bool) -> float:
    """Multiply-accumulates a token of one layer's matrices: the latent
    attention's six projections, and a dense SwiGLU of ``d_inner`` or
    the router, the shared expert and this member's EXPECTED share of
    the token's picks (``n_experts_per_tok`` x held / routed)."""
    m, h = build["d_model"], build["n_head"]
    ql, dc = build["q_lora_rank"], build["kv_lora_rank"]
    dn, dr, dv = (build["qk_nope_head_dim"], build["qk_rope_head_dim"],
                  build["v_head_dim"])
    mla = m * ql + ql * h * (dn + dr) + m * (dc + dr) + dc * h * dn \
        + dc * h * dv + h * dv * m
    if dense_ffn:
        return mla + 3.0 * m * build["d_inner"]
    f, e = build["d_expert"], build["n_routed_experts"]
    held = build["n_experts_per_tok"] * build["n_experts_held"] / e
    return mla + m * e + 3.0 * m * f * (build.get("n_shared_experts", 1)
                                        + held)


def lm_train_flops_per_step(build: dict, seq_len: int,
                            sequences: int) -> float:
    """Forward + backward of one step on ``sequences`` sequences of
    ``seq_len`` tokens: every layer's matrices and attention, the head
    once for the main loss and once for the multi-token-prediction
    module, whose ``eh_proj`` and one more expert layer count too
    (embedding lookups, norms, the router's top-k and the optimizer are
    not matrix products and count nothing)."""
    tokens = seq_len * sequences
    m, mtp = build["d_model"], build.get("mtp_layers", 0)
    macs = sum(layer_matrix_macs(build, i < build.get("first_k_dense", 0))
               for i in range(build["n_layer"]))
    macs += mtp * (layer_matrix_macs(build, False) + 2.0 * m * m)
    macs += (1 + mtp) * m * build["vocab"]
    return 3.0 * 2.0 * macs * tokens \
        + mla_attention_train_flops(build, seq_len, sequences)
