"""Plain reference of the decoder-only language model that
``paddle_tpu/models/transformer.py:decoder_lm`` serves, at GPT-2
medium's sizes, in jax.numpy float32 at matmul precision "highest":
one full causal forward over the whole sequence, no cache.

GPT-2 as published (Radford et al. 2019; openai-community/gpt2-medium
``config.json``) is a pre-norm decoder with a final LayerNorm, which
the program matches. Departures of the PROGRAM that this reference
follows: ReLU for GELU, sinusoidal for learned positions, embeddings
scaled by sqrt(d_model), an output projection not tied to the embedding,
no bias in the attention projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference._plain import layer_norm, position_encoding

def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i in range(cfg["n_layer"]):
        out += [f"{name}_l{i}_{p}" for p in (
            "ln1_scale", "ln1_bias", "attn.wq", "attn.wk", "attn.wv",
            "attn.wo", "ln2_scale", "ln2_bias", "ffn1_w", "ffn1_b",
            "ffn2_w", "ffn2_b")]
    return out + [f"{name}_lnf_scale", f"{name}_lnf_bias", f"{name}_head_w"]


def hidden_states(p: dict, ids, cfg: dict, name: str = "lm"):
    """[B, L] token ids -> [B, L, d_model] after the final LayerNorm."""
    b, length = ids.shape
    m, h = cfg["d_model"], cfg["n_head"]
    d = m // h
    x = p[f"{name}_emb"][ids] * m ** 0.5 \
        + jnp.asarray(position_encoding(length, m))
    keep = jnp.tril(jnp.ones((length, length), bool))
    for i in range(cfg["n_layer"]):
        g = lambda s: p[f"{name}_l{i}_{s}"]              # noqa: E731
        y = layer_norm(x, g("ln1_scale"), g("ln1_bias"))
        q = (y @ g("attn.wq")).reshape(b, length, h, d)
        k = (y @ g("attn.wk")).reshape(b, length, h, d)
        v = (y @ g("attn.wv")).reshape(b, length, h, d)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, length, m)
        x = x + ctx @ g("attn.wo")
        y = layer_norm(x, g("ln2_scale"), g("ln2_bias"))
        x = x + jax.nn.relu(y @ g("ffn1_w") + g("ffn1_b")) @ g("ffn2_w") \
            + g("ffn2_b")
    return layer_norm(x, p[f"{name}_lnf_scale"], p[f"{name}_lnf_bias"])


def next_token_logits(p: dict, ids, positions, cfg: dict, name="lm"):
    """Logits [B, n, V] at ``positions`` [B, n] of the full forward over
    ``ids`` [B, L] (right-padded; causal, so padding changes nothing
    before it)."""
    with jax.default_matmul_precision("highest"):
        def run(p, ids, positions):
            hid = hidden_states(p, ids, cfg, name)
            rows = jnp.take_along_axis(hid, positions[..., None], axis=1)
            return rows @ p[f"{name}_head_w"]
        return jax.jit(run)(p, jnp.asarray(ids), jnp.asarray(positions))


def worst_margin(p: dict, prompts, outputs, cfg: dict, name="lm") -> float:
    """How far below the reference's best logit the served tokens lie,
    at worst, in units of the standard deviation of that position's
    logits. ``outputs[i]`` are the tokens the system generated greedily
    after ``prompts[i]``; the reference is teacher-forced on them, so
    position j is judged given the system's own tokens before it. 0 for
    a system that picks the reference's argmax everywhere."""
    n = min(len(o) for o in outputs)
    length = max(len(q) for q in prompts) + n
    ids = np.zeros((len(prompts), length), np.int32)
    positions = np.zeros((len(prompts), n), np.int32)
    for i, (q, o) in enumerate(zip(prompts, outputs)):
        ids[i, :len(q)] = q
        ids[i, len(q):len(q) + n - 1] = o[:n - 1]
        positions[i] = len(q) - 1 + np.arange(n)
    logits = np.asarray(next_token_logits(p, ids, positions, cfg, name))
    worst = 0.0
    for i, o in enumerate(outputs):
        rows = logits[i]
        served = rows[np.arange(n), np.asarray(o[:n])]
        worst = max(worst, float(np.max(
            (rows.max(-1) - served) / rows.std(-1))))
    return worst
