"""Plain reference of the encoder-decoder Transformer ("big" row of
Table 3, arXiv:1706.03762) as ``paddle_tpu/models/transformer.py``
builds it, in jax.numpy float32 at matmul precision "highest".

As published: scaled dot-product multi-head attention, position-wise
ReLU feed-forward, sinusoidal positions added to embeddings scaled by
sqrt(d_model), label smoothing 0.1, separate (untied) embeddings and
output projection as assumed in the configuration file.
Departures of the PROGRAM that this reference follows: pre-norm
residuals with a final LayerNorm on each stack (the paper is post-norm),
no biases in the attention projections. Dropout is off in the
comparison (the program's masks are not reproducible outside it).

Parameters come as a flat list in the order the program creates them
(``param_shapes``); the runner checks every shape before use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference._plain import layer_norm, position_encoding

def param_shapes(cfg: dict) -> list:
    """[(role, shape)] in the program's creation order."""
    m, f = cfg["d_model"], cfg["d_inner"]
    ln = [("ln_scale", (m,)), ("ln_bias", (m,))]
    attn = [(w, (m, m)) for w in ("wq", "wk", "wv", "wo")]
    ffn = [("ffn1_w", (m, f)), ("ffn1_b", (f,)),
           ("ffn2_w", (f, m)), ("ffn2_b", (m,))]
    out = [("src_emb", (cfg["src_vocab"], m))]
    for _ in range(cfg["n_layer"]):
        out += ln + attn + ln + ffn
    out += ln + [("tgt_emb", (cfg["tgt_vocab"], m))]
    for _ in range(cfg["n_layer"]):
        out += ln + attn + ln + attn + ln + ffn
    return out + ln + [("head_w", (m, cfg["tgt_vocab"]))]


def attention(xq, xkv, wq, wk, wv, wo, n_head: int, causal: bool):
    b, tq, m = xq.shape
    tk, d = xkv.shape[1], m // n_head
    q = (xq @ wq).reshape(b, tq, n_head, d)
    k = (xkv @ wk).reshape(b, tk, n_head, d)
    v = (xkv @ wv).reshape(b, tk, n_head, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    if causal:
        keep = jnp.tril(jnp.ones((tq, tk), bool))
        logits = jnp.where(keep, logits, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    return ctx.reshape(b, tq, m) @ wo


def ffn(x, w1, b1, w2, b2):
    return jax.nn.relu(x @ w1 + b1) @ w2 + b2


def loss_fn(params, src, tgt, lbl, cfg: dict):
    """Mean label-smoothed cross entropy over all target positions.
    ``src``/``tgt``/``lbl`` are [B, T] integer arrays."""
    it = iter(params)
    take = lambda n: [next(it) for _ in range(n)]       # noqa: E731
    m, h = cfg["d_model"], cfg["n_head"]
    pe = jnp.asarray(position_encoding(src.shape[1], m))

    def embed(ids, table):
        return table[ids] * m ** 0.5 + pe

    x = embed(src, next(it))
    for _ in range(cfg["n_layer"]):
        y = layer_norm(x, *take(2))
        x = x + attention(y, y, *take(4), h, False)
        x = x + ffn(layer_norm(x, *take(2)), *take(4))
    enc = layer_norm(x, *take(2))
    x = embed(tgt, next(it))
    for _ in range(cfg["n_layer"]):
        y = layer_norm(x, *take(2))
        x = x + attention(y, y, *take(4), h, True)
        x = x + attention(layer_norm(x, *take(2)), enc, *take(4), h, False)
        x = x + ffn(layer_norm(x, *take(2)), *take(4))
    logits = layer_norm(x, *take(2)) @ next(it)
    logp = jax.nn.log_softmax(logits, axis=-1)
    eps = cfg["label_smooth_eps"]
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    return jnp.mean(-(1.0 - eps) * picked - eps * jnp.mean(logp, axis=-1))


def loss_and_grad_norms(params, src, tgt, lbl, cfg: dict, which):
    """(loss, [L2 norm of d loss / d params[i] for i in which])."""
    with jax.default_matmul_precision("highest"):
        # the sample is an ARGUMENT: closed over, its values would be
        # constants of the compiled program and every seed would compile
        def run(ps, src, tgt, lbl):
            loss, grads = jax.value_and_grad(loss_fn)(
                ps, src, tgt, lbl, cfg)
            return loss, [jnp.sqrt(jnp.sum(jnp.square(grads[i])))
                          for i in which]
        loss, norms = jax.jit(run)(list(params), jnp.asarray(src),
                                   jnp.asarray(tgt), jnp.asarray(lbl))
    return float(loss), [float(n) for n in norms]
