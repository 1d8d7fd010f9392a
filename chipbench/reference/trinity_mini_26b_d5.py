"""Plain reference of Trinity-Mini's block (arcee-ai/Trinity-Mini
``config.json``, ``model_type: "afmoe"``), as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no batching,
no kernels. Attention is computed in blocks of ``QUERY_BLOCK`` queries
over ALL keys under a mask (so that 6 000 tokens fit beside the server:
a block's scores are [32, 512, L] float32), which is the only departure
from the one-shot formula; the mask is the formula's.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix, and one expert, at a time.

The block (32 layers of hidden 2048 in the source; untied head):

- ``h0 = E[token] * sqrt(hidden)`` (``mup_enabled``);
- a layer: ``a = x + RMSNorm(Attn(RMSNorm(x)))``, ``y = a +
  RMSNorm(FFN(RMSNorm(a)))`` — four RMSNorms (``w * x / rms(x)``, eps
  1e-5), a final RMSNorm before the head;
- attention, every layer: 32 query heads and 4 KV heads of 128; q and k
  RMS-normalised over the 128 with a learned gain each, BEFORE
  positions; scores scaled by 128 ** -0.5; the context gated
  elementwise by ``sigmoid(W_gate x)`` before ``W_o``; no biases;
- ``swa`` layers (three of every four): rotary positions on all 128
  dimensions, theta 10 000, rotate-half, no scaling; query i sees keys j
  with ``0 <= i - j < window`` (2048 keys, itself included);
- ``gqa`` layers (every fourth): NO positions, causal, all keys;
- the first ``first_k_dense`` layers: dense SwiGLU of width 6144; the
  others: 128 routed experts of width 1024, router ``s = sigmoid(W_r
  x)`` in float32, the 8 best by ``s + expert_bias``, weights the picks'
  ``s`` renormalised over the 8 times ``route_scale``, one shared expert
  added.

``low_precision=True`` is NOT the reference: the same forward with every
precision the configuration states replaced by the nearest one below —
what it states as bfloat16 (weights, KV rows, activations that cross a
layer's boundary) rounded to float8_e4m3, what it states as float32
(softmax, norms' results, router scores) to bfloat16 — to show that the
comparison's limits lie between the served path and a path one precision
down. ``window``, ``rope_full`` and ``norm_last`` rebuild the forward
with another window, with rotary positions on the full layers too, or
with q and k normalised AFTER their rotation: the controls a check's
limits (and the CPU tests' tolerance) are shown to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_CHUNKS = 8

_ATTN = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm")
_FFN = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down", "s_gate",
        "s_up", "s_down")
_NORMS = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale")


def layer_kinds(cfg: dict) -> list:
    period = cfg["layer_kinds"]
    return [period[i % len(period)] for i in range(cfg["n_layer"])]


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i in range(cfg["n_layer"]):
        ffn = [f"ffn.{t}" for t in _FFN] if i < cfg["first_k_dense"] \
            else [f"moe.{t}" for t in _MOE]
        out += [f"{name}_l{i}_{p}" for p in
                list(_NORMS) + [f"attn.{t}" for t in _ATTN] + ffn]
    return out + [f"{name}_lnf_scale", f"{name}_head_w"]


class Prec:
    """Where the forward rounds: nowhere in the reference; ``low`` is
    the precision below (float8_e4m3 for weights ``w`` and bfloat16
    activations ``a``, bfloat16 for float32 quantities ``f``)."""

    def __init__(self, low=False):
        self.low = bool(low)

    def _via(self, x, dtype):
        return x.astype(dtype).astype(F32) if self.low else x

    def w(self, w):
        return self._via(jnp.asarray(w).astype(F32), jnp.float8_e4m3fn)

    def a(self, x):
        return self._via(x, jnp.float8_e4m3fn)

    def f(self, x):
        # by reduce_precision: the compiler removes a pair of converts
        return jax.lax.reduce_precision(x, exponent_bits=8,
                                        mantissa_bits=7) if self.low else x


REF = Prec()


def rms_norm(x, scale, eps, pr=REF):
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return pr.f(x * inv * jnp.asarray(scale).astype(F32))


def rope_half(x, theta: float):
    """x [L, heads, D] rotated at positions 0..L-1, rotate-half."""
    length, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(length, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_layer(g, x, cfg, window, rotary, pr=REF, norm_last=False):
    """x [L, M] -> [L, M]; ``window`` None attends every key up to the
    query, ``rotary`` turns q and k at their positions (``norm_last``:
    before their norm, which is not the model's order)."""
    length = x.shape[0]
    h, n_kv, d = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    eps = cfg["rms_eps"]
    q = (x @ pr.w(g("wq"))).reshape(length, h, d)
    k = (x @ pr.w(g("wk"))).reshape(length, n_kv, d)
    v = pr.a(x @ pr.w(g("wv"))).reshape(length, n_kv, d)      # a KV row
    if rotary and norm_last:
        q, k = (rope_half(t, float(cfg["rope_theta"])) for t in (q, k))
    if cfg.get("qk_norm"):
        q = rms_norm(q, g("q_norm"), eps, pr)
        k = rms_norm(k, g("k_norm"), eps, pr)
    if rotary and not norm_last:
        q, k = (rope_half(t, float(cfg["rope_theta"])) for t in (q, k))
    k = pr.a(k)                                               # a KV row
    q = q.reshape(length, n_kv, h // n_kv, d)
    blk = min(length, QUERY_BLOCK)
    pad = -length % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    cols = jnp.arange(length)

    def block(t0):
        rows = t0 + jnp.arange(blk)
        ahead = rows[:, None] - cols[None, :]
        keep = ahead >= 0
        if window is not None:
            keep &= ahead < window
        qb = jax.lax.dynamic_slice_in_dim(qp, t0, blk)
        s = jnp.einsum("tkgd,skd->kgts", qb, k) * d ** -0.5
        p = pr.f(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1))
        return jnp.einsum("kgts,skd->tkgd", p, v)

    o = jax.lax.map(block, jnp.arange(0, length + pad, blk))
    o = o.reshape(length + pad, h * d)[:length]
    if cfg.get("gqa_gate", True):
        o = o * jax.nn.sigmoid(x @ pr.w(g("wg")))
    return o @ pr.w(g("wo"))


def ffn(x, w_gate, w_up, w_down, pr=REF):
    return (jax.nn.silu(x @ pr.w(w_gate)) * (x @ pr.w(w_up))) @ pr.w(w_down)


def route(g, x, cfg, pr=REF):
    """Combine weights [L, E] float32, zero off the picks: the picks by
    score + bias, the weights the picks' scores renormalised, scaled."""
    scores = pr.f(jax.nn.sigmoid(x @ pr.w(g("router"))))
    biased = scores + jnp.asarray(g("router_bias")).astype(F32) \
        if cfg.get("router_bias") else scores
    _, idx = jax.lax.top_k(biased, cfg["n_experts_per_tok"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    vals = vals * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(scores.shape, F32).at[rows, idx].set(vals)


def expert_layer(g, x, cfg, pr=REF):
    """x [L, M] -> [L, M]: every routed expert (all are held) weighted
    by the router, one upcast at a time, plus the shared expert."""
    first, count = cfg.get("held_start", 0), cfg["n_experts_held"]
    combine = route(g, x, cfg, pr)[:, first:first + count]
    w_gate, w_up, w_down = (jnp.asarray(g(t))
                            for t in ("w_gate", "w_up", "w_down"))

    def one(acc, e):
        y = ffn(x, w_gate[e], w_up[e], w_down[e], pr)
        return acc + combine[:, e, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y + ffn(x, g("s_gate"), g("s_up"), g("s_down"), pr)


@functools.partial(jax.jit, static_argnames=(
    "cfg_items", "name", "low_precision", "window", "rope_full",
    "norm_last"))
def _forward(p, ids, positions, cfg_items, name, low_precision, window,
             rope_full, norm_last):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    pr = Prec(low_precision)
    eps = cfg["rms_eps"]
    emb = p[f"{name}_emb"]
    x = pr.w(emb)[ids] if pr.low else jnp.asarray(emb)[ids].astype(F32)
    x = pr.a(x * (cfg.get("embed_scale") or 1.0))
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i):
            group = "attn" if tag in _ATTN else \
                ("ffn" if i < cfg["first_k_dense"] else "moe")
            return p[f"{name}_l{i}_{group}.{tag}"]

        def norm(z, which, i=i):
            return rms_norm(z, p[f"{name}_l{i}_{which}_scale"], eps, pr)

        swa = kind == "swa"
        y = attention_layer(g, pr.a(norm(x, "ln1")), cfg,
                            window if swa else None, swa or rope_full, pr,
                            norm_last)
        if cfg.get("post_norms"):
            y = norm(pr.a(y), "ln1_post")
        x = pr.a(x + pr.a(y))
        y = pr.a(norm(x, "ln2"))
        y = ffn(y, g("w_gate"), g("w_up"), g("w_down"), pr) \
            if i < cfg["first_k_dense"] else expert_layer(g, y, cfg, pr)
        if cfg.get("post_norms"):
            y = norm(pr.a(y), "ln2_post")
        x = pr.a(x + pr.a(y))
    hid = pr.a(rms_norm(x[positions], p[f"{name}_lnf_scale"], eps, pr))
    head = p[f"{name}_head_w"]
    step = -(-head.shape[1] // HEAD_CHUNKS)
    return jnp.concatenate(
        [hid @ pr.w(head[:, c:c + step])
         for c in range(0, head.shape[1], step)], axis=-1)


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            low_precision=False, window=None, rope_full=False,
            norm_last=False):
    """The full causal forward over ONE sequence ``ids`` [L]: logits
    [n, V] at ``positions`` [n]. ``window`` (default: the
    configuration's) and ``rope_full`` build the wrong-window and the
    rotary-everywhere controls."""
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        bool(low_precision),
                        int(cfg["window"] if window is None else window),
                        bool(rope_full), bool(norm_last))


def attended(cfg: dict, positions, window=None) -> np.ndarray:
    """What a window layer's query at each of ``positions`` (true
    positions) attends: [n, 2] = (the lowest key position, the number
    of keys)."""
    w = int(cfg["window"] if window is None else window)
    positions = np.asarray(positions, np.int64)
    lo = np.maximum(0, positions - w + 1)
    return np.stack([lo, positions - lo + 1], axis=-1)


def compare(p: dict, prompt, tokens, served_logits, cfg: dict,
            name: str = "lm", **forward_kwargs):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens. ``served_logits`` [n, V]: what
    the served path chose each of ``tokens`` from (the prefill's row
    first, then the decode steps' through the pages). Returns the
    per-position relative logit errors ``|l_sys - l_ref| / |l_ref -
    mean(l_ref)|`` (2-norms over the vocabulary) [n], how far below the
    reference's best logit each served token lies, in standard
    deviations of its position's logits [n], and the true position each
    row was computed at [n]."""
    n = len(tokens)
    ids = np.concatenate([np.asarray(prompt), np.asarray(tokens[:n - 1])])
    positions = len(prompt) - 1 + np.arange(n)
    ref = np.asarray(forward(p, ids, positions, cfg, name,
                             **forward_kwargs), np.float64)
    sys_l = np.asarray(served_logits, np.float64)
    centred = ref - ref.mean(-1, keepdims=True)
    logit_err = np.linalg.norm(sys_l - ref, axis=-1) \
        / np.linalg.norm(centred, axis=-1)
    margin = (ref.max(-1) - ref[np.arange(n), np.asarray(tokens)]) \
        / ref.std(-1)
    return logit_err, margin, positions
