"""Plain reference of MiMo-V2-Flash's block (XiaomiMiMo/MiMo-V2-Flash
``config.json``), as ``paddle_tpu/models/transformer.py:decoder_lm(...,
layer_kinds=...)`` serves it: jax.numpy, float32, matmul precision
"highest", ONE sequence at a time, one full causal forward with no
cache, no pages, no batching, no kernels. Attention is computed in
blocks of ``QUERY_BLOCK`` queries over ALL keys under a mask (a block's
scores are [64, 256, L] float32, so that 5 100 tokens fit beside the
server), the only departure from the one-shot formula; the mask and the
sink's column are the formula's.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix, and one expert, at a time.

The block (48 layers of hidden 4096 in the source; everything that
``config.json`` does not state is listed under ``assumed`` in the
configuration's file):

- ``h0 = E[token]``; a layer: ``a = x + Attn(RMSNorm(x))``, ``y = a +
  FFN(RMSNorm(a))``, RMSNorm ``w * x / rms(x)``, eps
  ``layernorm_epsilon`` 1e-5, float32 statistics; a final RMSNorm; an
  untied head ``[4096, vocab]``;
- heads: ``q = x Wq -> [64, 192]``, ``k = x Wk -> [n_kv, 192]``, ``v =
  attention_value_scale * (x Wv) -> [n_kv, 128]`` with ``n_kv`` 4 in a
  full layer (``gqa``) and 8 in a window layer (``swa``); no biases, no
  QK norm, no gate. The first ``rotary_dim`` = 64 values of every q and
  k head are rotated (rotate-half INSIDE those 64: ``x[i]`` pairs with
  ``x[i + 32]``), base 5e6 in a full layer and 1e4 in a window layer,
  by TRUE position; the other 128 pass;
- full layer: ``s_ij = q_i . k_j / sqrt(192)``, causal softmax over ``j
  <= i``, ``c_i = sum_j p_ij v_j``; out ``= c [64 x 128] Wo [8192,
  4096]``;
- window layer: keys ``j`` with ``0 <= i - j < 128`` (the query's own
  included), and one learned float32 logit ``sink_h`` a query head that
  joins the softmax's denominator and carries no value: ``p_ij =
  exp(s_ij - m) / (exp(sink_h - m) + sum_j' exp(s_ij' - m))``, ``m`` the
  maximum over the scores AND the sink;
- experts: ``g = sigmoid(x W_r)`` in float32 over all 256; the 8 largest
  of ``g + bias`` (one group: no group limit); weights the picks' ``g``
  WITHOUT the bias, normalised to sum 1; no routed scale, no shared
  expert; an expert is ``W_down(SiLU(x W_gate) * (x W_up))``, width
  2048. This chip adds its ``n_experts_held`` experts' part (from
  ``held_start``) and nothing for the absent ones, here as in the
  program. Layer 0: dense SwiGLU of width 16384.

``low_precision=True`` is NOT the reference: the same forward with every
precision the configuration states replaced by the nearest one below —
what it states as bfloat16 (weights, KV rows, activations that cross a
layer's boundary) rounded to float8_e4m3, what it states as float32
(softmax, norms' results, router scores) to bfloat16. The other keywords
rebuild the forward with one fault each, the controls a check's limits
(and the CPU tests' tolerance) are shown to refuse: ``window`` (another
window), ``sink=False`` (no sink column), ``rotary_dim`` (192: every
value of a head rotated), ``value_scale`` (1.0: V unscaled),
``swa_n_kv_head=None`` (the window layers grouped over the FULL layers'
KV head count: the first of their KV heads, each shared by twice the
query heads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.trinity_mini_26b_d5 import (  # noqa: F401
    F32, REF, Prec, attended, ffn, rms_norm)

QUERY_BLOCK = 256
HEAD_CHUNKS = 8

_FFN = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down")
_NORMS = ("ln1_scale", "ln2_scale")


def layer_kinds(cfg: dict) -> list:
    period = cfg["layer_kinds"]
    return [period[i % len(period)] for i in range(cfg["n_layer"])]


def attn_names(cfg: dict, kind: str) -> tuple:
    sink = ("sink",) if kind == "swa" and cfg.get("swa_sink") else ()
    return ("wq", "wk", "wv", "wo") + sink


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        ff = [f"ffn.{t}" for t in _FFN] if i < cfg["first_k_dense"] \
            else [f"moe.{t}" for t in _MOE]
        out += [f"{name}_l{i}_{p}" for p in list(_NORMS) + [
            f"attn.{t}" for t in attn_names(cfg, kind)] + ff]
    return out + [f"{name}_lnf_scale", f"{name}_head_w"]


def rope_partial(x, theta: float, rotary: int):
    """x [L, heads, D] at positions 0..L-1: the first ``rotary`` values
    of a head rotated, rotate-half inside them; the others pass."""
    length = x.shape[0]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=F32) / rotary)
    ang = jnp.arange(length, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], -1)


def attention_layer(g, x, cfg, kind, pr=REF, window=None, sink=True):
    """x [L, M] -> [L, M] of a ``gqa`` (full) or ``swa`` (window, with
    its sink) layer."""
    length = x.shape[0]
    swa = kind == "swa"
    h, d = cfg["n_head"], cfg["head_dim"]
    dv = cfg.get("gqa_v_head_dim") or d
    n_kv = (cfg.get("swa_n_kv_head") if swa else None) or cfg["n_kv_head"]
    theta = cfg["rope_theta"] if swa else cfg["gqa_rope_theta"]
    rotary = cfg.get("rotary_dim") or d
    q = (x @ pr.w(g("wq"))).reshape(length, h, d)
    # (a control that states fewer KV heads than the weights hold reads
    # the first of them)
    k = (x @ pr.w(g("wk")))[:, :n_kv * d].reshape(length, n_kv, d)
    v = pr.a((cfg.get("value_scale") or 1.0) * (x @ pr.w(g("wv")))
             )[:, :n_kv * dv].reshape(length, n_kv, dv)       # a KV row
    q = rope_partial(q, float(theta), rotary)
    k = pr.a(rope_partial(k, float(theta), rotary))           # a KV row
    q = q.reshape(length, n_kv, h // n_kv, d)
    s_h = jnp.asarray(g("sink")).astype(F32).reshape(n_kv, h // n_kv, 1, 1) \
        if swa and sink and cfg.get("swa_sink") else None
    blk = min(length, QUERY_BLOCK)
    pad = -length % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    cols = jnp.arange(length)

    def block(t0):
        ahead = (t0 + jnp.arange(blk))[:, None] - cols[None, :]
        keep = ahead >= 0
        if swa:
            keep &= ahead < window
        qb = jax.lax.dynamic_slice_in_dim(qp, t0, blk)
        s = jnp.einsum("tkgd,skd->kgts", qb, k) * d ** -0.5
        s = jnp.where(keep, s, -jnp.inf)
        if s_h is None:
            p = jax.nn.softmax(s, axis=-1)
        else:
            m = jnp.maximum(jnp.max(s, -1, keepdims=True), s_h)
            e = jnp.exp(s - m)
            p = e / (jnp.exp(s_h - m) + jnp.sum(e, -1, keepdims=True))
        return jnp.einsum("kgts,skd->tkgd", pr.f(p), v)

    o = jax.lax.map(block, jnp.arange(0, length + pad, blk))
    return o.reshape(length + pad, h * dv)[:length] @ pr.w(g("wo"))


def route(g, x, cfg, pr=REF):
    """Combine weights [L, E] float32 over ALL routed experts, zero off
    the picks: the picks by score + bias, the weights the picks' scores
    normalised to sum 1 (no routed scale in this model: 1.0)."""
    scores = pr.f(jax.nn.sigmoid(x @ pr.w(g("router"))))
    biased = scores + jnp.asarray(g("router_bias")).astype(F32).reshape(-1) \
        if cfg.get("router_bias") else scores
    _, idx = jax.lax.top_k(biased, cfg["n_experts_per_tok"])
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    vals = vals * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(scores.shape, F32).at[rows, idx].set(vals)


def expert_layer(g, x, cfg, pr=REF):
    """x [L, M] -> [L, M]: the HELD experts' part (``n_experts_held``
    from ``held_start``; the weights given are theirs alone), weighted by
    the router over all experts, one upcast at a time; nothing for the
    absent ones, no shared expert."""
    first, count = cfg.get("held_start", 0), cfg["n_experts_held"]
    combine = route(g, x, cfg, pr)[:, first:first + count]
    w_gate, w_up, w_down = (jnp.asarray(g(t))
                            for t in ("w_gate", "w_up", "w_down"))

    def one(acc, e):
        y = ffn(x, w_gate[e], w_up[e], w_down[e], pr)
        return acc + combine[:, e, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y


@functools.partial(jax.jit, static_argnames=(
    "cfg_items", "name", "low_precision", "window", "sink"))
def _forward(p, ids, positions, cfg_items, name, low_precision, window,
             sink):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    pr = Prec(low_precision)
    eps = cfg["rms_eps"]
    emb = p[f"{name}_emb"]
    x = pr.a(pr.w(emb)[ids] if pr.low else jnp.asarray(emb)[ids].astype(F32))
    for i, kind in enumerate(layer_kinds(cfg)):
        dense = i < cfg["first_k_dense"]

        def g(tag, i=i, dense=dense, kind=kind):
            group = "attn" if tag in attn_names(cfg, kind) else \
                ("ffn" if dense else "moe")
            return p[f"{name}_l{i}_{group}.{tag}"]

        def norm(z, which, i=i):
            return rms_norm(z, p[f"{name}_l{i}_{which}_scale"], eps, pr)

        y = attention_layer(g, pr.a(norm(x, "ln1")), cfg, kind, pr, window,
                            sink)
        x = pr.a(x + pr.a(y))
        y = pr.a(norm(x, "ln2"))
        y = ffn(y, g("w_gate"), g("w_up"), g("w_down"), pr) if dense \
            else expert_layer(g, y, cfg, pr)
        x = pr.a(x + pr.a(y))
    hid = pr.a(rms_norm(x[positions], p[f"{name}_lnf_scale"], eps, pr))
    head = p[f"{name}_head_w"]
    step = -(-head.shape[1] // HEAD_CHUNKS)
    return jnp.concatenate(
        [hid @ pr.w(head[:, c:c + step])
         for c in range(0, head.shape[1], step)], axis=-1)


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            low_precision=False, window=None, sink=True, **changes):
    """The full causal forward over ONE sequence ``ids`` [L]: logits
    [n, V] at ``positions`` [n]. ``window`` (default: the
    configuration's), ``sink=False`` and ``changes`` to the
    configuration (``rotary_dim``, ``value_scale``) build the controls."""
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in {**cfg, **changes}.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        bool(low_precision),
                        int(cfg["window"] if window is None else window),
                        bool(sink))


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
