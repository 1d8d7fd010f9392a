"""Plain reference of LFM2-8B-A1B's block (LiquidAI/LFM2-8B-A1B
``config.json``, ``model_type: "lfm2_moe"``), as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no conv
window, no batching, no kernels. Attention is computed in blocks of 512
queries over ALL keys under the causal mask (``trinity_mini_26b_d5``'s
``attention_layer``, whose norm, rotation and grouped heads are this
model's: a block's scores are [32, 512, L] float32), the only departure
from the one-shot formula.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix, and one expert, at a time.

The block (24 layers of hidden 2048 in the source; RMSNorm eps 1e-5; the
head is the embedding's table):

- a layer: ``h = h + op(RMSNorm(h))``, then ``h = h + ff(RMSNorm(h))``;
  a final RMSNorm, then ``logits = h E^T``;
- ``op`` of a ``conv`` layer (``conv_L_cache`` 3, no bias): ``[B | C |
  x] = W_in u`` (2048 each, in that order), ``z = B * x``, ``c_t =
  sum_{j=0..2} w[j] * z_{t-2+j}`` (depthwise, causal, zeros before the
  sequence, NO activation), ``W_out (C * c)``;
- ``op`` of a ``full_attention`` layer: 32 query and 8 KV heads of 64,
  no biases, an RMSNorm with a learned gain over the 64 values of every
  q and k head, THEN rotate-half rotary positions on all 64 dimensions
  at theta 1e6, scores x 64 ** -0.5, causal, no gate, no window;
- ``ff`` of the first ``num_dense_layers`` = 2 layers: a dense SwiGLU of
  width 7168; of the others: ``s = sigmoid(W_r h)`` over 32 experts in
  float32, the 4 best by ``s + expert_bias``, weights ``s`` at the picks
  divided by (their sum + 1e-6) times ``routed_scaling_factor`` 1,
  experts ``W_2 (SiLU(W_1 h) * W_3 h)`` of width 1792, NO shared expert.

Departures of the SERVED program from this file (each also in the
configuration's file): it divides by the picks' sum without the 1e-6
(5e-7 relative at four sigmoid scores); it keeps ``z`` in bfloat16.

12 of 24 layers (``reduced``: ``n_layer``): the first of two pipeline
stages; all 32 experts, every head and the whole vocabulary are here.

``forward`` also returns, per conv layer, the last two rows of ``z`` —
what a slot's window holds after the sequence (zeros where the sequence
is shorter). Given the experts the SERVED path picked for every token
(``picks``) it weighs those in place of its own picks, and holds every
one of them to ITS OWN float32 scores: under random weights a router's
fourth and fifth best scores lie close together now and then, the pick
then turns on the rounding of bfloat16 activations (2-5 % of a layer's
tokens on the chip, so a quarter to a third of the tokens in one of ten
expert layers at least), and a token whose pick flipped moves its logits
by 0.17 to 0.64 where the same experts' results differ by 0.013
(PERF.md, PR 51). The comparison of logits is therefore of the same
experts' results, and a served pick that is not the reference's own has
to be one that bfloat16 could have decided: the served expert's score +
bias within a few thousandths of the reference's k-th best (:func:`route`
returns the distance; the check judges the layers' means and the
largest).

``low_precision=True`` is NOT the reference: the same forward with every
precision the configuration states replaced by the nearest one below —
what it states as bfloat16 (weights, KV rows, the conv window,
activations that cross a layer's boundary) rounded to float8_e4m3, what
it states as float32 (softmax, norms' results, router scores) to
bfloat16. ``rotary=False`` leaves the rotation out, ``use_bias=False``
picks by the scores alone, ``window_end=k`` takes the conv windows from
the rows before position ``k`` (a bucket's end) instead of the
sequence's: the controls a check's limits (and the CPU tests' tolerance)
are shown to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.trinity_mini_26b_d5 import (  # noqa: F401
    F32, REF, Prec, attention_layer, ffn, rms_norm)

HEAD_CHUNKS = 8
_CONV = ("w_in", "conv", "w_out")
_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_FFN = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "w_gate", "w_up", "w_down")
# added to the picks' sum before the division (the published block's)
NORM_EPS = 1e-6


def layer_kinds(cfg: dict) -> list:
    period = cfg["layer_kinds"]
    return [period[i % len(period)] for i in range(cfg["n_layer"])]


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        mixer = [f"conv.{t}" for t in _CONV] if kind == "conv" \
            else [f"attn.{t}" for t in _ATTN]
        ff = [f"ffn.{t}" for t in _FFN] if i < cfg["first_k_dense"] \
            else [f"moe.{t}" for t in _MOE]
        out += [f"{name}_l{i}_{p}"
                for p in ["ln1_scale", "ln2_scale"] + mixer + ff]
    return out + [f"{name}_lnf_scale"]


def conv_layer(g, x, cfg, pr=REF, window_end=None):
    """x [L, M] -> ([L, M], the last ``taps - 1`` rows of ``z = B * x``
    [taps - 1, M]: zeros where the sequence is shorter; with
    ``window_end`` the rows before that position instead — zeros past
    the sequence — which is a fault's window, not the model's)."""
    length, m = x.shape
    keep = cfg["conv_taps"] - 1
    bcx = x @ pr.w(g("w_in"))
    z = pr.a(bcx[:, :m] * bcx[:, 2 * m:])                # a window's row
    end = length if window_end is None else int(window_end)
    padded = jnp.concatenate([jnp.zeros((keep, m), F32), z,
                              jnp.zeros((max(end - length, 0), m), F32)])
    w = pr.w(g("conv"))                                  # [taps, M]
    c = sum(w[j] * padded[j:j + length] for j in range(keep + 1))
    return (bcx[:, m:2 * m] * c) @ pr.w(g("w_out")), padded[end:end + keep]


def route(g, x, cfg, pr=REF, use_bias=True, served_picks=None):
    """(combine weights [L, E] float32, zero off the picks; how far each
    token's ``served_picks`` lie from this router's own [L] float32):
    the picks by score + bias, the weights the picks' scores over (their
    sum + 1e-6), scaled. With ``served_picks`` [L, k] (what the served
    path picked) the weights are of THOSE experts, by this router's
    scores — a pick that flips on a near tie under bfloat16 activations
    then moves no logit — and every served pick is held to THIS router's
    float32 scores: the second result is how far the worst served expert
    lies under this router's k-th best, in units of score + bias. 0
    where the sets are the same; a tie that bfloat16 decided reads a few
    thousandths; a pick made by other scores (no bias, another router's
    row) reads what those scores differ by; picks that name an expert
    twice read inf."""
    scores = pr.f(jax.nn.sigmoid(x @ pr.w(g("router"))))
    biased = scores + jnp.asarray(g("router_bias")).astype(F32) \
        if cfg.get("router_bias") and use_bias else scores
    top, idx = jax.lax.top_k(biased, cfg["n_experts_per_tok"])
    gap = jnp.zeros(x.shape[:1], F32)
    if served_picks is not None:
        idx = served_picks
        gap = top[:, -1] - jnp.min(
            jnp.take_along_axis(biased, idx, axis=-1), -1)
        order = jnp.sort(idx, -1)
        gap = jnp.where(jnp.any(order[:, 1:] == order[:, :-1], -1),
                        jnp.inf, gap)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + NORM_EPS)
    vals = vals * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(scores.shape, F32).at[rows, idx].set(vals), gap


def expert_layer(g, x, cfg, pr=REF, use_bias=True, served_picks=None):
    """x [L, M] -> ([L, M], :func:`route`'s second result): every routed
    expert (all are held) weighted by the router, one upcast at a time;
    no shared expert."""
    combine, gap = route(g, x, cfg, pr, use_bias, served_picks)
    w_gate, w_up, w_down = (jnp.asarray(g(t))
                            for t in ("w_gate", "w_up", "w_down"))

    def one(acc, e):
        y = ffn(x, w_gate[e], w_up[e], w_down[e], pr)
        return acc + combine[:, e, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(cfg["n_experts_held"]))
    return y, gap


@functools.partial(jax.jit, static_argnames=(
    "cfg_items", "name", "low_precision", "rotary", "use_bias",
    "window_end"))
def _forward(p, ids, positions, picks, cfg_items, name, low_precision,
             rotary, use_bias, window_end):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    cfg["rope_theta"] = cfg["gqa_rope_theta"]     # attention_layer's key
    pr = Prec(low_precision)
    eps = cfg["rms_eps"]
    table = p[f"{name}_emb"]
    x = pr.a(pr.w(table)[ids] if pr.low
             else jnp.asarray(table)[ids].astype(F32))
    windows, gaps = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i, kind=kind, mixer=True):
            group = ("conv" if kind == "conv" else "attn") if mixer else \
                ("ffn" if i < cfg["first_k_dense"] else "moe")
            return p[f"{name}_l{i}_{group}.{tag}"]
        y = pr.a(rms_norm(x, p[f"{name}_l{i}_ln1_scale"], eps, pr))
        if kind == "conv":
            y, window = conv_layer(g, y, cfg, pr, window_end)
            windows.append(window)
        else:
            y = attention_layer(g, y, cfg, None, rotary, pr)
        x = pr.a(x + pr.a(y))
        y = pr.a(rms_norm(x, p[f"{name}_l{i}_ln2_scale"], eps, pr))
        gf = functools.partial(g, mixer=False)
        if i < cfg["first_k_dense"]:
            y = ffn(y, gf("w_gate"), gf("w_up"), gf("w_down"), pr)
        else:
            y, gap = expert_layer(
                gf, y, cfg, pr, use_bias,
                None if picks is None else picks[len(gaps)])
            gaps.append(gap)
        x = pr.a(x + pr.a(y))
    hid = pr.a(rms_norm(x[positions], p[f"{name}_lnf_scale"], eps, pr))
    # the tied table, read again, a slice of the vocabulary at a time
    step = -(-table.shape[0] // HEAD_CHUNKS)
    logits = jnp.concatenate(
        [hid @ pr.w(table[c:c + step]).T
         for c in range(0, table.shape[0], step)], axis=-1)
    return logits, windows, jnp.stack(gaps)


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            low_precision=False, rotary=True, use_bias=True,
            window_end=None, picks=None):
    """The full causal forward over ONE sequence ``ids`` [L]: (logits
    [n, V] at ``positions`` [n], [the last ``conv_taps - 1`` rows of
    ``B * x`` of each conv layer, [taps - 1, M]], how far each token's
    served picks lie from the router's own [expert layers, L] float32 —
    zeros without ``picks``). ``picks`` [expert layers, L, k]: the
    experts the served path picked for every token, which each expert
    layer then weighs in place of its own and holds to its own scores
    (:func:`route`)."""
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32),
                        None if picks is None
                        else jnp.asarray(picks, jnp.int32), items, name,
                        bool(low_precision), bool(rotary), bool(use_bias),
                        None if window_end is None else int(window_end))


def compare(p: dict, prompt, tokens, served_logits, served_windows,
            cfg: dict, name: str = "lm", served_picks=None,
            **forward_kwargs):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens — and, with ``served_picks``
    [expert layers, L, k] (what the served path's routers picked for
    each of the L = prompt + n - 1 tokens fed), on the served PICKS.
    ``served_logits`` [n, V]: what the served path chose each of
    ``tokens`` from (the prefill's row first, then the decode steps'
    through pages and conv windows); ``served_windows``: the slot's
    window per conv layer after the request, [taps - 1, M] each. Returns
    the per-position relative logit errors ``|l_sys - l_ref| / |l_ref -
    mean(l_ref)|`` (2-norms over the vocabulary) [n], the per-conv-layer
    relative errors of the window (2-norms over its rows; the absolute
    norm where the reference's window is all zeros) [conv layers], how
    far below the reference's best logit each served token lies, in
    standard deviations of its position's logits [n], and how far under
    the reference router's k-th best score + bias each token's worst
    served pick lies [expert layers, L] (:func:`route`: 0 where the
    picks are the router's own)."""
    n = len(tokens)
    ids = np.concatenate([np.asarray(prompt), np.asarray(tokens[:n - 1])])
    positions = len(prompt) - 1 + np.arange(n)
    ref, windows, gaps = forward(p, ids, positions, cfg, name,
                                 picks=served_picks, **forward_kwargs)
    ref = np.asarray(ref, np.float64)
    sys_l = np.asarray(served_logits, np.float64)
    centred = ref - ref.mean(-1, keepdims=True)
    logit_err = np.linalg.norm(sys_l - ref, axis=-1) \
        / np.linalg.norm(centred, axis=-1)
    window_err = []
    for w_ref, w_sys in zip(windows, served_windows):
        w_ref = np.asarray(w_ref, np.float64)
        diff = np.linalg.norm(np.asarray(w_sys, np.float64) - w_ref)
        window_err.append(diff / (np.linalg.norm(w_ref) or 1.0))
    margin = (ref.max(-1) - ref[np.arange(n), np.asarray(tokens)]) \
        / ref.std(-1)
    return logit_err, np.asarray(window_err), margin, np.asarray(gaps)
