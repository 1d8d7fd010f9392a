"""Plain reference of the first pipeline stage of Olmo-Hybrid-7B
(allenai/Olmo-Hybrid-7B ``config.json``, ``model_type`` olmo_hybrid), as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no chunks,
no kernels; the recurrence of the linear layers is a loop over positions
and full attention is one softmax over all earlier positions (computed a
block of ``QUERY_BLOCK`` queries at a time so that the scores fit beside
the server in the chip's memory: each row's softmax is whole).

Written from the layer equations of ISSUE 59, not from ``paddle_tpu/ops``.

The block (32 layers of hidden 3840 in the source; Olmo's reordered
norm: ``h = x + RMSNorm(mixer(x))``, ``out = h + RMSNorm(ffn(h))``, no
norm before a sub-layer; RMSNorm eps 1e-6; a final RMSNorm; untied head):

- three layers of four (``linear_attention``): Gated DeltaNet
  (arXiv:2412.06464), 30 heads with keys of 96 and values of 192:
  ``u = [Wq x ; Wk x ; Wv x]``, a causal depthwise conv of 4 taps and a
  SiLU, ``q = l2norm(c^q) / sqrt(96)``, ``k = l2norm(c^k)``, ``v = c^v``,
  ``g = -exp(A_log_h) softplus(wa_h . x + dt_bias_h)`` (ONE scalar a
  head), ``beta = 2 sigmoid(wb_h . x)`` (``linear_allow_neg_eigval``),
  ``S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T``
  (S in R^{96 x 192}), ``o_t = S_t^T q_t``,
  ``y = Wo concat_h(RMSNorm_192(o; gain) * SiLU(Wz x)_h)``;
- the fourth (``full_attention``): 30 query and 30 KV heads of 128,
  ``q = RMSNorm_3840(Wq x)``, ``k = RMSNorm_3840(Wk x)`` over the WHOLE
  projection, no rotation, causal softmax at scale 128^-0.5, ``Wo``; no
  bias, no gate;
- every layer: ``W_down (SiLU(W_gate h) * W_up h)`` of width 11 008.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix at a time, the head a block of columns at a
time.

``low_precision=True`` is NOT the reference: it is the same forward with
every precision the configuration states replaced by the nearest one
below it — what it states as bfloat16 (weights, KV rows, the conv
window, the activations that cross a layer's boundary) rounded to
float8_e4m3, what it states as float32 (the recurrent state after every
step, decay, softmax, norms' results) rounded to bfloat16 — to show that
the limits of the comparison that decides ``correct`` lie between what
the served path reads and what a path one precision down would read.
``low_precision="state"`` rounds ONLY the recurrent state to bfloat16,
after every step, and leaves everything else exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
L2_EPS = 1e-6
QUERY_BLOCK = 512
HEAD_BLOCKS = 8

_FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_GDN = ("wq", "wk", "wv", "wz", "wo", "conv", "a_log", "dt_bias", "wa",
        "wb", "onorm")
_FFN = ("w_gate", "w_up", "w_down")


def layer_kinds(cfg: dict) -> list:
    period = cfg["layer_kinds"]
    return [period[i % len(period)] for i in range(cfg["n_layer"])]


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        mixer = [f"attn.{t}" for t in _FULL] if kind == "gqa" \
            else [f"gdn.{t}" for t in _GDN]
        out += [f"{name}_l{i}_{p}" for p in
                ["ln1_post_scale", "ln2_post_scale"] + mixer
                + [f"ffn.{t}" for t in _FFN]]
    return out + [f"{name}_lnf_scale", f"{name}_head_w"]


class Prec:
    """Where the forward rounds. The reference rounds nowhere: ``w``
    upcasts a weight, ``a`` (an activation the configuration states as
    bfloat16) and ``f`` (a quantity it states as float32) are the
    identity. ``low`` is the precision below: float8_e4m3 for ``w`` and
    ``a``, bfloat16 for ``f``."""

    def __init__(self, low=False):
        self.low = low is True
        self.low_state = bool(low)        # True, or "state" alone

    def _via(self, x, dtype):
        return x.astype(dtype).astype(F32) if self.low else x

    def w(self, w):
        return self._via(jnp.asarray(w).astype(F32), jnp.float8_e4m3fn)

    def a(self, x):
        return self._via(x, jnp.float8_e4m3fn)

    # to bfloat16 by ``reduce_precision``: the compiler removes a pair
    # of converts to bfloat16 and back, and the rounding with it
    def f(self, x):
        return _to_bf16(x) if self.low else x

    def s(self, x):
        """The recurrent state after a step."""
        return _to_bf16(x) if self.low_state else x


def _to_bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def bf16_share(x) -> float:
    """The share of the non-zero elements of a float32 array that a
    bfloat16 holds exactly: about 1 / 65536 of a state that is float32
    as stated, all of one that was rounded after every step."""
    bits = np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)
    bits = bits[bits != 0]
    return float(np.mean((bits & 0xFFFF) == 0)) if bits.size else 0.0


REF = Prec()


def rms_norm(x, scale, eps, pr=REF):
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return pr.f(x * inv * jnp.asarray(scale).astype(F32))


def full_layer(g, x, cfg, pr=REF):
    """x [L, M] -> [L, M]: causal softmax attention, as many KV heads as
    query heads, each projection normalised as a whole, no positions."""
    length = x.shape[0]
    h, d, eps = cfg["n_head"], cfg["head_dim"], cfg["rms_eps"]
    q = rms_norm(x @ pr.w(g("wq")), g("q_norm"), eps, pr)
    k = pr.a(rms_norm(x @ pr.w(g("wk")), g("k_norm"), eps, pr))  # a KV row
    v = pr.a(x @ pr.w(g("wv")))
    q, k, v = (t.reshape(length, h, d) for t in (q, k, v))
    blk = min(QUERY_BLOCK, length)
    n_blk = -(-length // blk)
    qp = jnp.pad(q, ((0, n_blk * blk - length), (0, 0), (0, 0)))

    def rows(i):
        """One block of queries against every key: whole softmax rows."""
        qi = jax.lax.dynamic_slice_in_dim(qp, i * blk, blk)
        s = jnp.einsum("thd,shd->hts", qi, k) * d ** -0.5
        keep = (i * blk + jnp.arange(blk))[:, None] \
            >= jnp.arange(length)[None, :]
        p = pr.f(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1))
        return jnp.einsum("hts,shd->thd", p, v)

    o = jax.lax.map(rows, jnp.arange(n_blk))
    o = o.reshape(n_blk * blk, h * d)[:length]
    return o @ pr.w(g("wo"))


def gdn_layer(g, x, cfg, pr=REF):
    """x [L, M] -> (y [L, M], the state S [H, Dk, Dv] after the last
    position, each head's mean log-decay [H]: how slowly it forgets): the
    recurrence as a loop over positions."""
    length = x.shape[0]
    h, dk, dv = cfg["gdn_heads"], cfg["gdn_key_dim"], cfg["gdn_value_dim"]
    taps = cfg.get("gdn_conv_taps", 4)
    u = pr.a(jnp.concatenate(                      # the conv window's rows
        [x @ pr.w(g(t)) for t in ("wq", "wk", "wv")], -1))
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    cw = pr.w(g("conv"))
    c = jax.nn.silu(sum(cw[j] * padded[j:j + length] for j in range(taps)))

    def l2norm(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q = l2norm(c[:, :h * dk].reshape(length, h, dk)) * dk ** -0.5
    k = l2norm(c[:, h * dk:2 * h * dk].reshape(length, h, dk))
    v = c[:, 2 * h * dk:].reshape(length, h, dv)
    f32 = lambda t: jnp.asarray(g(t)).astype(F32)             # noqa: E731
    log_alpha = -jnp.exp(f32("a_log")) * jax.nn.softplus(
        x @ pr.w(g("wa")) + f32("dt_bias"))                   # [L, H]
    alpha = pr.f(jnp.exp(log_alpha))
    beta = 2.0 * jax.nn.sigmoid(x @ pr.w(g("wb")))            # [L, H]

    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t
        s = a_t[:, None, None] * s                    # exp(g) S
        u_t = jnp.einsum("hk,hkv->hv", k_t, s)        # S^T k
        s = pr.s(pr.f(s + b_t[:, None, None] * k_t[:, :, None]
                      * (v_t - u_t)[:, None, :]))
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    s, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32),
                        (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_eps"])
    o = pr.f(o * f32("onorm"))
    gate = jax.nn.silu(x @ pr.w(g("wz")))
    return (o.reshape(length, h * dv) * gate) @ pr.w(g("wo")), s, \
        jnp.mean(log_alpha, axis=0)


def ffn(g, x, pr=REF):
    return (jax.nn.silu(x @ pr.w(g("w_gate"))) * (x @ pr.w(g("w_up")))) \
        @ pr.w(g("w_down"))


def head(hid, w, pr=REF):
    """hid [n, M] @ w [M, V], ``HEAD_BLOCKS`` blocks of columns at a
    time (the whole head in float32 is 1.5 GB)."""
    w = jnp.asarray(w)
    v = w.shape[1]
    if v % HEAD_BLOCKS:
        return hid @ pr.w(w)
    blk = v // HEAD_BLOCKS
    out = jax.lax.map(
        lambda j: hid @ pr.w(jax.lax.dynamic_slice_in_dim(w, j * blk, blk,
                                                          axis=1)),
        jnp.arange(HEAD_BLOCKS))
    return jnp.moveaxis(out, 0, 1).reshape(hid.shape[0], v)


@functools.partial(jax.jit, static_argnames=("cfg_items", "name",
                                             "low_precision"))
def _forward(p, ids, positions, cfg_items, name, low_precision):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    pr = Prec(low_precision)
    x = pr.w(p[f"{name}_emb"])[ids] if pr.low \
        else jnp.asarray(p[f"{name}_emb"])[ids].astype(F32)
    eps = cfg["rms_eps"]
    states, decays = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i, group=None):
            return p[f"{name}_l{i}_{group}.{tag}"]
        if kind == "gqa":
            y = full_layer(functools.partial(g, group="attn"), pr.a(x), cfg,
                           pr)
        else:
            y, s, log_decay = gdn_layer(functools.partial(g, group="gdn"),
                                        pr.a(x), cfg, pr)
            states.append(s)
            decays.append(log_decay)
        x = pr.a(x + pr.a(rms_norm(
            pr.a(y), p[f"{name}_l{i}_ln1_post_scale"], eps, pr)))
        y = ffn(functools.partial(g, group="ffn"), pr.a(x), pr)
        x = pr.a(x + pr.a(rms_norm(
            pr.a(y), p[f"{name}_l{i}_ln2_post_scale"], eps, pr)))
    hid = rms_norm(x[positions], p[f"{name}_lnf_scale"], eps, pr)
    return head(pr.a(hid), p[f"{name}_head_w"], pr), states, decays


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            low_precision=False):
    """The full causal forward over ONE sequence ``ids`` [L]: (logits
    [n, V] at ``positions`` [n], [the state [H, Dk, Dv] of each linear
    layer after the last position], [each linear layer's mean log-decay
    per head [H]])."""
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        low_precision)


def compare(p: dict, prompt, tokens, served_logits, served_states,
            cfg: dict, name: str = "lm", low_precision=False):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens. ``served_logits`` [m, V]: what
    the served path computed when it chose the LAST ``m`` of ``tokens``
    (``m = len(tokens)`` with the prefill's row first, one fewer with
    the decode steps' alone: through pages and state);
    ``served_states``: the slot's state per linear layer after the
    request. Returns per-position relative logit errors
    ``|l_sys - l_ref| / |l_ref - mean(l_ref)|`` (2-norms over the
    vocabulary) [m], the per-(layer, head) relative errors of the state
    [layers, H], how far below the reference's best logit each served
    token lies, in standard deviations of its position's logits [n], and
    which (layer, head) pairs are the layer's slowest-forgetting quarter
    by mean log-decay over this sequence [layers, H] bool."""
    n = len(tokens)
    ids = np.concatenate([np.asarray(prompt), np.asarray(tokens[:n - 1])])
    positions = len(prompt) - 1 + np.arange(n)
    ref, states, decays = forward(p, ids, positions, cfg, name,
                                  low_precision=low_precision)
    ref = np.asarray(ref, np.float64)
    sys_l = np.asarray(served_logits, np.float64)
    judged = ref[n - len(sys_l):]
    centred = judged - judged.mean(-1, keepdims=True)
    logit_err = np.linalg.norm(sys_l - judged, axis=-1) \
        / np.linalg.norm(centred, axis=-1)
    state_err = []
    for s_ref, s_sys in zip(states, served_states):
        s_ref = np.asarray(s_ref, np.float64)
        diff = np.asarray(s_sys, np.float64) - s_ref
        state_err.append(np.linalg.norm(diff, axis=(1, 2))
                         / np.linalg.norm(s_ref, axis=(1, 2)))
    decays = np.asarray(decays, np.float64)
    slow = decays >= np.quantile(decays, 0.75, axis=1, keepdims=True)
    rows = np.arange(n)
    margin = (ref.max(-1) - ref[rows, np.asarray(tokens)]) / ref.std(-1)
    return logit_err, np.asarray(state_err), margin, slow
