"""Plain reference of AI21-Jamba2-3B (ai21labs/AI21-Jamba2-3B
``config.json``, ``model_type`` jamba), WHOLE, as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no chunks, no
kernels; the recurrence of the Mamba layers is a loop over positions, one
token at a time, and attention is one softmax over all earlier positions.

Written from the layer equations of ISSUE 65, not from ``paddle_tpu/ops``.

The block (28 layers of hidden 2560; pre-norm, ``x <- x + f(RMSNorm(x))``
twice a layer, eps 1e-6, a final RMSNorm, logits through the tied table):

- layer ``i`` with ``i % 14 == 7`` (layers 7 and 21): multi-query
  attention, 20 query heads over ONE KV head of 128, causal softmax at
  scale 128^-0.5, NO positions, no bias, no gate;
- every other layer (26 of 28): Mamba-1's mixer (arXiv:2312.00752) with
  Jamba's three inner norms, ``C = 5120`` channels, a state of 16, a step
  rank of 160, a conv of 4 taps:

      [x | z] = W_in u
      x_t = SiLU(sum_j conv_w[j] * x_{t-K+1+j} + conv_b)
      [dt | B | C] = W_x x_t
      dt = RMSNorm(dt) g_dt   B = RMSNorm(B) g_B   C = RMSNorm(C) g_C
      dt_t[c] = softplus((W_dt dt)[c] + b_dt[c])       A = -exp(A_log)
      h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
      y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]
      out = W_out (y_t * SiLU(z_t))

- every layer: ``W_down (SiLU(W_gate h) * W_up h)`` of width 8192.

Departures from the issue's text, each for the served layout's sake and
none of the arithmetic: ``A_log`` arrives FLAT, ``[N * C]`` with the
state index first (``ops/s6.py`` keeps it so), and is turned to the
issue's ``[C, N]`` here; the state is returned ``[C, N]`` and
:func:`served_state` turns a slot's ``[N, C]`` to it.

The attention layer and the rounding helpers are the other references'
(``granite4_h_small_ep4_d10.gqa_layer``, Solar's ``Prec``). It is fed
the served model's own weights (bfloat16 on the chip) and upcasts them
one matrix at a time, the tied table a block of rows at a time, so the
float32 model (12 GB) never sits beside the served one.

``low_precision=True`` is NOT the reference: it is the same forward with
every precision the configuration states replaced by the nearest one
below it — what it states as bfloat16 (weights, KV rows, the conv
window, the activations that cross a layer's boundary) rounded to
float8_e4m3, what it states as float32 (the state after every step, the
decay, the step, softmax, norms' results) rounded to bfloat16.
``low_precision="state"`` rounds ONLY the recurrent state to bfloat16,
after every step; ``low_precision="recurrence"`` rounds the step's
factors (``exp(dt A)``, ``dt B x``) and the state to bfloat16 — the
recurrence one precision down, everything around it exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.granite4_h_small_ep4_d10 import gqa_layer
from chipbench.reference.solar_open2_250b_ep8_d4 import (  # noqa: F401
    F32, _to_bf16, bf16_share, layer_kinds, rms_norm)
from chipbench.reference.solar_open2_250b_ep8_d4 import Prec as _Prec

HEAD_BLOCKS = 8

_GQA = ("wq", "wk", "wv", "wo")
_S6 = ("w_in", "w_out", "conv", "conv_bias", "w_x", "dt_norm", "b_norm",
       "c_norm", "w_dt", "dt_bias", "a_log", "d")
_FFN = ("w_gate", "w_up", "w_down")


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        mixer = [f"attn.{t}" for t in _GQA] if kind == "gqa" \
            else [f"s6.{t}" for t in _S6]
        out += [f"{name}_l{i}_{p}" for p in
                ["ln1_scale", "ln2_scale"] + mixer
                + [f"ffn.{t}" for t in _FFN]]
    return out + [f"{name}_lnf_scale"]


class Prec(_Prec):
    """Where the forward rounds: the other references' (``w`` a weight,
    ``a`` an activation stated as bfloat16, ``f`` a quantity stated as
    float32, ``s`` the state after a step: the identity in the
    reference), and ``r``, a factor of the recurrence's step, which
    ``low_precision="recurrence"`` rounds to bfloat16 with the state."""

    def __init__(self, low=False):
        super().__init__(low)
        self.low_rec = self.low or low == "recurrence"

    def r(self, x):
        return _to_bf16(x) if self.low_rec else x


REF = Prec()


def served_state(s):
    """A slot's state as the program keeps it, [N, C], as the equations
    have it: [C, N]."""
    return np.asarray(s).T


def s6_layer(g, u, cfg, pr=REF):
    """u [L, M] -> (out [L, M], the state h [C, N] after the last
    position, the mean of log(decay) per state index [N]: how slowly it
    forgets): the recurrence token by token."""
    length = u.shape[0]
    inner, n, r = cfg["s6_d_inner"], cfg["s6_d_state"], cfg["s6_dt_rank"]
    taps, eps = cfg.get("s6_conv_taps", 4), cfg["rms_eps"]
    f32 = lambda t: jnp.asarray(g(t)).astype(F32)             # noqa: E731
    xz = u @ pr.w(g("w_in"))
    x, z = pr.a(xz[:, :inner]), xz[:, inner:]       # x: the window's rows
    padded = jnp.concatenate([jnp.zeros((taps - 1, inner), F32), x])
    cw = pr.w(g("conv"))
    x = jax.nn.silu(sum(cw[j] * padded[j:j + length] for j in range(taps))
                    + pr.w(g("conv_bias")).reshape(-1))
    low = pr.a(x) @ pr.w(g("w_x"))
    dt = rms_norm(low[:, :r], g("dt_norm"), eps, pr)
    b = rms_norm(low[:, r:r + n], g("b_norm"), eps, pr)
    c = rms_norm(low[:, r + n:], g("c_norm"), eps, pr)
    dt = pr.f(jax.nn.softplus(pr.a(dt) @ pr.w(g("w_dt")) + f32("dt_bias")))
    a = -jnp.exp(f32("a_log").reshape(n, inner).T)            # [C, N]

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = pr.s(pr.r(jnp.exp(dt_t[:, None] * a)) * h
                 + pr.r(dt_t[:, None] * b_t[None, :] * x_t[:, None]))
        return h, h @ c_t

    h, y = jax.lax.scan(step, jnp.zeros((inner, n), F32), (x, dt, b, c))
    y = pr.f(y + f32("d") * x)
    out = pr.a(y * jax.nn.silu(z)) @ pr.w(g("w_out"))
    return out, h, jnp.mean(dt, axis=(0, 1)) * jnp.mean(a, axis=0)


def ffn(g, x, pr=REF):
    return pr.a(jax.nn.silu(x @ pr.w(g("w_gate"))) * (x @ pr.w(g("w_up")))) \
        @ pr.w(g("w_down"))


def tied_head(hid, table, pr=REF):
    """hid [n, M] against the table [V, M], ``HEAD_BLOCKS`` blocks of
    rows at a time (the whole table in float32 is 0.67 GB)."""
    table = jnp.asarray(table)
    v = table.shape[0]
    if v % HEAD_BLOCKS:
        return hid @ pr.w(table).T
    blk = v // HEAD_BLOCKS
    out = jax.lax.map(
        lambda j: hid @ pr.w(jax.lax.dynamic_slice_in_dim(
            table, j * blk, blk)).T, jnp.arange(HEAD_BLOCKS))
    return jnp.moveaxis(out, 0, 1).reshape(hid.shape[0], v)


@functools.partial(jax.jit, static_argnames=("cfg_items", "name",
                                             "low_precision"))
def _forward(p, ids, positions, cfg_items, name, low_precision):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    pr = Prec(low_precision)
    table = p[f"{name}_emb"]
    x = pr.w(table)[ids] if pr.low else jnp.asarray(table)[ids].astype(F32)
    eps = cfg["rms_eps"]
    states, decays = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i, group=None):
            return p[f"{name}_l{i}_{group}.{tag}"]
        y = pr.a(rms_norm(pr.a(x), p[f"{name}_l{i}_ln1_scale"], eps, pr))
        if kind == "gqa":
            # Granite's layer (grouped KV heads, no positions, no gate) at
            # ONE KV head and the scale 128^-0.5
            y = gqa_layer(functools.partial(g, group="attn"), y,
                          {**cfg, "attn_scale": cfg["head_dim"] ** -0.5},
                          pr)
        else:
            y, h, log_decay = s6_layer(functools.partial(g, group="s6"), y,
                                       cfg, pr)
            states.append(h)
            decays.append(log_decay)
        x = pr.a(x + pr.a(y))
        y = pr.a(rms_norm(x, p[f"{name}_l{i}_ln2_scale"], eps, pr))
        x = pr.a(x + pr.a(ffn(functools.partial(g, group="ffn"), y, pr)))
    hid = rms_norm(x[positions], p[f"{name}_lnf_scale"], eps, pr)
    return tied_head(pr.a(hid), table, pr), states, decays


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            low_precision=False):
    """The full causal forward over ONE sequence ``ids`` [L]: (logits
    [n, V] at ``positions`` [n], [the state [C, N] of each Mamba layer
    after the last position], [each Mamba layer's mean log-decay per
    state index [N]])."""
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        low_precision)


def compare(p: dict, prompt, tokens, served_logits, served_states,
            cfg: dict, name: str = "lm", low_precision=False,
            state_layers=None):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens. ``served_logits`` [m, V]: what
    the served path computed when it chose the LAST ``m`` of ``tokens``;
    ``served_states``: the slot's state [C, N] of the Mamba layers
    ``state_layers`` (indices among the Mamba layers; all of them when
    None) after the request. Returns per-position relative logit errors
    ``|l_sys - l_ref| / |l_ref - mean(l_ref)|`` (2-norms over the
    vocabulary) [m], the relative error of the state per (layer, state
    index) — 2-norms over the channels — [layers, N], how far below the
    reference's best logit each served token lies, in standard deviations
    of its position's logits [n], and which (layer, state index) pairs
    are the layer's slowest-forgetting quarter [layers, N] bool."""
    n = len(tokens)
    ids = np.concatenate([np.asarray(prompt), np.asarray(tokens[:n - 1])])
    positions = len(prompt) - 1 + np.arange(n)
    ref, states, decays = forward(p, ids, positions, cfg, name,
                                  low_precision=low_precision)
    if state_layers is not None:
        states = [states[j] for j in state_layers]
        decays = [decays[j] for j in state_layers]
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
        state_err.append(np.linalg.norm(diff, axis=0)
                         / np.linalg.norm(s_ref, axis=0))
    decays = np.asarray(decays, np.float64)
    slow = decays >= np.quantile(decays, 0.75, axis=1, keepdims=True)
    rows = np.arange(n)
    margin = (ref.max(-1) - ref[rows, np.asarray(tokens)]) / ref.std(-1)
    return logit_err, np.asarray(state_err), margin, slow
