"""Plain reference of one expert-parallel member's share of
Solar-Open2-250B (upstage/Solar-Open2-250B ``config.json``), as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no batching;
the recurrence of the linear-attention layers is a loop over positions.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix, and one expert, at a time, so that it fits
beside the server in the chip's memory.

The block (48 layers of hidden 4096 in the source; pre-norm residual,
RMSNorm eps 1e-5, untied head, no positions anywhere: ``use_rope`` false):

- layers 0, 4, 8, ...: softmax attention, 64 query heads and 8 KV heads
  of 128, causal, ``y = Wo (o * sigmoid(Wg x))`` (``use_gqa_gate``);
- the three layers after each: Kimi Delta Attention (arXiv:2510.26692),
  64 heads of 128, causal depthwise conv of 4 taps + SiLU on q, k and v,
  ``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T``,
  ``b_t = 2 sigmoid(.)`` (``kda_allow_neg_eigval``), ``o_t = S_t^T q_t``,
  ``y = Wo (RMSNorm_head(o) * sigmoid(Wg_up Wg_down x))``;
- every layer: a router over 320 experts, the 8 best per token, weights
  renormalised over the 8, one shared expert for every token; an expert
  is ``W_down (SiLU(W_gate x) * W_up x)`` of width 1280.

Departures and cuts, each also in the configuration's file:

- ``held``: the router scores all 320 experts; only the experts
  ``[held[0], held[0] + held[1])`` are computed — what the other seven
  members of the eight-way expert-parallel group would add is left out,
  here as in the program, and that partial sum goes on to the next layer;
- the vocabulary is this member's eighth (24 576 rows);
- 4 of 48 layers: one whole period (softmax, KDA, KDA, KDA).

Sizes the source does not give (``assumed`` in the configuration): the
softmax gate is elementwise over all 64 x 128 channels; the two low-rank
KDA projections (decay, output gate) have rank 128 = head_dim; the decay
is ``g = -exp(A_log_h) * softplus(Wa_up Wa_down x + dt_bias)`` per
channel, as Mamba-2 and Gated DeltaNet parametrise theirs; the router
scores with a sigmoid and has no correction bias; q is l2-normalised and
scaled by 1/sqrt(128), k l2-normalised; the recurrent state is float32.

``low_precision=True`` is NOT the reference: it is the same forward with
every precision the configuration states replaced by the nearest one
below it — what it states as bfloat16 (weights, KV rows, the conv
window, the activations that cross a layer's boundary) rounded to
float8_e4m3, what it states as float32 (the recurrent state after every
step, decay, softmax, norms' results, router scores) rounded to
bfloat16 — to show that the limits of the comparison that decides
``correct`` lie between what the served path reads and what a path one
precision down would read (PERF.md, PR 31). ``low_precision="state"``
rounds ONLY the recurrent state to bfloat16, after every step, and
leaves everything else exact: the one stated precision whose loss the
other readings drown, shown by the heads that forget slowest (a
rounding of every step adds up over the hundreds of steps such a head
remembers; ``compare`` returns which heads those are).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
L2_EPS = 1e-6

_GQA = ("wq", "wk", "wv", "wo", "wg")
_KDA = ("wq", "wk", "wv", "wo", "conv", "a_log", "dt_bias", "wa_down",
        "wa_up", "wbeta", "wg_down", "wg_up", "onorm")
_MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")


def layer_kinds(cfg: dict) -> list:
    period = cfg["layer_kinds"]
    return [period[i % len(period)] for i in range(cfg["n_layer"])]


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        mixer = [f"attn.{t}" for t in _GQA] if kind == "gqa" \
            else [f"kda.{t}" for t in _KDA]
        out += [f"{name}_l{i}_{p}" for p in
                ["ln1_scale", "ln2_scale"] + mixer
                + [f"moe.{t}" for t in _MOE]]
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
    # of converts to bfloat16 and back (it allows itself the excess
    # precision), and the rounding with it

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


def gqa_layer(g, x, cfg, pr=REF):
    """x [L, M] -> [L, M]: causal softmax attention, grouped KV heads,
    no positions, the context gated before the output projection."""
    length = x.shape[0]
    h, n_kv, d = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    q = (x @ pr.w(g("wq"))).reshape(length, n_kv, h // n_kv, d)
    k = pr.a(x @ pr.w(g("wk"))).reshape(length, n_kv, d)      # a KV row
    v = pr.a(x @ pr.w(g("wv"))).reshape(length, n_kv, d)
    s = jnp.einsum("tkgd,skd->kgts", q, k) * d ** -0.5
    keep = jnp.tril(jnp.ones((length, length), bool))
    p = pr.f(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1))
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(length, h * d)
    if cfg.get("gqa_gate", True):
        o = o * jax.nn.sigmoid(x @ pr.w(g("wg")))
    return o @ pr.w(g("wo"))


def kda_layer(g, x, cfg, pr=REF):
    """x [L, M] -> (y [L, M], the state S [H, D, D] after the last
    position, each head's mean log-decay [H]: how slowly it forgets):
    the recurrence as a loop over positions."""
    length = x.shape[0]
    h, d, taps = cfg["kda_heads"], cfg["kda_head_dim"], \
        cfg["kda_conv_taps"]
    u = pr.a(jnp.concatenate(                      # the conv window's rows
        [x @ pr.w(g(t)) for t in ("wq", "wk", "wv")], -1))
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    cw = pr.w(g("conv"))
    c = sum(cw[j] * padded[j:j + length] for j in range(taps))
    c = jax.nn.silu(c).reshape(length, 3, h, d)

    def l2norm(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q, k, v = l2norm(c[:, 0]) * d ** -0.5, l2norm(c[:, 1]), c[:, 2]
    f32 = lambda t: jnp.asarray(g(t)).astype(F32)             # noqa: E731
    decay = jax.nn.softplus(x @ pr.w(g("wa_down")) @ pr.w(g("wa_up"))
                            + f32("dt_bias")).reshape(length, h, d)
    log_alpha = -jnp.exp(f32("a_log"))[None, :, None] * decay
    alpha = pr.f(jnp.exp(log_alpha))
    beta = 2.0 * jax.nn.sigmoid(x @ pr.w(g("wbeta")))          # [L, H]

    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t
        s = a_t[:, :, None] * s                       # Diag(alpha) S
        u_t = jnp.einsum("hk,hkv->hv", k_t, s)        # k^T S
        s = pr.s(pr.f(s + b_t[:, None, None] * k_t[:, :, None]
                      * (v_t - u_t)[:, None, :]))
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    s, o = jax.lax.scan(step, jnp.zeros((h, d, d), F32),
                        (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_eps"])
    o = pr.f(o * f32("onorm"))
    gate = jax.nn.sigmoid(x @ pr.w(g("wg_down")) @ pr.w(g("wg_up")))
    return (o.reshape(length, h * d) * gate) @ pr.w(g("wo")), s, \
        jnp.mean(log_alpha, axis=(0, 2))


def route(g, x, cfg, pr=REF):
    """(combine weights [L, E] float32, zero off the picks)."""
    scores = pr.f(jax.nn.sigmoid(x @ pr.w(g("router"))))
    vals, idx = jax.lax.top_k(scores, cfg["n_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    vals = vals * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros(scores.shape, F32).at[rows, idx].set(vals)


def ffn(x, w_gate, w_up, w_down, pr=REF):
    return (jax.nn.silu(x @ pr.w(w_gate)) * (x @ pr.w(w_up))) @ pr.w(w_down)


def expert_layer(g, x, cfg, held, shared: bool = True, pr=REF):
    """x [L, M] -> [L, M]: the part of the routed result that the held
    experts ``held = (first, count)`` give — the expert weights ``g``
    returns hold exactly those, in order — plus the shared expert (once;
    ``shared=False`` leaves it out, for adding shares up). One expert is
    upcast at a time."""
    first, count = held
    combine = route(g, x, cfg, pr)[:, first:first + count]    # [L, n]
    w_gate, w_up, w_down = (jnp.asarray(g(t))     # storage dtype still
                            for t in ("w_gate", "w_up", "w_down"))

    def one(acc, e):
        y = ffn(x, w_gate[e], w_up[e], w_down[e], pr)
        return acc + combine[:, e, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    if shared:
        y = y + ffn(x, g("s_gate"), g("s_up"), g("s_down"), pr)
    return y


@functools.partial(jax.jit, static_argnames=("cfg_items", "name", "held",
                                             "low_precision"))
def _forward(p, ids, positions, cfg_items, name, held, low_precision):
    cfg = dict(cfg_items)
    cfg["layer_kinds"] = list(cfg["layer_kinds"])
    pr = Prec(low_precision)
    x = pr.w(p[f"{name}_emb"])[ids] if pr.low \
        else jnp.asarray(p[f"{name}_emb"])[ids].astype(F32)
    states, decays = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i, kind=kind):
            group = "moe" if tag in _MOE else \
                ("attn" if kind == "gqa" else "kda")
            return p[f"{name}_l{i}_{group}.{tag}"]
        y = rms_norm(x, p[f"{name}_l{i}_ln1_scale"], cfg["rms_eps"], pr)
        if kind == "gqa":
            y = gqa_layer(g, pr.a(y), cfg, pr)
        else:
            y, s, log_decay = kda_layer(g, pr.a(y), cfg, pr)
            states.append(s)
            decays.append(log_decay)
        x = pr.a(x + pr.a(y))
        y = rms_norm(x, p[f"{name}_l{i}_ln2_scale"], cfg["rms_eps"], pr)
        x = pr.a(x + pr.a(expert_layer(g, pr.a(y), cfg, held, pr=pr)))
    hid = rms_norm(x[positions], p[f"{name}_lnf_scale"], cfg["rms_eps"],
                   pr)
    return pr.a(hid) @ pr.w(p[f"{name}_head_w"]), states, decays


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            held=None, low_precision=False):
    """The full causal forward over ONE sequence ``ids`` [L]: (logits
    [n, V] at ``positions`` [n], [the recurrent state [H, D, D] of each
    KDA layer after the last position], [each KDA layer's mean
    log-decay per head [H]])."""
    held = tuple(held) if held is not None \
        else (cfg.get("held_start", 0), cfg["n_experts_held"])
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        held, low_precision)


def compare(p: dict, prompt, tokens, served_logits, served_states,
            cfg: dict, name: str = "lm", low_precision=False):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens. ``served_logits`` [m, V]: what
    the served path computed when it chose the LAST ``m`` of ``tokens``
    (``m = len(tokens)`` with the prefill's row first, one fewer with
    the decode steps' alone: through pages and state);
    ``served_states``: the slot's recurrent state per KDA layer after
    the request. Returns per-position relative logit errors
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
