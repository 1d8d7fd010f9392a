"""Plain reference of one expert-parallel member's share of
Granite-4.0-H-Small (ibm-granite/granite-4.0-h-small ``config.json``,
``model_type`` ``granitemoehybrid``), as
``paddle_tpu/models/transformer.py:decoder_lm(..., layer_kinds=...)``
serves it: jax.numpy, float32, matmul precision "highest", ONE sequence
at a time, one full causal forward with no cache, no pages, no batching,
NO CHUNKS — the recurrence of the state-space layers is a loop over
positions, so that it is independent of the chunked scan under test.

It is fed the served model's own weights (bfloat16 on the chip) and
upcasts them one matrix, and one expert, at a time, so that it fits
beside the server in the chip's memory.

The block (40 layers of hidden 4096 in the source; RMSNorm eps 1e-5):
``x0 = embedding_multiplier * E[ids]``; per layer, with r =
``residual_multiplier``, ``x <- x + r * Mixer(RMSNorm(x))`` then
``x <- x + r * (Routed(u) + Shared(u))``, ``u = RMSNorm(x)``; logits
``= RMSNorm(x_L) E^T / logits_scaling`` (``tie_word_embeddings``).

- ``layer_types[i] == "mamba"`` (Mamba-2, arXiv:2405.21060; 128 heads of
  64 channels, one group, state 128, conv 4 taps with bias):
  ``[z | xBC | dt] = W_in u``; ``xBC <- SiLU(conv(xBC) + b)``; ``x, B, C
  = split(xBC)``; ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log)
  dt)``; per head ``S <- a S + dt x (x) B``, ``y = S C + D x``; ``out =
  W_out (RMSNorm_8192(y * SiLU(z)) * w_norm)`` (the gate BEFORE the norm);
- ``"attention"`` (layer 5 of each 10): 32 query and 8 KV heads of 128,
  causal, no positions (``position_embedding_type`` "nope"), scores
  scaled by ``attention_multiplier`` (0.0078125, not 128 ** -0.5), no
  gate, no QK-norm, no bias;
- every layer: router logits over 72 experts, the 10 largest, gates a
  softmax over those 10 logits (``GraniteMoeTopKGating``), an expert
  ``W_down (SiLU(W_gate u) * W_up u)`` of width 768; one shared expert
  of the same form, width 1536, for every token.

Departures and cuts, each also in the configuration's file:

- ``held``: the router scores all 72 experts; only the experts
  ``[held[0], held[0] + held[1])`` are computed — what the other three
  members of the four-way expert-parallel group would add is left out,
  here as in the program, and that partial sum goes on to the next layer;
- the vocabulary is this member's quarter (25 088 rows), one tied table;
- 10 of 40 layers: one whole period (ssd x5, gqa, ssd x4).

Not in the source's config (``assumed`` in the configuration): the order
``z | xBC | dt`` inside ``W_in`` (random weights do not see it), ``D`` =
1, ``dt`` unclamped, the state float32.

``low_precision=True`` is NOT the reference: the same forward with every
precision the configuration states replaced by the nearest one below it
(bfloat16 -> float8_e4m3 for weights, KV rows, the conv window and the
activations that cross a layer's boundary; float32 -> bfloat16 for the
state after every step, decay, softmax, norms' results, router logits).
``low_precision="state"`` rounds ONLY the state to bfloat16, after every
step (chipbench/reference/solar_open2_250b_ep8_d4.py has the same two
controls, for the same reason).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.solar_open2_250b_ep8_d4 import (  # noqa: F401
    F32, REF, Prec, bf16_share, ffn, layer_kinds, rms_norm)

_GQA = ("wq", "wk", "wv", "wo")
_SSD = ("w_in", "w_out", "conv", "conv_bias", "a_log", "dt_bias", "d",
        "norm")
_MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")


def param_names(cfg: dict, name: str = "lm") -> list:
    out = [f"{name}_emb"]
    for i, kind in enumerate(layer_kinds(cfg)):
        mixer = [f"attn.{t}" for t in _GQA] if kind == "gqa" \
            else [f"ssd.{t}" for t in _SSD]
        out += [f"{name}_l{i}_{p}" for p in
                ["ln1_scale", "ln2_scale"] + mixer
                + [f"moe.{t}" for t in _MOE]]
    return out + [f"{name}_lnf_scale"]


def gqa_layer(g, x, cfg, pr=REF):
    """x [L, M] -> [L, M]: causal softmax attention, grouped KV heads,
    no positions, scores scaled by ``attn_scale``, no gate."""
    length = x.shape[0]
    h, n_kv, d = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    q = (x @ pr.w(g("wq"))).reshape(length, n_kv, h // n_kv, d)
    k = pr.a(x @ pr.w(g("wk"))).reshape(length, n_kv, d)      # a KV row
    v = pr.a(x @ pr.w(g("wv"))).reshape(length, n_kv, d)
    s = jnp.einsum("tkgd,skd->kgts", q, k) * cfg["attn_scale"]
    keep = jnp.tril(jnp.ones((length, length), bool))
    p = pr.f(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1))
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(length, h * d)
    return o @ pr.w(g("wo"))


def ssd_layer(g, x, cfg, pr=REF):
    """x [L, M] -> (y [L, M], the state S [H, P, N] after the last
    position, each head's mean log-decay [H]: how slowly it forgets):
    the recurrence as a loop over positions, one token at a time."""
    length = x.shape[0]
    h, p, n = cfg["ssd_heads"], cfg["ssd_head_dim"], cfg["ssd_d_state"]
    grp, taps = cfg.get("ssd_groups", 1), cfg.get("ssd_conv_taps", 4)
    inner, wide = h * p, h * p + 2 * grp * n
    f32 = lambda t: jnp.asarray(g(t)).astype(F32)             # noqa: E731
    zxd = x @ pr.w(g("w_in"))
    z, dt_raw = zxd[:, :inner], zxd[:, inner + wide:]
    u = pr.a(zxd[:, inner:inner + wide])           # the conv window's rows
    padded = jnp.concatenate([jnp.zeros((taps - 1, wide), F32), u])
    cw = pr.w(g("conv"))
    c = sum(cw[j] * padded[j:j + length] for j in range(taps)) \
        + pr.w(g("conv_bias"))
    c = jax.nn.silu(c)
    xs = c[:, :inner].reshape(length, h, p)
    b = c[:, inner:inner + grp * n].reshape(length, grp, n)
    cc = c[:, inner + grp * n:].reshape(length, grp, n)
    dt = jax.nn.softplus(dt_raw + f32("dt_bias"))              # [L, H]
    log_a = -jnp.exp(f32("a_log")) * dt
    alpha = pr.f(jnp.exp(log_a))
    per_head = lambda v: jnp.repeat(v, h // grp, axis=0)      # noqa: E731

    def step(s, t):
        x_t, b_t, c_t, a_t, dt_t = t
        s = pr.s(pr.f(a_t[:, None, None] * s
                      + (dt_t[:, None] * x_t)[:, :, None]
                      * per_head(b_t)[:, None, :]))
        return s, jnp.einsum("hpn,hn->hp", s, per_head(c_t))

    s, y = jax.lax.scan(step, jnp.zeros((h, p, n), F32),
                        (xs, b, cc, alpha, dt))
    y = (y + f32("d")[None, :, None] * xs).reshape(length, inner)
    y = y * jax.nn.silu(z)                       # the gate BEFORE the norm
    y = pr.f(rms_norm(y, g("norm"), cfg["rms_eps"]))
    return y @ pr.w(g("w_out")), s, jnp.mean(log_a, axis=0)


def topk_gating(logits, top_k: int):
    """``GraniteMoeTopKGating``: (gates [L, E] float32, zero off the
    picks) — the ``top_k`` largest logits, a softmax over those alone."""
    vals, idx = jax.lax.top_k(logits, top_k)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros(logits.shape, F32).at[rows, idx].set(
        jax.nn.softmax(vals, axis=-1))


def expert_layer(g, x, cfg, held, shared: bool = True, pr=REF):
    """x [L, M] -> [L, M]: the part of the routed result that the held
    experts ``held = (first, count)`` give — the expert weights ``g``
    returns hold exactly those, in order — plus the shared expert (once;
    ``shared=False`` leaves it out, for adding shares up). One expert is
    upcast at a time."""
    first, count = held
    combine = topk_gating(pr.f(x @ pr.w(g("router"))),
                          cfg["n_experts_per_tok"])[:, first:first + count]
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
    table = p[f"{name}_emb"]
    x = pr.w(table)[ids] if pr.low else jnp.asarray(table)[ids].astype(F32)
    x = pr.a(x * cfg.get("embed_scale", 1.0))
    r = cfg.get("residual_scale", 1.0)
    states, decays = [], []
    for i, kind in enumerate(layer_kinds(cfg)):
        def g(tag, i=i, kind=kind):
            group = "moe" if tag in _MOE else \
                ("attn" if kind == "gqa" else "ssd")
            return p[f"{name}_l{i}_{group}.{tag}"]
        y = rms_norm(x, p[f"{name}_l{i}_ln1_scale"], cfg["rms_eps"], pr)
        if kind == "gqa":
            y = gqa_layer(g, pr.a(y), cfg, pr)
        else:
            y, s, log_decay = ssd_layer(g, pr.a(y), cfg, pr)
            states.append(s)
            decays.append(log_decay)
        x = pr.a(x + r * pr.a(y))
        y = rms_norm(x, p[f"{name}_l{i}_ln2_scale"], cfg["rms_eps"], pr)
        x = pr.a(x + r * pr.a(expert_layer(g, pr.a(y), cfg, held, pr=pr)))
    hid = rms_norm(x[positions], p[f"{name}_lnf_scale"], cfg["rms_eps"],
                   pr)
    # the tied table, read again; logits / logits_scaling
    return pr.a(hid) @ pr.w(table).T / cfg.get("logits_scale", 1.0), \
        states, decays


def forward(p: dict, ids, positions, cfg: dict, name: str = "lm",
            held=None, low_precision=False):
    """The full causal forward over ONE sequence ``ids`` [L]: (logits
    [n, V] at ``positions`` [n], [the state [H, P, N] of each SSD layer
    after the last position], [each SSD layer's mean log-decay per head
    [H]])."""
    held = tuple(held) if held is not None \
        else (cfg.get("held_start", 0), cfg["n_experts_held"])
    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items() if k != "prompt_buckets"))
    with jax.default_matmul_precision("highest"):
        return _forward(p, jnp.asarray(ids, jnp.int32),
                        jnp.asarray(positions, jnp.int32), items, name,
                        held, low_precision)


def served_state(var, cfg: dict):
    """A slot's row [N, H*P] of an ``<model>_ssd_state_<i>`` variable
    (the layout the program keeps: ops/ssd.py) -> [H, P, N], as
    :func:`forward` returns a layer's state."""
    h, p = cfg["ssd_heads"], cfg["ssd_head_dim"]
    return np.asarray(var).T.reshape(h, p, -1)


def compare(p: dict, prompt, tokens, served_logits, served_states,
            cfg: dict, name: str = "lm", low_precision=False):
    """One served request against the reference's full forward,
    teacher-forced on the served tokens. ``served_logits`` [m, V]: what
    the served path computed when it chose the LAST ``m`` of ``tokens``;
    ``served_states``: the slot's state per SSD layer after the request,
    [H, P, N] each (:func:`served_state`). Returns per-position relative
    logit errors ``|l_sys - l_ref| / |l_ref - mean(l_ref)|`` (2-norms
    over the vocabulary) [m], the per-(layer, head) relative errors of
    the state [layers, H], how far below the reference's best logit each
    served token lies, in standard deviations of its position's logits
    [n], and which (layer, head) pairs are the layer's slowest-forgetting
    quarter by mean log-decay over this sequence [layers, H] bool."""
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
