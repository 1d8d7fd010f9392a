"""Kimi Delta Attention (KDA, arXiv:2510.26692): a gated delta-rule
linear-attention layer whose per-request memory is a FIXED-SIZE
recurrent state instead of a cache that grows with the sequence — the
slot server's second kind of per-slot state, beside the KV pages
(docs/serving.md "Recurrent state").

Per head (H heads of D channels), with x the layer's normed input:

    u_t = [Wq x_t ; Wk x_t ; Wv x_t]                      (3 * H * D)
    c_t = SiLU(sum_j conv_w[j] * u_{t-K+1+j})             (causal, K taps)
    q_t = l2norm(c_t^q) / sqrt(D),  k_t = l2norm(c_t^k),  v_t = c_t^v
    g_t = -exp(A_log_h) * softplus(Wa_up Wa_down x_t + dt_bias)   (R^D)
    beta_t = 2 * sigmoid(w_beta_h . x_t)       (negative eigenvalues)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y_t = Wo (RMSNorm_head(o_t) * sigmoid(Wg_up Wg_down x_t))

State: ``S`` [n_slots, H, D, D] float32 and the conv window's last K-1
pre-conv rows [n_slots, K-1, 3*H*D] in the activation dtype, both
persistable and donated (updated in place).

- ``kda_prefill`` runs the exact recurrence over ONE request's true
  prompt length as a sequential loop (``lax.fori_loop`` to ``seq_len``:
  padded positions never touch the state) and writes the result into
  slot ``Slot`` of both state variables (a slot >= n_slots drops: the
  warm-up's dispatch writes nothing).
- ``kda_decode`` advances every slot by one token in one fused update;
  slots with ``Active`` == 0 keep their state bit for bit.

Precision: projections multiply in the storage dtype with float32
accumulation; conv, norms, decay, beta, gates and the whole recurrence
are float32. The state update is multiply-and-reduce on the VPU, not
D x D matrix products: a [2, D] x [D, D] product per (slot, head) at
precision HIGHEST would cost more MXU passes than the state costs HBM
time.

``kda_decode``'s update has two tiers of one algorithm, chosen at
lowering by what the code can observe (``state_tier``;
``paddle_kda_decode_lowered_total{path}`` counts which):

- ``kernel``: ``ops/pallas/kda_state.py`` — a block of heads of one slot
  in VMEM, ONE read and one write of the state a step, in place. On the
  chip, off a mesh, for a float32 state whose tile the kernel is
  written for (``kda_state.supported``).
- ``refer``: ``_delta_step`` as XLA fuses it — a reduction over the old
  state, then an elementwise pass that reads it again: two reads and
  one write. Everywhere else, and always ``kda_prefill``'s loop body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.ops import pallas as _plk
from paddle_tpu.ops.math_ops import dense
from paddle_tpu.ops.pallas import kda_state as _ks

F32 = jnp.float32
L2_EPS = 1e-6
_phase = functools.partial(_device_scopes.phase, "kda_decode")

# exporter-catalog family (docs/observability.md). Counts LOWERINGS, as
# ``paddle_kv_gather_lowered_total`` does: one increment per delta-rule
# layer (``kda_decode`` here, ``gdn_decode`` in ops/gdn.py: one
# recurrence, one kernel) each
# time a decode program is traced, labelled with the tier that
# advances its state — ``kernel`` (one read of the state a step) or
# ``refer`` (``_delta_step``: two).
KDA_DECODE_LOWERED = _metrics.counter(
    "paddle_kda_decode_lowered_total",
    "Delta-rule (KDA, GDN) decode layers lowered, by the state update's "
    "tier (kernel|refer)",
    labelnames=("path",))

_WEIGHTS = ("Wq", "Wk", "Wv", "Wo", "ConvW", "ALog", "DtBias", "WaDown",
            "WaUp", "WBeta", "WgDown", "WgUp", "ONorm")


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def _token_terms(x, w, h, d):
    """What every token contributes before the conv and the recurrence:
    pre-conv rows u [N, 3*H*D] (activation dtype), log-decay g [N, H, D],
    beta [N, H] and the output gate [N, H, D] (float32), x being [N, M]."""
    dt = x.dtype
    u = jnp.concatenate([dense(x, w[n], dt) for n in ("Wq", "Wk", "Wv")],
                        axis=-1)
    a = dense(dense(x, w["WaDown"], dt), w["WaUp"])
    g = -jnp.exp(w["ALog"].astype(F32))[None, :, None] * jax.nn.softplus(
        a + w["DtBias"].astype(F32)).reshape(-1, h, d)
    beta = 2.0 * jax.nn.sigmoid(dense(x, w["WBeta"]))
    gate = jax.nn.sigmoid(dense(dense(x, w["WgDown"], dt), w["WgUp"]))
    return u, g, beta, gate.reshape(-1, h, d)


def _qkv(c, h, d):
    """Conv output c [N, 3*H*D] float32 -> q, k, v [N, H, D]."""
    c = jax.nn.silu(c).reshape(-1, 3, h, d)
    return (_l2norm(c[:, 0]) * float(d) ** -0.5, _l2norm(c[:, 1]),
            c[:, 2])


def _delta_step(s, q, k, v, g, beta):
    """One step of the recurrence on s [..., Dk, Dv] (rows: key channel)
    with q, k [..., Dk], v [..., Dv], the log-decay g [..., Dk] (a key
    channel: KDA) or [..., 1] (a head: Gated DeltaNet, ``ops/gdn.py``)
    and beta [...]: (s_new, o [..., Dv]).
    ``(k * alpha)^T s`` and ``(q * alpha)^T s`` come out of ONE
    reduction over the old state, the new state out of one elementwise
    pass, and o = q^T s_new follows without reading it again:
    q^T s_new = (q*alpha)^T s + (q . beta k) (v - u)."""
    alpha = jnp.exp(g)
    bk = beta[..., None] * k
    both = jnp.stack([k * alpha, q * alpha], axis=-2)       # [..., 2, D]
    red = jnp.sum(both[..., :, :, None] * s[..., None, :, :], axis=-2)
    dv = v - red[..., 0, :]                                  # v - u
    s_new = alpha[..., :, None] * s + bk[..., :, None] * dv[..., None, :]
    o = red[..., 1, :] + jnp.sum(q * bk, axis=-1, keepdims=True) * dv
    return s_new, o


def state_tier(state, mesh=None) -> str:
    """``kernel`` where the Pallas update runs — on a TPU, off a mesh,
    a float32 state [.., H, Dk, Dv] whose tile the kernel is written
    for (``kda_state.supported``) — or is forced onto the interpreter
    (``pallas.forced_interpret``, the tests' way in); ``refer``
    otherwise."""
    if not _ks.supported(*state.shape[2:], state.dtype):
        return "refer"
    if _plk.kernel_enabled(mesh=mesh) or _plk.forced_interpret():
        return "kernel"
    return "refer"


def _output(o, gate, w, eps, dt):
    """o, gate [N, H, D] float32 -> y [N, M]: per-head RMSNorm, the
    sigmoid gate, the output projection."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * w["ONorm"].astype(F32) * gate
    n = o.shape[0]
    return dense(o.reshape(n, -1).astype(dt), w["Wo"], dt)


def _weights(ins):
    return {n: first(ins, n) for n in _WEIGHTS}


@register_op("kda_prefill", no_grad=True,
             slot_state=("kda", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: Kimi Delta Attention "
                 "(arXiv:2510.26692) over one request's prompt, the "
                 "exact recurrence to its true length, writing the "
                 "slot's recurrent and conv state (ops/kda.py)")
def _kda_prefill(ctx, ins, attrs):
    """X [1,T,M], the layer's weights, State [n_slots,H,D,D] float32,
    Conv [n_slots,K-1,3*H*D], SeqLen [1,1] int, Slot [1,1] int (>=
    n_slots: nothing is written) -> Out [1,T,M], StateOut, ConvOut.
    attrs: n_head, head_dim, epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    h, d = int(attrs["n_head"]), int(attrs["head_dim"])
    eps = float(attrs.get("epsilon", 1e-5))
    if x.shape[0] != 1:
        raise ValueError("kda_prefill takes one request (batch 1)")
    t, dt = x.shape[1], x.dtype
    taps = w["ConvW"].shape[0]
    n = jnp.asarray(first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    slot = jnp.asarray(first(ins, "Slot")).reshape((1,)).astype(jnp.int32)

    u, g, beta, gate = _token_terms(x[0], w, h, d)
    # rows at and past the true length are padding: they must reach
    # neither the conv window that is kept nor the recurrence
    u = jnp.where(jnp.arange(t)[:, None] < n, u, 0)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
    cw = w["ConvW"].astype(F32)
    c = sum(cw[j] * padded[j:j + t].astype(F32) for j in range(taps))
    q, k, v = _qkv(c, h, d)

    def body(i, carry):
        s, out = carry
        s, o = _delta_step(s, q[i], k[i], v[i], g[i], beta[i])
        return s, jax.lax.dynamic_update_slice(out, o[None], (i, 0, 0))

    s, o = jax.lax.fori_loop(
        0, n, body, (jnp.zeros(state.shape[1:], F32),
                     jnp.zeros((t, h, d), F32)))
    window = jax.lax.dynamic_slice(padded, (n, 0),
                                   (taps - 1, padded.shape[1]))
    y = _output(o, gate, w, eps, dt)
    return {"Out": [y[None]],
            "StateOut": [state.at[slot].set(s[None], mode="drop")],
            "ConvOut": [conv.at[slot].set(window[None].astype(conv.dtype),
                                          mode="drop")]}


@register_op("kda_decode", no_grad=True,
             slot_state=("kda", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: one Kimi Delta Attention step "
                 "for every decode slot, the recurrent and conv state "
                 "updated in place, inactive slots untouched "
                 "(ops/kda.py)")
def _kda_decode(ctx, ins, attrs):
    """X [B,1,M] (B = n_slots), the layer's weights, State [B,H,D,D]
    float32, Conv [B,K-1,3*H*D], Active [B,1] int -> Out [B,1,M],
    StateOut, ConvOut. attrs: n_head, head_dim, epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    h, d = int(attrs["n_head"]), int(attrs["head_dim"])
    eps = float(attrs.get("epsilon", 1e-5))
    b, dt = x.shape[0], x.dtype
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0

    u, g, beta, gate = _token_terms(x[:, 0], w, h, d)
    with _phase("conv"):
        window = jnp.concatenate([conv, u[:, None].astype(conv.dtype)],
                                 axis=1)
        c = jnp.sum(w["ConvW"].astype(F32)[None] * window.astype(F32),
                    axis=1)
        q, k, v = _qkv(c, h, d)
    beta = beta.reshape(b, h)
    tier = state_tier(state, ctx.mesh)
    KDA_DECODE_LOWERED.labels(path=tier).inc()
    with _phase("state"):
        if tier == "kernel":
            state_out, o = _ks.kda_state_update(
                state, q, k, v, g, beta, active,
                interpret=_plk.interpret_mode())
        else:
            s_new, o = _delta_step(state, q, k, v, g, beta)
            state_out = jnp.where(active[:, None, None, None], s_new,
                                  state)
    y = _output(o, gate, w, eps, dt)
    with _phase("conv"):
        conv_out = jnp.where(active[:, None, None], window[:, 1:], conv)
    return {"Out": [y[:, None]], "StateOut": [state_out],
            "ConvOut": [conv_out]}
