"""Mamba-2's state-space dual (SSD, arXiv:2405.21060) as the slot server
serves it: a mixer whose per-request memory is a FIXED-SIZE state, as
``ops/kda.py``'s is, with other equations — a SCALAR decay per head,
``B`` and ``C`` shared by the heads of a group, a state that is not
square, a gated norm over the whole inner width, and a chunked scan as
the prefill (docs/serving.md "Recurrent state").

Per head ``h`` (H heads of P channels, G groups, state size N), with u
the layer's normed input and ``d_inner = H * P``:

    [z | xBC | dt] = W_in u               (d_inner | d_inner + 2 G N | H)
    xBC_t = SiLU(sum_j conv_w[j] * xBC_{t-K+1+j} + conv_b)   (causal, K taps)
    x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t)
    dt_t = softplus(dt_t + dt_bias)                  a_t = exp(-exp(A_log) dt_t)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t             (S [P, N] a head)
    y_t = S_t C_t + D x_t
    out = W_out (RMSNorm_{d_inner}(y * SiLU(z)) * w_norm)

State: ``S`` [n_slots, N, H * P] float32 — the state index on the
sublanes and (head, channel) on the lanes, so a head of 64 channels
wastes no half lane tile and the decode step's update has no head
structure left: with ``c = h * P + p``, ``S[n, c] <- a[c] S[n, c] +
B[n] u[c]`` and ``y[c] = sum_n S[n, c] C[n]`` are elementwise with row
and column vectors and a reduction over sublanes — and the conv window's
last K-1 pre-conv rows [n_slots, K-1, d_inner + 2 G N] in the activation
dtype, both persistable and donated (updated in place).

- ``ssd_prefill`` runs the recurrence over ONE request's prompt
  CHUNKWISE (``chunk`` rows at a time): inside a chunk the quadratic
  form ``Y = (L o (C B^T)) (dt x)``, ``L_ij = prod_{j<k<=i} a_k``;
  across chunks the state is carried, ``S_end = a_(chunk) S_start +
  sum_j (prod_{k>j} a_k) dt_j x_j (x) B_j``. The loop runs over the
  chunks that hold a TRUE token (``ceil(seq_len / chunk)`` of them: a
  padded bucket's empty chunks cost nothing) and rows at and past
  ``seq_len`` inside the last one have ``dt = 0`` and ``x = 0``: they
  change neither the state nor the conv window. The result lands in
  slot ``Slot`` of both state variables (a slot >= n_slots drops: the
  warm-up's dispatch writes nothing).
- ``ssd_decode`` advances every slot by one token; slots with ``Active``
  == 0 keep their state bit for bit.

Precision: the projections multiply in the storage dtype with float32
accumulation; conv, softplus, decay, the gated norm and the whole
recurrence are float32 — the scan's products too (matmul precision
HIGHEST: the state a prefill leaves is the state the decode steps'
float32 VPU update would have left, to float32 rounding).

``ssd_decode``'s update (:func:`state_step`) is plain XLA, and in that
layout XLA makes ONE fusion a layer of it — the new state and ``y`` as
two results of one pass that reads the state once and writes it once,
into the donated buffer it came from. A Pallas kernel of the same
update was written and measured beside it (PERF.md section 6, PR 42:
1.71-1.74 ms a layer at 128 slots against this fusion's 1.70, both 77 %
of the chip's HBM rate) and is not in the tree: it bought nothing, and
an in-place kernel's aliased operand makes XLA copy the whole state in
any executable that does not donate it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.ops.math_ops import dense

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_decode_phase = functools.partial(_device_scopes.phase, "ssd_decode")
_prefill_phase = functools.partial(_device_scopes.phase, "ssd_prefill")

_WEIGHTS = ("WIn", "WOut", "ConvW", "ConvB", "ALog", "DtBias", "D", "Norm")


def _sizes(attrs):
    """(H, P, N, G) of a layer."""
    return (int(attrs["n_head"]), int(attrs["head_dim"]),
            int(attrs["d_state"]), int(attrs.get("n_groups", 1)))


def _project(x, w, sizes):
    """x [T, M] -> z [T, H*P] float32, pre-conv rows xBC [T, H*P + 2*G*N]
    in x's dtype (what the conv window keeps), dt [T, H] float32 (before
    the bias and the softplus)."""
    h, p, n, g = sizes
    inner, wide = h * p, h * p + 2 * g * n
    zxd = dense(x, w["WIn"])                     # one product, float32
    return (zxd[:, :inner], zxd[:, inner:inner + wide].astype(x.dtype),
            zxd[:, inner + wide:])


def _decay(dt_raw, w):
    """dt [T, H] = softplus(. + dt_bias) and log a = -exp(A_log) * dt."""
    dt = jax.nn.softplus(dt_raw + w["DtBias"].astype(F32))
    return dt, -jnp.exp(w["ALog"].astype(F32)) * dt


def _split(c, sizes):
    """Conv output c [T, H*P + 2*G*N] float32 (bias added) -> x [T, H, P],
    B, C [T, G, N] after the SiLU."""
    h, p, n, g = sizes
    c = jax.nn.silu(c)
    return (c[:, :h * p].reshape(-1, h, p),
            c[:, h * p:h * p + g * n].reshape(-1, g, n),
            c[:, h * p + g * n:].reshape(-1, g, n))


def _output(y, z, w, eps, dt):
    """y, z [T, H*P] float32 -> [T, M]: the gate BEFORE the norm, one
    RMSNorm over the whole inner width, the output projection."""
    y = y * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return dense((y * w["Norm"].astype(F32)).astype(dt), w["WOut"], dt)


def state_step(state, a, u, b, c):
    """One step of the recurrence on state [B, N, H*P] (the layout the
    variables have) with a (decay) and u = dt * x [B, H, P], b, c
    [B, G, N]: (new state, y [B, H, P]). One fusion on the chip: the
    state is read once and written once, in place where it is donated."""
    n_slots, n, wide = state.shape
    h, g = a.shape[1], b.shape[1]
    s = state.reshape(n_slots, n, g, h // g, wide // h)       # [B,N,G,Hg,P]
    per = lambda v: v.reshape(n_slots, 1, g, h // g, -1)     # noqa: E731
    bc = lambda v: jnp.swapaxes(v, 1, 2)[..., None, None]    # noqa: E731
    new = per(a) * s + bc(b) * per(u)
    y = jnp.sum(new * bc(c), axis=1)
    return new.reshape(state.shape), y.reshape(u.shape)


def chunk_scan(x, b, c, dt, log_a, n_chunks, chunk: int):
    """The chunked form of the recurrence from a zero state over rows
    x [T, H, P], b, c [T, G, N], dt, log_a [T, H] (all float32; rows that
    are padding have dt = log_a = 0), ``chunk`` rows a turn of a loop
    over the first ``n_chunks`` chunks (a traced count): (y [T, H, P]
    without the D term — zero past the chunks computed —, the state
    after them [H, P, N])."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    q = int(chunk)
    if t % q:
        raise ValueError(f"a prompt bucket of {t} rows is not a whole "
                         f"number of chunks of {q}")
    hg = h // g
    lower = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def body(i, carry):
        s, y = carry                                  # [G,Hg,P,N], [T,H,P]
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, i * q, q)  # noqa: E731
        heads = lambda v: jnp.moveaxis(                          # noqa: E731
            v.reshape((q, g, hg) + v.shape[2:]), 0, 2)   # rows after heads
        bc, cc = cut(b), cut(c)                                   # [Q,G,N]
        cum = jnp.cumsum(heads(cut(log_a)), axis=-1)              # [G,Hg,Q]
        ux = heads(cut(dt))[..., None] * heads(cut(x))   # dt * x [G,Hg,Q,P]
        # inside the chunk: (L o C B^T) (dt x), L_ij = exp(cum_i - cum_j)
        cb = jnp.einsum("ign,jgn->gij", cc, bc, precision=_HIGHEST)
        ell = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                                -jnp.inf))                    # [G,Hg,Q,Q]
        y_in = jnp.einsum("ghij,ghjp->ghip", ell * cb[:, None], ux,
                          precision=_HIGHEST)
        # what the state at the chunk's start still gives each row
        y_out = jnp.exp(cum)[..., None] * jnp.einsum(
            "ign,ghpn->ghip", cc, s, precision=_HIGHEST)
        # the state at the chunk's end
        last = cum[..., -1:]                                      # [G,Hg,1]
        s = jnp.exp(last)[..., None] * s + jnp.einsum(
            "ghjp,jgn->ghpn", jnp.exp(last - cum)[..., None] * ux, bc,
            precision=_HIGHEST)
        rows = jnp.moveaxis(y_in + y_out, 2, 0).reshape(q, h, p)
        return s, jax.lax.dynamic_update_slice_in_dim(y, rows, i * q, axis=0)

    s, y = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros((g, hg, p, n), F32), jnp.zeros((t, h, p), F32)))
    return y, s.reshape(h, p, n)


def _weights(ins):
    return {n: first(ins, n) for n in _WEIGHTS}


@register_op("ssd_prefill", no_grad=True,
             slot_state=("ssd", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: a Mamba-2 (SSD, arXiv:2405.21060) "
                 "mixer over one request's prompt, the recurrence "
                 "chunkwise over the chunks its true length fills, "
                 "writing the slot's state and conv window (ops/ssd.py)")
def _ssd_prefill(ctx, ins, attrs):
    """X [1,T,M], the layer's weights, State [n_slots,N,H*P] float32,
    Conv [n_slots,K-1,H*P+2*G*N], SeqLen [1,1] int, Slot [1,1] int (>=
    n_slots: nothing is written) -> Out [1,T,M], StateOut, ConvOut.
    attrs: n_head, head_dim, d_state, n_groups, chunk, epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    sizes = _sizes(attrs)
    h, p, _, _ = sizes
    eps = float(attrs.get("epsilon", 1e-5))
    if x.shape[0] != 1:
        raise ValueError("ssd_prefill takes one request (batch 1)")
    t, dt_ = x.shape[1], x.dtype
    chunk = min(int(attrs["chunk"]), t)
    taps = w["ConvW"].shape[0]
    n = jnp.asarray(first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    slot = jnp.asarray(first(ins, "Slot")).reshape((1,)).astype(jnp.int32)
    real = jnp.arange(t)[:, None] < n

    z, u, dt_raw = _project(x[0], w, sizes)
    with _prefill_phase("conv"):
        # rows at and past the true length are padding: they must reach
        # neither the conv window that is kept nor the recurrence
        u = jnp.where(real, u, 0)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
        cw = w["ConvW"].astype(F32)
        conv_out = sum(cw[j] * padded[j:j + t].astype(F32)
                       for j in range(taps)) + w["ConvB"].astype(F32)
        xs, b, c = _split(conv_out, sizes)
        xs = jnp.where(real[:, :, None], xs, 0.0)
        window = jax.lax.dynamic_slice(padded, (n, 0),
                                       (taps - 1, padded.shape[1]))
    with _prefill_phase("scan"):
        dt, log_a = _decay(dt_raw, w)
        dt, log_a = jnp.where(real, dt, 0.0), jnp.where(real, log_a, 0.0)
        y, s = chunk_scan(xs, b, c, dt, log_a, (n + chunk - 1) // chunk,
                          chunk)
        y = y + w["D"].astype(F32)[None, :, None] * xs
        # [H, P, N] -> the variables' [N, H*P]
        s = s.reshape(h * p, -1).T
    out = _output(y.reshape(t, h * p), z, w, eps, dt_)
    return {"Out": [out[None]],
            "StateOut": [state.at[slot].set(s[None], mode="drop")],
            "ConvOut": [conv.at[slot].set(window[None].astype(conv.dtype),
                                          mode="drop")]}


@register_op("ssd_decode", no_grad=True,
             slot_state=("ssd", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: one Mamba-2 (SSD) step for every "
                 "decode slot, the state and the conv window updated in "
                 "place, inactive slots untouched (ops/ssd.py)")
def _ssd_decode(ctx, ins, attrs):
    """X [B,1,M] (B = n_slots), the layer's weights, State [B,N,H*P]
    float32, Conv [B,K-1,H*P+2*G*N], Active [B,1] int -> Out [B,1,M],
    StateOut, ConvOut. attrs: n_head, head_dim, d_state, n_groups,
    epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    sizes = _sizes(attrs)
    h, p, _, _ = sizes
    eps = float(attrs.get("epsilon", 1e-5))
    b_, dt_ = x.shape[0], x.dtype
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0

    z, u, dt_raw = _project(x[:, 0], w, sizes)
    with _decode_phase("conv"):
        window = jnp.concatenate([conv, u[:, None].astype(conv.dtype)],
                                 axis=1)
        conv_out = jnp.sum(w["ConvW"].astype(F32)[None] * window.astype(F32),
                           axis=1) + w["ConvB"].astype(F32)
        xs, b, c = _split(conv_out, sizes)
    dt, log_a = _decay(dt_raw, w)
    a = jnp.broadcast_to(jnp.exp(log_a)[:, :, None], xs.shape)
    with _decode_phase("state"):
        new, y = state_step(state, a, dt[:, :, None] * xs, b, c)
        state_out = jnp.where(active[:, None, None], new, state)
    y = y + w["D"].astype(F32)[None, :, None] * xs
    out = _output(y.reshape(b_, h * p), z, w, eps, dt_)
    with _decode_phase("conv"):
        conv_new = jnp.where(active[:, None, None], window[:, 1:], conv)
    return {"Out": [out[:, None]], "StateOut": [state_out],
            "ConvOut": [conv_new]}
