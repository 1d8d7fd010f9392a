"""The gated short convolution of the LFM2 family (``Lfm2ShortConv``,
``model_type: "lfm2_moe"``) as the slot server serves it: a mixer whose
per-request memory is the last ``taps - 1`` rows of ONE elementwise
product — the third kind of fixed-size per-slot state, beside
``ops/kda.py``'s and ``ops/ssd.py``'s, and the smallest: no recurrence,
no decay, no square or state-space matrix, only a window (docs/serving.md
"Recurrent state").

With u the layer's normed input [T, M] and K taps:

    [B | C | x] = W_in u                 (M | M | M, in that order)
    z_t = B_t * x_t                      (elementwise)
    c_t = sum_j conv_w[j] * z_{t-K+1+j}  (depthwise, causal, zeros before
                                          the prompt, NO bias, NO activation)
    out = W_out (C * c)

State: ``z``'s last K-1 rows [n_slots, K-1, M] in the activation dtype,
persistable and donated (updated in place).

- ``shortconv_prefill`` runs ONE request's prompt: the projections and
  the conv over the whole bucket, and the window written for slot
  ``Slot`` from the rows at the prompt's TRUE end (``seq_len - K + 1`` to
  ``seq_len - 1``; zeros where the prompt is shorter than that), never
  from the bucket's padded end: rows at and past ``seq_len`` are zeroed
  before they can reach it. A slot >= n_slots drops (the warm-up's
  dispatch writes nothing).
- ``shortconv_decode`` advances every slot by one token: the window
  shifted by one row; slots with ``Active`` == 0 keep theirs bit for bit.

Precision: the projections multiply in the storage dtype with float32
accumulation; ``z`` is rounded to the activation dtype where it is made —
the value the window keeps, so a prefill and the steps after it convolve
the same numbers — and the conv and the gate ``C * c`` are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.ops.math_ops import dense

F32 = jnp.float32
_prefill_phase = functools.partial(_device_scopes.phase, "shortconv_prefill")
_decode_phase = functools.partial(_device_scopes.phase, "shortconv_decode")


def _project(x, w_in):
    """x [T, M] -> (z = B * x [T, M] in x's dtype: what the window
    keeps; the gate C [T, M] float32)."""
    m = x.shape[-1]
    bcx = dense(x, w_in)                         # one product, float32
    return (bcx[:, :m] * bcx[:, 2 * m:]).astype(x.dtype), bcx[:, m:2 * m]


@register_op("shortconv_prefill", no_grad=True,
             slot_state=("shortconv", ("ConvOut",)),
             ref="TPU-native serving op: LFM2's gated short convolution "
                 "over one request's prompt, writing the slot's window "
                 "from the rows at the prompt's true end "
                 "(ops/shortconv.py)")
def _shortconv_prefill(ctx, ins, attrs):
    """X [1,T,M], WIn [M,3M], ConvW [K,M], WOut [M,M], Conv
    [n_slots,K-1,M], SeqLen [1,1] int, Slot [1,1] int (>= n_slots:
    nothing is written) -> Out [1,T,M], ConvOut."""
    x, conv = first(ins, "X"), first(ins, "Conv")
    cw = first(ins, "ConvW")
    if x.shape[0] != 1:
        raise ValueError("shortconv_prefill takes one request (batch 1)")
    t, taps = x.shape[1], cw.shape[0]
    n = jnp.asarray(first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    slot = jnp.asarray(first(ins, "Slot")).reshape((1,)).astype(jnp.int32)
    with _prefill_phase("project"):
        z, gate = _project(x[0], first(ins, "WIn"))
    with _prefill_phase("conv"):
        # rows at and past the true length are padding: they must not
        # reach the window that is kept
        z = jnp.where(jnp.arange(t)[:, None] < n, z, 0)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
        c = sum(cw[j].astype(F32) * padded[j:j + t].astype(F32)
                for j in range(taps))
        window = jax.lax.dynamic_slice(padded, (n, 0),
                                       (taps - 1, padded.shape[1]))
    with _prefill_phase("out"):
        out = dense((gate * c).astype(x.dtype), first(ins, "WOut"), x.dtype)
    return {"Out": [out[None]],
            "ConvOut": [conv.at[slot].set(window[None].astype(conv.dtype),
                                          mode="drop")]}


@register_op("shortconv_decode", no_grad=True,
             slot_state=("shortconv", ("ConvOut",)),
             ref="TPU-native serving op: one step of LFM2's gated short "
                 "convolution for every decode slot, the window shifted "
                 "in place, inactive slots untouched (ops/shortconv.py)")
def _shortconv_decode(ctx, ins, attrs):
    """X [B,1,M] (B = n_slots), WIn [M,3M], ConvW [K,M], WOut [M,M], Conv
    [B,K-1,M], Active [B,1] int -> Out [B,1,M], ConvOut."""
    x, conv = first(ins, "X"), first(ins, "Conv")
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0
    with _decode_phase("project"):
        z, gate = _project(x[:, 0], first(ins, "WIn"))
    with _decode_phase("conv"):
        window = jnp.concatenate([conv, z[:, None].astype(conv.dtype)],
                                 axis=1)
        c = jnp.sum(first(ins, "ConvW").astype(F32)[None]
                    * window.astype(F32), axis=1)
        conv_new = jnp.where(active[:, None, None], window[:, 1:], conv)
    with _decode_phase("out"):
        out = dense((gate * c).astype(x.dtype), first(ins, "WOut"), x.dtype)
    return {"Out": [out[:, None]], "ConvOut": [conv_new]}
