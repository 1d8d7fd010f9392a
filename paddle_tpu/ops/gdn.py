"""Gated DeltaNet (GDN, arXiv:2412.06464) as Olmo-Hybrid's linear
layers hold it: the gated delta rule of ``ops/kda.py`` with ONE decay a
head instead of one a key channel, a state that is not square (keys of
``Dk``, values of ``Dv``), a full-rank SiLU output gate through a gated
RMSNorm, and a prefill that runs CHUNK BY CHUNK — the slot server's
fourth kind of per-slot state (docs/serving.md "Recurrent state").

Per head ``h`` (H heads, keys of Dk, values of Dv), x the layer's input:

    u_t = [Wq x_t ; Wk x_t ; Wv x_t]                  (H*Dk + H*Dk + H*Dv)
    c_t = SiLU(sum_j conv_w[j] * u_{t-K+1+j})         (causal, K taps, no bias)
    q_t = l2norm(c_t^q) / sqrt(Dk),  k_t = l2norm(c_t^k),  v_t = c_t^v
    g_t = -exp(A_log_h) * softplus(wa_h . x_t + dt_bias_h)     (a scalar)
    beta_t = 2 * sigmoid(wb_h . x_t)                  (negative eigenvalues)
    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t
    y_t = Wo concat_h(RMSNorm_Dv(o_t; gain) * SiLU(Wz x_t)_h)

State: ``S`` [n_slots, H, Dk, Dv] float32 and the conv window's last K-1
pre-conv rows [n_slots, K-1, 2*H*Dk + H*Dv] in the activation dtype, both
persistable and donated (updated in place).

ONE recurrence serves this layer and KDA's: the decode step is
``ops/kda.py:_delta_step`` (the decay handed over ``[.., H, 1]``) or its
Pallas kernel (``ops/pallas/kda_state.py``, which takes the rectangular
tile), chosen by ``ops/kda.py:state_tier`` and counted in
``paddle_kda_decode_lowered_total{path}``.

- ``gdn_prefill`` runs the recurrence over ONE request's prompt by
  chunks of ``chunk`` rows (:func:`chunk_scan`, the WY form of section
  3.3 of the paper): with ``gamma`` the running sum of g inside a chunk
  and ``Gamma_ij = exp(gamma_i - gamma_j)``,

      A = tril(diag(beta) (Gamma * K K^T), -1)
      (I + A) [W | U] = diag(beta) [K * exp(gamma) | V]  (no state in them)
      V' = U - W S
      O = (Q * exp(gamma)) S + tril(Gamma * Q K^T) V'
      S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

  Only the last three lines are sequential, a chunk a turn; the first
  two are made for ``BLOCK_CHUNKS`` chunks at once, the unit lower
  triangular system solved in diagonal blocks of ``SOLVE_ROWS`` rows
  (:func:`_unit_lower_solve`: a row a turn inside the blocks, all of
  them at once, then a block a turn). The scan runs over
  the blocks that hold a TRUE token (a padded bucket's empty blocks cost
  nothing) and rows at and past ``seq_len`` have ``beta = g = 0`` and
  ``u = 0``: they change neither the state nor the conv window. No
  exponent is ever positive, so no decay overflows however fast a head
  forgets. The result lands in slot ``Slot`` of both state variables (a
  slot >= n_slots drops: the warm-up's dispatch writes nothing).
- ``gdn_decode`` advances every slot by one token; slots with ``Active``
  == 0 keep their state bit for bit.

Precision: the projections multiply in the storage dtype with float32
accumulation; conv, norms, decay, beta, the gate and the whole recurrence
are float32 — the scan's products too (matmul precision HIGHEST), and
a chunk's triangular system is solved by forward substitution alone, in
float32: the 16 x 16 blocks on its diagonal inverted a row a turn on the
VPU (15 turns over every block of 16 chunks at once, where the whole
64 x 64 inverse took 63), ``[W | U]`` a block a turn by HIGHEST products
against those inverses; neither the chunk's inverse nor a power of ``A``
is formed. The state a prefill leaves is the state the decode steps'
float32 update would have left, to float32 rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.ops import kda as _kda
from paddle_tpu.ops import pallas as _plk
from paddle_tpu.ops.math_ops import dense
from paddle_tpu.ops.pallas import kda_state as _ks

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# every product of the scan: float32 in, six passes of the MXU
_mm = functools.partial(jnp.einsum, precision=_HIGHEST)
_decode_phase = functools.partial(_device_scopes.phase, "gdn_decode")
_prefill_phase = functools.partial(_device_scopes.phase, "gdn_prefill")
# chunks whose state-free part (T, W, U, the chunk's own attention) is
# made at once, and the granule of the scan's loop: a prompt's rows are
# computed up to the next whole block (:func:`scan_rows`)
BLOCK_CHUNKS = 16
# rows of the diagonal blocks a chunk's triangular system is solved in
# (:func:`_unit_lower_solve`): a row a turn inside them, a block a turn
# (products) under them
SOLVE_ROWS = 16

_WEIGHTS = ("Wq", "Wk", "Wv", "Wz", "Wo", "ConvW", "ALog", "DtBias", "Wa",
            "Wb", "ONorm")


def _sizes(attrs):
    """(H, Dk, Dv) of a layer."""
    return (int(attrs["n_head"]), int(attrs["key_dim"]),
            int(attrs["value_dim"]))


def _token_terms(x, w):
    """What every token contributes before the conv and the recurrence:
    pre-conv rows u [N, 2*H*Dk + H*Dv] (activation dtype), the log-decay
    g [N, H] and beta [N, H] (float32), x being [N, M]."""
    dt = x.dtype
    u = jnp.concatenate([dense(x, w[n], dt) for n in ("Wq", "Wk", "Wv")],
                        axis=-1)
    g = -jnp.exp(w["ALog"].astype(F32)) * jax.nn.softplus(
        dense(x, w["Wa"]) + w["DtBias"].astype(F32))
    beta = 2.0 * jax.nn.sigmoid(dense(x, w["Wb"]))
    return u, g, beta


def _qkv(c, sizes):
    """Conv output c [N, 2*H*Dk + H*Dv] float32 -> q, k [N, H, Dk] and
    v [N, H, Dv] after the SiLU."""
    h, dk, dv = sizes
    c = jax.nn.silu(c)
    q = c[:, :h * dk].reshape(-1, h, dk)
    k = c[:, h * dk:2 * h * dk].reshape(-1, h, dk)
    return (_kda._l2norm(q) * float(dk) ** -0.5, _kda._l2norm(k),
            c[:, 2 * h * dk:].reshape(-1, h, dv))


def _output(o, x, w, eps, dt):
    """o [N, H, Dv] float32, the layer's input x [N, M] -> y [N, M]: the
    RMSNorm of each head's Dv values, times the SiLU gate, the output
    projection."""
    n, h, dv = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    gate = jax.nn.silu(dense(x, w["Wz"])).reshape(n, h, dv)
    o = o * w["ONorm"].astype(F32) * gate
    return dense(o.reshape(n, -1).astype(dt), w["Wo"], dt)


def block_rows(bucket: int, chunk: int) -> int:
    """Rows a turn of the scan's outer loop over a prompt bucket of
    ``bucket`` rows: ``BLOCK_CHUNKS`` chunks, or as many as divide the
    bucket's."""
    chunk = min(int(chunk), bucket)
    if bucket % chunk:
        raise ValueError(f"a prompt bucket of {bucket} rows is not a whole "
                         f"number of chunks of {chunk}")
    n = bucket // chunk
    return chunk * next(b for b in range(min(BLOCK_CHUNKS, n), 0, -1)
                        if n % b == 0)


def scan_rows(length: int, bucket: int, chunk: int) -> int:
    """Rows the chunked scan computes for a prompt of ``length`` true
    tokens in a bucket of ``bucket``: whole blocks up to the length (what
    ``paddle_gdn_chunk_rows_total`` counts a layer)."""
    rows = block_rows(bucket, chunk)
    return -(-int(length) // rows) * rows


def solve_rows(chunk: int) -> int:
    """Rows of a diagonal block of a chunk's triangular system:
    ``SOLVE_ROWS``, or as many under it as divide the chunk's."""
    return next(s for s in range(min(SOLVE_ROWS, int(chunk)), 0, -1)
                if chunk % s == 0)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a [..., S, S] strictly lower triangular, by forward
    substitution a row a turn: row_i = e_i - sum_{j<i} a_ij row_j.
    Multiply-and-reduce in float32 (the VPU): no product is rounded, and
    no power of ``a`` is formed — with beta near 2 they grow before they
    vanish."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=F32)

    def row(i, x):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = eye[i] - jnp.sum(a_i[..., :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, axis=-2)

    return jax.lax.fori_loop(
        1, c, row, jnp.broadcast_to(eye, a.shape).astype(F32))


def _unit_lower_solve(a, rhs):
    """(I + a)^-1 rhs for a [..., C, C] strictly lower triangular and rhs
    [..., C, D], in diagonal blocks of S = ``solve_rows(C)`` rows: the
    C / S blocks ``D_i`` on the diagonal are inverted all at once
    (:func:`_unit_lower_inverse`: S - 1 turns whatever C) and the rest
    is forward substitution a BLOCK a turn, by products:
    ``X_i = D_i^-1 (R_i - sum_{j<i} A_ij X_j)``. The inverse of the whole
    is never formed, nor a power of ``a``."""
    c = a.shape[-1]
    s = solve_rows(c)
    cuts = [slice(i, i + s) for i in range(0, c, s)]
    d_inv = _unit_lower_inverse(
        jnp.stack([a[..., rows, rows] for rows in cuts], axis=-3))
    xs = []
    for i, rows in enumerate(cuts):
        r_i = rhs[..., rows, :]
        if i:
            r_i = r_i - _mm("...ij,...jd->...id", a[..., rows, :i * s],
                           jnp.concatenate(xs, axis=-2))
        xs.append(_mm("...ij,...jd->...id", d_inv[..., i, :, :], r_i))
    return jnp.concatenate(xs, axis=-2)


def chunk_scan(q, k, v, g, beta, n_blocks, chunk: int, rows: int):
    """The chunked form of the gated delta rule from a zero state over
    q, k [T, H, Dk], v [T, H, Dv], g, beta [T, H] (all float32; rows that
    are padding have g = beta = 0), ``rows`` rows (whole chunks of
    ``chunk``) a turn of a loop over the first ``n_blocks`` blocks (a
    traced count): (o [T, H, Dv] — zero past the blocks computed —, the
    state after them [H, Dk, Dv])."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    c, nb = int(chunk), int(rows) // int(chunk)
    at_or_below = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    below = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]

    def block(i, carry):
        s, out = carry                              # [H,Dk,Dv], [T,H,Dv]

        def cut(x):
            """rows [i * rows, (i + 1) * rows) of x [T, H, ...] as
            [nb, H, C, ...]: chunks, heads, a chunk's rows."""
            x = jax.lax.dynamic_slice_in_dim(x, i * rows, rows)
            return jnp.moveaxis(x.reshape((nb, c) + x.shape[1:]), 2, 1)
        qb, kb, vb, bb = cut(q), cut(k), cut(v), cut(beta)
        gam = jnp.cumsum(cut(g), axis=-1)                     # [nb,H,C]
        # Gamma_ij = exp(gamma_i - gamma_j) at and below the diagonal,
        # 0 above it: no exponent is positive
        decay = jnp.exp(jnp.where(
            at_or_below, gam[..., :, None] - gam[..., None, :], -jnp.inf))
        a = jnp.where(below, bb[..., :, None] * decay
                      * _mm("nhik,nhjk->nhij", kb, kb), 0.0)
        e_gam = jnp.exp(gam)[..., None]
        # beta times each part, not times the joined pair: the compiled
        # 8192-token view holds 31 MB less (tests/test_aot_tpu_compile.py)
        wu = _unit_lower_solve(a, jnp.concatenate(
            [bb[..., None] * kb * e_gam, bb[..., None] * vb], axis=-1))
        attn = decay * _mm("nhik,nhjk->nhij", qb, kb)
        last = gam[..., -1:]                                  # [nb,H,1]
        k_end = kb * jnp.exp(last - gam)[..., None]

        def one(s, xs):
            w, u, p, q_in, k_out, a_end = xs
            v_new = u - _mm("hck,hkv->hcv", w, s)
            o = _mm("hck,hkv->hcv", q_in, s) + _mm("hij,hjv->hiv", p, v_new)
            s = a_end[..., None] * s + _mm("hck,hcv->hkv", k_out, v_new)
            return s, o

        s, o = jax.lax.scan(one, s, (wu[..., :dk], wu[..., dk:], attn,
                                     qb * e_gam, k_end, jnp.exp(last)))
        o = jnp.moveaxis(o, 1, 2).reshape(rows, h, dv)
        return s, jax.lax.dynamic_update_slice_in_dim(out, o, i * rows,
                                                      axis=0)

    s, o = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.zeros((h, dk, dv), F32), jnp.zeros((t, h, dv), F32)))
    return o, s


def _weights(ins):
    return {n: first(ins, n) for n in _WEIGHTS}


@register_op("gdn_prefill", no_grad=True,
             slot_state=("gdn", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: a Gated DeltaNet "
                 "(arXiv:2412.06464) layer over one request's prompt, the "
                 "gated delta rule chunk by chunk over the blocks its true "
                 "length fills, writing the slot's state and conv window "
                 "(ops/gdn.py)")
def _gdn_prefill(ctx, ins, attrs):
    """X [1,T,M], the layer's weights, State [n_slots,H,Dk,Dv] float32,
    Conv [n_slots,K-1,2*H*Dk+H*Dv], SeqLen [1,1] int, Slot [1,1] int (>=
    n_slots: nothing is written) -> Out [1,T,M], StateOut, ConvOut.
    attrs: n_head, key_dim, value_dim, chunk, epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    sizes = _sizes(attrs)
    eps = float(attrs.get("epsilon", 1e-5))
    if x.shape[0] != 1:
        raise ValueError("gdn_prefill takes one request (batch 1)")
    t, dt = x.shape[1], x.dtype
    chunk = min(int(attrs["chunk"]), t)
    rows = block_rows(t, chunk)
    taps = w["ConvW"].shape[0]
    n = jnp.asarray(first(ins, "SeqLen")).reshape(()).astype(jnp.int32)
    slot = jnp.asarray(first(ins, "Slot")).reshape((1,)).astype(jnp.int32)
    real = jnp.arange(t)[:, None] < n

    u, g, beta = _token_terms(x[0], w)
    with _prefill_phase("conv"):
        # rows at and past the true length are padding: they must reach
        # neither the conv window that is kept nor the recurrence
        u = jnp.where(real, u, 0)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
        window = jax.lax.dynamic_slice(padded, (n, 0),
                                       (taps - 1, padded.shape[1]))
        # the window is cut BEFORE the conv reads the rows: left to the
        # scheduler, the cut of every layer waited for the program's end
        # and the rows of all twelve layers (189 MB each at 8192) stayed
        # alive until then (2.3 of the 8192-token view's 3.5 GB of
        # temporaries, compiled for a described v5e: PERF.md, PR 59)
        padded, window = jax.lax.optimization_barrier((padded, window))
        cw = w["ConvW"].astype(F32)
        c = sum(cw[j] * padded[j:j + t].astype(F32) for j in range(taps))
        q, k, v = _qkv(c, sizes)
    with _prefill_phase("scan"):
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
        o, s = chunk_scan(q, k, v, g, beta, (n + rows - 1) // rows, chunk,
                          rows)
    with _prefill_phase("gate"):
        y = _output(o, x[0], w, eps, dt)
    return {"Out": [y[None]],
            "StateOut": [state.at[slot].set(s[None], mode="drop")],
            "ConvOut": [conv.at[slot].set(window[None].astype(conv.dtype),
                                          mode="drop")]}


@register_op("gdn_decode", no_grad=True,
             slot_state=("gdn", ("StateOut", "ConvOut")),
             ref="TPU-native serving op: one Gated DeltaNet step for every "
                 "decode slot, the state and the conv window updated in "
                 "place, inactive slots untouched (ops/gdn.py)")
def _gdn_decode(ctx, ins, attrs):
    """X [B,1,M] (B = n_slots), the layer's weights, State [B,H,Dk,Dv]
    float32, Conv [B,K-1,2*H*Dk+H*Dv], Active [B,1] int -> Out [B,1,M],
    StateOut, ConvOut. attrs: n_head, key_dim, value_dim, epsilon."""
    x = first(ins, "X")
    w = _weights(ins)
    state, conv = first(ins, "State"), first(ins, "Conv")
    sizes = _sizes(attrs)
    eps = float(attrs.get("epsilon", 1e-5))
    dt = x.dtype
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0

    u, g, beta = _token_terms(x[:, 0], w)
    with _decode_phase("conv"):
        window = jnp.concatenate([conv, u[:, None].astype(conv.dtype)],
                                 axis=1)
        c = jnp.sum(w["ConvW"].astype(F32)[None] * window.astype(F32),
                    axis=1)
        q, k, v = _qkv(c, sizes)
    tier = _kda.state_tier(state, ctx.mesh)
    _kda.KDA_DECODE_LOWERED.labels(path=tier).inc()
    with _decode_phase("state"):
        if tier == "kernel":
            state_out, o = _ks.kda_state_update(
                state, q, k, v, g[..., None], beta, active,
                interpret=_plk.interpret_mode())
        else:
            s_new, o = _kda._delta_step(state, q, k, v, g[..., None], beta)
            state_out = jnp.where(active[:, None, None, None], s_new,
                                  state)
    with _decode_phase("gate"):
        y = _output(o, x[:, 0], w, eps, dt)
    with _decode_phase("conv"):
        conv_out = jnp.where(active[:, None, None], window[:, 1:], conv)
    return {"Out": [y[:, None]], "StateOut": [state_out],
            "ConvOut": [conv_out]}
