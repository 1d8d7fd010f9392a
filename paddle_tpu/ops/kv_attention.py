"""Decode-mode attention: the KV-cache op family that turns
autoregressive serving from O(T) full forwards into prefill +
O(1)-per-token decode — and, since ISSUE 9, lets requests join and
leave a RUNNING decode without recompiling anything.

Inference-only ops (no VJP — serving programs are is_test), all spelled
with the same numerics as ``ops/attention_block.py`` (fp32 MXU
accumulation via preferred_element_type, softmax in fp32, probabilities
applied in the storage dtype) so a prefill+decode transcript matches the
full-forward graph token for token. The decode-side ops (paged decode,
paged verify) share ONE contraction over the cache,
:func:`_decode_contract`: it consumes K and V as ``[B, S, H*Dk]`` — the
model width on the minor dimension, as the paged gather leaves them and
never re-laid — through a block-diagonal query, and with fp32 compute
its two dots run at precision HIGHEST (an fp32 cache is multiplied in
fp32, not rounded to bf16 by a default MXU pass):

- ``kv_attention_prefill_paged`` — the in-flight-batching prefill: same
  causal attention, but the K/V rows are scattered into the shared
  ``[n_pages, page_size, H*D]`` page pool at per-position flat row
  indices (``PageRows [T, 1]``), so a new request's cache joins a live
  pool without disturbing the slots that are mid-decode. A reused page
  never leaks its previous occupant's keys: the decode mask admits only
  rows this request wrote.

- ``kv_attention_decode_paged`` — ONE new token per ROW per call, with
  fully per-row geometry: ``Pos [B,1]`` is each row's cache write index,
  ``GenStart [B,1]`` is where its generated region begins (the prompt
  bucket it was prefilled at), ``SeqLen [B,1]`` its true prompt length,
  and ``Active [B,1]`` gates the cache write — an inactive (free) slot
  flows through the batch untouched; each row's cache is read and
  written through a ``[n_slots, max_pages]`` page table. Every decode
  step of every mix of in-flight requests runs the SAME static-shape
  executable: zero steady-state compiles.

- ``kv_attention_verify_paged`` — the speculative-decoding verify step
  (ISSUE 19): score a ``[B, K+1]`` token window per row in ONE causal dispatch. Window position 0 is the
  row's last committed token (its KV row is re-written with identical
  values — the projection depends only on the token and the weights),
  positions 1..K are the drafted tokens. ``WinLen [B,1]`` bounds how
  many window positions actually write (1 = plain decode); positions at
  and beyond ``WinLen`` produce outputs the host ignores. Rollback of
  rejected positions is free: rejected rows sit ABOVE the committed
  frontier, the mask ``j <= pos + i`` never admits them once the host
  rewinds, and the next window overwrites them through the still-leased
  pages (the lease keeps the pages, only the slot's logical length
  rewinds).

- ``token_sample`` — on-device next-token selection: greedy argmax when
  ``temperature <= 0`` or ``top_k == 1`` (bit-identical to host argmax
  over the same logits), otherwise temperature-scaled top-k sampling via
  the Gumbel trick with a key derived ONLY from the per-request
  ``Seed`` and the token index — reproducible across processes and
  server restarts, independent of the framework step seed.

Cache layout & masking (docs/serving.md):
  cache[b, j] is valid for row b iff  j < seq_len[b]            (prompt)
                                  or  gen_start[b] <= j <= pos[b]  (gen)
  Prompts are RIGHT-padded to their prompt bucket; generated tokens land
  contiguously from ``gen_start``. Each row's semantic position (for the
  model's additive positional encoding, applied upstream at the
  embedding) is ``seq_len[b] + (pos[b] - gen_start[b])`` — slot index is
  storage only, attention order comes entirely from the mask.

The decode step's cost is O(S) in the STATIC cache length and
independent of how many tokens were already emitted — ``analyzed_flops``
of the decode executable is position-free by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import first, register_op
from paddle_tpu.observability import device_scopes as _device_scopes
from paddle_tpu.observability import metrics as _metrics

from paddle_tpu.ops import attention_block as _ab
from paddle_tpu.ops.math_ops import dense

# exporter-catalog family (docs/serving.md "Metric names"; preregistered
# via exporters._preregister_catalog importing this module). Counts
# LOWERINGS, not steps: one increment per K or V gather each time a
# paged decode / verify program is traced, labelled with the tier
# ``_paged_gather`` chose — the program-side witness of which kernel a
# deployment's geometry gets (the device trace names the same thing:
# ``gather_pages.NN`` / ``gather_rows.NN`` custom calls).
KV_GATHER_LOWERED = _metrics.counter(
    "paddle_kv_gather_lowered_total",
    "Paged K/V gathers lowered, by tier (pages|rows|take)",
    labelnames=("path",))


# same catalog, same discipline: one increment each time
# ``_gqa_attend`` is traced, labelled with the implementation its shapes
# chose (``_gqa_attend_tier``; the device trace shows the kernel as a
# ``tpu_custom_call`` ``[B * H, T, D]`` under ``kv_attention_prefill_paged``)
GQA_PREFILL_ATTEND_LOWERED = _metrics.counter(
    "paddle_gqa_prefill_attend_lowered_total",
    "Grouped-KV prefill attentions lowered, by implementation "
    "(flash|blocked|whole)",
    labelnames=("path",))


def _scores_to_probs(s, mask, dt, sink=None):
    """fp32 scaled+masked scores -> storage-dtype probabilities, the
    shared softmax spelling (mirrors attention_block._fwd_impl). With
    ``sink`` (float32, one logit a query head, broadcastable against
    ``s`` with a last axis of 1) the softmax has one more column that
    carries no value: the maximum is taken over the scores AND the
    sink, and exp(sink - m) joins the denominator alone."""
    s = jnp.where(mask, s, _ab._NEG)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        p = _softmax_beside_sink(s, jnp.max(s, axis=-1, keepdims=True), sink)
    return p.astype(dt)


def _softmax_beside_sink(s, row_max, sink):
    """softmax of s over its last axis with one more column, ``sink``,
    that carries no value: its share of the denominator alone."""
    m = jnp.maximum(row_max, sink)
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def _decode_contract(q, k, v, valid, dt, n_kv=None, scale=None, sink=None):
    """The decode-side attention contraction, over a cache that keeps
    the model width on its minor dimension: q [B, K1, H, Dk] (K1 query
    rows per batch row: 1 for a decode step, the window for a verify),
    k / v [B, S, M] with M = H * Dk exactly as ``_paged_gather`` leaves
    them, valid [B, K1, S] bool -> context [B, K1, H, Dk] in ``dt``.
    With ``n_kv`` grouped KV heads (H / n_kv query heads share one) the
    cache is [B, S, n_kv * Dk] and row (k1, h) of the block-diagonal
    query holds q's head h in the lanes of KV head h // (H / n_kv).
    ``scale`` multiplies the scores (Dk ** -0.5 when None: a latent
    cache's rows are wider than the head the model scales by). The
    value plane may keep heads of another size: v [B, S, n_kv * Dv]
    gives a context [B, K1, H, Dv] (the block-diagonal query spans the
    K lanes alone). ``sink`` [H] float32: a logit a query head that
    joins the softmax's denominator (:func:`_scores_to_probs`).

    The cache is never reshaped to [.., H, Dk]: a 64-wide head is half
    a lane tile, and a per-head contraction of one query row made the
    TPU relay each gathered cache twice with the position on the lanes
    (29.4 of a 55.8 ms decode step; PERF.md, PR 30). Instead the QUERY
    is made block-diagonal: row (k1, h) of ``qbd`` [B, K1*H, M] holds
    head h's Dk values of q[:, k1] in head h's own lanes and selected
    zeros elsewhere, so ``qbd . k`` over all M lanes is head h's score
    (the same products plus exact zeros), and row (k1, h) of ``p . v``
    over S is head h's context in head h's own lanes. H times the
    useful MXU work, under the HBM time of reading the cache while
    K1 * H stays below ~80 (fp32) / ~240 (bf16).

    fp32 accumulation always; with fp32 compute both dots run at
    precision HIGHEST, which is what an fp32 cache states: a DEFAULT
    MXU pass would round K and V to bf16."""
    b, k1, h, d = q.shape
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    if n_kv is None or n_kv == h:
        n_kv = h
        own = jnp.eye(h, dtype=bool)[None, None, :, :, None]
        qbd = jnp.where(own, q[:, :, None], 0)     # row h, lanes h'
    else:
        own = (jnp.arange(h)[:, None] // (h // n_kv)
               == jnp.arange(n_kv)[None, :])[None, None, :, :, None]
        qbd = jnp.where(own, q[:, :, :, None], 0)  # row h, lanes h // G
    m = n_kv * d
    qbd = qbd.reshape(b, k1 * h, m)
    s = jax.lax.dot_general(qbd, k, (((2,), (2,)), ((0,), (0,))),
                            precision=prec,
                            preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32).reshape(b, k1, h, -1) \
        * (float(d) ** -0.5 if scale is None else scale)
    p = _scores_to_probs(
        s, valid[:, :, None, :], dt,
        None if sink is None else sink.reshape(1, 1, h, 1))  # [B,K1,H,S]
    c = jax.lax.dot_general(p.reshape(b, k1 * h, -1), v,
                            (((2,), (1,)), ((0,), (0,))),
                            precision=prec,
                            preferred_element_type=jnp.float32)
    # row (k1, h) keeps head h's own lanes: the sum adds exact zeros
    c = jnp.where(own, c.reshape(b, k1, h, n_kv, v.shape[2] // n_kv),
                  0).sum(axis=3)
    return c.astype(dt)


def _gqa(attrs):
    """(H, n_kv, Dk, Dv) of a grouped-KV, output-gated attention layer,
    or None: the attrs ``n_kv_head`` / ``head_dim`` are set only by a
    model that has such layers, so the programs of one that has not
    carry neither (and stay what they were). ``v_head_dim`` is set only
    where a value head is of another size than a key head."""
    if "n_kv_head" not in attrs:
        return None
    d = int(attrs["head_dim"])
    return (int(attrs["n_head"]), int(attrs["n_kv_head"]), d,
            int(attrs.get("v_head_dim", d)))


def _sink(ins):
    """A window layer's learned sink logits [H] in float32, or None."""
    sink = first(ins, "Sink")
    return None if sink is None else sink.astype(jnp.float32)


def _gqa_heads(x, w, heads, d):
    """x [B,T,M] @ w [M, heads*d] -> [B,T,heads,d] in x's dtype."""
    return dense(x, w, x.dtype).reshape(x.shape[:2] + (heads, d))


def _gqa_output(x, c, wg, wo):
    """y = Wo (c * sigmoid(Wg x)): the context c [B,T,H*D] gated
    elementwise (float32) before the output projection."""
    if wg is not None:
        c = (c.astype(jnp.float32)
             * jax.nn.sigmoid(dense(x, wg))).astype(x.dtype)
    return dense(c, wo, x.dtype)


def rope_half(x, pos, theta: float, rotary=None):
    """x [B, T, heads, D] float32 rotated at positions pos [B, T], the
    rotate-half convention: (x[i], x[i + D/2]) turns by pos *
    theta^(-2i/D) — all D dimensions, no scaling. With ``rotary`` < D
    only the first ``rotary`` values of a head turn (rotate-half INSIDE
    them: x[i] pairs with x[i + rotary/2]); the others pass."""
    if rotary is not None and int(rotary) != x.shape[-1]:
        r = int(rotary)
        return jnp.concatenate(
            [rope_half(x[..., :r], pos, theta), x[..., r:]], axis=-1)
    d = x.shape[-1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv    # [B,T,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _rms(x, scale, eps):
    """x * scale / rms(x) over the last axis, float32."""
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * scale.astype(jnp.float32)


# the float32 heads [T, heads, D] of a projection that is normalised or
# rotated hold at most this many values at once: a longer prompt's are
# made ``GQA_PROJECT_ROW_BLOCK`` rows at a time (64 heads of 192 over
# 32768 rows are 1.6 GB in float32 beside 0.8 in bfloat16; PR 56). Every
# projection of the configurations served before is under it (the
# largest: 16384 rows x 32 heads of 128) and lowers what it did
GQA_PROJECT_WHOLE_MAX = 1 << 28
GQA_PROJECT_ROW_BLOCK = 4096


def _gqa_qkv(x, wq, wk, wv, ins, attrs, gqa, positions, grouped=False):
    """(q [B,T,H,D], k [B,T,n_kv,D], v [B,T,n_kv,Dv]) of a grouped-KV
    layer in x's dtype; with ``grouped`` q is [B,T,n_kv,G,D]. With attr
    ``value_scale`` v is that times the projection, and with
    ``rotary_dim`` the rotation turns a head's first values alone
    (:func:`rope_half`). With attr ``qk_norm``
    every head of q and k is RMS-normalised over its D values (gains
    ``QNorm`` / ``KNorm`` [D], eps ``rms_eps``; with ``qk_norm_whole``
    the WHOLE projection over its heads * D values, gains of that
    length: Olmo's) and with attr
    ``rope_theta`` rotated at ``positions()`` [B,T] (the tokens' TRUE
    positions), in that order and in float32: what is cached is the
    normalised, rotated key. Without either the projections are what
    they were, op for op (q grouped before k is projected: a plain
    layer's programs keep their compile-cache keys)."""
    h, n_kv, d, dv = gqa
    qshape = x.shape[:2] + ((n_kv, h // n_kv, d) if grouped else (h, d))
    qk_norm, theta = bool(attrs.get("qk_norm")), attrs.get("rope_theta")
    whole = bool(attrs.get("qk_norm_whole"))

    def value():
        if not attrs.get("value_scale"):
            return _gqa_heads(x, wv, n_kv, dv)
        # what is cached is the scaled value, rounded once
        return (dense(x, wv) * float(attrs["value_scale"])).astype(
            x.dtype).reshape(x.shape[:2] + (n_kv, dv))

    if not qk_norm and not theta:
        return (_gqa_heads(x, wq, h, d).reshape(qshape),
                _gqa_heads(x, wk, n_kv, d), value())
    eps = float(attrs.get("rms_eps", 1e-5))
    b, t = x.shape[:2]
    out = []
    for w, heads, gain in ((wq, h, "QNorm"), (wk, n_kv, "KNorm")):
        def rows(xr, pos, w=w, heads=heads, gain=gain):
            y = dense(xr, w)
            if whole:       # one norm over all of the projection's heads
                y = _rms(y, first(ins, gain), eps)
            y = y.reshape(xr.shape[:2] + (heads, d))
            if qk_norm and not whole:
                y = _rms(y, first(ins, gain), eps)
            if theta:
                y = rope_half(y, pos(), theta, attrs.get("rotary_dim"))
            return y.astype(x.dtype)

        blk = GQA_PROJECT_ROW_BLOCK
        if t * heads * d <= GQA_PROJECT_WHOLE_MAX or t % blk:
            out.append(rows(x, positions))
            continue
        # the float32 heads of a long prompt a block of rows at a time
        split = lambda z: jnp.swapaxes(                        # noqa: E731
            z.reshape((b, t // blk, blk) + z.shape[2:]), 0, 1)
        y = jax.lax.map(lambda a: rows(a[0], lambda: a[1]),
                        (split(x), split(positions())))
        out.append(jnp.swapaxes(y, 0, 1).reshape(b, t, heads, d))
    return out[0].reshape(qshape), out[1], value()


def _softmax_rows(s, sink=None):
    """softmax over the last axis with the row maximum behind an
    optimization barrier: fused with the subtraction, XLA's TPU
    pipeline turned ``max`` over 8192 keys into a ``reduce-window`` of
    16383 taps for EVERY score — 47 ms a block of 256 queries, 7.5 of a
    prefill's 8.2 s (PERF.md, PR 33). Every row has its own key, so no
    row is all -inf. ``sink``: as in :func:`_scores_to_probs`."""
    m = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    if sink is None:
        e = jnp.exp(s - m)
        return e / jnp.sum(e, axis=-1, keepdims=True)
    return _softmax_beside_sink(s, m, sink)


# queries a block of a long grouped-KV prefill: a prompt bucket longer
# than this attends in blocks (the scores of a whole 16384-token prompt
# are [32, 16384, 16384] float32, 34 GB; of a block over all its keys
# 1.1 GB, over a window's 0.17 GB), a shorter one at once, as before
GQA_QUERY_BLOCK = 512


def _attended(keep, first_col=0):
    """keep [Q, S] bool -> [Q, 2] int32: the lowest key each query
    attends (columns count from ``first_col``) and how many."""
    return jnp.stack([first_col + jnp.argmax(keep, axis=-1),
                      jnp.sum(keep, axis=-1)], axis=-1).astype(jnp.int32)


def _gqa_attend_tier(t, d, window, mesh=None, dv=None):
    """Which implementation attends a grouped-KV prefill of ``t`` rows
    at heads of ``d`` (values of ``dv``: ``d`` when None), decided from what is being lowered and never
    from a flag (as ``_gather_tier``): ``("whole", None)`` for a bucket
    of at most ``GQA_QUERY_BLOCK`` rows (one square of scores),
    ``("flash", (bq, bk))`` for a longer bucket of a FULL layer where
    the causal flash forward kernel may run (a TPU, no mesh of more
    than one device and heads of whole half lane tiles —
    ``kernel_enabled`` — or the tests' interpreter) and its blocks
    divide the bucket, ``("blocked", None)`` otherwise:
    a window layer (the band, and the ``Attended`` output that only the
    composition makes), the CPU, a mesh."""
    if t <= GQA_QUERY_BLOCK:
        return "whole", None
    if window is None:
        from paddle_tpu.ops import pallas as _plk
        dv = d if dv is None else dv
        blocks = _plk.causal_blocks(t, d, dv)
        if None not in blocks and (
                _plk.kernel_enabled(64, t, d, dv, mesh=mesh)
                or _plk.forced_interpret()):
            return "flash", blocks
    return "blocked", None


def _gqa_attend(q, k, v, window=None, scale=None, mesh=None, sink=None):
    """Causal attention of q [B,T,n_kv,G,D] over k [B,T,n_kv,D] and v
    [B,T,n_kv,Dv] -> ([B,T,n_kv,G,Dv] in q's dtype, with a window what
    each query attends [T, 2]: ``_attended``). With ``window`` query t
    sees the keys s with 0 <= t - s < window: a band, and a block of
    queries reads only the keys its band can reach. ``scale`` multiplies
    the scores (D ** -0.5 when None). ``sink`` [n_kv * G] float32 (a
    window layer's alone): one more column of every softmax, with no
    value. One algorithm, the implementation
    chosen by shape (:func:`_gqa_attend_tier`, under ``mesh``): float32
    scores, maximum, denominator and accumulator, the probabilities in
    q's dtype for ``p . V``, whichever runs."""
    b, t, n_kv, g, d = q.shape
    dt = q.dtype
    scale = float(d) ** -0.5 if scale is None else float(scale)
    dv = v.shape[-1]
    tier, blocks = _gqa_attend_tier(t, d, window, mesh, dv)
    GQA_PREFILL_ATTEND_LOWERED.labels(path=tier).inc()
    if sink is not None:
        sink = sink.reshape(1, n_kv, g, 1, 1)                  # "bkgts"
    if tier == "whole":
        s = jnp.einsum("btkgd,bskd->bkgts", q, k,
                       preferred_element_type=jnp.float32) * scale
        keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        if window is not None:
            keep &= jnp.arange(t)[:, None] - jnp.arange(t)[None, :] < window
        p = _scores_to_probs(s, keep, dt, sink)
        c = jnp.einsum("bkgts,bskd->btkgd", p, v,
                       preferred_element_type=jnp.float32).astype(dt)
        return c, (None if window is None else _attended(keep))
    if tier == "flash":
        # the kernel's key / value index maps read key head h // G: the
        # keys stay [B, n_kv, T, D] in HBM, never broadcast to H heads
        from paddle_tpu.ops import pallas as _plk
        heads_first = lambda z: jnp.swapaxes(z, 1, 2)          # noqa: E731
        o = _plk.flash_attention(
            heads_first(q.reshape(b, t, n_kv * g, d)), heads_first(k),
            heads_first(v), True, scale, blocks[0], blocks[1],
            _plk.interpret_mode())
        return heads_first(o).reshape(b, t, n_kv, g, dv), None
    blk = GQA_QUERY_BLOCK
    if t % blk:
        raise ValueError(f"a prompt bucket of {t} is not a whole number "
                         f"of {blk}-query blocks")
    span = t if window is None else min(t, window + blk)

    def block(t0):
        k0 = jnp.clip(t0 + blk - span, 0, t - span)
        cut = lambda z, at, n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            z, at, n, axis=1)
        ahead = (t0 + jnp.arange(blk))[:, None] \
            - (k0 + jnp.arange(span))[None, :]
        keep = ahead >= 0
        if window is not None:
            keep &= ahead < window
        s = jnp.einsum("btkgd,bskd->bkgts", cut(q, t0, blk),
                       cut(k, k0, span),
                       preferred_element_type=jnp.float32) * scale
        p = _softmax_rows(jnp.where(keep, s, -jnp.inf), sink).astype(dt)
        c = jnp.einsum("bkgts,bskd->btkgd", p, cut(v, k0, span),
                       preferred_element_type=jnp.float32).astype(dt)
        return c, _attended(keep, k0)

    o, seen = jax.lax.map(block, jnp.arange(0, t, blk))  # [T/blk,B,blk,..]
    return (jnp.moveaxis(o, 0, 1).reshape(b, t, n_kv, g, dv),
            None if window is None else seen.reshape(t, 2))


def _gqa_causal_prefill(x, wq, wk, wv, wo, wg, ins, attrs, gqa, mesh=None):
    """Causal self-attention of a grouped-KV layer over X [B,T,M], plus
    the K/V projections [B,T,n_kv*d] the caller caches and, of a window
    layer, what each query attended (``_attended``). Without
    positions the mask alone orders it; a layer's attrs may give its
    heads a norm and rotary positions (:func:`_gqa_qkv`: a prompt's
    rows ARE its positions) and its queries a ``window``."""
    h, _, _, dv = gqa
    b, t, _ = x.shape
    q, k, v = _gqa_qkv(x, wq, wk, wv, ins, attrs, gqa,
                       lambda: jnp.broadcast_to(jnp.arange(t), (b, t)),
                       grouped=True)
    window = attrs.get("window")
    c, seen = _gqa_attend(q, k, v, int(window) if window else None,
                          attrs.get("attn_scale"), mesh, _sink(ins))
    out = _gqa_output(x, c.reshape(b, t, h * dv), wg, wo)
    return out, k.reshape(b, t, -1), v.reshape(b, t, -1), seen


def _causal_prefill(x, wq, wk, wv, wo, h):
    """Shared prefill math: causal self-attention over X [B,T,M] plus
    the K/V projections ([B,T,H,D]) the caller caches."""
    b, t, m = x.shape
    d = m // h
    dt = x.dtype
    q = _ab._proj(x, wq, h)                     # [B,T,H,D]
    k = _ab._proj(x, wk, h)
    v = _ab._proj(x, wv, h)
    s = jax.lax.dot_general(q, k, (((3,), (3,)), ((0, 2), (0, 2))),
                            preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32) * (float(d) ** -0.5)   # [B,H,T,T]
    causal = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
    p = _scores_to_probs(s, causal[None, None], dt)
    c = jax.lax.dot_general(p, v, (((3,), (1,)), ((0, 1), (0, 2))),
                            preferred_element_type=jnp.float32).astype(dt)
    out = jax.lax.dot_general(c, wo.reshape(h, d, m),
                              (((1, 3), (0, 1)), ((), ())),
                              preferred_element_type=jnp.float32).astype(dt)
    return out, k, v


def _kv_quant(rows):
    """rows [..., H, D] fp32 -> (int8 codes, fp32 scales [..., H]):
    symmetric per-(position, head) scaling — the per-row-scale wire
    discipline of FLAGS_embed_exchange_codec applied at rest
    (FLAGS_kv_cache_codec=int8)."""
    amax = jnp.max(jnp.abs(rows), axis=-1)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(rows / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _gather_tier(flat, scales, ps, mesh=None) -> str:
    """Which implementation gathers a paged pool, decided from what is
    being lowered and never from a flag (``mesh``: see
    ``kernel_enabled``). On aligned TPU shapes a Pallas kernel of
    ops/pallas/paged_attention.py: ``"pages"`` (a whole page per DMA)
    when the storage has no codec scales and a page is a whole number
    of the dtype's sublane tiles, ``"rows"`` (a row per DMA, dequant
    included) for int8 pages and page sizes that are not. ``"take"``,
    the jnp refer path, otherwise."""
    from paddle_tpu.ops import pallas as _plk
    from paddle_tpu.ops.pallas.embed_cache import sublane_tile
    if not _plk.kernel_enabled(128, flat.shape[1], mesh=mesh):
        return "take"
    if scales is None and ps % sublane_tile(flat.dtype) == 0:
        return "pages"
    return "rows"


def _paged_gather(flat, scales, table, ps, dt, mesh=None):
    """Gather every slot's logical cache through the page table: flat
    [R, M] storage (fp32 | bf16 | int8 codes; M = H * Dk), scales
    [R, H] fp32 or None, table [B, MP] int32 page ids (sentinel ids >=
    n_pages clamp to the last page — what they gather is exactly zeroed
    by the attention mask). Returns [B, MP * ps, M] in the compute
    dtype, the same bits whichever tier (:func:`_gather_tier`) moves
    them. The pool goes to the kernels as it is stored: no reshape
    touches its minor dimension."""
    r, m = flat.shape
    tier = _gather_tier(flat, scales, ps, mesh)
    KV_GATHER_LOWERED.labels(path=tier).inc()
    if tier != "take":
        from paddle_tpu.ops import pallas as _plk
        from paddle_tpu.ops.pallas import paged_attention as _pk
        interp = _plk.interpret_mode()
    if tier == "pages":
        out = _pk.gather_pages(flat, table.reshape(-1), ps,
                               interpret=interp)
    else:
        rows = (table[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
                ).reshape(-1)
        idx = jnp.minimum(rows, r - 1)
        if tier == "take":
            out = jnp.take(flat, idx, axis=0)
            if scales is not None:
                h = scales.shape[1]
                out = (out.astype(jnp.float32).reshape(-1, h, m // h)
                       * jnp.take(scales, idx, axis=0)[..., None])
        elif scales is not None:
            out = _pk.gather_rows_dequant(flat, scales, idx,
                                          scales.shape[1],
                                          interpret=interp)
        else:
            out = _pk.gather_rows(flat, idx, interpret=interp)
    return out.reshape(table.shape[0], -1, m).astype(dt)


def _paged_pools(ins, codec):
    """The paged pool operands as flat [R, M] row views, M = H * Dk
    (+ flat [R, H] scale views for int8), and the pool's geometry.

    The pool variable is [n_pages, page_size, M]. With the whole model
    width on the minor dimension an fp32 (8, 128) or bf16 (16, 128)
    tile is full, so the TPU keeps the pool row-major at rest, a page
    is ``page_size * M`` contiguous elements, and [n_pages, ps, M] ->
    [R, M] is a bitcast: scatter, gather kernel and state output share
    one layout and the donated input/output aliasing holds through it
    (proglint --memory and tests/test_aot_tpu_compile.py witness
    this). It was NOT one while the variable was [n_pages, ps, H, Dk]:
    a 64-wide minor dimension fills half a tile, the chip kept that
    pool with the PAGE index on the lanes, and every program transposed
    every pool in and out (PERF.md, PR 25 and PR 28)."""
    page_k, page_v = first(ins, "PageK"), first(ins, "PageV")
    n_pages, ps, m = (int(d) for d in page_k.shape)
    rtot = n_pages * ps
    flat_k = page_k.reshape(rtot, m)
    flat_v = page_v.reshape(rtot, int(page_v.shape[2]))  # n_kv * Dv
    fks = fvs = None
    if codec == "int8":
        fks = first(ins, "PageKS").reshape(rtot, -1)
        fvs = first(ins, "PageVS").reshape(rtot, -1)
    return flat_k, flat_v, fks, fvs, n_pages, ps, rtot


def _paged_write(flat, fscale, rows, vals):
    """Scatter new K/V rows ``vals`` [N, M] (the projections, flattened
    over heads) into the flat [R, M] pool at ``rows``; sentinel rows
    (>= R: skipped shared-prefix positions, inactive slots) DROP — the
    copy-on-write contract: a shared page is never written, the
    divergent request's rows land in its own private page. With scale
    planes ``fscale`` [R, H] (codec int8) the NEW rows are quantised
    per (position, head) on their own [N, H, Dk] view — never on a view
    of the pool — and the codes land as [N, M] rows like any other."""
    n, m = vals.shape
    if fscale is None:
        return flat.at[rows].set(vals.astype(flat.dtype),
                                 mode="drop"), None
    codes, scale = _kv_quant(
        vals.astype(jnp.float32).reshape(n, fscale.shape[1], -1))
    return (flat.at[rows].set(codes.reshape(n, m), mode="drop"),
            fscale.at[rows].set(scale, mode="drop"))


def _paged_result(out, flat_k, flat_v, fks, fvs, n_pages, ps, seen=None):
    """The paged ops' outputs, pools back in their declared shapes;
    ``Attended`` (a window layer's: per query the lowest key position it
    attended and how many, for a check to read — dead code in an
    executable that does not fetch it)."""
    res = {"Out": [out],
           "PageKOut": [flat_k.reshape(n_pages, ps, -1)],
           "PageVOut": [flat_v.reshape(n_pages, ps, -1)]}
    if seen is not None:
        res["Attended"] = [seen]
    if fks is not None:
        res["PageKSOut"] = [fks.reshape(n_pages, ps, -1)]
        res["PageVSOut"] = [fvs.reshape(n_pages, ps, -1)]
    return res


@register_op("kv_attention_prefill_paged", no_grad=True,
             ref="TPU-native serving op: causal prefill whose K/V rows "
                 "scatter into the PAGED pool at per-position flat row "
                 "indices from the slot's page table — sentinel rows "
                 "skip prefix-SHARED pages (already resident, "
                 "bit-identical by construction: K/V at position j "
                 "depends only on token j)")
def _kv_attention_prefill_paged(ctx, ins, attrs):
    """X [B,T,M], Wq..Wo [M,M], PageK/PageV [n_pages, ps, H * Dk]
    (+ PageKS/PageVS [n_pages, ps, H] fp32 when codec=int8),
    Rows [B*T, 1] int: flat pool row per prompt position, sentinel
    (>= n_pages*ps) for shared-prefix and skipped positions -> Out
    [B,T,M] + the pools with this prompt's K/V written through the
    page table. attrs: n_head, codec; with n_kv_head and head_dim the
    layer has grouped KV heads (Wq/Wo [M, H*D] / [H*D, M], Wk/Wv
    [M, n_kv*D], pools [n_pages, ps, n_kv*D]), no positions, and an
    output gate Wg [M, H*D] when given (:func:`_gqa_causal_prefill`)."""
    x = first(ins, "X")
    wq, wk, wv, wo = (first(ins, n) for n in ("Wq", "Wk", "Wv", "Wo"))
    h = int(attrs["n_head"])
    codec = str(attrs.get("codec", "none"))
    rows = jnp.asarray(first(ins, "Rows")).reshape(-1).astype(jnp.int32)
    flat_k, flat_v, fks, fvs, n_pages, ps, _ = _paged_pools(ins, codec)
    gqa = _gqa(attrs)
    seen = None
    if gqa is not None:
        # a window layer lowers under a scope of its own below the op's
        with _device_scopes.variant("kv_attention_prefill_paged",
                                    "window", bool(attrs.get("window"))):
            out, k, v, seen = _gqa_causal_prefill(
                x, wq, wk, wv, wo, first(ins, "Wg"), ins, attrs, gqa,
                ctx.mesh)
    else:
        out, k, v = _causal_prefill(x, wq, wk, wv, wo, h)
    flat_k, fks = _paged_write(flat_k, fks, rows,
                               k.reshape(-1, flat_k.shape[1]))
    flat_v, fvs = _paged_write(flat_v, fvs, rows,
                               v.reshape(-1, flat_v.shape[1]))
    return _paged_result(out, flat_k, flat_v, fks, fvs, n_pages, ps, seen)


def window_ring(window: int, page_size: int) -> int:
    """Pages that ``window`` consecutive positions can touch: the length
    of a slot's ring in the page pool's window group (what the decode
    view's ``page_table_w`` feed is wide, what ``PagePool`` leases at
    most)."""
    return -(-int(window) // int(page_size)) + 1


def _window_decode(q, k_t, v_t, table, true_pos, active, window, n_kv,
                   mesh, pools, sink=None):
    """A window layer's decode step over its group's pools: ``table``
    [B, ring] is the slot's RING of pages, entry ``e`` holding the
    logical page ``lp`` of true positions with ``lp % ring == e``
    (serving/kv_pool.py "Window group"). Writes this token's rows at
    its true position, gathers the ring's pages — O(window) rows a slot
    whatever the context — and attends the keys ``j`` with ``0 <=
    true_pos - j < window``: entry e holds page ``cur - (cur - e) %
    ring`` of the slot (``cur`` the page being written), so a gathered
    row's position is known without a feed, and a page the host has
    returned (sentinel) or not yet written lies outside the window.
    Returns (context [B,1,H,D], what each slot attended [B,2]:
    ``_attended``, the pools)."""
    flat_k, flat_v, fks, fvs, ps, rtot = pools
    b, ring = table.shape
    dt, mk, mv = q.dtype, flat_k.shape[1], flat_v.shape[1]
    phase = functools.partial(_device_scopes.phase,
                              "kv_attention_decode_paged/window")
    cur = true_pos // ps
    with phase("write"):
        wpage = jnp.take_along_axis(table, (cur % ring)[:, None],
                                    axis=1)[:, 0]
        wrow = jnp.where(active, wpage * ps + true_pos % ps, rtot)
        flat_k, fks = _paged_write(flat_k, fks, wrow, k_t.reshape(b, mk))
        flat_v, fvs = _paged_write(flat_v, fvs, wrow, v_t.reshape(b, mv))
    with phase("gather"):
        kk = _paged_gather(flat_k, fks, table, ps, dt, mesh)
        vv = _paged_gather(flat_v, fvs, table, ps, dt, mesh)
    with phase("attend"):
        e = jnp.arange(ring, dtype=jnp.int32)[None, :]
        page = cur[:, None] - (cur[:, None] - e) % ring          # [B,ring]
        j = (page[:, :, None] * ps
             + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
             ).reshape(b, ring * ps)
        ahead = true_pos[:, None] - j
        valid = (j >= 0) & (ahead >= 0) & (ahead < window)
        c = _decode_contract(q, kk, vv, valid[:, None], dt, n_kv,
                             sink=sink)
        seen = jnp.stack(
            [jnp.min(jnp.where(valid, j, jnp.iinfo(jnp.int32).max), -1),
             jnp.sum(valid, -1)], axis=-1).astype(jnp.int32)
    return c, seen, flat_k, flat_v, fks, fvs


@register_op("kv_attention_decode_paged", no_grad=True,
             ref="TPU-native serving op: one-token decode over the "
                 "PAGED KV pool — write row and gather rows resolved "
                 "through the per-slot page table feed (static shapes: "
                 "zero steady-state compiles; Pallas scalar-prefetch "
                 "page gather on TPU, ops/pallas/paged_attention.py)")
def _kv_attention_decode_paged(ctx, ins, attrs):
    """X [B,1,M], Wq..Wo [M,M], PageK/PageV [n_pages, ps, H * Dk]
    (+ PageKS/PageVS when codec=int8), PageTable [B, MP] int (flat page
    id per logical page; sentinel n_pages past the slot's span),
    Pos/SeqLen/GenStart/Active [B,1] int (the module docstring's
    per-row geometry); the cache row for logical position j lives at
    flat row table[b, j//ps]*ps + j%ps. attrs: n_head, codec. The mask
    {j < seq_len} ∪ {gen_start <= j <= pos} zeroes sentinel/garbage
    rows EXACTLY. The gathered [B, S, H*Dk] caches go to
    ``_decode_contract`` as they are — no reshape to [.., H, Dk], no
    relayout. With
    n_kv_head and head_dim (grouped KV heads, optional output gate Wg:
    see kv_attention_prefill_paged) the gathered caches are
    [B, S, n_kv*D] and the block-diagonal query carries H / n_kv query
    heads per KV head."""
    x = first(ins, "X")
    wq, wk, wv, wo = (first(ins, n) for n in ("Wq", "Wk", "Wv", "Wo"))
    h = int(attrs["n_head"])
    codec = str(attrs.get("codec", "none"))
    b, _, m = x.shape
    dt = x.dtype
    flat_k, flat_v, fks, fvs, n_pages, ps, rtot = \
        _paged_pools(ins, codec)
    table = jnp.asarray(first(ins, "PageTable")).astype(jnp.int32)
    mp = table.shape[1]
    s_len = mp * ps

    pos = jnp.asarray(first(ins, "Pos")).reshape(-1).astype(jnp.int32)
    lens = jnp.asarray(first(ins, "SeqLen")).reshape(-1).astype(jnp.int32)
    gen0 = jnp.asarray(first(ins, "GenStart")).reshape(-1)\
        .astype(jnp.int32)
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0

    gqa = _gqa(attrs)
    n_kv = None
    if gqa is not None:
        h, n_kv = gqa[:2]
        # each token's TRUE position: generated rows start at the bucket
        true_pos = lambda: lens + pos - gen0                 # noqa: E731
        # q [B,1,H,D], k_t [B,1,n_kv,D], v_t [B,1,n_kv,Dv]
        q, k_t, v_t = _gqa_qkv(x, wq, wk, wv, ins, attrs, gqa,
                               lambda: true_pos()[:, None])
        if attrs.get("window"):
            with _device_scopes.variant("kv_attention_decode_paged",
                                        "window"):
                c, seen, flat_k, flat_v, fks, fvs = _window_decode(
                    q, k_t, v_t, table, true_pos(), active,
                    int(attrs["window"]), n_kv, ctx.mesh,
                    (flat_k, flat_v, fks, fvs, ps, rtot), _sink(ins))
            out = _gqa_output(x, c.reshape(b, 1, -1), first(ins, "Wg"), wo)
            return _paged_result(out, flat_k, flat_v, fks, fvs, n_pages,
                                 ps, seen)
    else:
        q = _ab._proj(x, wq, h)                     # [B,1,H,D]
        k_t = _ab._proj(x, wk, h)
        v_t = _ab._proj(x, wv, h)
    mk, mv = flat_k.shape[1], flat_v.shape[1]   # the pools' row widths

    # this step's write row through the page table, sentinel (dropped)
    # for inactive slots — a free slot's pages are bit-identical before
    # and after the step
    phase = functools.partial(_device_scopes.phase,
                              "kv_attention_decode_paged")
    with phase("write"):
        wpage = jnp.take_along_axis(table, (pos // ps)[:, None],
                                    axis=1)[:, 0]
        wrow = jnp.where(active, wpage * ps + pos % ps, rtot)
        flat_k, fks = _paged_write(flat_k, fks, wrow, k_t.reshape(b, mk))
        flat_v, fvs = _paged_write(flat_v, fvs, wrow, v_t.reshape(b, mv))

    # gather every slot's logical cache through its table row
    with phase("gather"):
        kk = _paged_gather(flat_k, fks, table, ps, dt, ctx.mesh)  # [B,S,M]
        vv = _paged_gather(flat_v, fvs, table, ps, dt, ctx.mesh)

    with phase("attend"):
        j = jnp.arange(s_len, dtype=jnp.int32)
        valid = (j[None, :] < lens[:, None]) | \
                ((j[None, :] >= gen0[:, None]) &
                 (j[None, :] <= pos[:, None]))           # [B,S]
        c = _decode_contract(q, kk, vv, valid[:, None], dt, n_kv,
                             attrs.get("attn_scale"))
    if gqa is not None:
        out = _gqa_output(x, c.reshape(b, 1, -1), first(ins, "Wg"), wo)
    else:
        out = jax.lax.dot_general(
            c, wo.reshape(h, -1, m), (((2, 3), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32).astype(dt)
    return _paged_result(out, flat_k, flat_v, fks, fvs, n_pages, ps)


@register_op("kv_attention_verify_paged", no_grad=True,
             ref="TPU-native serving op: speculative-decode verify over "
                 "the PAGED KV pool — the K+1 window's write rows "
                 "resolve through the per-slot page table (sentinel "
                 "rows drop: beyond-lease and inactive writes never "
                 "land), gather and mask as kv_attention_decode_paged")
def _kv_attention_verify_paged(ctx, ins, attrs):
    """X [B,K1,M] (window: last committed token + K drafts), Wq..Wo
    [M,M], PageK/PageV [n_pages, ps, H * Dk] (+ PageKS/PageVS when
    codec=int8), PageTable [B, MP] int, Pos [B,1] int (logical cache
    row of window position 0 — the row's committed frontier),
    SeqLen/GenStart/Active [B,1] as in kv_attention_decode_paged, WinLen
    [B,1] int (valid window positions, 1..K1; 1 degenerates to plain
    decode). The cache row for logical position j is flat row
    table[b, j//ps]*ps + j%ps. attrs: n_head, codec.

    Writes k/v for window position i at logical row ``pos + i`` where
    ``active & i < win_len & pos + i < S``; attends position i over
    {j < seq_len} ∪ {gen_start <= j <= pos + i} — causal INSIDE the
    window, so Out[:, i] is what i sequential kv_attention_decode_paged
    steps over the same tokens would produce. Window writes that fall past the slot's leased span hit the table's
    sentinel page (row >= n_pages*ps) and DROP — a draft window can
    never corrupt another slot's pages (the admission span reserves
    the draft-window overshoot, serving/kv_pool.py)."""
    x = first(ins, "X")
    wq, wk, wv, wo = (first(ins, n) for n in ("Wq", "Wk", "Wv", "Wo"))
    h = int(attrs["n_head"])
    codec = str(attrs.get("codec", "none"))
    _, k1, m = x.shape
    dt = x.dtype
    flat_k, flat_v, fks, fvs, n_pages, ps, rtot = \
        _paged_pools(ins, codec)
    table = jnp.asarray(first(ins, "PageTable")).astype(jnp.int32)
    mp = table.shape[1]
    s_len = mp * ps

    pos = jnp.asarray(first(ins, "Pos")).reshape(-1).astype(jnp.int32)
    lens = jnp.asarray(first(ins, "SeqLen")).reshape(-1).astype(jnp.int32)
    gen0 = jnp.asarray(first(ins, "GenStart")).reshape(-1)\
        .astype(jnp.int32)
    active = jnp.asarray(first(ins, "Active")).reshape(-1) > 0
    wlen = jnp.asarray(first(ins, "WinLen")).reshape(-1).astype(jnp.int32)

    q = _ab._proj(x, wq, h)                     # [B,K1,H,D]
    k_t = _ab._proj(x, wk, h)
    v_t = _ab._proj(x, wv, h)

    # window position i writes logical position pos + i; resolve each
    # through the page table, sentinel for inactive rows, positions at
    # or past win_len, and positions past the table span
    phase = functools.partial(_device_scopes.phase,
                              "kv_attention_verify_paged")
    with phase("write"):
        i = jnp.arange(k1, dtype=jnp.int32)
        wp = pos[:, None] + i[None, :]                          # [B,K1]
        wpage = jnp.take_along_axis(table, jnp.clip(wp // ps, 0, mp - 1),
                                    axis=1)
        ok = active[:, None] & (i[None, :] < wlen[:, None]) & (wp < s_len)
        wrow = jnp.where(ok, wpage * ps + wp % ps, rtot).reshape(-1)
        flat_k, fks = _paged_write(flat_k, fks, wrow, k_t.reshape(-1, m))
        flat_v, fvs = _paged_write(flat_v, fvs, wrow, v_t.reshape(-1, m))

    with phase("gather"):
        kk = _paged_gather(flat_k, fks, table, ps, dt, ctx.mesh)  # [B,S,M]
        vv = _paged_gather(flat_v, fvs, table, ps, dt, ctx.mesh)

    with phase("attend"):
        j = jnp.arange(s_len, dtype=jnp.int32)
        valid = (j[None, None, :] < lens[:, None, None]) | \
                ((j[None, None, :] >= gen0[:, None, None]) &
                 (j[None, None, :]
                  <= (pos[:, None] + i[None, :])[:, :, None]))
        c = _decode_contract(q, kk, vv, valid, dt)        # [B,K1,S] mask
    out = jax.lax.dot_general(c, wo.reshape(h, -1, m),
                              (((2, 3), (0, 1)), ((), ())),
                              preferred_element_type=jnp.float32).astype(dt)
    return _paged_result(out, flat_k, flat_v, fks, fvs, n_pages, ps)


def _kth_largest(x, k):
    """x [B,V] float32, k [B] int in [1,V] -> [B] float32: the k-th
    largest value of each row, EXACTLY what ``-jnp.sort(-x)[:, k-1]``
    reads, found by selection and not by sorting: every float maps to
    the uint32 whose unsigned order is the sort's order (-0.0 beside
    0.0, NaN below everything), and the k-th largest key is built bit
    by bit, most significant first — a bit stays set where at least k
    keys reach the candidate. 32 compare-and-count passes over [B,V]
    whatever k is; a full-vocabulary sort was 2.2-2.8 ms of every
    decode step on a v5e (PERF.md, PR 32)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(0x80000000)
    key = jnp.where(bits < top, bits | top, ~bits)
    key = jnp.where(x == 0, top, key)
    key = jnp.where(jnp.isnan(x), jnp.uint32(0), key)

    def one_bit(i, found):
        cand = found | (top >> i.astype(jnp.uint32))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, found)

    found = jax.lax.fori_loop(0, 32, one_bit,
                              jnp.zeros(x.shape[:1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(found >= top, found ^ top, ~found), jnp.float32)


@register_op("token_sample", no_grad=True,
             ref="TPU-native serving op: on-device next-token selection "
                 "— greedy argmax or temperature/top-k Gumbel sampling "
                 "keyed ONLY by the per-request seed + token index "
                 "(restart-reproducible; independent of the framework "
                 "step seed). A batch whose rows are all greedy runs "
                 "the argmax alone (a device-side conditional on the "
                 "op's own inputs); the top-k threshold is an exact "
                 "selection, never a sort")
def _token_sample(ctx, ins, attrs):
    """Logits [B,V], Temperature [B,1] float, TopK [B,1] int
    (<=0: no top-k filter; 1: argmax), Seed [B,1] int (per-request),
    StepIdx [B,1] int (index of the token being sampled) -> Out [B,1]
    int64. Rows with temperature <= 0 OR top_k == 1 take the raw argmax
    (bit-identical to a host argmax over the same logits — the greedy
    parity oracle); other rows sample from the temperature-scaled
    top-k distribution via Gumbel-max, the gumbel noise derived
    ELEMENTWISE from a murmur-finalizer mix of (seed, step_idx, vocab
    index) — the same counter-based idiom as the flash kernels'
    hash_keep_mask, so a row's noise is independent of the batch shape
    and of which slot it occupies (vmapped jax.random streams are NOT:
    they change with the batch).

    The sampled branch runs only where some row of the batch samples
    (``jax.lax.cond`` on the op's own Temperature and TopK, inside the
    caller's executable: no flag, no second op), so an all-greedy decode
    step or prefill pays for one argmax. Where it runs, the top-k set
    is exact: the threshold is the k-th largest scaled logit
    (``_kth_largest``) and every logit that TIES it is kept."""
    logits = first(ins, "Logits")
    temp = jnp.asarray(first(ins, "Temperature")).reshape(-1)\
        .astype(jnp.float32)
    topk = jnp.asarray(first(ins, "TopK")).reshape(-1).astype(jnp.int32)
    seed = jnp.asarray(first(ins, "Seed")).reshape(-1).astype(jnp.int32)
    stepi = jnp.asarray(first(ins, "StepIdx")).reshape(-1)\
        .astype(jnp.int32)
    v = logits.shape[-1]
    lg = jnp.asarray(logits).reshape(-1, v).astype(jnp.float32)

    greedy = jnp.argmax(lg, axis=-1)
    use_greedy = (temp <= 0.0) | (topk == 1)

    def sample():
        scaled = lg / jnp.maximum(temp, 1e-6)[:, None]
        kth = _kth_largest(scaled, jnp.clip(topk, 1, v))[:, None]
        # ties AT the kth value are all kept (documented; deterministic)
        keep = (scaled >= kth) | (topk <= 0)[:, None]
        masked = jnp.where(keep, scaled, -jnp.inf)

        j = jnp.arange(v, dtype=jnp.uint32)[None, :]
        x = (j * jnp.uint32(0x9E3779B9)
             ^ seed.astype(jnp.uint32)[:, None] * jnp.uint32(0x85EBCA6B))
        x = x ^ (stepi.astype(jnp.uint32)[:, None]
                 * jnp.uint32(0x27D4EB2F))
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        # uniform in (0, 1) from the 24 high bits; never exactly 0 or 1
        u = ((x >> jnp.uint32(8)).astype(jnp.float32) + 0.5) \
            * (1.0 / (1 << 24))
        noise = -jnp.log(-jnp.log(u))

        sampled = jnp.argmax(masked + noise, axis=-1)
        return jnp.where(use_greedy, greedy, sampled)

    out = jax.lax.cond(jnp.any(~use_greedy), sample, lambda: greedy)
    return {"Out": [out.astype(jnp.int64)[:, None]]}
