"""Flash attention for 64-wide heads, two heads a lane tile (Pallas TPU).

A 64-wide head is half a 128-lane tile, and ``[B, H, T, 64]`` is a
relayout away from what the projections produce. These kernels take q,
k, v and give o as ``[B, T, M]`` — the projection's output viewed flat —
in blocks ``(1, bq, 128)`` over the M axis: one program holds a PAIR of
heads as lanes 0:64 and 64:128 and the pair's WHOLE key axis (at
T = 512 a pair's K and V are 2 x 128 KB), so a query block's softmax is
made in one pass: no online rescale, no statistics in HBM.

No lane is ever shifted. A head's operand is the pair's tile with the
other head's lanes zeroed: ``(q . 1[half]) k^T`` contracts 128 lanes of
which 64 are exact zeros, and ``p (v . 1[half])`` lands in that head's
lanes of a ``[bq, 128]`` result — the MXU passes of a 64-wide product,
in the layout the next projection reads. The scale ``64 ** -0.5`` is a
power of two and rides on q exactly.

``pairs_forward``: grid ``(B, H/2, Tq/bq)``, gives o. ``pairs_backward``:
ONE kernel on the same grid, from ``(q, k, v, do)`` ALONE: it makes the
probabilities once — row maximum and sum again, not read from a
residual — and from them dq, dk and dv (accumulated over query blocks
in float32 scratch) and o again. It takes nothing the forward kernel
wrote, because the program's ``__vjp__`` op re-traces an op's forward
and XLA merges the copy with the forward pass only where it is XLA's
own ops: a Mosaic call stays (its body carries the trace's source
locations), so a backward that read ``o`` or a log-sum-exp would run the
forward kernel twice a step. The re-traced call's results are unused
and XLA drops it. ``p`` never reaches HBM.

Dropout is ``flash_attention.hash_keep_mask``'s bits at
``(seed, b * H + h, qpos, kpos)`` with ``h = 2 * pair + half``; the
upscale ``1 / (1 - p)`` multiplies float32 results, not probabilities.
``b`` is the program id: the row of the batch the call was GIVEN. Mapped
over a data-parallel mesh (``attention_block._on_shards``) a call sees a
shard's rows, and its caller hands it the seed advanced by the shard's
first global row — the seed and ``b * H + h`` enter the hash as one
uint32 sum — so ``b`` there is the GLOBAL row and the bits are the whole
batch's; these kernels do not know."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas.flash_attention import (
    _C_K, _C_Q, _NEG, _finalize, _grid_spec, _seed_args, _seed_mix,
    keep_threshold)

LANES = 128
D_HEAD = 64
SCALE = float(D_HEAD) ** -0.5
# a query block's scores against the whole key axis are [bq, Tk] float32
# tiles in VMEM, several alive at once: what was compiled and measured
MAX_TILE = 512 * 512


def supported(tq, tk, m, n_head, bq, bk):
    """Whether these kernels take the shape: heads of 64 in whole pairs,
    self-shaped (``Tq == Tk``), the key axis in ONE block and query
    blocks of whole bf16 tiles whose score tile fits."""
    return (n_head > 0 and n_head % 2 == 0 and m == n_head * D_HEAD
            and tq == tk == bk and bq % 16 == 0 and tq % bq == 0
            and tk % LANES == 0 and bq * tk <= MAX_TILE)


class _Tile:
    """What both kernels make of a (batch, pair, query block) program:
    the pair's operands, the causal and keep masks' coordinates, and a
    head's unnormalised probabilities."""

    def __init__(self, seed_ref, q_ref, k_ref, n_head, bq, causal,
                 dropout_p):
        self.b, self.pair, i = (pl.program_id(a) for a in range(3))
        self.n_head, self.dropout_p = n_head, dropout_p
        self.seed_ref = seed_ref
        self.q = q_ref[0] * SCALE
        self.k = k_ref[0]
        tk = self.k.shape[0]
        self.low = jax.lax.broadcasted_iota(
            jnp.int32, (1, LANES), 1) < D_HEAD
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        self.live = qpos >= kpos if causal else None
        if dropout_p > 0:
            self.qcol = qpos.astype(jnp.uint32) * _C_Q
            self.kcol = kpos.astype(jnp.uint32) * _C_K

    def mine(self, half):
        return self.low if half == 0 else ~self.low

    def softmax(self, half):
        """(qh, e, r): the head's q (other lanes zero), exp(s - max)
        ``[bq, Tk]`` float32 and 1 / rowsum ``[bq, 1]``."""
        qh = jnp.where(self.mine(half), self.q, 0)
        s = jax.lax.dot_general(qh, self.k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if self.live is not None:
            s = jnp.where(self.live, s, _NEG)
        e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        return qh, e, 1.0 / jnp.sum(e, axis=1, keepdims=True)

    def keep(self, half):
        """``hash_keep_mask``'s keep decision over the tile, the seed and
        head folded into the query column first (xor associates: one
        operation an element instead of two, the same bits)."""
        bh = self.b * self.n_head + 2 * self.pair + half
        mix = _seed_mix(self.seed_ref[0], bh)
        return (_finalize((self.qcol ^ mix) ^ self.kcol)
                >= jnp.uint32(keep_threshold(self.dropout_p)))


def _fwd_kernel(*refs, n_head, bq, causal, dropout_p):
    seed_ref = None
    if dropout_p > 0:
        seed_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref = refs
    t = _Tile(seed_ref, q_ref, k_ref, n_head, bq, causal, dropout_p)
    v = v_ref[0]
    upscale = 1.0 / (1.0 - dropout_p)
    o = []
    for half in (0, 1):             # two heads a lane tile: static
        _, e, r = t.softmax(half)
        if dropout_p > 0:
            e = jnp.where(t.keep(half), e, 0.0)
        o.append(jnp.dot(e.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
                 * (r * upscale))
    o_ref[0] = jnp.where(t.low, o[0], o[1]).astype(o_ref.dtype)


def _bwd_kernel(*refs, n_head, bq, nq, causal, dropout_p):
    seed_ref = None
    if dropout_p > 0:
        seed_ref, *refs = refs
    (q_ref, k_ref, v_ref, g_ref, dq_ref, dk_ref, dv_ref, o_ref,
     dk_scr, dv_scr) = refs
    i = pl.program_id(2)
    t = _Tile(seed_ref, q_ref, k_ref, n_head, bq, causal, dropout_p)
    k, v, g = t.k, v_ref[0], g_ref[0]
    upscale = 1.0 / (1.0 - dropout_p)

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    dq, o = [], []
    for half in (0, 1):
        mine = t.mine(half)
        qh, e, r = t.softmax(half)
        gh = jnp.where(mine, g, 0)
        dp = jax.lax.dot_general(gh, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        em = e
        if dropout_p > 0:
            keep = t.keep(half)
            em = jnp.where(keep, e, 0.0)
            dp = jnp.where(keep, dp, 0.0)
        em16 = em.astype(v.dtype)
        # with w = e r the softmax and U the upscale: o = U (w . keep) v;
        # ds = U w (keep . dp - delta), delta = rowsum(w . keep . dp). U
        # and the scale multiply the float32 results, r rides on do's rows
        delta = jnp.sum(em * dp, axis=1, keepdims=True) * r
        ds = ((e * r) * (dp - delta)).astype(k.dtype)
        o.append(jnp.dot(em16, v, preferred_element_type=jnp.float32)
                 * (r * upscale))
        dv_scr[...] += jax.lax.dot_general(
            em16, (gh * r).astype(g.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, qh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq.append(jnp.dot(ds, k, preferred_element_type=jnp.float32))
    dq_ref[0] = (jnp.where(t.low, dq[0], dq[1]) * (SCALE * upscale)
                 ).astype(dq_ref.dtype)
    o_ref[0] = jnp.where(t.low, o[0], o[1]).astype(o_ref.dtype)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_scr[...] * upscale).astype(dk_ref.dtype)
        dv_ref[0] = (dv_scr[...] * upscale).astype(dv_ref.dtype)


def _specs(q, k, n_head, bq):
    b, tq, m = q.shape
    tk = k.shape[1]
    if not supported(tq, tk, m, n_head, bq, tk):
        raise ValueError(
            f"flash_pairs: q {q.shape}, k {k.shape}, {n_head} heads, "
            f"query blocks of {bq} is not a shape these kernels take")
    rows = pl.BlockSpec((1, bq, LANES), lambda b, p, i: (b, i, p))
    keys = pl.BlockSpec((1, tk, LANES), lambda b, p, i: (b, 0, p))
    return (b, n_head // 2, tq // bq), rows, keys


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def pairs_forward(q, k, v, seed, n_head, causal, dropout_p, bq, interpret):
    """q, k, v ``[B, T, n_head * 64]`` -> o of q's shape: softmax(q k^T /
    8 [+ causal]) v head by head, dropout on the probabilities
    (upscale_in_train) from ``seed`` (int32 ``[1]``, read only when
    ``dropout_p > 0``). ``supported`` says which shapes. Jitted, so the
    blocks of a program share one trace and one lowering."""
    grid, rows, keys = _specs(q, k, n_head, bq)
    seed = seed if dropout_p > 0 else None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_head=n_head, bq=bq, causal=causal,
                          dropout_p=dropout_p),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        **_grid_spec(grid, [rows, keys, keys], rows, [], seed),
    )(*_seed_args(seed), q, k, v)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def pairs_backward(q, k, v, g, seed, n_head, causal, dropout_p, bq,
                   interpret):
    """(dq, dk, dv, o) for ``pairs_forward``'s o and its cotangent g,
    from q, k, v alone (the module's docstring says why)."""
    from jax.experimental.pallas import tpu as pltpu
    grid, rows, keys = _specs(q, k, n_head, bq)
    seed = seed if dropout_p > 0 else None
    tk = k.shape[1]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n_head=n_head, bq=bq, nq=grid[2],
                          causal=causal, dropout_p=dropout_p),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype)],
        interpret=interpret,
        **_grid_spec(grid, [rows, keys, keys, rows],
                     [rows, keys, keys, rows],
                     [pltpu.VMEM((tk, LANES), jnp.float32),
                      pltpu.VMEM((tk, LANES), jnp.float32)],
                     seed),
    )(*_seed_args(seed), q, k, v, g)
