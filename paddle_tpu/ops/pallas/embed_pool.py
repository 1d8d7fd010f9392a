"""Fused embedding gather + masked sum-pool kernel (Pallas TPU).

The refer tier of `fused_embedding_seq_pool` gathers ``W[ids]`` into a
``[B, T, D]`` tensor in HBM, masks it, and sum-reduces over T — three
full-width HBM passes over an intermediate that exists only to be reduced
away. This kernel does the whole thing in one pass: ids and lens ride in
SMEM via scalar prefetch, each grid step owns an 8-row output tile, and
per (row, t) the id'd table row is DMA'd HBM→VMEM (double-buffered so the
next row's fetch overlaps the current accumulate) straight into an fp32
accumulator. The ``[B, T, D]`` intermediate never exists.

The reference's CPU counterpart is the fused_embedding_seq_pool_op +
jit seqpool microkernel pair (operators/fused/fused_embedding_seq_pool_op.cc,
operators/jit/); the bandwidth argument for keeping the pooled working set
on-chip is the TPP/XLA-fusion one (PAPERS.md: arxiv 2104.05755, 2301.13062).

Backward never runs through the kernel: training uses the row-sparse
(rows, values) VJP emitted by ops/grad_ops.py; the custom_vjp here exists
so a *densified* fallback (FLAGS_disable_sparse_grad, or a program that
differentiates ids-producing inputs) still traces — it returns the same
dense scatter-add gradient the refer tier would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.embed_cache import pad_to, sublane_tile

_BB = 8             # batch rows per grid step (fp32 sublane tile)


def _embed_pool_kernel(ids_ref, lens_ref, w_hbm, o_ref, grp_ref, sem_ref,
                       *, t_total, g):
    """ids_ref [Bp, T] / lens_ref [Bp] in SMEM (scalar prefetch);
    w_hbm [Vp, D] stays in HBM; o_ref [BB, D] output tile in VMEM;
    grp_ref [2, g, D] VMEM double buffer; sem_ref DMA semaphores (2,).
    Mosaic slices an HBM ref only at whole sublane tiles, so each DMA
    carries the aligned g-row group holding the id'd row and the row is
    picked out by mask in VMEM (see embed_cache.py)."""
    i = pl.program_id(0)
    d = o_ref.shape[-1]

    for j in range(_BB):                       # static sublane unroll
        b = i * _BB + j
        n = lens_ref[b]

        def group_dma(slot, t):
            start = pl.multiple_of((ids_ref[b, t] // g) * g, g)
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(start, g), :],
                grp_ref.at[slot], sem_ref.at[slot])

        group_dma(0, 0).start()

        def body(t, acc):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < t_total)
            def _():
                group_dma(jax.lax.rem(t + 1, 2), t + 1).start()

            group_dma(slot, t).wait()
            grp = grp_ref[slot].astype(jnp.float32)         # [g, D]
            sub = jax.lax.broadcasted_iota(jnp.int32, grp.shape, 0)
            keep = (sub == ids_ref[b, t] % g) & (t < n)
            return acc + jnp.sum(jnp.where(keep, grp, 0.0), axis=0,
                                 keepdims=True)

        acc = jax.lax.fori_loop(0, t_total, body,
                                jnp.zeros((1, d), jnp.float32))
        o_ref[pl.ds(j, 1), :] = acc.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_embed_seq_pool(w, ids, lens, interpret=False):
    """w [V, D], ids [B, T] int, lens [B] (or None: all T valid) →
    [B, D] = sum over t < lens[b] of w[ids[b, t]]."""
    return _embed_pool_impl(w, ids, lens, interpret)


def _embed_pool_impl(w, ids, lens, interpret=False):
    v, d = w.shape
    b, t = ids.shape
    ids = jnp.clip(ids.astype(jnp.int32), 0, v - 1)
    if lens is None:
        lens = jnp.full((b,), t, jnp.int32)
    lens = lens.reshape(-1).astype(jnp.int32)
    if b % _BB != 0:
        pad = _BB - b % _BB
        ids = jnp.concatenate([ids, jnp.zeros((pad, t), ids.dtype)])
        lens = jnp.concatenate([lens, jnp.zeros((pad,), lens.dtype)])
    bp = ids.shape[0]
    g = sublane_tile(w.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # ids + lens live in SMEM
        grid=(bp // _BB,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # W stays in HBM
        out_specs=pl.BlockSpec((_BB, d), lambda i, ids, lens: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, g, d), w.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_embed_pool_kernel, t_total=t, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, d), w.dtype),
        interpret=interpret,
    )(ids, lens, pad_to(w, g))
    return out[:b]


def _embed_pool_fwd(w, ids, lens, interpret):
    return _embed_pool_impl(w, ids, lens, interpret), \
        (ids, lens, w.shape)


def _embed_pool_bwd(interpret, res, g):
    # densified fallback gradient (the training path normally bypasses
    # this: ops/grad_ops.py emits the RowSparseGrad analytically); the
    # cotangent dtype matches the table dtype (fwd output dtype is w's)
    ids, lens, wshape = res
    b, t = ids.shape
    d = wshape[1]
    gx = jnp.broadcast_to(g[:, None, :], (b, t, d))
    if lens is not None:
        from paddle_tpu.ops.sequence_ops import _mask_bt
        gx = gx * _mask_bt(lens, b, t)[:, :, None].astype(g.dtype)
    dw = jnp.zeros(wshape, g.dtype).at[ids.reshape(-1).astype(jnp.int32)] \
        .add(gx.reshape(b * t, d), mode="drop")
    return dw, None, None


fused_embed_seq_pool.defvjp(_embed_pool_fwd, _embed_pool_bwd)
