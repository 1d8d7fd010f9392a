"""Page-table K/V gather for the paged KV cache (Pallas TPU; ISSUE 17
tentpole, page-granular since ISSUE 25).

The paged decode attention reads each slot's K/V through its page
table: logical cache position ``j`` of slot ``b`` lives at flat pool
row ``table[b, j // page_size] * page_size + j % page_size`` of the
``[n_pages * page_size, H * D]`` pool view — since ISSUE 28 a bitcast
of the pool variable, which is declared ``[n_pages, page_size, H * D]``
so that the TPU keeps it row-major at rest
(``ops/kv_attention.py:_paged_pools``; as ``[.., H, D]`` with D = 64 it
was transposed into this view, whole, before every call). So every run
of ``page_size`` gathered rows is ONE WHOLE PAGE, contiguous in the
pool and starting at a multiple of ``page_size`` rows. Two kernels read
it,
and ``ops/kv_attention.py:_paged_gather`` picks between them from the
storage dtype, the page size and the codec:

- :func:`gather_pages` — ``pool.reshape(n_pages, ps, D)[pages]``, a
  page per DMA. Page ids ride in SMEM by scalar prefetch, pool and
  result both stay in HBM (``pl.ANY``) and each page moves with one
  aligned HBM-to-HBM copy, ``_WINDOW`` of them in flight at any time: no
  byte crosses VMEM, nothing is read that is not wanted, nothing is
  selected. It needs ``page_size`` to be a whole number of the dtype's
  sublane tiles (Mosaic slices HBM only at tile granularity): fp32 and
  bf16 pages of 16 rows qualify, int8 pages (32-row tile) do not. On
  the v5e a call over the benchmark's pool (3072 pages of 16 x 1024
  fp32, 201 MB in and out) takes 0.70 ms, 70 % of the HBM roofline,
  where the row kernel took 12.1 ms (PERF.md, PR 25).
- :func:`gather_rows` — ``pool[rows] -> [K, D]`` for ARBITRARY row
  indices, a row per DMA (the kernel of ``embed_cache.py``, whose
  docstring has why each DMA carries the row's whole sublane-tile
  group). The paged pool keeps it for int8 storage and for page sizes
  that are not whole tiles; the hot-rows embedding cache is its other
  user.
- :func:`gather_rows_dequant` — the codec read: int8 code rows gathered
  by the row kernel and multiplied, in VMEM before the output tile is
  written, by their fp32 per-(position, head) scales —
  ``FLAGS_kv_cache_codec=int8`` never materializes a full-pool fp32
  copy. Mosaic has no in-kernel ``[D] -> [H, Dk]`` reshape, so the
  gathered ``[K, H]`` scales are broadcast to ``[K, D]`` outside the
  ``pallas_call`` and enter as a regular VMEM tile.

Indices are clamped into range by all three: page-table sentinel
entries (unallocated span, inactive slots) point one past the pool, and
what they gather is garbage the attention mask zeroes exactly. Every
table entry is gathered, sentinels included — the result is fully
written, so ``p @ V`` never meets uninitialised memory.

Page WRITES (one row per decode step per slot, a whole prompt per
prefill) stay on the jnp scatter-with-drop path in
``ops/kv_attention.py``: they are the donated in-place pool update the
``proglint --memory`` audit gates, and XLA already emits them as an
in-place dynamic-update per row.

All run under ``interpret=True`` on the CPU test backend
(tests/test_pallas_kernels.py discipline; tier selection via
``ops.pallas.kernel_enabled``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.embed_cache import gather_rows  # noqa: F401

# page copies in flight: 8 already saturate the v5e's DMA engine on
# 64 KB pages (0.70 ms a call from 8 to 64, my chip run, PR 25); 32
# keeps small pages moving too and costs 32 semaphores
_WINDOW = 32


def _gather_pages_kernel(pages_ref, pool_hbm, out_hbm, sem_ref, *,
                         ps, n, window):
    """pages_ref [n] in SMEM (pre-clamped into range); pool_hbm [R, D]
    and out_hbm [n * ps, D] in HBM; sem_ref [window] DMA semaphores.
    Copy k moves page ``pages_ref[k]`` to output rows ``[k*ps, (k+1)*ps)``
    on semaphore ``k % window``; as soon as copy k has landed its
    semaphore carries copy ``k + window``."""
    def page_dma(k):
        src = pl.multiple_of(pages_ref[k] * ps, ps)
        dst = pl.multiple_of(k * ps, ps)
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(src, ps), :],
            out_hbm.at[pl.ds(dst, ps), :], sem_ref.at[k % window])

    def prime(k, carry):
        page_dma(k).start()
        return carry

    def roll(k, carry):
        page_dma(k).wait()

        @pl.when(k + window < n)
        def _():
            page_dma(k + window).start()
        return carry

    jax.lax.fori_loop(0, min(window, n), prime, 0)
    jax.lax.fori_loop(0, n, roll, 0)


def gather_pages(pool, pages, page_size: int, interpret: bool = False):
    """pool [R, D] (the flat view of ``n_pages = R // page_size``
    pages), pages [N] int (the flattened page table; ids are clamped
    into ``[0, n_pages)`` — a sentinel reads the last page, which the
    caller masks) -> [N * page_size, D] in the pool's dtype, equal to
    ``pool.reshape(n_pages, page_size, D)[pages].reshape(-1, D)`` bit
    for bit. ``page_size`` must be a whole number of the dtype's
    sublane tiles (``embed_cache.sublane_tile``)."""
    r, d = pool.shape
    n = pages.shape[0]
    pages = jnp.clip(pages.astype(jnp.int32), 0, r // page_size - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # page ids live in SMEM
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # pool in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_WINDOW,))],
    )
    return pl.pallas_call(
        functools.partial(_gather_pages_kernel, ps=page_size, n=n,
                          window=_WINDOW),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n * page_size, d), pool.dtype),
        interpret=interpret,
        name="gather_pages",
    )(pages, pool)


def gather_rows_dequant(pool, scales, rows, heads: int,
                        interpret: bool = False):
    """pool [R, H*Dk] int8, scales [R, H] fp32, rows [K] int ->
    [K, H*Dk] fp32 = pool[rows] * scales[rows] per head — the
    dequantizing gather of ``FLAGS_kv_cache_codec=int8``."""
    r, d = pool.shape
    if d % heads:
        raise ValueError(f"row width {d} not divisible by heads {heads}")
    idx = jnp.clip(rows.astype(jnp.int32), 0, r - 1)
    row_scales = jnp.repeat(jnp.take(scales, idx, axis=0), d // heads,
                            axis=1)                     # [K, D]
    return gather_rows(pool, idx, scales=row_scales, interpret=interpret)
