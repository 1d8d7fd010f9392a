"""Page-table K/V gather for the paged KV cache (Pallas TPU; ISSUE 17
tentpole).

The paged decode attention reads each slot's K/V through its page
table: logical cache position ``j`` of slot ``b`` lives at flat pool
row ``table[b, j // page_size] * page_size + j % page_size`` of the
``[n_pages * page_size, H * D]`` pool view. The row-index vector is
computed in-graph from the (static-shape) page-table feed and rides
into the kernel via SCALAR PREFETCH — the row-gather kernel of
``embed_cache.py``, whose module docstring has the DMA construction
(and why each DMA carries the row's whole sublane-tile group).

- :func:`gather_rows` — ``pool[rows] -> [K, D]`` for fp32 / bf16 / int8
  storage, rows clamped into range (page-table sentinel entries —
  unallocated span, inactive slots — point one past the pool; their
  gathered rows are garbage the attention mask zeroes exactly).
- :func:`gather_rows_dequant` — the codec read: int8 code rows gathered
  by the kernel and multiplied, in VMEM before the output tile is
  written, by their fp32 per-(position, head) scales —
  ``FLAGS_kv_cache_codec=int8`` never materializes a full-pool fp32
  copy. Mosaic has no in-kernel ``[D] -> [H, Dk]`` reshape, so the
  gathered ``[K, H]`` scales are broadcast to ``[K, D]`` outside the
  ``pallas_call`` and enter as a regular VMEM tile.

Page WRITES (one row per decode step per slot, a whole prompt per
prefill) stay on the jnp scatter-with-drop path in
``ops/kv_attention.py``: they are the donated in-place pool update the
``proglint --memory`` audit gates, and XLA already emits them as an
in-place dynamic-update per row.

Both run under ``interpret=True`` on the CPU test backend
(tests/test_pallas_kernels.py discipline; tier selection via
``ops.pallas.kernel_enabled``).
"""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.ops.pallas.embed_cache import gather_rows  # noqa: F401


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
