"""Row gather/scatter kernels over an HBM-resident ``[R, D]`` table
(Pallas TPU; ISSUE 14 tentpole — the TPP argument, arXiv:2104.05755:
keep the cache maintenance hot loop a small set of reusable TPU-native
primitives instead of bespoke per-model code). The hot-rows embedding
cache uses both. The paged KV pool (``paged_attention.py``) reuses the
gather only where its indices are NOT whole aligned pages — int8
storage (with the dequant) and page sizes that are not a whole number
of tiles; fp32 and bf16 pools move a page per DMA there
(``gather_pages``, ISSUE 25).

Row indices ride in SMEM via scalar prefetch and the table stays in HBM
(``pl.ANY``). Mosaic slices an HBM ref only at whole-tile granularity
along the second-minor dim (8 rows of a 32-bit dtype, 16 of bf16, 32 of
int8 — :func:`sublane_tile`), so a row never moves alone: each DMA
carries the ALIGNED TILE GROUP that contains the row, on a 2-slot
rotation so the next group's DMA overlaps the current one, and the row
is selected from the group in VMEM (a masked integer-domain reduce —
bit-exact for every dtype, -0.0 and NaN payloads included). That is a
``sublane_tile``-fold read amplification and one DMA a row: 242 ns a
row and 4 % of the HBM roofline on the v5e (PERF.md, PR 23-25) — the
price of arbitrary row indices, which the embedding cache has.

- :func:`gather_rows` — ``table[rows] -> [K, D]``, optionally scaled
  per element in the same grid step (the int8 KV dequant).
- :func:`scatter_rows` — ``table.at[slots].set(rows)`` with the table
  buffer aliased in-place: read-modify-write of the row's tile group.
  Slots outside ``[0, R)`` are DROPPED, which is what makes the pow2
  bucket padding of ``ops/embed_cache.py`` free: padding slots point
  one past the pad row and simply never write.

A table whose row count is not a multiple of its tile is padded to one
first (a copy — the embedding cache's ``[capacity + 1, D]`` arrays pay
it today; no caller turns ``use_pallas`` on yet). Both kernels run
under ``interpret=True`` on the CPU test backend
(tests/test_pallas_kernels.py discipline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sublane_tile(dtype) -> int:
    """Rows in one (sublane, 128-lane) tile of ``dtype``."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def pad_to(x, n: int, axis: int = 0, value=0):
    """Zero-(or ``value``-)pad ``axis`` of ``x`` up to a multiple of
    ``n``."""
    extra = -x.shape[axis] % n
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, constant_values=value)


def _select_row(group, r):
    """group [g, D] (any dtype), r scalar -> row r as [1, D] int32:
    the value for integer dtypes, the fp32 bit pattern for floats. One
    nonzero term per lane, summed in the integer domain: exact."""
    if jnp.issubdtype(group.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(
            group.astype(jnp.float32), jnp.int32)
    else:
        bits = group.astype(jnp.int32)
    sub = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    return jnp.sum(jnp.where(sub == r, bits, 0), axis=0, keepdims=True)


def _gather_kernel(rows_ref, table_hbm, *refs, g, bb, scaled):
    """rows_ref [Kp] in SMEM (pre-clamped into range); table_hbm [R, D]
    in HBM; optional scale_ref [bb, D] fp32 tile; o_ref [bb, D] output
    tile; grp_ref [2, g, D] tile-group double buffer."""
    if scaled:
        scale_ref, o_ref, grp_ref, sem_ref = refs
    else:
        o_ref, grp_ref, sem_ref = refs
    i = pl.program_id(0)

    def group_dma(buf, j):
        start = pl.multiple_of((rows_ref[i * bb + j] // g) * g, g)
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(start, g), :],
            grp_ref.at[buf], sem_ref.at[buf])

    group_dma(0, 0).start()
    for j in range(bb):                         # static sublane unroll
        if j + 1 < bb:
            group_dma((j + 1) % 2, j + 1).start()
        group_dma(j % 2, j).wait()
        row = _select_row(grp_ref[j % 2], rows_ref[i * bb + j] % g)
        if scaled:
            val = row.astype(jnp.float32) * scale_ref[pl.ds(j, 1), :]
        elif jnp.issubdtype(table_hbm.dtype, jnp.floating):
            val = jax.lax.bitcast_convert_type(row, jnp.float32)
        else:
            val = row
        o_ref[pl.ds(j, 1), :] = val.astype(o_ref.dtype)


def gather_rows(table, rows, scales=None, interpret: bool = False):
    """table [R, D], rows [K] int -> [K, D] = table[rows] in the
    table's dtype (rows are clamped into range — the caller's padding
    or sentinel entries read an edge row it then discards). With
    ``scales`` [K, D] fp32 the result is ``table[rows] * scales`` in
    fp32 — the dequantizing read of an int8 table."""
    r, d = table.shape
    k = rows.shape[0]
    g = sublane_tile(table.dtype)
    out_dtype = jnp.float32 if scales is not None else table.dtype
    bb = sublane_tile(out_dtype)                # rows per grid step
    rows = pad_to(jnp.clip(rows.astype(jnp.int32), 0, r - 1), bb)
    kp = rows.shape[0]
    operands = [rows, pad_to(table, g)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]      # table in HBM
    if scales is not None:
        operands.append(pad_to(scales.astype(jnp.float32), bb))
        in_specs.append(pl.BlockSpec((bb, d), lambda i, rows: (i, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # row ids live in SMEM
        grid=(kp // bb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, d), lambda i, rows: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, g, d), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, g=g, bb=bb,
                          scaled=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kp, d), out_dtype),
        interpret=interpret,
        name="gather_rows",
    )(*operands)
    return out[:k]


def _scatter_kernel(slots_ref, rows_ref, table_hbm, table_out, grp_ref,
                    sem_ref, *, rows_total, cap, g):
    """slots_ref [Kp] in SMEM; rows_ref [g, D] VMEM tile of new rows;
    table_out is the SAME buffer as table_hbm (input_output_alias).
    Per in-range slot: DMA its tile group in, overwrite the one row in
    VMEM, DMA the group back — strictly serial, so two slots sharing a
    group compose."""
    del table_hbm                       # aliased: table_out IS the table
    i = pl.program_id(0)
    for j in range(g):                  # static sublane unroll
        k = i * g + j
        slot = slots_ref[k]

        @pl.when((k < rows_total) & (slot >= 0) & (slot < cap))
        def _():
            start = pl.multiple_of((slot // g) * g, g)
            group = table_out.at[pl.ds(start, g), :]
            load = pltpu.make_async_copy(group, grp_ref, sem_ref.at[0])
            load.start()
            load.wait()
            sub = jax.lax.broadcasted_iota(jnp.int32, grp_ref.shape, 0)
            grp_ref[...] = jnp.where(sub == slot % g,
                                     rows_ref[pl.ds(j, 1), :],
                                     grp_ref[...])
            store = pltpu.make_async_copy(grp_ref, group, sem_ref.at[1])
            store.start()
            store.wait()


def scatter_rows(table, slots, rows, interpret: bool = False):
    """table [R, D], slots [K] int, rows [K, D] -> table with
    ``table[slots[k]] = rows[k]`` for every in-range slot; slots >= R
    (or < 0) are dropped. The table buffer is donated/aliased — the
    update is in-place in HBM when R is a multiple of the dtype's
    sublane tile."""
    r, d = table.shape
    k = slots.shape[0]
    g = sublane_tile(table.dtype)
    slots = pad_to(slots.astype(jnp.int32), g, value=r)    # dropped
    rows = pad_to(rows.astype(table.dtype), g)
    padded = pad_to(table, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots.shape[0] // g,),
        in_specs=[pl.BlockSpec((g, d), lambda i, slots: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # table in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((g, d), table.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, rows_total=k, cap=r, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(padded.shape, table.dtype),
        # inputs are (slots, rows, table) after scalar prefetch: alias
        # the table operand onto the output buffer (in-place install)
        input_output_aliases={2: 0},
        interpret=interpret,
    )(slots, rows, padded)
    return out[:r]
