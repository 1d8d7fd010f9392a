"""The expert layer's grouped products (Pallas TPU): rows sorted by held
expert, each group through ITS expert's matrix, in row tiles that follow
the groups — and only as far as the groups go.

``ops/expert_ffn.py``'s grouped way sorts a prefill's held assignments
by expert into a buffer ``[R, K]`` and multiplies group ``e``'s rows by
``w[e]``. ``jax.lax.ragged_dot`` does that at 19-70 TFLOP/s on the v5e
and in a time that follows ``R``, not the rows that hold a token (3.22
ms at a 6 400-row buffer, 4.82 at 10 240, the same draw; PERF.md, PR
44). Here the groups' sizes (on the device before the products start)
become a table of VISITS that rides in SMEM by scalar prefetch — the
arrangement of ``jax.experimental.pallas.ops.tpu.megablox.gmm``:

- a visit is (row tile, expert): a tile that lies inside one group is
  visited once, a tile that straddles a boundary once per expert that
  has rows in it, the other rows masked out of the store (the output
  tile stays resident between consecutive visits of one row tile);
- visits are in row order, so an expert's visits are consecutive and
  its weight tile is copied once — BY HAND, one GROUP ahead: the
  matrices stay in HBM, the first visit of a group waits for its tile
  (started a group earlier) and starts the next live group's into the
  other of two VMEM slots. The pipeline's own double buffer looks one
  VISIT ahead, and a visit of 128 rows multiplies for 9.5 us where a
  group change copies LFM2's 14.7 MB of gate and up in 18: left to the
  pipeline the three products of a 2 048-token prefill's layer took
  1.75 ms, by hand 1.30 (PERF.md section 6, PR 64);
- **the visits past the last group are at the tail of the table and go
  on naming the row tile that is resident**: the pipeline issues no
  copy for an unchanged block index and the body is off, so a dead row
  tile costs a grid step's bookkeeping (~0.5 us) and neither a product
  nor a weight's bytes. The call's time follows ``sizes.sum()``, not
  ``R``. Rows past the last group are never written: they hold whatever
  the buffer held (the caller SELECTS live rows, as it did over
  ``ragged_dot``).

A visit multiplies the row tile ``[tm, K]`` — the whole contraction, no
accumulator round trip — by a tile ``[K, tn]`` of the expert's matrix in
the storage dtype with float32 accumulation. Two bodies share that
arrangement:

- :func:`grouped_matmul`: one matrix per expert, float32 out (the down
  product; the backward's recompute of gate and up);
- :func:`grouped_swiglu`: the gate AND the up matrix in one pass over
  the rows — the row tile is read once, the two float32 products stay in
  VMEM, and ``silu(g) * u`` is computed there in float32 and cast once
  to the storage dtype: the hidden rows ``[R, F]`` are all that is
  written (the two ``f32[R, F]`` results, 117 MB each at 16 384 x 1 792,
  never are).

Tiles follow the shapes of the call (:func:`tiles`): rows in tiles of
``ROW_TILE``, columns in the widest whole number of lane tiles that
divides ``N`` and keeps ONE weight tile at or under ``TILE_BYTES``.
VMEM: the weight tiles' two slots (two matrices in the fused body:
4 x 7.3 MB at LFM2's 2 048 x 1 792), the row tile and the output tile
double-buffered and the float32 products beside them — 33 MB at LFM2's
gate / up, the most of the shapes served; the call asks for
``VMEM_LIMIT_BYTES`` = 64 MB of the v5e's 128, as ``expert_stream.py``
does (``paged_attention.py`` stays under the 16 MB scoped default with
tiles of 8 MB).

Runs under ``interpret=True`` on the CPU test backend
(tests/test_grouped_matmul.py); which calls engage it is
``ops/expert_ffn.py:grouped_path``'s to say."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
VMEM_LIMIT_BYTES = 64 << 20
# bytes of ONE weight tile [K, tn] a visit multiplies by
TILE_BYTES = 8 << 20
# rows of a tile: one lane tile. The op alone on one v5e (PERF.md section
# 6, PR 64): LFM2's three products of a 4 096-token prefill 1.98 ms at
# 128 rows, 2.38 at 256, 3.31 at 512 (``ragged_dot``, whose own tiles are
# 512 rows, 5.89); Granite's 0.74 / 0.86; Trinity's 3.67 / 4.37 / 6.43 —
# a visit multiplies a WHOLE tile, so at a few hundred rows an expert the
# smaller tile wastes fewer rows on its straddles than it loses by
# streaming fewer rows past each weight tile
ROW_TILE = 128


def tiles(rows: int, k: int, n: int, itemsize: int,
          row_tile: int = 0, col_tile: int = 0) -> tuple[int, int]:
    """(tm, tn) for a buffer of ``rows`` rows contracted over ``k`` into
    ``n`` columns, from the shapes alone; (0, 0) where no whole tiles
    fit (``rows`` not whole tiles of ``ROW_TILE``, ``k`` or ``n`` not
    whole lane tiles): the caller keeps ``ragged_dot``."""
    tm = row_tile or ROW_TILE
    if k % _LANES or n % _LANES or rows % tm:
        return 0, 0
    most = max(_LANES, TILE_BYTES // (k * itemsize) // _LANES * _LANES)
    tn = col_tile or next(t for t in range(min(n, most), 0, -_LANES)
                          if n % t == 0)
    return (tm, tn) if n % tn == 0 else (0, 0)


@functools.partial(jax.jit, static_argnames=("rows", "tm"))
def visit_tables(sizes, rows: int, tm: int):
    """sizes [E] (rows of each group, in order, from row 0) -> the
    kernel's tables, all int32. By visit, [V] with V = rows / tm + E - 1,
    the most any draw needs: ``tile`` / ``group`` (its row tile and
    expert; past the live visits the last live one again). By group,
    [E]: ``starts`` / ``ends`` (its rows, clipped to the buffer) and
    ``v_start`` / ``v_end``
    (its visits: at the first its weights are waited for, and the visit
    at ``v_end`` names the next group with rows, whose weights it then
    starts). And ``n_live`` [1]. Masked sums: no sort, no cumsum (a
    window reduction, which the long prefills' modules keep out), and
    ``lax`` operations over ONE membership matrix in place of indexing,
    ``where`` and ``//`` — each of those is a function of its own at
    every lowering of every program, Python that a serving cell's
    set-up pays (PERF.md section 6, PR 64). Jitted: the two calls of a
    turn and every layer of a program share one trace."""
    n_groups = sizes.shape[0]
    n_tiles = rows // tm
    e = jax.lax.iota(jnp.int32, n_groups)
    at = jax.lax.iota(jnp.int32, n_tiles + n_groups - 1)
    upto = (e[:, None] <= e[None, :]).astype(jnp.int32)          # [E, E]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.sum(upto * sizes[:, None], axis=0)
    # (a bare call's groups stop at the buffer's last row)
    starts, ends = jnp.minimum(ends - sizes, rows), jnp.minimum(ends, rows)
    first = jax.lax.div(starts, tm)
    # visits of a group: the row tiles its rows touch (none: no visit)
    count = (ends > starts) * (jax.lax.div(ends - 1, tm) - first + 1)
    v_end = jnp.sum(upto * count[:, None], axis=0)
    v_start = v_end - count
    n_live = jnp.sum(count)
    v = jnp.minimum(at, jnp.maximum(n_live - 1, 0))
    member = ((v_start[None, :] <= v[:, None])
              & (v[:, None] < v_end[None, :])).astype(jnp.int32)  # [V, E]
    group = jnp.sum(member * e[None, :], axis=1)
    tile = v + jnp.sum(member * (first - v_start)[None, :], axis=1)
    return (jnp.minimum(tile, n_tiles - 1), group, starts, ends, v_start,
            v_end, n_live[None])


def _kernel(swiglu, tn, tile_ref, group_ref, starts_ref, ends_ref,
            v_start_ref, v_end_ref, n_live_ref, x_ref, *rest):
    """One visit. x_ref [tm, K] the row tile; then the weight matrices
    [E, K, N] in HBM (gate and up with ``swiglu``, else one), o_ref
    [tm, tn] the resident output tile, wbuf [n_w, 2, K, tn] the weight
    tiles' two slots, sem [n_w, 2] a DMA semaphore each and side [1] in
    SMEM: the slot of the group being visited."""
    *w_hbm, o_ref, wbuf, sem, side = rest
    j, v = pl.program_id(0), pl.program_id(1)
    n_live = n_live_ref[0]
    column = pl.multiple_of(j * tn, _LANES)

    def copies(g, slot):
        return [pltpu.make_async_copy(w.at[g, :, pl.ds(column, tn)],
                                      wbuf.at[i, slot], sem.at[i, slot])
                for i, w in enumerate(w_hbm)]

    @pl.when(v < n_live)
    def _():
        g = group_ref[v]

        @pl.when(v == v_start_ref[g])       # the group's first visit
        def _():
            @pl.when(v == 0)            # nobody was there to start these
            def _():
                side[0] = 1
                for c in copies(g, 0):
                    c.start()
            slot = 1 - side[0]
            side[0] = slot
            for c in copies(g, slot):
                c.wait()
            after = v_end_ref[g]

            @pl.when(after < n_live)
            def _():
                for c in copies(group_ref[after], 1 - slot):
                    c.start()
        slot = side[0]
        x = x_ref[...]
        value = jnp.dot(x, wbuf[0, slot], preferred_element_type=F32)
        if swiglu:
            value = jax.nn.silu(value) * jnp.dot(
                x, wbuf[1, slot], preferred_element_type=F32)
        # the rows of this visit's group into the resident output tile;
        # the tile's other rows stay what they are
        row = tile_ref[v] * o_ref.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        mine = (row >= starts_ref[g]) & (row < ends_ref[g])
        o_ref[...] = jnp.where(mine, value.astype(o_ref.dtype), o_ref[...])


def _call(swiglu, name, x, weights, sizes, out_dtype, row_tile, col_tile,
          interpret):
    rows, k = x.shape
    n = weights[0].shape[2]
    tm, tn = tiles(rows, k, n, x.dtype.itemsize, row_tile, col_tile)
    if not tm:
        raise ValueError(f"no whole tiles for rows [{rows}, {k}] into "
                         f"{n} columns (tiles {row_tile} x {col_tile})")
    tables = visit_tables(sizes, rows, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables),        # the tables: SMEM
        grid=(n // tn, tables[0].shape[0]),
        in_specs=[pl.BlockSpec((tm, k),
                               lambda j, v, tile, *_: (tile[v], 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, v, tile, *_: (tile[v], j)),
        scratch_shapes=[pltpu.VMEM((len(weights), 2, k, tn), x.dtype),
                        pltpu.SemaphoreType.DMA((len(weights), 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, swiglu, tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n * len(weights),
            transcendentals=rows * n * (len(weights) - 1),
            bytes_accessed=(rows * k * (n // tn) + sum(
                w.size for w in weights)) * x.dtype.itemsize
            + rows * n * jnp.dtype(out_dtype).itemsize),
        name=name,
        interpret=interpret,
    )(*tables, x, *weights)


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "col_tile", "interpret"))
def grouped_matmul(x, w, sizes, row_tile: int = 0, col_tile: int = 0,
                   interpret=False):
    """x [R, K] sorted by group, w [E, K, N] in x's dtype, sizes [E] int
    (rows of each group, from row 0) -> [R, N] float32: group ``e``'s
    rows times ``w[e]``. Rows past the last group are not written."""
    return _call(False, "grouped_matmul", x, (w,), sizes, F32,
                 row_tile, col_tile, interpret)


@functools.partial(jax.jit,
                   static_argnames=("row_tile", "col_tile", "interpret"))
def grouped_swiglu(x, w_gate, w_up, sizes, row_tile: int = 0,
                   col_tile: int = 0, interpret=False):
    """x [R, K] sorted by group, w_gate / w_up [E, K, F] in x's dtype,
    sizes [E] int -> the hidden rows [R, F] in x's dtype: ``silu(x
    w_gate[e]) * (x w_up[e])`` for group ``e``'s rows, the products and
    the activation in float32, cast once. Rows past the last group are
    not written."""
    return _call(True, "grouped_swiglu", x, (w_gate, w_up), sizes,
                 x.dtype, row_tile, col_tile, interpret)
