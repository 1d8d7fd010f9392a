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
pool and starting at a multiple of ``page_size`` rows. Three kernels
COPY it out, and ``ops/kv_attention.py:_paged_gather`` picks between
them from the storage dtype, the page size and the codec; a fourth and
a fifth, :func:`attend_pages` and :func:`score_pages`, copy nothing
(below):

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
what they gather is garbage the attention mask zeroes exactly. A GATHER
copies every table entry, sentinels included — the result is fully
written, so ``p @ V`` never meets uninitialised memory — and what
attends it reads the copy again.

- :func:`attend_pages` — the attention itself over a slot's LIVE pages,
  read where they lie: the table rides in SMEM, a block of pages is
  copied page by page into one of two VMEM tiles under the products of
  the block before, the softmax runs online in float32. No array of
  gathered rows exists, and a page that holds no token (the padding
  between a prompt's end and its bucket, pages leased beyond ``pos``,
  an idle slot's, sentinels) is never read. One plane whose rows are
  key and value at once (a latent cache: ``ops/mla.py``, PR 34), or a
  key plane and a VALUE plane of another width under the same table (a
  full grouped-KV layer's decode step:
  ``ops/kv_attention.py:attends_in_place`` says where, PR 62).
- :func:`score_pages` — the DSA indexer's scores ``sum_j w_j ReLU(q_j .
  row)`` of a slot's LIVE rows of the index plane, read where they lie
  (``ops/mla.py:scores_in_place`` says where, PR 66). It is
  ``attend_pages``' walk with another block of work: ONE ``_page_walk``
  in this file holds what the two share — the plan (``_attend_plan``:
  the page to read for every table entry, the live blocks), the scalar
  prefetch, the two tiles, the page copies and their waits, the
  hand-over of the tiles from a slot to the next — and each kernel
  brings what it does with a landed tile: an online softmax and ``p .
  rows`` there, one product, a ReLU and a weighted sum over the
  indexer's heads here, stored to the block's row of the slot's scores.

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
import numpy as np
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


# rows of a slot's cache attended per product of ``attend_pages``: a
# block is this many rows' worth of whole pages, copied page by page
# into one of two VMEM tiles. One latent layer of 32 slots x 7 140 live
# rows of bf16[640] on the v5e: 0.587 ms at 512, 0.539 at 1024, 0.583 at
# 2048 (a slot's two part-filled blocks re-read more) (my chip run, PR 34)
_ATTEND_ROWS = 1024
_LANES = 128
# VMEM the kernel's tiles may take, both buffers of both planes: the
# block FOLLOWS THE ROW WIDTH. The compiler's default scoped limit on the
# v5e is 16 MiB and the call sets no other; half of it leaves room for
# the query, the bias, the float32 accumulator and the products'
# intermediates. It binds nowhere the driver has measured (PRs 57, 58:
# 1 024 rows of 768 + 512 are 5.2 MB, of 512 + 512 4.2 MB; 256 rows of
# 1 024 + 1 024 2.1 MB; the latent plane's 1 024 rows of 640 2.6 MB) and
# gives rows of 3 840 + 3 840 (a multi-head layer of 30 heads of 128)
# blocks of 256 rows, 7.9 MB, where 768 rows would ask for 23.6 MB
_ATTEND_TILE_BYTES = 8 << 20


def attend_row_bytes(pool, values=None) -> int:
    """Bytes one cache row costs a tile of :func:`attend_pages`: the
    pool's width, and the value plane's where there is one (anything
    with a ``shape`` and a ``dtype``)."""
    width = pool.shape[1] + (values.shape[1] if values is not None else 0)
    return width * jnp.dtype(pool.dtype).itemsize


def attend_block_pages(max_pages: int, page_size: int,
                       rows: int = _ATTEND_ROWS, row_bytes: int = 0) -> int:
    """Pages a block of :func:`attend_pages` holds for a table of
    ``max_pages`` pages of ``page_size`` rows: the most that divide the
    table, fit ``rows`` rows, fill whole lane tiles (a block's scores
    are ``[H, rows]``) and — given what a row costs
    (:func:`attend_row_bytes`) — keep the two buffers of every plane
    within ``_ATTEND_TILE_BYTES``, or 0 where the geometry has no such
    block."""
    if row_bytes:
        rows = min(rows, _ATTEND_TILE_BYTES // (2 * row_bytes))
    for pb in range(min(max_pages, rows // page_size), 0, -1):
        if max_pages % pb == 0 and (pb * page_size) % _LANES == 0:
            return pb
    return 0


def _tile_precision(dtype):
    """How the in-place kernels multiply a tile: float32 planes at
    precision HIGHEST, narrower ones as the MXU takes them."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _page_walk(pages_ref, n1_ref, start2_ref, n_ref, planes, state, *,
               ps, mp, pb, n_slots):
    """The walk over a slot's live blocks that the in-place kernels
    share, one slot a grid step. SMEM (scalar prefetch): pages_ref
    [B * mp] the page to READ for each entry of each slot's table (a dead
    entry of a live block names a live page of that block:
    ``_attend_plan``), n1_ref / start2_ref / n_ref [B] the slot's live
    blocks — block k of its n is table block ``k`` while ``k < n1`` (the
    prompt's), ``start2 + k - n1`` after (the generated rows'). planes:
    one ``(hbm [R, W], tiles [2, pb * ps, W], sems [2])`` a plane read —
    a page of the table is a copy out of each plane, into the same rows
    of that plane's tile. state [2] in SMEM: the tile the next block
    lands in, and whether the previous slot already started this slot's
    first block.

    A block is pb pages aligned in the table, a page a DMA, every block
    pb of them whatever it holds: no branch in the walk. While block k
    is worked on the copies of the block after it are in flight — the
    NEXT slot's first after this slot's last, and where nothing comes
    after, this block again, waited for behind the loop and never read.

    Starts this slot's first block (unless the slot before did) and
    returns ``(block_at, run)``: ``block_at(k)`` the table block the
    slot's k-th live block is, and ``run(work, carry)``, which walks the
    live blocks — ``work(k, side, carry) -> carry`` finds block k landed
    in tile ``side`` of every plane — and hands the tiles over to the
    next slot."""
    b = pl.program_id(0)

    def block_of(slot, k):
        n1 = n1_ref[slot]
        return jnp.where(k < n1, k, start2_ref[slot] + k - n1)

    def copies(src, i, side):
        """The copies — one a plane — of pool rows ``[src, src + ps)``
        to page i of tile ``side``; ``src`` and ``i`` traced or plain
        integers."""
        aligned = lambda x: x if isinstance(x, int) \
            else pl.multiple_of(x, ps)                      # noqa: E731
        return [pltpu.make_async_copy(
            hbm.at[pl.ds(aligned(src), ps), :],
            tiles.at[side, pl.ds(aligned(i * ps), ps), :], sems.at[side])
            for hbm, tiles, sems in planes]

    # The walk's copies are straight-line code, pb starts and pb waits a
    # block: 7 % faster than a loop of eight a turn (0.542 against 0.590
    # ms a layer on the v5e). Every copy written out is ~20 ms of a
    # server's set-up (traced once a process, lowered once a program), so
    # the two places outside the walk, once a call each, loop.
    def start(slot, k, side, inline):
        """Start the pb page copies of the slot's k-th live block into
        tile ``side``."""
        first = slot * mp + block_of(slot, k) * pb

        def page(i):
            for c in copies(pages_ref[first + i] * ps, i, side):
                c.start()
        if inline:
            for i in range(pb):
                page(i)
        else:
            pl.loop(0, pb)(page)

    def wait(side, inline):
        """Wait for the pb page copies into tile ``side`` (a wait names
        the tile and the semaphore; where the page came from is
        nothing to it)."""
        def page(i):
            for c in copies(0, i, side):
                c.wait()
        if inline:
            for i in range(pb):
                page(i)
        else:
            pl.loop(0, pb)(page)

    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0

    n = n_ref[b]
    side0 = state[0]

    @pl.when((n > 0) & (state[1] == 0))
    def _():
        start(b, 0, side0, inline=False)
    nxt = jnp.minimum(b + 1, n_slots - 1)
    follows = (b + 1 < n_slots) & (n_ref[nxt] > 0)
    state[1] = ((n > 0) & follows).astype(jnp.int32)

    def run(work, carry):
        def block(k, carry):
            side = (side0 + k) % 2
            last = k + 1 == n
            start(jnp.where(last & follows, nxt, b),
                  jnp.where(last, jnp.where(follows, 0, k), k + 1),
                  1 - side, inline=True)
            wait(side, inline=True)
            return work(k, side, carry)

        carry = jax.lax.fori_loop(0, n, block, carry)

        @pl.when((n > 0) & jnp.logical_not(follows))
        def _():
            wait((side0 + n) % 2, inline=False)
        state[0] = (side0 + n) % 2
        return carry

    return (lambda k: block_of(b, k)), run


def _attend_pages_kernel(pages_ref, n1_ref, start2_ref, n_ref, q_ref,
                         bias_ref, pool_hbm, *rest, ps, mp, pb, vw, n_slots,
                         scale, precision, two_planes=False):
    """One slot a grid step, over the blocks ``_page_walk`` brings (its
    docstring has the scalar-prefetch operands, the tiles, the
    semaphores and ``state``). q_ref [1, H, W] and bias_ref [1, mp / pb,
    pb * ps] float32 (0 where a row is attended, -inf where not) in
    VMEM, pool_hbm [R, W] in HBM, out_ref [1, H, W], buf [2, pb * ps, W]
    the two tiles, sem [2] a DMA semaphore each.

    With ``two_planes`` the values are a plane of their own: values_hbm
    [R, Wv] follows pool_hbm (the KEY plane), out_ref is [1, H, Wv], and
    vbuf [2, pb * ps, Wv] / vsem [2] follow state. A block's rows' bias
    is one row of bias_ref."""
    if two_planes:
        values_hbm, out_ref, buf, sem, state, vbuf, vsem = rest
        planes = ((pool_hbm, buf, sem), (values_hbm, vbuf, vsem))
    else:
        out_ref, buf, sem, state = rest
        planes = ((pool_hbm, buf, sem),)
    f32 = jnp.float32
    block_at, run = _page_walk(pages_ref, n1_ref, start2_ref, n_ref, planes,
                               state, ps=ps, mp=mp, pb=pb, n_slots=n_slots)
    q = q_ref[0]

    def work(k, side, carry):
        m, l, acc = carry
        tile = buf[side]
        s = jax.lax.dot_general(q, tile, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=f32)
        s = s * scale + bias_ref[0, pl.ds(block_at(k), 1), :]
        top = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing attended yet keeps -inf: exp of (-inf - 0)
        # is the 0 it should be, of (-inf + inf) a NaN
        ref = jnp.where(top == -jnp.inf, 0.0, top)
        p = jnp.exp(s - ref)
        fade = jnp.exp(m - ref)
        acc = fade * acc + jax.lax.dot_general(
            p.astype(tile.dtype),
            vbuf[side] if two_planes else tile[:, :vw],
            (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)
        return top, fade * l + jnp.sum(p, axis=-1, keepdims=True), acc

    h, w = q.shape
    m, l, acc = run(work, (jnp.full((h, 1), -jnp.inf, f32),
                           jnp.zeros((h, 1), f32), jnp.zeros((h, vw), f32)))
    out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(out_ref.dtype)
    if two_planes:
        out_ref[0] = out
        return
    if vw < w:
        out_ref[0, :, vw:] = jnp.zeros((h, w - vw), out_ref.dtype)
    out_ref[0, :, :vw] = out


def _score_pages_kernel(pages_ref, n1_ref, start2_ref, n_ref, q_ref, w_ref,
                        pool_hbm, out_ref, buf, sem, state, *, ps, mp, pb,
                        n_slots, precision):
    """One slot a grid step, over the blocks ``_page_walk`` brings:
    q_ref [1, J, W] the indexer's queries and w_ref [1, J, 1] float32
    their head weights in VMEM, pool_hbm [R, W] the index plane in HBM,
    out_ref [1, mp / pb, pb * ps] float32 the slot's scores, a table
    block a row: zeros, then row ``block_at(k)`` = ``sum_j w_j ReLU(q_j .
    tile^T)`` for each live block k. buf [2, pb * ps, W], sem [2] and
    state [2] as the walk's."""
    f32 = jnp.float32
    block_at, run = _page_walk(pages_ref, n1_ref, start2_ref, n_ref,
                               ((pool_hbm, buf, sem),), state, ps=ps, mp=mp,
                               pb=pb, n_slots=n_slots)
    out_ref[...] = jnp.zeros(out_ref.shape, f32)
    q, w = q_ref[0], w_ref[0]

    def work(k, side, carry):
        dots = jax.lax.dot_general(q, buf[side], (((1,), (1,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)
        out_ref[0, pl.ds(block_at(k), 1), :] = jnp.sum(
            jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
        return carry

    run(work, 0)


def _live_blocks(lens, gen0, pos, ps, pb, xp):
    """Of each slot's live rows ``[0, lens)`` and ``[gen0, pos]`` (int
    arrays of ``xp``: jnp in the plan, numpy on the host): the prompt's
    pages, the first and the last generated page (-1: none), the blocks
    of pb pages the prompt's span, the first block of generated pages
    alone, and how many such blocks."""
    npr, g0 = -(-lens // ps), gen0 // ps
    g1 = xp.where(pos >= gen0, pos // ps, -1)
    n1 = -(-npr // pb)
    start2 = xp.maximum(g0 // pb, n1)
    n2 = xp.maximum((g1 + pb) // pb - start2, 0)
    return npr, g0, g1, n1, start2, n2


def attend_rows_read(lens, gen0, pos, page_size: int, block_pages: int):
    """Rows of EACH plane that :func:`attend_pages` reads for slots of
    these live rows (numpy int arrays [B], as the engine's host mirror
    has them) in blocks of ``block_pages`` pages
    (:func:`attend_block_pages` of the layer's table and rows): its live
    blocks', whole — a part-filled block re-reads a live page for every
    dead entry (``_attend_plan``)."""
    *_, n1, _, n2 = _live_blocks(lens, gen0, pos, page_size, block_pages, np)
    return (n1 + n2) * block_pages * page_size


def _attend_plan(table, lens, gen0, pos, ps, pb, n_pages):
    """What ``attend_pages`` walks, from the table [B, MP] and each
    slot's live rows ``[0, lens)`` and ``[gen0, pos]``: (the page to read
    for every table entry [B * MP], and per slot [B] the blocks the
    prompt's pages span, the first block of generated pages alone, the
    live blocks in all). A block is LIVE if it holds a live page; its
    dead entries (padding before the bucket, pages leased beyond pos,
    sentinels) are given a live page of the same block to read instead —
    the block's first, or the first generated one — whose rows the mask
    drops: every block is then pb copies, and no page is read that
    holds no token."""
    i32 = jnp.int32
    b, mp = table.shape
    lens, gen0, pos = (x.astype(i32) for x in (lens, gen0, pos))
    npr, g0, g1, n1, start2, n2 = _live_blocks(lens, gen0, pos, ps, pb, jnp)
    lp = jnp.arange(mp, dtype=i32)[None]
    live = (lp < npr[:, None]) | ((lp >= g0[:, None]) & (lp <= g1[:, None]))
    pages = jnp.clip(table.astype(i32), 0, n_pages - 1)
    blocks = pages.reshape(b, mp // pb, pb)
    first = jnp.arange(0, mp, pb, dtype=i32)[None]            # [1, blocks]
    at_g0 = jnp.sum(jnp.where(lp == g0[:, None], pages, 0), axis=-1)
    own = (first < npr[:, None]) | (first >= g0[:, None])
    spare = jnp.where(own[:, :, None], blocks[:, :, :1],
                      at_g0[:, None, None])
    read = jnp.where(live.reshape(blocks.shape), blocks, spare)
    return read.reshape(-1), n1, start2, n1 + n2


@functools.partial(jax.jit, static_argnames=(
    "page_size", "scale", "value_width", "block_rows", "interpret"))
def attend_pages(q, pool, table, lens, gen0, pos, keep, page_size: int,
                 scale: float, value_width: int = 0,
                 block_rows: int = _ATTEND_ROWS, interpret=False,
                 values=None):
    """Single-key-head attention of each slot over its own pages of a
    paged pool, read IN PLACE: q [B, H, W] (the pool's dtype), pool
    [R, W] (the flat view of ``R // page_size`` pages; a row is key and
    value at once), table [B, MP] int page ids (sentinels clamp, and lie
    beyond every live extent), lens / gen0 / pos [B] int the slot's live
    ROWS — ``[0, lens)`` and ``[gen0, pos]`` as ``ops/mla.py:live_rows``
    has them (lens 0 and pos < gen0: none, and the slot reads no page) —
    keep [B, MP * page_size] bool the rows to attend, a subset of the
    live ones -> [B, H, W] in the pool's dtype: ``softmax(scale * q .
    rows^T over keep) . rows``, zeros where a slot keeps nothing. Of the
    result only the first ``value_width`` lanes (whole lane tiles; 0:
    all) are computed, the rest zeros. Products multiply in the pool's
    dtype (float32 at precision HIGHEST) and accumulate in float32; the
    softmax is float32, online over blocks of ``attend_block_pages``
    pages, its probabilities cast to the pool's dtype before ``p .
    rows``.

    With ``values`` [R, Wv] (the pool's dtype, whole lane tiles, under
    the same table) the pool is the KEY plane alone and the result is
    ``softmax(..) . values`` [B, H, Wv]: a block is its pages out of
    each plane. Several key heads go through as ONE: the query arrives
    block-diagonal (``ops/kv_attention.py:block_diagonal_query`` — row h
    holds head h in the lanes of its key head and zeros elsewhere) and
    the caller keeps row h's own lanes of the result.

    Neither the pool nor the rows ever leave their place as an array:
    each live page is one DMA into a VMEM tile (``page_size`` a whole
    number of the dtype's sublane tiles, as :func:`gather_pages`), the
    next block's under this block's products. What it costs goes by the
    LIVE rows; the padding between a prompt's end and its bucket and
    the pages leased beyond ``pos`` are not read."""
    b, h, w = q.shape
    mp = table.shape[1]
    two_planes = values is not None
    row_bytes = attend_row_bytes(pool, values)
    pb = attend_block_pages(mp, page_size, block_rows, row_bytes)
    if not pb:
        raise ValueError(f"no block of whole lane tiles divides a table "
                         f"of {mp} pages of {page_size} rows of "
                         f"{row_bytes} bytes")
    if two_planes and value_width:
        raise ValueError("value_width cuts a row that is key and value "
                         "at once; a value plane is attended whole")
    vw = values.shape[1] if two_planes else value_width or w
    if vw % _LANES or (not two_planes and vw > w):
        raise ValueError(f"value_width {vw} of rows {w} wide")
    rows = pb * page_size
    plan = _attend_plan(table, lens, gen0, pos, page_size, pb,
                        pool.shape[0] // page_size)
    bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)\
        .reshape(b, mp // pb, rows)
    wo = vw if two_planes else w
    hbm = pl.BlockSpec(memory_space=pl.ANY)             # a plane in HBM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # the pages and the blocks: SMEM
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, mp // pb, rows),
                               lambda i, *_: (i, 0, 0)),
                  hbm] + [hbm] * two_planes,
        out_specs=pl.BlockSpec((1, h, wo), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, rows, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)]
        + [pltpu.VMEM((2, rows, vw), pool.dtype),
           pltpu.SemaphoreType.DMA((2,))] * two_planes,
    )
    return pl.pallas_call(
        functools.partial(
            _attend_pages_kernel, ps=page_size, mp=mp, pb=pb, vw=vw,
            n_slots=b, scale=float(scale),
            precision=_tile_precision(pool.dtype), two_planes=two_planes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, wo), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),    # slots in order
        interpret=interpret,
        name="attend_pages",
    )(*plan, q, bias, pool, *([values] if two_planes else []))


@functools.partial(jax.jit, static_argnames=(
    "page_size", "block_rows", "interpret"))
def score_pages(qi, wi, pool, table, lens, gen0, pos, page_size: int,
                block_rows: int = _ATTEND_ROWS, interpret=False):
    """The DSA indexer's scores of each slot's LIVE rows of a paged
    index plane, read IN PLACE: qi [B, J, W] (the pool's dtype) the
    slot's J indexer queries, wi [B, J] float32 their weights, pool
    [R, W] (the flat view of ``R // page_size`` pages of indexer keys),
    table [B, MP] int page ids, lens / gen0 / pos [B] int the slot's live
    rows as :func:`attend_pages` takes them -> [B, MP * page_size]
    float32: ``sum_j wi_j ReLU(qi_j . row)`` at every live row — the
    products in the pool's dtype (float32 at precision HIGHEST),
    accumulated, weighed and summed over J in float32
    (``ops/mla.py:_slot_scores`` over the gathered plane, without the
    gathered plane).

    It walks what :func:`attend_pages` walks (``_attend_plan``,
    ``_page_walk``: a live page a DMA into one of two VMEM tiles, the
    next block's under this block's product) and costs by the live rows.
    A table block the walk never visits — past a slot's live rows, an
    idle slot's — holds ZEROS, and the dead rows of a live block hold the
    scores of whatever live page was read in their place: only a live
    row's entry is a score, and every reader masks with the live rows
    first (``ops/mla.py:select_topk`` and ``jax.lax.top_k``'s branch
    both do)."""
    b, j, w = qi.shape
    mp = table.shape[1]
    pb = attend_block_pages(mp, page_size, block_rows,
                            attend_row_bytes(pool))
    if not pb:
        raise ValueError(f"no block of whole lane tiles divides a table "
                         f"of {mp} pages of {page_size} rows")
    rows = pb * page_size
    plan = _attend_plan(table, lens, gen0, pos, page_size, pb,
                        pool.shape[0] // page_size)
    per_slot = lambda *block: pl.BlockSpec(                    # noqa: E731
        (1,) + block, lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # the pages and the blocks: SMEM
        grid=(b,),
        in_specs=[per_slot(j, w), per_slot(j, 1),
                  pl.BlockSpec(memory_space=pl.ANY)],   # the plane in HBM
        out_specs=per_slot(mp // pb, rows),
        scratch_shapes=[pltpu.VMEM((2, rows, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    scores = pl.pallas_call(
        functools.partial(
            _score_pages_kernel, ps=page_size, mp=mp, pb=pb, n_slots=b,
            precision=_tile_precision(pool.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, mp // pb, rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),    # slots in order
        interpret=interpret,
        name="score_pages",
    )(*plan, qi, wi.astype(jnp.float32)[:, :, None], pool)
    return scores.reshape(b, mp * page_size)


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
