"""Mamba-1's selective scan over one request's prompt (Pallas TPU): the
state stays in VMEM and the rows are walked in order.

The recurrence of ``ops/s6.py`` has a decay per CHANNEL AND STATE INDEX,

    h_t[n, c] = exp(dt_t[c] * A[n, c]) * h_{t-1}[n, c] + dt_t[c] * x_t[c] * B_t[n]
    y_t[c]    = sum_n h_t[n, c] * C_t[n]

so it has no matrix form (``ops/ssd.py``'s chunked product needs ONE
decay a head), and written as array operations it makes ``exp(dt A)`` and
``dt B x`` as ``[T, N, C]`` float32 arrays: 320 KB a token a layer at
Jamba's ``N x C = 16 x 5120``, written and read again. Here a tile of
the state ``[N, ct]`` — the state index on the sublanes, ``ct`` channels
on the lanes — is resident in VMEM (it IS the kernel's state output,
whose block index does not move along the time axis), and a chunk of
``chunk`` rows of ``x``, ``dt`` (``[chunk, ct]``) and of ``B^T``, ``C^T``
(``[N, chunk]``) is copied in a grid step: each input is read once and
``y`` is written once, 12 bytes a (token, channel).

A row's step is elementwise on the ``[N, ct]`` tile: ``dt_t`` and
``dt_t x_t`` are rows (a sublane broadcast, which a load does), ``B_t``
and ``C_t`` columns — taken out of the chunk's ``[N, chunk]`` tile by a
one-hot select and a lane reduction, ``2 N chunk`` values a row beside
the ``N ct`` of the update itself — and ``y_t`` a reduction over the
sublanes. Eight rows are unrolled a turn of the loop and their ``y`` rows
leave as one aligned ``[8, ct]`` store.

The grid is (channel tiles, chunks of the BUCKET). The chunks past the
prompt's true length (``n_chunks``, a scalar prefetched into SMEM) have
their body off and their block indices held at the last live chunk's, so
the pipeline copies nothing for them: a padded bucket's empty chunks cost
a grid step's bookkeeping each. Their rows of ``y`` are never written
(the caller selects the live rows), and rows past the true length inside
the last live chunk must come in with ``dt = x = 0``: ``exp(0) = 1``
leaves the state as it is.

Runs under ``interpret=True`` on the CPU test backend
(tests/test_s6.py); which calls engage it is ``ops/s6.py:scan_path``'s
to say."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES, _SUBLANES = 128, 8
# channels of a state tile. Twenty-six scans of a 1 024-row bucket chained
# on one v5e, chunks of 64 rows (PERF.md section 6, PR 65): 0.470 ms a
# scan at 256 channels, 0.347 at 512, 0.331 at 640, 0.281 at 1 280, 0.272
# at 2 560 — a wider tile amortises a row's B and C columns and the
# loop's bookkeeping over more of the update, until the tile's twenty
# vector registers and the row's temporaries no longer fit the file
CHANNEL_TILE = 1280


def lane_tile(channels: int, most: int, tile: int = 0) -> int:
    """The widest whole number of lane tiles at or under ``most`` that
    divides ``channels`` (``tile`` itself where one is asked for); 0
    where there is none."""
    if tile:
        return tile if channels % tile == 0 and tile % _LANES == 0 else 0
    if channels % _LANES:
        return 0
    return next(t for t in range(min(channels, most), 0, -_LANES)
                if channels % t == 0)


def channel_tile(channels: int, tile: int = 0) -> int:
    """The state tile's channels for a layer of ``channels``; 0 where
    they are not whole lane tiles."""
    return lane_tile(channels, CHANNEL_TILE, tile)


def step(h, a, dt, x, b, c):
    """One token of the recurrence on a state tile h [N, ct] with a
    [N, ct], the rows dt and x [1, ct] and the columns b and c [N, 1]:
    (the new tile, y [1, ct] without the D term)."""
    h = jnp.exp(dt * a) * h + (dt * x) * b
    return h, jnp.sum(h * c, axis=0, keepdims=True)


def _kernel(n_ref, x_ref, dt_ref, bt_ref, ct_ref, a_ref, y_ref, s_ref):
    """One chunk of one channel tile. x_ref, dt_ref, y_ref [chunk, ct];
    bt_ref, ct_ref [N, chunk] (this chunk's B and C, transposed); a_ref
    [N, ct]; s_ref [N, ct]: the state, resident along the time axis."""
    i = pl.program_id(1)
    chunk = x_ref.shape[0]

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, F32)

    @pl.when(i < n_ref[0])
    def _():
        a, bt, ct = a_ref[...], bt_ref[...], ct_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, a.shape[1]), 0)

        def rows(g, h):
            t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
            y8 = jnp.zeros(sub.shape, F32)
            for j in range(_SUBLANES):
                t = t0 + j
                here = lane == t
                b = jnp.sum(jnp.where(here, bt, 0.0), axis=1, keepdims=True)
                c = jnp.sum(jnp.where(here, ct, 0.0), axis=1, keepdims=True)
                h, y = step(h, a, dt_ref[pl.ds(t, 1), :],
                            x_ref[pl.ds(t, 1), :], b, c)
                y8 = jnp.where(sub == j, y, y8)
            y_ref[pl.ds(t0, _SUBLANES), :] = y8
            return h

        s_ref[...] = jax.lax.fori_loop(0, chunk // _SUBLANES, rows,
                                       s_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("chunk", "tile", "interpret"))
def s6_scan(x, dt, b, c, a, n_chunks, chunk: int, tile: int = 0,
            interpret=False):
    """x, dt [T, C], b, c [T, N], a [N, C] (A itself: negative), all
    float32; ``n_chunks`` the chunks of ``chunk`` rows that hold a true
    token (a traced count; rows past the true length inside the last of
    them have dt = x = 0) -> (y [T, C] without the D term — the rows of
    the chunks past ``n_chunks`` are NOT written —, the state after the
    last true token [N, C])."""
    t, channels = x.shape
    n = b.shape[1]
    ct = channel_tile(channels, tile)
    if not ct or t % chunk or chunk % _SUBLANES:
        raise ValueError(f"no whole tiles for a scan of [{t}, {channels}] "
                         f"by chunks of {chunk} rows (tile {tile})")
    # a chunk's B and C with the state index on the sublanes
    by_chunk = lambda v: jnp.swapaxes(                       # noqa: E731
        v.reshape(t // chunk, chunk, n), 1, 2)
    live = jnp.maximum(jnp.asarray(n_chunks, jnp.int32).reshape(1), 1)

    def at(j, i, n_ref):
        return jnp.minimum(i, n_ref[0] - 1), j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(channels // ct, t // chunk),
        in_specs=[
            pl.BlockSpec((chunk, ct), at),
            pl.BlockSpec((chunk, ct), at),
            pl.BlockSpec((None, n, chunk),
                         lambda j, i, n_ref: (at(j, i, n_ref)[0], 0, 0)),
            pl.BlockSpec((None, n, chunk),
                         lambda j, i, n_ref: (at(j, i, n_ref)[0], 0, 0)),
            pl.BlockSpec((n, ct), lambda j, i, n_ref: (0, j))],
        out_specs=[pl.BlockSpec((chunk, ct), at),
                   pl.BlockSpec((n, ct), lambda j, i, n_ref: (0, j))],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, channels), F32),
                   jax.ShapeDtypeStruct((n, channels), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * t * n * channels, transcendentals=t * n * channels,
            bytes_accessed=4 * (3 * t * channels + 2 * n * channels
                                + 2 * t * n * (channels // ct))),
        name="s6_scan",
        interpret=interpret,
    )(live, x, dt, by_chunk(b), by_chunk(c), a)
