"""The expert layer's dense way over the HIT experts alone (Pallas TPU):
a decode step's few tokens through every held expert that some token
picked, the weights of the others never read.

``ops/expert_ffn.py:held_experts_part``'s dense way multiplies every
token by every held expert and lets the combine weight — zero where the
token did not pick the expert — silence what was not asked for. Under
the chip's ridge point that costs what reading the weights costs, and a
step reads ALL of them: with 32 tokens of 8 picks over a router 256
wide, four held experts in ten are given no token and their 75 MB a
layer cross HBM to be multiplied by zero.

Here the tokens a held expert was given (``sizes``, on the device before
the products start) ride in SMEM by scalar prefetch as the list of hit
experts, hit ones first. The grid runs over that list and the tiles of
``d_expert``; a grid step streams one tile of the expert's gate, up and
down matrices through VMEM (double-buffered by the pipeline), makes
``SiLU(x W_gate) * (x W_up) * w`` for all tokens in float32 from
products in the storage dtype — exactly the dense way's — and adds its
product with the down tile to a float32 ``[n_tokens, d_model]``
accumulator that stays in VMEM for the whole call: the hidden rows never
leave the chip's fast memory and the sum over experts is part of the
kernel. Past the last hit expert the index maps go on naming the block
that is already resident — the pipeline issues no copy for an unchanged
block index — and the body is off: an unhit expert costs a grid step's
bookkeeping (~0.35 us), not its bytes. It would have added exact zeros,
so leaving it out is the same sum, not an approximation.

Runs under ``interpret=True`` on the CPU test backend
(tests/test_pallas_kernels.py); which calls engage it is
``ops/expert_ffn.py:dense_tier``'s to say."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
# a grid step's three weight tiles of up to 6.3 MB, double-buffered
# (37.7 MB), beside the tokens and the accumulator; the scoped default
# is 16 MB, and tiles of 12.6 MB (1 024 columns at d_model 6 144) do
# not fit under this either
VMEM_LIMIT_BYTES = 64 << 20
# bytes of ONE weight tile the default tile of ``d_expert`` aims at
# (:func:`expert_tile`; passes/autotune_table.json holds the measured
# rows)
TILE_BYTES = 6 << 20


def expert_tile(d_model: int, d_expert: int, itemsize: int) -> int:
    """Columns of ``d_expert`` a grid step: the committed table's row
    for the shape where it has one, else the largest whole number of
    lane tiles that divides ``d_expert`` and keeps a weight tile at or
    under ``TILE_BYTES`` (0: ``d_expert`` is not whole lane tiles, the
    refer tier runs)."""
    if d_expert % _LANES:
        return 0
    from paddle_tpu.passes import autotune as at
    entry = at.lookup("expert_stream", {"M": int(d_model),
                                        "F": int(d_expert)})
    if entry is not None and d_expert % int(entry["tf"]) == 0:
        return int(entry["tf"])
    # one lane tile always divides
    most = max(_LANES, TILE_BYTES // (d_model * itemsize) // _LANES * _LANES)
    return next(tf for tf in range(min(d_expert, most), 0, -_LANES)
                if d_expert % tf == 0)


def hit_order(sizes):
    """sizes [E] (tokens per held expert) -> (order [E] int32, n_hit [1]
    int32): the hit experts in rising order, then the last of them again
    for every place past them (expert 0 where none is hit) — the block
    the kernel leaves resident. Masked sums, no sort and no cumsum."""
    e = jnp.arange(sizes.shape[0], dtype=jnp.int32)
    hit = sizes > 0
    # an expert's place among the hit ones
    place = jnp.sum((e[:, None] > e[None, :]) & hit[None, :], axis=1,
                    dtype=jnp.int32)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    at = hit[None, :] & (place[None, :] == e[:, None])          # [i, e]
    order = jnp.sum(jnp.where(at, e[None, :], 0), axis=1, dtype=jnp.int32)
    last = jnp.max(jnp.where(hit, e, 0))
    return jnp.where(e < n_hit, order, last), n_hit.reshape(1)


def _hit_experts_kernel(order_ref, n_hit_ref, x_ref, w_ref, wg_ref, wu_ref,
                        wd_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_hit_ref[0])
    def _():
        x = x_ref[...]
        hidden = jax.nn.silu(jnp.dot(x, wg_ref[0],
                                     preferred_element_type=F32)) \
            * jnp.dot(x, wu_ref[0], preferred_element_type=F32) \
            * w_ref[0]                                       # [N, tf]
        o_ref[...] += jnp.dot(hidden.astype(x.dtype), wd_ref[0],
                              preferred_element_type=F32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def hit_experts(x, w, sizes, w_gate, w_up, w_down, tile: int = 0,
                interpret=False):
    """x [N, M] in the storage dtype, w [N, E] float32 (a token's
    combine weight for the held expert, zero where it did not pick it),
    sizes [E] int (tokens per held expert), w_gate / w_up [E, M, F],
    w_down [E, F, M] in x's dtype -> y [N, M] float32: the sum over the
    experts with ``sizes > 0`` of ``(SiLU(x W_gate) * (x W_up) * w)
    W_down``. ``tile`` columns of F a grid step (0:
    :func:`expert_tile`)."""
    n, m = x.shape
    n_held, _, f = w_gate.shape
    tf = tile or expert_tile(m, f, x.dtype.itemsize)
    if not tf or f % tf:
        raise ValueError(f"no tile of {tile or 'whole lane tiles of'} "
                         f"columns divides an expert {f} wide")
    # whole sublane tiles of tokens in either dtype; a padded token's
    # weight is zero
    pad = -n % 16
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    rows = n + pad
    order, n_hit = hit_order(sizes)
    tiles = f // tf

    def tile_at(i, j, n_hit):
        # past the hit experts: the tile that is resident
        return jnp.where(i < n_hit[0], j, tiles - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # order, n_hit: SMEM
        grid=(n_held, tiles),
        in_specs=[
            pl.BlockSpec((rows, m), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, j, o, h: (o[i], 0, 0)),
            pl.BlockSpec((1, m, tf),
                         lambda i, j, o, h: (o[i], 0, tile_at(i, j, h))),
            pl.BlockSpec((1, m, tf),
                         lambda i, j, o, h: (o[i], 0, tile_at(i, j, h))),
            pl.BlockSpec((1, tf, m),
                         lambda i, j, o, h: (o[i], tile_at(i, j, h), 0)),
        ],
        out_specs=pl.BlockSpec((rows, m), lambda i, j, *_: (0, 0)),
    )
    y = pl.pallas_call(
        _hit_experts_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, m), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="hit_experts",
        interpret=interpret,
    )(order, n_hit, x, w.astype(F32).T[:, :, None], w_gate, w_up, w_down)
    return y[:n] if pad else y
