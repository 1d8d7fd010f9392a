"""Mamba-1's decode step over every slot's state (Pallas TPU): the state
stays in HBM and streams through VMEM once, in place.

``ops/s6.py:state_step`` as plain XLA is one fusion a layer, and on the
v5e the compiler stages that fusion's WHOLE operand in VMEM — 84 MB a
layer at 256 slots of ``[16, 5120]`` float32, prefetched in four slices
and copied back to HBM by an asynchronous copy after it — so the state's
bytes move while OTHER instructions wait for the copy engine: the
fusion's own time is 3.2 ms a step where the bytes alone need 5.3, and
2.1 ms of ``copy-done`` under no scope, 1.1 ms of a conv weight's
``copy-done`` and the like sit wherever the queue drains (PERF.md
section 6, PR 65). A kernel whose state operand the pipeline's own
``BlockSpec`` windows is staged the same way (the operand's memory space
is the compiler's to choose, and 84 MB fit the chip's 128 MB of VMEM).
Here the state's two refs are PINNED to HBM (``memory_space=pltpu.HBM``)
and the kernel copies by hand: a block of ``SLOT_TILE`` slots by ``ct``
channels into one of two VMEM slots while the block before it is
updated, the updated block back to where it came from
(``input_output_aliases``) while the next is updated — so the call's time
is its own, and the bytes it moves are the state's, once in and once out.

A slot's update is ``ops/pallas/s6_scan.py``'s row step with the slot in
the row's place: ``dt`` and ``dt x`` are rows (a sublane broadcast),
``B``, ``C`` and the slot's ``active`` arrive ``[slots, N, 1]`` and
broadcast along the lanes, ``y`` is a reduction over the sublanes; eight
slots are updated a grid step and their ``y`` rows leave as one aligned
``[8, ct]`` store. A slot that is not active is written back as it was
read, bit for bit. The small operands ride the pipeline's own windows.

Runs under ``interpret=True`` on the CPU test backend
(tests/test_s6.py); which calls engage it is ``ops/s6.py:state_path``'s
to say."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.s6_scan import lane_tile, step

F32 = jnp.float32
_LANES = 128
SLOT_TILE = 8
# channels of a block: [8, 16, 2560] float32 is 1.3 MB, four of them in
# VMEM (two coming in, two going out)
CHANNEL_TILE = 2560
VMEM_LIMIT_BYTES = 32 << 20


def tiles(slots: int, channels: int, tile: int = 0) -> int:
    """The block's channels for ``slots`` slots of ``channels``: the
    widest whole number of lane tiles at or under ``CHANNEL_TILE`` that
    divides them; 0 where there is none, or the slots are not whole
    blocks of ``SLOT_TILE``."""
    return 0 if slots % SLOT_TILE else lane_tile(channels, CHANNEL_TILE,
                                                 tile)


def _kernel(nb, s_hbm, dt_ref, x_ref, b_ref, c_ref, on_ref, a_ref, o_hbm,
            y_ref, ibuf, obuf, isem, osem):
    """Grid step ``k``: block ``k % nb`` of the slots, ``k // nb`` of the
    channels. s_hbm, o_hbm [B, N, C] in HBM (one buffer); dt_ref, x_ref,
    y_ref [8, ct]; b_ref, c_ref, on_ref [8, N, 1]; a_ref [N, ct]; ibuf,
    obuf [2, 8, N, ct] the blocks coming in and going out; isem, osem
    [2] a DMA semaphore a slot."""
    k, last = pl.program_id(0), pl.num_programs(0) - 1
    ct = ibuf.shape[3]

    def block(ref, at):
        return ref.at[pl.ds((at % nb) * SLOT_TILE, SLOT_TILE), :,
                      pl.ds(pl.multiple_of((at // nb) * ct, _LANES), ct)]

    def fetch(at, slot):
        return pltpu.make_async_copy(block(s_hbm, at), ibuf.at[slot],
                                     isem.at[slot])

    def store(at, slot):
        return pltpu.make_async_copy(obuf.at[slot], block(o_hbm, at),
                                     osem.at[slot])

    slot = k % 2

    @pl.when(k == 0)                    # nobody was there to start this one
    def _():
        fetch(k, slot).start()
    fetch(k, slot).wait()

    @pl.when(k < last)
    def _():
        fetch(k + 1, 1 - slot).start()

    @pl.when(k >= 2)                    # the block that left this slot
    def _():
        store(k - 2, slot).wait()

    a = a_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 0)
    y8 = jnp.zeros(y_ref.shape, F32)
    for j in range(SLOT_TILE):
        h = ibuf[slot, j]
        new, y = step(h, a, dt_ref[pl.ds(j, 1), :], x_ref[pl.ds(j, 1), :],
                      b_ref[j], c_ref[j])
        y8 = jnp.where(sub == j, y, y8)
        obuf[slot, j] = jnp.where(on_ref[j] > 0.0, new, h)
    y_ref[...] = y8
    store(k, slot).start()

    @pl.when(k == last)
    def _():
        store(k, slot).wait()

        @pl.when(k >= 1)
        def _():
            store(k - 1, 1 - slot).wait()


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def s6_state_update(state, dt, x, b, c, a, active, tile: int = 0,
                    interpret=False):
    """state [B, N, C], dt and x [B, C], b and c [B, N], a [N, C], all
    float32, active [B] (> 0: the slot runs) -> (the new state — the
    slots that do not run as they were —, y [B, C] without the D term).
    The state is updated in place where the caller donates it."""
    slots, n, channels = state.shape
    ct = tiles(slots, channels, tile)
    if not ct:
        raise ValueError(f"no whole tiles for a state of [{slots}, {n}, "
                         f"{channels}] (tile {tile})")
    nb = slots // SLOT_TILE
    on = jnp.broadcast_to(
        (jnp.asarray(active).reshape(-1, 1, 1) > 0).astype(F32),
        (slots, n, 1))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    rows = lambda k: (k % nb, k // nb)                       # noqa: E731
    cols = lambda k: (k % nb, 0, 0)                          # noqa: E731
    column = pl.BlockSpec((SLOT_TILE, n, 1), cols)
    return pl.pallas_call(
        functools.partial(_kernel, nb),
        grid=(nb * (channels // ct),),
        in_specs=[hbm, pl.BlockSpec((SLOT_TILE, ct), rows),
                  pl.BlockSpec((SLOT_TILE, ct), rows), column, column,
                  column, pl.BlockSpec((n, ct), lambda k: (0, k // nb))],
        out_specs=[hbm, pl.BlockSpec((SLOT_TILE, ct), rows)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((slots, channels), F32)],
        scratch_shapes=[pltpu.VMEM((2, SLOT_TILE, n, ct), F32),
                        pltpu.VMEM((2, SLOT_TILE, n, ct), F32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=7 * slots * n * channels,
            transcendentals=slots * n * channels,
            bytes_accessed=4 * (2 * slots * n * channels
                                + 3 * slots * channels)),
        name="s6_state_update",
        interpret=interpret,
    )(state, dt, x, b[:, :, None], c[:, :, None], on, a)
