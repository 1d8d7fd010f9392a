"""The gated delta rule's decode-step state update (Pallas TPU): every
slot's and head's recurrent state ``S [Dk, Dv]`` float32 advanced by one
token with ONE read and ONE write of the state, in place — Kimi Delta
Attention's square state with a decay a key channel (``ops/kda.py``) and
Gated DeltaNet's rectangular one with a decay a head (``ops/gdn.py``:
the caller hands the scalar over broadcast along the key channels).

``ops/kda.py:_delta_step`` (the refer tier, and ``kda_prefill``'s loop
body) is the algebra; XLA makes two fusions of it — a reduction over the
old state, then an elementwise pass that reads the state again — so the
state crosses HBM three times a step where the algorithm needs two. Here
a block of heads of one slot is one VMEM tile: the two reductions over
the key-channel axis, ``dv = v - u``, the rank-one update and ``o`` all
read the tile once, and the new state goes back to the buffer it came
from (``input_output_aliases``).

A tile need not be whole lane tiles: a [96, 192] state is a block whose
last two dimensions are the array's own, which VMEM (and HBM's tiled
layout) pads to [96, 256] — a third more bytes than the state has, and
still one read and one write of them.

Layout. A state tile has the key channel ``i`` on the sublanes and the
value channel ``j`` on the lanes, so what the update scales ROWS by
(``alpha_i``, ``k_i alpha_i``, ``q_i alpha_i``, ``beta k_i``) must be
broadcast along lanes. An operand ``[.., D, 1]`` would pad 128-fold in
HBM; q, k and g come in as ``[heads, Dk]`` tiles (Dk on the lanes, as
they are computed; the heads of a block padded to whole sublane tiles
and Dk to the transposed square's side by the caller, which costs a
state of whole tiles nothing), the kernel makes the four vectors of them
and transposes the ``[4 * heads, Dk]`` tile once a grid step on the XLU:
column ``c`` of the transposed tile is vector ``c // heads`` of head
``c % heads``, a static lane slice. What is indexed by ``j`` (``v``) stays a row; a
head's scalars (``beta``, ``q . beta k``) are read from SMEM. All
products and sums are float32 on the VPU (a [2, D] x [D, D] product at
precision HIGHEST would cost more MXU passes than the tile costs HBM
time), and all of them hide under the tile's DMA: the kernel takes what
a copy of the state through VMEM takes (PERF.md section 6, PR 36)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
# a grid step's block of the state: 32 heads of [128, 128] (whose 4 x 32
# vectors fill the [128, 128] tile the XLU transposes) are 2 MB in and
# 2 MB out, 8 MB double-buffered
BLOCK_BYTES = 2 << 20
# heads of a block the kernel's Python writes out; the block is a loop
# over groups of them. Every head written out is MLIR to build EACH time
# a decode program is lowered, warm compile cache or not: all 32 cost the
# hybrid cell 5 s of a 21 s set-up (0.27 s a lowering of the kernel on
# the chip's host, about a dozen of them); 4 cost 0.05 s a lowering. One
# head a turn of the loop leaves its arithmetic in the open (the kernel
# alone at the cell's shape, ms: 32 written out 1.699, 8 1.702, 4 1.704,
# 2 1.717, 1 1.954: my chip run, PR 36).
UNROLL = 4


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_bytes(key_dim: int, value_dim: int) -> int:
    """A head's float32 tile as VMEM (and HBM's tiled layout) holds it:
    the value channels padded to whole lane tiles."""
    return 4 * key_dim * _ceil_to(value_dim, _LANES)


def head_block(n_head: int, key_dim: int, value_dim: int) -> int:
    """Heads a grid step: the largest count that divides ``n_head`` and
    whose state block, as VMEM pads it, is at most ``BLOCK_BYTES``."""
    most = max(1, min(n_head, BLOCK_BYTES // _tile_bytes(key_dim,
                                                         value_dim)))
    return next(hb for hb in range(most, 0, -1) if n_head % hb == 0)


def supported(key_dim: int, value_dim: int, dtype) -> bool:
    """What the kernel is written for: a float32 state whose key
    channels are whole sublane tiles (the transposed vectors are cut to
    them), whose value channels are a lane tile or more, to half a tile
    (192: a third of what VMEM holds of it is padding; at 64 half would
    be) and whose tile fits a block."""
    return (dtype == F32 and key_dim % 8 == 0
            and value_dim >= _LANES and value_dim % (_LANES // 2) == 0
            and _tile_bytes(key_dim, value_dim) <= BLOCK_BYTES)


def _advance(s, alpha, ka, qa, bk, v, qbk):
    """``ops/kda.py:_delta_step`` on one head's tile s [Dk, Dv]: alpha,
    ka = k * alpha, qa = q * alpha, bk = beta * k are [Dk, 1] (a key
    channel a row), v is [1, Dv], qbk = q . bk the head's scalar ->
    (s_new, o [1, Dv])."""
    u = jnp.sum(ka * s, axis=0, keepdims=True)
    red_q = jnp.sum(qa * s, axis=0, keepdims=True)
    dv = v - u
    return alpha * s + bk * dv, red_q + qbk * dv


def _kda_state_kernel(active_ref, beta_ref, qbk_ref, s_ref, q_ref, k_ref,
                      v_ref, g_ref, so_ref, o_ref, *, hb, hbp, dk, n_head,
                      unroll):
    slot = pl.program_id(0)
    live = active_ref[slot] > 0
    first = slot * n_head + pl.program_id(1) * hb
    k = k_ref[0, 0]
    alpha = jnp.exp(g_ref[0, 0])                            # [hbp, side]
    # four vectors of hbp heads (hb of them real), padded to a square
    # the XLU transposes: t[i, n * hbp + h] = vector n of head h at key
    # channel i
    cols = jnp.concatenate([alpha, k * alpha, q_ref[0, 0] * alpha, k],
                           axis=0)
    side = cols.shape[1]
    cols = jnp.pad(cols, ((0, side - 4 * hbp), (0, 0)))

    def group(gi, t):
        """``unroll`` heads written out (a lane slice must be static);
        then the next group's columns rotate to the front."""
        for h in range(unroll):
            head = gi * unroll + h
            a_col, ka, qa, k_col = (t[:, n * hbp + h:n * hbp + h + 1]
                                    for n in range(4))      # [Dk, 1]
            s = s_ref[0, head]
            s_new, o = _advance(
                s, a_col, ka, qa, beta_ref[first + head] * k_col,
                v_ref[0, 0, pl.ds(head, 1), :], qbk_ref[first + head])
            so_ref[0, head] = jnp.where(live, s_new, s)
            o_ref[0, 0, pl.ds(head, 1), :] = o
        return pltpu.roll(t, side - unroll, axis=1)
    jax.lax.fori_loop(0, hb // unroll, group, cols.T[:dk])


@functools.partial(jax.jit,
                   static_argnames=("heads", "unroll", "interpret"))
def kda_state_update(state, q, k, v, g, beta, active, heads: int = 0,
                     unroll: int = UNROLL, interpret=False):
    """One step of the gated delta rule for every slot and head: state
    [B, H, Dk, Dv] float32 (aliased to the result: in place where the
    caller donates it), q, k [B, H, Dk], v [B, H, Dv] float32, the
    log-decay g [B, H, Dk] (a key channel) or [B, H, 1] (a head), beta
    [B, H], active [B] (a slot with 0 keeps its state bit for bit; its
    ``o`` is not meaningful) -> (new state, o [B, H, Dv]) — the state
    FIRST: the device trace names a kernel by the first shape of its
    result, and the benchmark's reader of the state's time selects on
    it. ``heads`` a grid step (0: :func:`head_block`).

    Beside the state the kernel reads q, k, v, g and writes o, 2 % of
    the state's bytes; what a head scales by as a whole (``beta`` and
    ``q . beta k``, [B, H]) rides in SMEM."""
    b, h, dk, dv = state.shape
    hb = heads or head_block(h, dk, dv)
    if h % hb:
        raise ValueError(f"no block of {hb} heads divides {h}")
    unroll = next(u for u in range(min(unroll, hb), 0, -1) if hb % u == 0)
    # a block's heads on whole sublane tiles, the key channels on the
    # lanes of the square the kernel transposes
    hbp = _ceil_to(hb, 8)
    side = _ceil_to(max(4 * hbp, dk), _LANES)
    qbk = jnp.sum(q * (beta[..., None] * k), axis=-1)

    def blocks(x, width):
        x = x.reshape(b, h // hb, hb, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, 0), (0, hbp - hb),
                           (0, width - x.shape[-1])))
    tile = pl.BlockSpec((1, hb, dk, dv), lambda i, j, *_: (i, j, 0, 0))
    keyed = pl.BlockSpec((1, 1, hbp, side), lambda i, j, *_: (i, j, 0, 0))
    valued = pl.BlockSpec((1, 1, hbp, dv), lambda i, j, *_: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # active, beta, q . beta k: SMEM
        grid=(b, h // hb),
        in_specs=[tile, keyed, keyed, valued, keyed],
        out_specs=[tile, valued],
    )
    state, o = pl.pallas_call(
        functools.partial(_kda_state_kernel, hb=hb, hbp=hbp, dk=dk,
                          n_head=h, unroll=unroll),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((b, h // hb, hbp, dv), F32)],
        # operands 0-2 are the prefetched scalars, 3 the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="kda_state_update",
        interpret=interpret,
    )(active.astype(jnp.int32).reshape(b), beta.astype(F32).reshape(b * h),
      qbk.reshape(b * h), state, blocks(q, side), blocks(k, side),
      blocks(v, dv), blocks(jnp.broadcast_to(g, k.shape), side))
    return state, o[:, :, :hb].reshape(b, h, dv)
