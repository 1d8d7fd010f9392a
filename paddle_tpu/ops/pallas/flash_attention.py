"""Flash attention, forward and backward (Pallas TPU): three kernels.

Forward: the [B, H, Tq, Tk] score tensor that the composed attention
(parallel/ring_attention.py ``full_attention``, the jnp tier the tests
compare against) writes to HBM never exists. A grid step owns one
[BQ, D] query block in VMEM, streams [BK, D] key/value blocks, and keeps
running (max, denom, acc) statistics — the standard flash recurrence.
HBM traffic drops from O(Tq*Tk) to O(Tq*D + Tk*D) per head. The callers:
``ops/mla.py:causal_attention`` (latent attention's heads of 192 / 128,
the trained cell), ``ops/nn_ops.py:fused_attention_block`` (heads of
128 where ``flash_engage`` names blocks), ring attention's per-shard
inner loop (``flash_attention_lse``) and — the forward alone, PR 52 —
``ops/kv_attention.py:_gqa_attend``: the serving prefill of a full
grouped-KV layer over a bucket longer than 512 rows (LFM2's 32 query
heads of 64 over 8 key heads, Granite's and Trinity's heads of 128).

Grouped key heads (forward only). ``k`` and ``v`` may hold ``n_kv``
heads for ``H = n_kv * G`` query heads: the key / value ``BlockSpec``
index maps (``_kv_index_map``, dense grid and causal schedule alike)
read row ``bh // G`` of the [B * n_kv, Tk, D] keys for query row ``bh``
of [B * H, Tq, D], so the keys are never broadcast to H heads in HBM.
A group of one keeps the maps, and the lowered text, it always had.
The backward kernels have no such maps: both ``custom_vjp`` entries
REFUSE a gradient through a grouped call (``_ungrouped``).

Backward: jax.custom_vjp over blockwise Pallas kernels. Residuals are
(q, k, v, o, lse) — O(T*D) — and the bwd recomputes scores tile-by-tile in
two kernels (dQ over k-blocks; dK/dV over q-blocks, the flash-attention-2
schedule), so training peak memory is O(T*D) end to end. The forward
call NAMES ``out`` and ``lse`` as the kernel wrote them (``_named``): a
recomputed op keeps the two (contrib/recompute.py) and its backward runs
no forward kernel.

The causal schedule (PR 48). A causal call whose every query sees a key
(``tq <= tk``) runs its grid over the VISIBLE (query block, key block)
pairs alone: the grid is (heads, pairs) and two int32 tables in SMEM
(scalar prefetch) give a step its blocks, so no step is spent and no
tile is fetched above the diagonal (``causal_schedule`` is the
arithmetic, ``paddle_flash_causal_blocks_total`` what a lowering did
with its shape). Every visited tile takes ``_masked_scores``' mask: a
second, mask-free body for the tiles wholly under the diagonal was
measured on the chip and not kept — the vector units have the slack
(0.2 ms of a 494 ms step) and two bodies a kernel are twice the code
to trace, lower and load at every set-up. A non-causal call, and a
causal one with ``tq > tk`` (queries that see no key: rows of zeros, as
before), keep the dense (heads, Tq/bq, Tk/bk) grid and lower to the
text they always had."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.observability import metrics as _metrics

_NEG = -1e30

# exporter-catalog family (docs/observability.md). Counts LOWERINGS, as
# ``paddle_mla_decode_lowered_total`` does: each time a causal call of
# one of the three kernels is traced, the grid steps it will run
# (``visited``) and those that compute a tile (``computed``), over all
# heads of the call. Under the causal schedule visited == computed; a
# shape that fell back to the dense grid (tq > tk) reads visited >
# computed.
CAUSAL_BLOCKS = _metrics.counter(
    "paddle_flash_causal_blocks_total",
    "Causal flash-attention tiles by lowering: grid steps visited, "
    "tiles computed (kernel=fwd|dq|dkv)",
    labelnames=("kernel", "kind"))


def _block_visible(causal, kb, bk, q_last):
    """A (q block, k block) tile contributes iff any key pos < q_last."""
    if not causal:
        return True
    return (kb * bk) < q_last


def _visible_tiles(tq, tk, bq, bk):
    """The (query block, key block) tiles of a causal [tq, tk] call that
    hold a key at or under some query, query-major."""
    q_off = tk - tq
    return [(i, j) for i in range(tq // bq) for j in range(tk // bk)
            if _block_visible(True, j, bk, q_off + (i + 1) * bq)]


def causal_schedule(tq, tk, bq, bk, key_major=False):
    """The causal grid of a [tq, tk] call in [bq, bk] tiles, in visit
    order: (query blocks, key blocks) as int32 numpy arrays over the
    visible tiles (``_block_visible``, with the diagonal shifted by
    ``q_off = tk - tq``). Query-major (forward, dQ: a query block's key
    blocks are 0..its last) or key-major (dK/dV: a key block's query
    blocks are its first..the last). None where some query block sees
    no key (tq > tk): its output would never be written, so such a call
    keeps the dense grid."""
    if tq > tk:
        return None
    tiles = _visible_tiles(tq, tk, bq, bk)
    if key_major:
        tiles.sort(key=lambda t: (t[1], t[0]))
    qi, kj = (np.asarray(x, np.int32) for x in zip(*tiles))
    return qi, kj


def _count_causal(kernel, heads, tq, tk, bq, bk):
    """-> the (outer, inner) tables of the schedule for this kernel, or
    None for the dense grid; counts what the lowering will do."""
    sched = causal_schedule(tq, tk, bq, bk, key_major=kernel == "dkv")
    computed = len(_visible_tiles(tq, tk, bq, bk))
    visited = computed if sched else (tq // bq) * (tk // bk)
    for kind, n in (("visited", visited), ("computed", computed)):
        CAUSAL_BLOCKS.labels(kernel=kernel, kind=kind).inc(heads * n)
    if sched is None:
        return None
    qi, kj = sched
    return (kj, qi) if kernel == "dkv" else (qi, kj)


def _masked_scores(q, k, scale, causal, qb, j, bq, bk, q_off):
    """Scaled q·kᵀ with the causal iota mask — the single source of the
    mask convention shared by the forward and both backward kernels
    (forward/backward desync here would corrupt gradients silently).
    Operands stay in their storage dtype (bf16 under AMP — an fp32
    upcast before the dot runs the MXU at the fp32 rate, ~6x slower);
    accumulation is fp32 and the scale applies post-dot in fp32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = (q_off + qb * bq +
                jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG)
    return s


_C_Q = np.uint32(0x9E3779B9)
_C_K = np.uint32(0x85EBCA6B)


def _seed_mix(seed, bh):
    return (jnp.asarray(seed).astype(jnp.uint32)
            + jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))


def _finalize(x):
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def keep_threshold(dropout_p):
    """A hashed coordinate is kept iff its 32 bits reach this."""
    return min(int(dropout_p * 2.0 ** 32), 2 ** 32 - 1)


def hash_keep_mask(seed, bh, qpos, kpos, dropout_p):
    """Attention-weight dropout keep mask, upscale_in_train convention:
    keep/(1-p) as float32. Counter-based: a murmur3-finalizer mix of
    (seed, batch*head index, query position, key position) in uint32
    arithmetic — pure jnp, so the SAME function runs inside the Pallas
    kernels (TPU and interpret mode both) and in the jnp fallback paths,
    and the backward kernels regenerate the forward's mask bit-exactly
    from the same coordinates (reference semantics: dropout on the
    softmax weights, dist_transformer.py:1044). ``flash_pairs`` builds
    the same bits from the same pieces."""
    x = (qpos.astype(jnp.uint32) * _C_Q ^ kpos.astype(jnp.uint32) * _C_K)
    x = _finalize(x ^ _seed_mix(seed, bh))
    thresh = jnp.uint32(keep_threshold(dropout_p))
    keep = (x >= thresh).astype(jnp.float32)
    return keep * (1.0 / (1.0 - dropout_p))


def _block_keep_mask(seed, bh, qb, j, bq, bk, q_off, dropout_p):
    """hash_keep_mask over one [bq, bk] tile — coordinates derived exactly
    like the causal mask in _masked_scores, so fwd/dq/dkv agree."""
    qpos = (q_off + qb * bq +
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return hash_keep_mask(seed, bh, qpos, kpos, dropout_p)


def _step_ids(args, paired):
    """(batch*head, outer block, inner block) of this grid step, and the
    kernel's other refs: the dense grid's own ids, or under the causal
    schedule the step's row of the two tables in SMEM."""
    if not paired:
        return pl.program_id(0), pl.program_id(1), pl.program_id(2), args
    outer_ref, inner_ref, *args = args
    p = pl.program_id(1)
    return pl.program_id(0), outer_ref[p], inner_ref[p], args


def _causal_tiles(tile, paired, causal, qb, kb, bq, bk, q_off):
    """Run ``tile`` where the step's place against the diagonal asks.
    Dense grid: skip a tile wholly above it. Causal schedule: every
    step is a visible tile."""
    if paired:
        tile()
    else:
        pl.when(_block_visible(causal, kb, bk, q_off + (qb + 1) * bq))(tile)


def _last_key_block(paired, qb, j, nk, bq, bk, q_off):
    """Key block j closes query block qb's row: the last one there is,
    or under the causal schedule the last one the block sees (its key
    blocks are 0..that one)."""
    last = j == nk - 1
    if not paired:
        return last
    return jnp.logical_or(last, jnp.logical_not(
        _block_visible(True, j + 1, bk, q_off + (qb + 1) * bq)))


def _fwd_kernel(*args, bq, bk, nk, causal, paired, scale, q_off,
                dropout_p):
    """Dense grid (BH, Tq/bq, Tk/bk), or under the causal schedule
    (BH, visible tiles, query-major): the innermost steps stream [bk, D]
    key/value tiles from HBM while (m, l, acc) persist in VMEM scratch —
    TPU grid steps run sequentially, so the scratch carries the online-
    softmax state across a query block's key blocks; VMEM use is
    O(bq*d + bk*d), independent of sequence length.

    dropout_p > 0 applies attention-weight dropout (upscale_in_train):
    the keep mask multiplies the numerator accumulator only — the
    denominator stays the full softmax sum, matching the composed
    softmax→dropout→matmul graph the reference trains
    (dist_transformer.py:1044). The seed rides scalar prefetch."""
    bh, qb, j, args = _step_ids(args, paired)
    if dropout_p > 0:
        seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, \
            m_scr, l_scr, acc_scr = args
    else:
        seed_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = args

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile():
        q = q_ref[0]                                      # [BQ, D]
        k = k_ref[0]                                      # [BK, D]
        v = v_ref[0]
        s = _masked_scores(q, k, scale, causal, qb, j, bq, bk, q_off)
        m = m_scr[:]
        l = l_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = p
        if dropout_p > 0:
            pv = p * _block_keep_mask(seed_ref[0], bh, qb, j, bq, bk,
                                      q_off, dropout_p)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            pv.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # causal: key blocks wholly above the diagonal contribute nothing
    _causal_tiles(tile, paired, causal, qb, j, bq, bk, q_off)

    @pl.when(_last_key_block(paired, qb, j, nk, bq, bk, q_off))
    def _():
        safe_l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(safe_l)           # [BQ, 1]


def _grid_spec(grid, in_specs, out_specs, scratch_shapes, seed, pairs=None):
    """pallas_call kwargs. The index maps are written over (bh, outer
    block, inner block). Plain dense grid with neither tables nor seed;
    else a scalar-prefetch grid whose leading operands, in SMEM, are the
    causal schedule's two tables (``pairs``: the grid becomes (bh,
    visible tiles) and a step's blocks are its row of the tables, so
    what lies above the diagonal is neither visited nor fetched) and
    then the dropout seed."""
    from jax.experimental.pallas import tpu as pltpu
    n_scalars = len(pairs or ()) + (seed is not None)
    if not n_scalars:
        return dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                    scratch_shapes=scratch_shapes)
    if pairs is not None:
        grid = (grid[0], len(pairs[0]))

    def lift(spec):
        im = spec.index_map

        def index_map(*args):
            # the scalar refs arrive as the TRAILING arguments after the
            # grid indices
            ids, refs = args[:-n_scalars], args[-n_scalars:]
            if pairs is not None:
                bh, step = ids
                ids = (bh, refs[0][step], refs[1][step])
            return im(*ids)
        return pl.BlockSpec(spec.block_shape, index_map)

    in_specs = [lift(s) for s in in_specs]
    out_specs = (lift(out_specs) if isinstance(out_specs, pl.BlockSpec)
                 else [lift(s) for s in out_specs])
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes))


def _seed_args(seed):
    if seed is None:
        return ()
    return (jnp.asarray(seed, jnp.int32).reshape(1),)


def _named(out, lse):
    """The forward kernel's two results under the names a recomputed op
    keeps (``contrib/recompute.py:KEPT``): a kernel to remake, a query
    row's width to hold. Outside a checkpoint with a policy a name is
    an identity that lowers to nothing. Named AS THE KERNEL WROTE THEM
    (``lse`` [BH, T, 1]): the kept array is then the kernel's own
    buffer, which the backward kernels read as they read a recomputed
    one. A kept [B, H, T] copy of ``lse`` is made on the chip by a
    reduction over the padded unit dimension, and the step's gradients
    read 1-3 % low with it (PR 50: the check's norms 0.018-0.020 off
    where they are 0.0025 off without)."""
    from jax.ad_checkpoint import checkpoint_name
    from paddle_tpu.contrib.recompute import FLASH_LSE, FLASH_OUT
    return checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)


def _kv_index_map(h, n_kv):
    """The key / value ``BlockSpec`` index map of the forward kernel
    over (batch * query head, query block, key block). With grouped key
    heads (``h = n_kv * G`` query heads, the serving prefill's) query
    row ``bh`` of the [B * h, ...] view reads row ``bh // G`` of the
    [B * n_kv, ...] keys: batch row ``bh // h``, key head ``(bh % h) //
    G``. A group of one keeps the map, and so the lowered text, it
    always had."""
    if h % n_kv:
        raise ValueError(f"{h} query heads are not a whole number of "
                         f"groups over {n_kv} key heads")
    g = h // n_kv
    if g == 1:
        return lambda bh, i, j: (bh, j, 0)
    return lambda bh, i, j: (bh // g, j, 0)


def _ungrouped(q, k):
    """The backward kernels have no grouped index maps (the grouped
    callers are the serving ops, ``no_grad``): a gradient through a
    grouped call is refused here, in the forward rule, and never taken
    with the wrong keys."""
    if k.shape[1] != q.shape[1]:
        raise ValueError(
            f"flash attention over grouped key heads ({q.shape[1]} query, "
            f"{k.shape[1]} key) is forward-only: no backward kernel "
            "reads them")


def _flash_fwd(q, k, v, causal, scale, bq, bk, interpret,
               dropout_p=0.0, seed=None):
    from jax.experimental.pallas import tpu as pltpu
    if dropout_p <= 0:
        seed = None
    b, h, tq, d = q.shape
    n_kv, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    q4 = q.reshape(b * h, tq, d)
    k4 = k.reshape(b * n_kv, tk, d)
    v4 = v.reshape(b * n_kv, tk, dv)
    kv_block = _kv_index_map(h, n_kv)
    nk = tk // bk
    grid = (b * h, tq // bq, nk)
    pairs = _count_causal("fwd", b * h, tq, tk, bq, bk) if causal else None
    kern = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                             paired=pairs is not None,
                             scale=scale, q_off=tk - tq,
                             dropout_p=dropout_p if seed is not None else 0.0)
    out, lse = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
        ],
        interpret=interpret,
        **_grid_spec(
            grid,
            [
                pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bk, d), kv_block),
                pl.BlockSpec((1, bk, dv), kv_block),
            ],
            [
                pl.BlockSpec((1, bq, dv), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            ],
            [
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
            seed, pairs),
    )(*(pairs or ()), *_seed_args(seed), q4, k4, v4)
    out, lse = _named(out, lse)
    return out.reshape(b, h, tq, dv), lse.reshape(b, h, tq)


def pick_blocks(tq, tk):
    """Largest hardware-friendly block sizes dividing the sequence lengths:
    bq=512/bk=1024 won the on-chip sweep at T=4096..16384 — a sweep of
    2026-08 at heads of 128 on the dense grid, non-latent. Where a call
    was measured since, the committed table overrides it
    (``causal_blocks``: latent attention's heads of 192 / 128 take
    1024 x 1024 under the causal schedule)."""
    bq = next((s for s in (512, 256, 128) if tq % s == 0), None)
    bk = next((s for s in (1024, 512, 256, 128) if tk % s == 0), None)
    return bq, bk


def causal_blocks(t, d, dv):
    """(bq, bk) of a causal call over whole sequences of ``t`` tokens at
    query / key heads of ``d`` and value heads of ``dv``: the committed
    table's entry where a whole-model A/B set one, else ``pick_blocks``.
    The one entry (PR 48, one v5e, ``train_joyai_seq8k_1chip`` — 32
    heads of 192 / 128 over 8 192 tokens, the causal schedule): 512 x
    1024 a step of 494.4 ms and 15 488-15 552 tokens/s, 1024 x 1024
    **485.4 ms and 15 747-15 817** (the dense grid at 512 x 1024: 547.4
    and 14 127-14 192). Alone, a layer's four calls (forward twice, dQ,
    dK/dV), ms: 1024 x 1024 **36.1**, 512 x 1024 37.7, 512 x 2048 40.1
    and 2048 x 512 48.6 (both only over the default VMEM limit), 256 x
    2048 41.9, 256 x 1024 42.1, 512 x 512 45.2, 1024 x 512 46.6, 256 x
    512 51.0, 2048 x 256 58.9, 512 x 256 64.8, 1024 x 256 67.6, 256 x
    256 76.5. Square tiles multiply 36/32 of the causal half where
    512 x 1024 multiply 72/64 — the same: it is the fewer, larger steps
    that pay, and keys in tiles under 512 cost most.

    The grouped forward's rows (PR 52, one v5e; the serving prefill,
    ``kv_attention._gqa_attend``), 1024 x 1024 everywhere from 2 048
    tokens. 32 query / 8 key heads of 64 (``serve_lfm2_extract_closed``,
    traced in the cell, a prefill's three calls): 4 096 tokens **3.901
    ms** against 4.155 at 512 x 1024, 2 048 tokens **1.214** against
    1.282; a call alone at 4 096, ms: 1024 x 1024 **1.417** (48.5
    TFLOP/s of the causal half: both products half-fill the MXU at a
    contraction and a result width of 64), 512 x 1024 1.489, 1024 x
    2048 1.726, 256 x 1024 1.749, 512 x 2048 1.761, 512 x 512 2.144,
    256 x 512 2.417, 1024 x 512 2.587, 128 x 512 3.468, 256 x 256
    4.330 (2048 x 1024 and over: past the VMEM limit). 32 query / 4
    key heads of 128 alone (Trinity's full layer; Granite's 8 key heads
    read the same), 1024 x 1024 against 512 x 1024, ms: 2 048 tokens
    0.452 | 0.486, 4 096 1.358 | 1.483, 8 192 4.674 | 5.082, 16 384
    **17.21 | 18.75** (128 TFLOP/s). At 1 024 tokens (Granite's short
    bucket: no row, ``pick_blocks``) 512 x 1024 0.238, 256 x 1024
    0.234, 1024 x 1024 0.246."""
    try:
        from paddle_tpu.passes import autotune as at
        entry = at.lookup("flash_attention", {"T": int(t), "d": int(d),
                                              "dv": int(dv), "causal": 1})
    except Exception:
        entry = None
    if entry is not None and entry.get("impl") == "flash":
        bq, bk = int(entry["bq"]), int(entry["bk"])
        if t % bq == 0 and t % bk == 0:
            return bq, bk
    return pick_blocks(t, t)


# Benchmark-derived kernel selection (round-4 VERDICT #4 — the
# reference's jit-tier discipline: kernel_pool.cc Get() picks whichever
# implementation won its own benchmark, not a hand threshold).
# Round 6 moved the winner data out of this file into the UNIFIED
# autotune cache (paddle_tpu/passes/autotune_table.json, v5e sweep of
# 2026-08-01: fwd+bwd of the attention REGION at 8192 tokens, (bq, bk)
# grid vs the XLA fused-dot composition) — ONE committed-table
# discipline for every measured choice, re-tuned with
# `tools/autotune.py --kind flash_attention --commit`. Where a FULL
# MODEL row exists, its A/B overrides the region sweep (isolated
# regions mispredict block choice under real co-residency; entries
# marked source="model-ab" in the table). Model-level verification of
# the T=512 crossover, by head size, both on transformer_big at
# [16, 512] tokens a chip, dropout 0.3, one v5e:
#   8 heads of 128 (NOT the published model; 2026-08): 73.2k -> 77.1k
#     tok/s (42.8 -> 45.1% MFU) when this table routed it to flash; r04
#     had measured the OPPOSITE with the then-kernels — which is exactly
#     why the rule must be a measured table, not a hand threshold.
#   16 heads of 64 (the published widths; cell train_big_1chip,
#     2026-09-30, PR 41): composed 54.0k -> 76.9k tok/s (36.9 -> 52.6%
#     MFU) through flash_pairs; THIS file's kernel behind a [B,H,T,D]
#     relayout read 55.8k there (+3 %) and was not kept for heads of 64.
#     The d=64 rows at T=512 are that reading; their blocks are
#     flash_pairs' (bq, and bk = the whole key axis). No other d=64 row
#     is kept: the region sweep's were of this file's kernel behind a
#     [B,H,T,D] relayout, which heads of 64 no longer take.
#   32 heads of 192 / 128, causal, T=8192 (latent attention expanded;
#     cell train_joyai_seq8k_1chip, 2026-10-01, PR 48): the row with
#     ``dv`` in its key, read by ``causal_blocks`` (its docstring has
#     the readings).


def flash_engage(tq, tk, d, causal):
    """(bq, bk) when the flash path is the measured winner for this
    region shape, else None (composition/fused-block keeps the row).

    Below T=512 the region wins in AUTOTUNE are within the
    bthd<->bhtd boundary-transpose cost the composed path pays at the
    model level (the r4 fused block won T=256 by +1.5 MFU), so the
    crossover is T>=512 where the model-level A/B confirmed it (heads
    of 128 in 2026-08, heads of 64 through ``flash_pairs`` in PR 41:
    the comment above). Shapes
    beyond the table (T>2048, uneven tq/tk) fall back to the long-
    context heuristic blocks that won the T=4096..16384 sweep."""
    def _valid(blocks):
        # never hand the caller a tuple with None inside (pick_blocks
        # returns None entries for non-128-multiple lengths)
        if blocks and blocks[0] and blocks[1] \
                and tq % blocks[0] == 0 and tk % blocks[1] == 0:
            return blocks
        return None

    if d not in (64, 128):
        # beyond the benchmark grid: only the long-context regime
        # (where flash's O(T·D) HBM advantage is shape-generic) engages
        return _valid(pick_blocks(tq, tk)) if min(tq, tk) >= 2048 \
            else None
    if tq != tk:                      # cross-shape (beam decode etc.)
        if min(tq, tk) >= 2048:
            return _valid(pick_blocks(tq, tk))
        return None
    # T=256 model A/B measured a TIE (transformer base: 220.1k tok/s
    # via flash vs 220.2k via the fused block) — the fused block keeps
    # the row below the 512 crossover
    if tq < 512:
        return None
    entry = None
    try:
        from paddle_tpu.passes import autotune as at
        entry = at.lookup("flash_attention",
                          at.flash_params(tq, d, causal))
        # the committed keys are exact sweep-grid Ts: only honor a
        # bucketed hit when the bucket IS the shape (blocks tuned at
        # T=512 do not transfer to T=640 — fall to pick_blocks there)
        if entry is not None and at.bucket_pow2(tq) != tq:
            entry = None
    except Exception:
        entry = None
    if entry is not None:
        if entry.get("impl") != "flash":
            return None               # XLA composition won the region
        return _valid((int(entry["bq"]), int(entry["bk"]))) \
            or _valid(pick_blocks(tq, tk))
    if tq >= 2048:                    # beyond the sweep grid
        return _valid(pick_blocks(tq, tk))
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, scale=None, bq=128, bk=128,
                    interpret=False, dropout_p=0.0, seed=None):
    """q [B,H,Tq,D], k [B,H,Tk,D], v [B,H,Tk,Dv] → [B,H,Tq,Dv] (value
    heads of another size than the query/key heads': latent attention's
    192 / 128). Tq % bq == Tk % bk == 0. Forward only, k and v may hold
    n_kv heads with H = n_kv * G (the module docstring).
    dropout_p applies attention-weight dropout (upscale_in_train) with a
    keep mask derived from `seed` (int32 scalar, traced ok) + tile
    coordinates — identical in fwd and bwd kernels."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    out, _ = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret,
                        dropout_p, seed)
    return out


def _vjp_fwd(q, k, v, causal, scale, bq, bk, interpret, dropout_p, seed):
    _ungrouped(q, k)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret,
                          dropout_p, seed)
    return out, (q, k, v, out, lse, seed)


def _dq_kernel(*args, bq, bk, nk, causal, paired, scale, q_off, has_glse,
               dropout_p):
    """Grid (BH, Tq/bq, Tk/bk), or the causal schedule's (BH, visible
    tiles, query-major): accumulate dQ for one q block across k
    blocks; ds = p * (mask·(dO·Vᵀ) − delta + dLSE) — the dLSE term carries
    the cotangent of the exposed log-sum-exp (∂lse/∂s_ij = p_ij), used by
    ring attention's block-merge; zero for plain attention. The dropout
    keep mask regenerates bit-exactly from the tile coordinates (only the
    dp term is masked: out = Σ_k w_k·m_k·v_k gives ds_j = w_j(m_j·dp_j −
    g·out), and delta = g·out already absorbs the mask)."""
    bh, qb, j, args = _step_ids(args, paired)
    if dropout_p > 0:
        seed_ref, *args = args
    else:
        seed_ref = None
    if has_glse:
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            glse_ref, dq_ref, dq_scr = args
    else:
        glse_ref = None
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dq_ref, dq_scr = args

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = _masked_scores(q, k, scale, causal, qb, j, bq, bk, q_off)
        p = jnp.exp(s - lse_ref[0])                       # [BQ, BK]
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0:
            dp = dp * _block_keep_mask(seed_ref[0], bh, qb, j, bq, bk,
                                       q_off, dropout_p)
        corr = delta_ref[0] - (glse_ref[0] if has_glse else 0.0)
        ds = p * (dp - corr)
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32) * scale

    _causal_tiles(tile, paired, causal, qb, j, bq, bk, q_off)

    @pl.when(_last_key_block(paired, qb, j, nk, bq, bk, q_off))
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*args, bq, bk, nq, causal, paired, scale, q_off, has_glse,
                dropout_p):
    """Grid (BH, Tk/bk, Tq/bq), or the causal schedule's (BH, visible
    tiles, key-major): accumulate dK/dV for one k block across q
    blocks; dV = (p·mask)ᵀ·dO, dK = scale · dsᵀ·Q."""
    bh, kb, i, args = _step_ids(args, paired)
    if dropout_p > 0:
        seed_ref, *args = args
    else:
        seed_ref = None
    if has_glse:
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            glse_ref, dk_ref, dv_ref, dk_scr, dv_scr = args
    else:
        glse_ref = None
        q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, \
            dk_ref, dv_ref, dk_scr, dv_scr = args
    first = i == 0
    if paired:          # a key block's query blocks are its first..nq-1
        first = jnp.logical_or(first, jnp.logical_not(
            _block_visible(True, kb, bk, q_off + i * bq)))

    @pl.when(first)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        s = _masked_scores(q, k, scale, causal, i, kb, bq, bk, q_off)
        p = jnp.exp(s - lse_ref[0])                       # [BQ, BK]
        pm = p
        if dropout_p > 0:
            mask = _block_keep_mask(seed_ref[0], bh, i, kb, bq, bk,
                                    q_off, dropout_p)
            pm = p * mask
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pm.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (p·m)ᵀ·dO [BK, D]
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0:
            dp = dp * mask
        corr = delta_ref[0] - (glse_ref[0] if has_glse else 0.0)
        ds = p * (dp - corr)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # scale·dsᵀ·Q

    # q block i sees this k block iff its LAST query reaches it
    _causal_tiles(tile, paired, causal, i, kb, bq, bk, q_off)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_impl(causal, scale, bq, bk, interpret, res, g, glse,
                    dropout_p=0.0, seed=None):
    from jax.experimental.pallas import tpu as pltpu
    q, k, v, o, lse = res
    if dropout_p <= 0:
        seed = None
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    nq, nk = tq // bq, tk // bk
    q4 = q.reshape(b * h, tq, d)
    k4 = k.reshape(b * h, tk, d)
    v4 = v.reshape(b * h, tk, dv)
    g4 = g.reshape(b * h, tq, dv)
    lse4 = lse.reshape(b * h, tq, 1)
    delta4 = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                     axis=-1).reshape(b * h, tq, 1)
    has_glse = glse is not None
    glse4 = (glse.astype(jnp.float32).reshape(b * h, tq, 1)
             if has_glse else None)
    q_off = tk - tq
    dp_eff = dropout_p if seed is not None else 0.0
    q_pairs = k_pairs = None
    if causal:
        q_pairs = _count_causal("dq", b * h, tq, tk, bq, bk)
        k_pairs = _count_causal("dkv", b * h, tq, tk, bq, bk)
    glse_in = ([glse4], [pl.BlockSpec((1, bq, 1),
                                      lambda bh, i, j: (bh, i, 0))])         if has_glse else ([], [])

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          paired=q_pairs is not None,
                          scale=scale, q_off=q_off, has_glse=has_glse,
                          dropout_p=dp_eff),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        interpret=interpret,
        **_grid_spec(
            (b * h, nq, nk),
            [
                pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bk, dv), lambda bh, i, j: (bh, j, 0)),
                pl.BlockSpec((1, bq, dv), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            ] + glse_in[1],
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            [pltpu.VMEM((bq, d), jnp.float32)],
            seed, q_pairs),
    )(*(q_pairs or ()), *_seed_args(seed), q4, k4, v4, g4, lse4, delta4,
      *glse_in[0])

    glse_in_kv = ([glse4], [pl.BlockSpec((1, bq, 1),
                                         lambda bh, j, i: (bh, i, 0))])         if has_glse else ([], [])
    dk, dval = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, nq=nq, causal=causal,
                          paired=k_pairs is not None,
                          scale=scale, q_off=q_off, has_glse=has_glse,
                          dropout_p=dp_eff),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, dv), v.dtype),
        ],
        interpret=interpret,
        **_grid_spec(
            (b * h, nk, nq),
            [
                pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bk, dv), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bq, dv), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda bh, j, i: (bh, i, 0)),
            ] + glse_in_kv[1],
            [
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bk, dv), lambda bh, j, i: (bh, j, 0)),
            ],
            [pltpu.VMEM((bk, d), jnp.float32),
             pltpu.VMEM((bk, dv), jnp.float32)],
            seed, k_pairs),
    )(*(k_pairs or ()), *_seed_args(seed), q4, k4, v4, g4, lse4, delta4,
      *glse_in_kv[0])

    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dval.reshape(b, h, tk, dv))


def _vjp_bwd(causal, scale, bq, bk, interpret, dropout_p, res, g):
    q, k, v, o, lse, seed = res
    grads = _flash_bwd_impl(causal, scale, bq, bk, interpret,
                            (q, k, v, o, lse), g, None, dropout_p, seed)
    return grads + (_zero_seed_cot(seed),)


def _zero_seed_cot(seed):
    if seed is None:
        return None
    return np.zeros(jnp.shape(seed), dtype=jax.dtypes.float0)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_lse(q, k, v, causal=False, scale=None, bq=128, bk=128,
                        interpret=False, dropout_p=0.0, seed=None):
    """Like flash_attention but also returns the per-query log-sum-exp —
    the interface ring attention needs to merge per-block results
    (o_total = Σ_j o_j·exp(lse_j − lse_total)). Differentiable in both
    outputs: the bwd kernels carry the lse cotangent via the dLSE term.
    Note lse itself is dropout-free (mask applies to the numerator only),
    so the ring block-merge stays exact under dropout."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    return _flash_fwd(q, k, v, causal, scale, bq, bk, interpret,
                      dropout_p, seed)


def _lse_vjp_fwd(q, k, v, causal, scale, bq, bk, interpret, dropout_p,
                 seed):
    _ungrouped(q, k)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, scale, bq, bk, interpret,
                          dropout_p, seed)
    return (out, lse), (q, k, v, out, lse, seed)


def _lse_vjp_bwd(causal, scale, bq, bk, interpret, dropout_p, res, gs):
    q, k, v, o, lse, seed = res
    g, glse = gs
    grads = _flash_bwd_impl(causal, scale, bq, bk, interpret,
                            (q, k, v, o, lse), g, glse, dropout_p, seed)
    return grads + (_zero_seed_cot(seed),)


flash_attention_lse.defvjp(_lse_vjp_fwd, _lse_vjp_bwd)
