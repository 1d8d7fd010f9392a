"""Sequence/context parallelism: ring attention and Ulysses-style
all-to-all head parallelism.

The reference predates attention partitioning entirely (SURVEY §5: its
long-sequence story is LoD tensors + while-op RNNs), but long-context is
first-class here: attention over a sequence sharded across the mesh `sp`
axis, with the KV shards rotating around the ICI ring (ppermute) and a
flash-attention-style online-softmax accumulator so no device ever holds
the full [T, T] score matrix — memory per chip is O(T_local * T_block).

Two interchangeable schedules:

- ``ring``   — KV blocks circulate; Tq_local × Tk_local partial scores per
  step; comm = (n-1) ppermute hops of the local KV (overlappable with the
  MXU work of the current block by XLA's latency-hiding scheduler).
- ``ulysses`` — two all-to-alls re-shard [T/n, H] → [T, H/n]; full local
  attention in head-parallel form; best when H ≥ n and T_local is small.

Both are pure jax and differentiable (grads flow through ppermute /
all_to_all); both run inside shard_map over the program's mesh, nested
under the CompiledBlock jit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG = -1e30


def _use_flash_blocks(tq, tk, d):
    """Route the per-shard block compute through the Pallas flash kernel
    when it can tile (TPU + lane-aligned head dim), or when forced for
    interpret-mode testing."""
    from paddle_tpu.ops import pallas as pk
    if pk.forced_interpret():
        # test-only override: interpret mode has no tiling constraints;
        # on real TPU the alignment gate below always applies
        return tq % 8 == 0 and tk % 8 == 0
    return (pk.kernel_enabled(128, d) and tq % 128 == 0 and tk % 128 == 0)


def _ring_attention_shard_flash(q, k, v, seed, axis_name: str, causal: bool,
                                scale: float, dropout_p: float = 0.0):
    """Flash-kernel variant: each ring step computes its [Tq_loc, Tk_loc]
    block with the Pallas flash kernel (O(T·D) VMEM) returning (o_j, lse_j)
    and merges blocks by log-sum-exp — compounding sp sharding with flash
    tiling. Block visibility under causal masking: kv from an earlier rank
    is fully visible, the diagonal block is causally masked, later ranks
    are skipped (lse = -inf)."""
    import functools as _ft
    from paddle_tpu.ops import pallas as pk

    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    dtype = q.dtype
    interpret = pk.interpret_mode()
    bq, bk = pk.pick_blocks(Tq, Tk)
    if interpret:               # tiny test shapes: no tiling constraints
        bq = bq or 8            # _use_flash_blocks guarantees Tq % 8 == 0
        bk = bk or 8
    base = _ft.partial(pk.flash_attention_lse, scale=scale, bq=bq, bk=bk,
                       interpret=interpret)

    def flash(qq, kk, vv, causal, kv_rank):
        if dropout_p <= 0:
            return base(qq, kk, vv, causal=causal)
        # per-(rank, kv_rank) seeds decorrelate the tile masks across ring
        # steps; the custom_vjp carries the seed in its residuals, so
        # fwd/bwd masks agree. (Masks are iid Bernoulli but not
        # bit-identical to the single-device kernel's — documented
        # divergence; the jnp ring path below IS bit-identical.)
        return base(qq, kk, vv, causal=causal, dropout_p=dropout_p,
                    seed=seed + rank * 1000003 + kv_rank)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(o, lse, oj, lsej):
        lse_new = jnp.logaddexp(lse, lsej)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + oj.astype(jnp.float32)
             * jnp.exp(lsej - lse_new)[..., None])
        return o, lse_new

    # step 0 is ALWAYS the diagonal block (kv starts as this rank's own
    # shard), so the causal flag is static per phase — no double compute
    o, lse = flash(q, k, v, causal, rank)
    o = o.astype(jnp.float32)
    lse = lse.astype(jnp.float32)
    kj = lax.ppermute(k, axis_name, perm=perm)
    vj = lax.ppermute(v, axis_name, perm=perm)

    def step(carry, j):
        o, lse, kj, vj = carry
        kv_rank = (rank - j) % n
        oj, lsej = flash(q, kj, vj, False, kv_rank)
        if causal:
            # off-diagonal: earlier ranks fully visible, later ranks masked
            visible = kv_rank < rank
            lsej = jnp.where(visible, lsej, _NEG)
            oj = jnp.where(visible, oj, 0.0)
        o, lse = merge(o, lse, oj, lsej)
        kj = lax.ppermute(kj, axis_name, perm=perm)
        vj = lax.ppermute(vj, axis_name, perm=perm)
        return (o, lse, kj, vj), None

    (o, lse, _, _), _ = lax.scan(step, (o, lse, kj, vj),
                                 jnp.arange(1, n))
    return o.astype(dtype)


def _ring_attention_shard(q, k, v, seed, axis_name: str, causal: bool,
                          scale: float, dropout_p: float = 0.0):
    """Per-shard ring attention. q/k/v: [B, H, T_local, D] (this rank's
    sequence shard); returns [B, H, T_local, D]."""
    if _use_flash_blocks(q.shape[2], k.shape[2], q.shape[3]):
        return _ring_attention_shard_flash(q, k, v, seed, axis_name, causal,
                                           scale, dropout_p)
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q_pos = rank * Tq + jnp.arange(Tq)                    # global positions
    dtype = q.dtype
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    bh_idx = jnp.arange(B * H).reshape(B, H, 1, 1)        # global coords →
    # masks bit-identical to full_attention's jnp path with the same seed

    # derive the accumulators from qf so they carry the same manual-axis
    # "varying" annotation as the rotating kv (shard_map VMA typing)
    m0 = qf[..., 0] * 0 + _NEG        # [B, H, Tq]
    l0 = qf[..., 0] * 0
    o0 = qf * 0
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, j):
        m, l, o, kj, vj = carry
        kv_rank = (rank - j) % n
        k_pos = kv_rank * Tk + jnp.arange(Tk)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj)
        if causal:
            valid = (q_pos[:, None] >= k_pos[None, :])    # [Tq, Tk]
            s = jnp.where(valid[None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = p * valid[None, None]
        l = l * alpha + p.sum(axis=-1)
        pv = p
        if dropout_p > 0:
            from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
            pv = p * hash_keep_mask(seed[0], bh_idx,
                                    q_pos[None, None, :, None],
                                    k_pos[None, None, None, :], dropout_p)
        o = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", pv, vj)
        # rotate KV to the next rank (ring hop over ICI)
        kj = lax.ppermute(kj, axis_name, perm=perm)
        vj = lax.ppermute(vj, axis_name, perm=perm)
        return (m_new, l, o, kj, vj), None

    (m, l, o, _, _), _ = lax.scan(step, (m0, l0, o0, kf, vf),
                                  jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(dtype)


def _ulysses_attention_shard(q, k, v, seed, axis_name: str, causal: bool,
                             scale: float, dropout_p: float = 0.0):
    """All-to-all head-parallel attention (Ulysses). q/k/v:
    [B, H, T_local, D]; H must divide by the axis size."""
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    H = q.shape[1]
    if H % n != 0:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp={n}")

    def exchange(x):       # [B, H, T/n, D] -> [B, H/n, T, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def unexchange(x):     # [B, H/n, T, D] -> [B, H, T/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = exchange(q), exchange(k), exchange(v)
    T = qg.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk",
                   qg.astype(jnp.float32) * scale, kg.astype(jnp.float32))
    if causal:
        pos = jnp.arange(T)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0:
        from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
        B, Hl = qg.shape[0], qg.shape[1]
        # global (batch*head) index: this rank owns heads
        # [rank*H/n, (rank+1)*H/n) — bit-identical to the unsharded mask
        bh = (jnp.arange(B)[:, None] * H
              + rank * Hl + jnp.arange(Hl)[None, :])[..., None, None]
        pos = jnp.arange(T)
        p = p * hash_keep_mask(seed[0], bh, pos[None, None, :, None],
                               pos[None, None, None, :], dropout_p)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vg.astype(jnp.float32))
    return unexchange(out.astype(q.dtype))


def sp_attention(q, k, v, mesh, sp_axis: str, causal: bool = False,
                 scale=None, impl: str = "ring", batch_axis=None,
                 head_axis=None, dropout_p: float = 0.0, seed=None):
    """Sequence-parallel attention over global [B, H, T, D] arrays whose T
    dim is (or will be) sharded over `sp_axis`. Runs inside jit; shard_map
    drops to per-device code and XLA rides the ICI ring.

    batch_axis/head_axis: optionally keep the surrounding dp (batch) / tp
    (head) sharding inside the manual region, so entering the shard_map
    does not force a reshard of activations that are already dp×tp
    partitioned (both dims are embarrassingly parallel here)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    base_fn = {"ring": _ring_attention_shard,
               "ulysses": _ulysses_attention_shard}[impl]
    if dropout_p > 0 and seed is None:
        raise ValueError("sp_attention: dropout_p > 0 requires a seed")

    def fn(qq, kk, vv, sd, axis_name, causal, scale):
        if dropout_p > 0:
            # decorrelate masks across dp/tp shards (the sp shards already
            # decorrelate via global positions / per-rank seeds)
            for ax in (batch_axis, head_axis):
                if ax and ax in mesh.axis_names and ax != sp_axis:
                    sd = sd + lax.axis_index(ax) * 7919
        return base_fn(qq, kk, vv, sd, axis_name=axis_name, causal=causal,
                       scale=scale, dropout_p=dropout_p)

    def ok(axis, dim):
        return (axis and axis != sp_axis and axis in mesh.axis_names
                and dim % mesh.shape[axis] == 0) or None

    b_ax = batch_axis if ok(batch_axis, q.shape[0]) else None
    h_ax = head_axis if ok(head_axis, q.shape[1]) else None
    spec = P(b_ax, h_ax, sp_axis, None)
    # pallas_call outputs carry no vma/replication annotation, so the
    # checker must be off when the ring shard routes through the flash
    # kernels; keep it on for the pure-jnp paths where it still catches
    # missing collectives.
    sp_size = mesh.shape[sp_axis]
    uses_flash = impl == "ring" and _use_flash_blocks(
        q.shape[2] // sp_size, k.shape[2] // sp_size, q.shape[3])
    kwargs = {"check_vma": False} if uses_flash else {}
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape(1)
    mapped = shard_map(
        partial(fn, axis_name=sp_axis, causal=causal, scale=float(scale)),
        mesh=mesh, in_specs=(spec, spec, spec, P(None)), out_specs=spec,
        **kwargs)
    return mapped(q, k, v, seed)


def full_attention(q, k, v, causal: bool = False, scale=None, bias=None,
                   dropout_p: float = 0.0, seed=None, layout: str = "bhtd",
                   mesh=None):
    """Single-device attention ([B, H, Tq, D] x [B, H, Tk, D]); also the
    emitter fallback when no sp axis is configured. On TPU with aligned
    shapes this routes to the Pallas flash kernel (ops/pallas/ — the jit-
    microkernel tier): measured faster than the XLA-fused path from
    T≈4096 (11.3 vs 14.3 ms) to T=16384 (44.6 vs 75.9 ms on v5e) and
    O(T·D) HBM instead of O(T²).

    dropout_p > 0 applies attention-weight dropout (upscale_in_train;
    reference semantics dist_transformer.py:1044) with a hash-derived
    keep mask over (seed, batch*head, q position, k position) — the SAME
    mask function as the flash kernels, so the two paths agree
    bit-exactly given the same seed.

    layout="bthd" takes/returns [B, T, H, D] instead — the head-split
    then becomes a free reshape at the call site and XLA folds the
    would-be transpose into the einsum's dimension numbers (a materialized
    [B,H,T,D] transpose per q/k/v per attention block costs real HBM;
    measured ~7 ms/step on Transformer-base bs128 v5e)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    if dropout_p > 0 and seed is None:
        raise ValueError("full_attention: dropout_p > 0 requires a seed")
    bthd = layout == "bthd"
    if bthd:
        b, tq, h, d = q.shape
        tk = k.shape[1]
    else:
        b, h, tq, d = q.shape
        tk = k.shape[2]
    if bias is None:
        from paddle_tpu.ops import pallas as pk
        if pk.kernel_enabled(128, d, mesh=mesh) and tq >= 2048:
            bq, bk = pk.pick_blocks(tq, tk)
            if bq and bk:
                if bthd:
                    out = pk.flash_attention(
                        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal, scale, bq, bk,
                        False, dropout_p, seed)
                    return out.transpose(0, 2, 1, 3)
                return pk.flash_attention(q, k, v, causal, scale, bq, bk,
                                          False, dropout_p, seed)
    # inputs stay in their storage dtype (bf16 under AMP) — the MXU
    # accumulates in fp32 via preferred_element_type; the scale applies
    # AFTER the dot, in fp32. For bthd the dots take the [B,T,H,D] arrays
    # DIRECTLY with batch dims (b, h) in place: an einsum spelling of the
    # same contraction makes XLA pre-transpose each operand to put batch
    # dims major — ~4 materialized [B,T,H,D] relayout copies per attention
    # block, measured 33% slower fwd+bwd at base dims (bs128 T64 v5e)
    if bthd:
        s = jax.lax.dot_general(
            q, k, (((3,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=jnp.float32) * scale      # [b,h,q,k]
    else:
        s = jax.lax.dot_general(
            q, k, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qp = jnp.arange(tq) + (tk - tq)
        s = jnp.where((qp[:, None] >= jnp.arange(tk)[None, :])[None, None],
                      s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0:
        from paddle_tpu.ops.pallas.flash_attention import hash_keep_mask
        seed = jnp.asarray(seed, jnp.int32).reshape(-1)[0]
        bh = jnp.arange(b * h).reshape(b, h, 1, 1)
        qpos = (tk - tq) + jnp.arange(tq)
        p = p * hash_keep_mask(seed, bh, qpos[None, None, :, None],
                               jnp.arange(tk)[None, None, None, :],
                               dropout_p)
    # probabilities in the storage dtype for the PV matmul (the flash
    # convention), fp32 accumulation on the MXU
    if bthd:
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((3,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)              # [b,h,q,d]
        return o.astype(q.dtype).transpose(0, 2, 1, 3)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
