"""Expert parallelism (MoE) over a mesh `ep` axis.

The reference has no MoE (SURVEY §2 parallelism inventory: EP absent) —
TPU-first extension: a switch-style (top-1) mixture-of-experts FFN whose
expert weights shard over the `ep` mesh axis and whose token dispatch /
combine are `lax.all_to_all` collectives over ICI — the same
sharded-table + id-exchange shape as the pserver's distributed embedding
(SURVEY §2 #24/#27 sparse prefetch), applied to expert FFNs.

Fixed expert capacity keeps every shape static for XLA: each token picks
its top expert, tokens beyond an expert's capacity are dropped (standard
switch-transformer semantics), and the auxiliary load-balancing loss
pushes routing toward uniform.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _shard_moe(x, gate_w, w1, b1, w2, b2, *, ep_axis, n_experts,
               capacity, mean_axes):
    """Per-shard switch FFN. x: this rank's tokens [S, D] (the token axis
    is sharded over BOTH dp and ep, so every ep rank routes a distinct
    shard — standard EP layout, no duplicated expert work); w1/b1/w2/b2:
    this rank's local experts [E_local, ...]."""
    n_ranks = lax.axis_size(ep_axis)
    e_local = n_experts // n_ranks
    s, d = x.shape

    # --- routing (every rank routes its own tokens over ALL experts)
    logits = x @ gate_w                                 # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                 # [S]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)   # [S, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot                     # 1-based
    pos = jnp.sum(pos, axis=-1) - 1                               # [S]
    keep = pos < capacity

    # --- dispatch: [E, C, D] buffer, dropped tokens contribute zeros
    disp = jnp.zeros((n_experts, capacity, d), x.dtype)
    safe_e = jnp.where(keep, expert, 0)
    safe_p = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[:, None], x, 0.0)
    disp = disp.at[safe_e, safe_p].add(contrib)

    # --- all-to-all: regroup so each rank holds its local experts' queues
    # [E, C, D] -> [n_ranks, E_local, C, D] -> a2a -> [n_ranks, E_local, C, D]
    disp = disp.reshape(n_ranks, e_local, capacity, d)
    tokens = lax.all_to_all(disp, ep_axis, split_axis=0, concat_axis=0,
                            tiled=False)                # [R, E_local, C, D]

    # --- expert FFN on local experts (batched over E_local)
    def expert_ffn(tok, w1e, b1e, w2e, b2e):
        h = jnp.maximum(tok @ w1e + b1e, 0.0)
        return h @ w2e + b2e

    out = jax.vmap(
        lambda tok_e, w1e, b1e, w2e, b2e: expert_ffn(
            tok_e.reshape(-1, d), w1e, b1e, w2e, b2e
        ).reshape(n_ranks, capacity, d),
        in_axes=(1, 0, 0, 0, 0), out_axes=1,
    )(tokens, w1, b1, w2, b2)                           # [R, E_local, C, D]

    # --- return trip
    back = lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                          tiled=False)                  # [R, E_local, C, D]
    back = back.reshape(n_experts, capacity, d)

    # --- combine: gather each kept token's expert output, weight by gate
    gathered = back[safe_e, safe_p]                     # [S, D]
    y = jnp.where(keep[:, None], gathered * gate[:, None], 0.0)

    # load-balance aux loss (Switch: E * sum_e f_e * p_e)
    frac_tokens = jnp.mean(onehot.astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    # average over every axis the token dim shards across (ep AND dp) so
    # the replicated output really is the global mean
    aux = lax.pmean(aux, mean_axes)
    return y, aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, mesh, ep_axis: str,
            capacity_factor: float = 1.25, data_axis=None):
    """Expert-parallel switch FFN.

    x [N, D] tokens (shard N over data_axis if given); gate_w [D, E];
    w1 [E, D, F], b1 [E, F], w2 [E, F, D], b2 [E, D] — expert dim sharded
    over ep_axis. Returns (y [N, D], aux_loss scalar)."""
    n_experts = w1.shape[0]
    n_ranks = mesh.shape[ep_axis]
    if n_experts % n_ranks != 0:
        raise ValueError(f"experts ({n_experts}) must divide over "
                         f"ep={n_ranks}")
    # tokens shard over dp AND ep jointly: every ep rank routes a distinct
    # shard (otherwise each expert would process ep-fold duplicate queues)
    token_axes = (data_axis, ep_axis) if data_axis else ep_axis
    shards = n_ranks * (mesh.shape[data_axis] if data_axis else 1)
    tokens_per_rank = x.shape[0] // shards
    capacity = max(1, int(np.ceil(
        tokens_per_rank / n_experts * capacity_factor)))

    xs = P(token_axes, None)
    es = P(ep_axis)
    mean_axes = (ep_axis, data_axis) if data_axis else (ep_axis,)
    mapped = shard_map(
        partial(_shard_moe, ep_axis=ep_axis, n_experts=n_experts,
                capacity=capacity, mean_axes=mean_axes),
        mesh=mesh,
        in_specs=(xs, P(None, None), es, es, es, es),
        out_specs=(xs, P()),
        check_vma=False)
    return mapped(x, gate_w, w1, b1, w2, b2)
