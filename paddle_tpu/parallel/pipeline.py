"""Pipeline parallelism over a mesh `pp` axis (GPipe schedule).

The reference has no pipeline parallelism (SURVEY §2 parallelism
inventory: PP absent) — this is a TPU-first extension: stage parameters
are sharded over the `pp` mesh axis (stage s's weights live only on rank
s), microbatched activations flow rank→rank over the ICI ring via
ppermute, and the (n_micro + n_stages - 1)-step GPipe schedule runs as a
lax.fori_loop inside shard_map. Reverse-mode differentiates straight
through (ppermute has a transpose rule), so `jax.grad` of a pipelined
loss is pipelined backward automatically — no hand-written 1F1B needed
for correctness (1F1B is a scheduling optimization, not a semantic one).

API shape mirrors the rest of paddle_tpu.parallel: pure functions over a
Mesh, composable under jit with dp/tp axes on the same mesh.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def stack_stage_params(per_stage_params):
    """[{pytree per stage}] -> pytree with leading stage dim (shard this
    over the pp axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def gpipe(stage_fn: Callable, mesh, pp_axis: str, n_micro: int):
    """Build a pipelined apply: (stacked_params, x [n_micro, mb, ...]) ->
    y [n_micro, mb, ...].

    stage_fn(params_s, h) -> h' must preserve the activation shape (the
    classic homogeneous-stage pipeline, e.g. a run of transformer blocks).
    stacked_params' leading dim = n_stages = mesh.shape[pp_axis], sharded
    over pp; x/y are replicated along pp (dp/tp sharding of the microbatch
    dims composes freely)."""
    n_stages = mesh.shape[pp_axis]
    if n_micro < 1:
        raise ValueError("need at least one microbatch")

    def per_shard(params, x):
        # params: this rank's stage params (leading stage dim of size 1)
        my_params = jax.tree.map(lambda p: p[0], params)
        rank = lax.axis_index(pp_axis)
        mb_shape = x.shape[1:]
        n_steps = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def step(carry, t):
            buf, outs = carry
            # rank s works on microbatch (t - s) when 0 <= t-s < n_micro
            mb_idx = t - rank
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests a fresh microbatch; others use the buffer
            fresh = x[jnp.clip(t, 0, n_micro - 1)]
            h_in = jnp.where(rank == 0, fresh, buf)
            h_out = stage_fn(my_params, h_in)
            h_out = jnp.where(active, h_out, buf)
            # last stage records its finished microbatch
            done_idx = t - (n_stages - 1)
            record = (rank == n_stages - 1) & (done_idx >= 0)
            outs = jnp.where(
                record,
                outs.at[jnp.clip(done_idx, 0, n_micro - 1)].set(h_out),
                outs)
            # ship activations to the next stage over the ICI ring
            buf_next = lax.ppermute(h_out, pp_axis, perm=fwd_perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros(mb_shape, x.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
        (_, outs), _ = lax.scan(step, (buf0, outs0),
                                jnp.arange(n_steps))
        # everyone returns the last rank's outputs (psum of one-hot owner)
        owner = (lax.axis_index(pp_axis) == n_stages - 1).astype(x.dtype)
        return lax.psum(outs * owner, pp_axis)

    def apply(stacked_params, x):
        spec_params = jax.tree.map(lambda _: P(pp_axis), stacked_params)
        mapped_ = shard_map(per_shard, mesh=mesh,
                            in_specs=(spec_params, P()), out_specs=P(),
                            check_vma=False)
        return mapped_(stacked_params, x)

    return apply
