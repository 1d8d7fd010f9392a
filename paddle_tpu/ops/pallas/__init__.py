"""Pallas TPU kernel tier.

Capability parity with the reference's JIT microkernel library
(reference: operators/jit/ — runtime Xbyak x86 codegen for the hot
LSTM/GRU/seqpool/softmax microkernels, with `refer/` scalar fallbacks and
per-shape benchmarking to pick an implementation, jit/gen/jitcode.h:22,
jit/kernel_pool.cc). The TPU analogue: hand-written Pallas kernels for the
few patterns XLA schedules sub-optimally — flash attention (online-softmax
tiling keeps the [Tq, Tk] score matrix out of HBM) and whole-sequence
recurrent cells (h/c live in VMEM across all timesteps instead of
round-tripping HBM per lax.scan step) — with the plain-jnp emitters as the
`refer` tier.

Tier selection (mirrors jit/kernel_pool.cc Get): `kernel_enabled(name)`
returns True only on a real TPU backend with aligned shapes; the
PADDLE_TPU_DISABLE_PALLAS env var forces the refer tier. On CPU the
kernels still run under interpret=True for the self-test
(tests/test_pallas_kernels.py, the analogue of jit/test.cc)."""

from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    # a backend that fails to initialise raises here: a chip that cannot
    # be reached must not read as "not on TPU" and turn every kernel off
    return jax.default_backend() == "tpu"


def kernels_disabled() -> bool:
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS", "0") == "1":
        return True
    from paddle_tpu import flags
    return flags.get("disable_pallas")


def interpret_mode() -> bool:
    """Interpret kernels when not on real TPU (CPU tests)."""
    return not on_tpu()


def forced_interpret() -> bool:
    """The tests' way into a kernel inside a whole program on the CPU:
    with PADDLE_TPU_FORCE_PALLAS=1 an emitter that has no TPU runs its
    kernel on the interpreter. Never true on a chip."""
    return (interpret_mode()
            and os.environ.get("PADDLE_TPU_FORCE_PALLAS", "0") == "1")


def kernel_enabled(min_align: int = 128, *dims, mesh=None) -> bool:
    """Pallas path is worth it only when the lane dims align to hardware
    tiles; otherwise the refer (jnp) tier wins.

    ``mesh``: the mesh the calling emitter lowers under (``ctx.mesh``).
    XLA cannot partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    under a mesh of more than one device an emitter's kernel is off and
    the refer tier — which XLA does partition — runs. Kernels already
    inside a shard_map region see per-shard arrays and pass no mesh:
    parallel/ring_attention.py's flash shards, and the ONE emitter that
    maps its kernel itself — ``fused_attention_block`` at heads of 64
    (``nn_ops._attention_kernel_blocks``: ``flash_pairs`` over a mesh
    that is the data axis alone, ``attention_block.flash_block``)."""
    if kernels_disabled():
        return False
    if not on_tpu():
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return all(d % min_align == 0 for d in dims)


from paddle_tpu.ops.pallas.flash_attention import (  # noqa: E402,F401
    causal_blocks, flash_attention, flash_attention_lse, flash_engage,
    pick_blocks)
from paddle_tpu.ops.pallas import flash_pairs  # noqa: E402,F401
from paddle_tpu.ops.pallas.fused_ce import fused_linear_ce  # noqa: E402,F401
from paddle_tpu.ops.pallas.fused_rnn import (fused_gru_train,  # noqa: E402,F401
                                             fused_lstm_train)
from paddle_tpu.ops.pallas.seqpool import masked_seqpool  # noqa: E402,F401
from paddle_tpu.ops.pallas.embed_pool import (  # noqa: E402,F401
    fused_embed_seq_pool)
