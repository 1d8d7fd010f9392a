"""Ask the TPU compiler, without a chip, whether it accepts the Pallas
kernels of the main path at their real widths.

Interpret mode (every other kernel test) checks results, not Mosaic's
rules: kernels that passed all of those were refused on the first chip
compile for a slice not aligned to the tiling and for an in-kernel
reshape. libtpu is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology (on-chip-measurement guide, section 2 step 3), so
each case lowers one kernel for that device and requires a
``tpu_custom_call`` in the compiled program. Nothing runs: a compile
that passes is not a chip run.

Named to sort early — the tier-1 run is cut at a time limit, and a test
the clock never reaches guards nothing.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")    # no log files in /tmp

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e:2x2, with the persistent
    compile cache off around the module (an entry written for a
    described device cannot be read back without a chip, and the retry
    warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology here: "
                    f"{type(e).__name__}: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e[0])


F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


def _paged_gather(dtype):
    from paddle_tpu.ops.pallas import paged_attention as pa
    return pa.gather_rows, [((4096, 1024), dtype), ((2048,), I32)]


def _paged_gather_pages(dtype):
    # the benchmark's decode cell: 3072 pages of 16 rows, table 48 x 64
    from paddle_tpu.ops.pallas import paged_attention as pa
    return (lambda p, t: pa.gather_pages(p, t, 16),
            [((49152, 1024), dtype), ((48 * 64,), I32)])


def _attend_pages(dtype, ps):
    # the latent cell: 32 slots of 12 288 rows in pages of a bf16 tile
    # (or an fp32 tile), 64 heads over rows of 640, 512 lanes of value
    from paddle_tpu.ops.pallas import paged_attention as pa
    b, s_len = 32, 12288
    return (lambda q, pool, t, lens, g0, pos, keep: pa.attend_pages(
        q, pool, t, lens, g0, pos, keep, page_size=ps, scale=0.0625,
        value_width=512),
        [((b, 64, 640), dtype), ((b * s_len, 640), dtype),
         ((b, s_len // ps), I32), ((b,), I32), ((b,), I32), ((b,), I32),
         ((b, s_len), jnp.bool_)])


def _score_pages(dtype, ps):
    # the latent cell's indexer (ISSUE 66): 32 slots of 12 288 rows of
    # indexer keys 128 wide, 32 indexer heads, the scores float32
    from paddle_tpu.ops.pallas import paged_attention as pa
    b, s_len = 32, 12288
    return (lambda qi, wi, pool, t, lens, g0, pos: pa.score_pages(
        qi, wi, pool, t, lens, g0, pos, page_size=ps),
        [((b, 32, 128), dtype), ((b, 32), F32), ((b * s_len, 128), dtype),
         ((b, s_len // ps), I32), ((b,), I32), ((b,), I32), ((b,), I32)])


def _attend_two_planes(b, s_len, wk, wv, heads):
    # a full grouped-KV layer's decode attention in place (ISSUE 62) at
    # a cell's own size: b slots of s_len rows in bf16 pages of 16, the
    # key plane wk wide and the value plane wv, the table in SMEM whole
    from paddle_tpu.ops.pallas import paged_attention as pa
    return (lambda q, keys, vals, t, lens, g0, pos, keep: pa.attend_pages(
        q, keys, t, lens, g0, pos, keep, page_size=16, scale=0.0722,
        values=vals),
        [((b, heads, wk), BF16), ((b * s_len, wk), BF16),
         ((b * s_len, wv), BF16), ((b, s_len // 16), I32), ((b,), I32),
         ((b,), I32), ((b,), I32), ((b, s_len), jnp.bool_)])


def _paged_dequant():
    from paddle_tpu.ops.pallas import paged_attention as pa
    return (lambda p, s, r: pa.gather_rows_dequant(p, s, r, heads=8),
            [((4096, 1024), I8), ((4096, 8), F32), ((2048,), I32)])


def _cache_gather(width):
    # capacity + 1 rows, as HotRowsCache allocates: the ragged-tile path
    from paddle_tpu.ops.pallas import embed_cache as ec
    return ec.gather_rows, [((4097, width), F32), ((512,), I32)]


def _cache_scatter(width):
    from paddle_tpu.ops.pallas import embed_cache as ec
    return ec.scatter_rows, [((4097, width), F32), ((512,), I32),
                             ((512, width), F32)]


def _embed_pool(width):
    from paddle_tpu.ops.pallas import fused_embed_seq_pool
    return fused_embed_seq_pool, [((10000, width), F32), ((64, 20), I32),
                                  ((64,), I32)]


def _flash(t, d):
    """fwd + bwd at the committed autotune-table blocks for (T, d)."""
    from paddle_tpu.ops.pallas import flash_attention, flash_engage
    bq, bk = flash_engage(t, t, d, True)
    b = 8192 // t

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
                       .astype(F32))
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((b, 8, t, d), BF16)] * 3)


def _flash_latent():
    """fwd + bwd at latent attention's head sizes — query / key heads of
    192 (128 + 64 rotated), value heads of 128 — over the trainer's
    8 192-token sequence of 32 heads, the blocks ``causal_attention``
    picks (ISSUE 47; 1024 x 1024 under the causal schedule, PR 48)."""
    from paddle_tpu.ops.pallas import causal_blocks, flash_attention
    bq, bk = causal_blocks(8192, 192, 128)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 192 ** -0.5, bq, bk)
                       .astype(F32))
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((1, 32, 8192, 192), BF16)] * 2 + [((1, 32, 8192, 128), BF16)])


def _flash_pairs(dropout_p):
    """Forward and backward kernels of the trainer's d = 64 path at the
    cell's shape and the table's blocks: 16 heads of 64 as
    [16, 512, 1024], causal (the mask's operations beside the rest)."""
    from paddle_tpu.ops.pallas import flash_engage, flash_pairs
    bq, bk = flash_engage(512, 512, 64, True)
    assert flash_pairs.supported(512, 512, 1024, 16, bq, bk)

    def both(q, k, v, g, seed):
        return (flash_pairs.pairs_forward(q, k, v, seed, 16, True,
                                          dropout_p, bq, False),
                flash_pairs.pairs_backward(q, k, v, g, seed, 16, True,
                                           dropout_p, bq, False))
    return both, [((16, 512, 1024), BF16)] * 4 + [((1,), I32)]


def _kda_state():
    # the hybrid cell: 128 slots x 64 heads of [128, 128] float32
    from paddle_tpu.ops.pallas import kda_state as ks
    b, h, d = 128, 64, 128
    vec = ((b, h, d), F32)
    return (ks.kda_state_update,
            [((b, h, d, d), F32), vec, vec, vec, vec, ((b, h), F32),
             ((b,), I32)])


def _gdn_state():
    # Olmo-Hybrid's cell: 8 slots x 30 heads of [96, 192] float32, one
    # decay a head (two blocks of 15 heads, [96, 256] each in VMEM)
    from paddle_tpu.ops.pallas import kda_state as ks
    b, h, dk, dv = 8, 30, 96, 192
    key = ((b, h, dk), F32)
    return (ks.kda_state_update,
            [((b, h, dk, dv), F32), key, key, ((b, h, dv), F32),
             ((b, h, 1), F32), ((b, h), F32), ((b,), I32)])


def _s6_scan(t, n_true_chunks=None):
    # Jamba2's prefill: a bucket of t rows of 5120 channels, a state of
    # 16, chunks of 64 rows, the state tile [16, 1280] in VMEM
    from paddle_tpu.ops.pallas import s6_scan as k
    row = ((t, 5120), F32)
    return (lambda x, dt, b, c, a, n: k.s6_scan(x, dt, b, c, a, n,
                                                chunk=64),
            [row, row, ((t, 16), F32), ((t, 16), F32), ((16, 5120), F32),
             ((), I32)])


def _s6_state():
    # Jamba2's decode step: 256 slots of [16, 5120] float32, blocks of
    # 8 slots by 2560 channels, in place
    from paddle_tpu.ops.pallas import s6_state as k
    row = ((256, 5120), F32)
    return (k.s6_state_update,
            [((256, 16, 5120), F32), row, row, ((256, 16), F32),
             ((256, 16), F32), ((16, 5120), F32), ((256,), I32)])


def _fused_ce():
    from paddle_tpu.ops.pallas import fused_linear_ce

    def loss(x, w, labels):
        return jnp.sum(fused_linear_ce(x, w, labels, label_smoothing=0.1))
    return (jax.grad(loss, argnums=(0, 1)),
            [((8192, 512), BF16), ((512, 32000), BF16), ((8192, 1), I32)])


def _fused_lstm():
    from paddle_tpu.ops.pallas import fused_lstm_train
    t, b, h = 100, 64, 512

    def loss(xproj, w, peep, lens, h0, c0):
        return sum(jnp.sum(o) for o in
                   fused_lstm_train(xproj, w, peep, lens, h0, c0))
    return (jax.grad(loss, argnums=(0, 1, 2, 4, 5)),
            [((t, b, 4 * h), F32), ((h, 4 * h), F32), ((1, 3 * h), F32),
             ((b, 1), I32), ((b, h), F32), ((b, h), F32)])


def _hit_experts(n, m, f, held):
    # a decode step's tokens through the HIT held experts (ISSUE 54) at
    # the committed table's tile of d_expert
    from paddle_tpu.ops.pallas import expert_stream as es
    return es.hit_experts, [((n, m), BF16), ((n, held), F32), ((held,), I32),
                            ((held, m, f), BF16), ((held, m, f), BF16),
                            ((held, f, m), BF16)]


def _grouped_products(rows, m, f, held, dtype=BF16):
    # a prefill's grouped products (ISSUE 64) at the tiles the shapes
    # give: gate and up with the epilogue, then down
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    def both(x, w_gate, w_up, w_down, sizes):
        hidden = gm.grouped_swiglu(x, w_gate, w_up, sizes)
        return gm.grouped_matmul(hidden, w_down, sizes)
    return both, [((rows, m), dtype), ((held, m, f), dtype),
                  ((held, m, f), dtype), ((held, f, m), dtype),
                  ((held,), I32)]


CASES = {
    "paged_gather-f32-4096x1024": lambda: _paged_gather(F32),
    "paged_gather-bf16-4096x1024": lambda: _paged_gather(BF16),
    "paged_gather-int8-dequant-4096x1024": _paged_dequant,
    "paged_gather_pages-f32-49152x1024": lambda: _paged_gather_pages(F32),
    "paged_gather_pages-bf16-49152x1024": lambda: _paged_gather_pages(BF16),
    "attend_pages-bf16-ps16-32x12288x640": lambda: _attend_pages(BF16, 16),
    "attend_pages-f32-ps8-32x12288x640": lambda: _attend_pages(F32, 8),
    "score_pages-bf16-ps16-32x12288x128": lambda: _score_pages(BF16, 16),
    "score_pages-f32-ps8-32x12288x128": lambda: _score_pages(F32, 8),
    # MiMo's full layers (64 heads over 4 of 192 / 128), Trinity's (32
    # over 4 of 128) and Granite's (32 over 8 of 128, blocks of 256 rows:
    # 176 pages have no longer one of whole lane tiles)
    "attend_pages-two_planes-bf16-24x36864x768x512":
        lambda: _attend_two_planes(24, 36864, 768, 512, 64),
    "attend_pages-two_planes-bf16-32x20480x512x512":
        lambda: _attend_two_planes(32, 20480, 512, 512, 32),
    "attend_pages-two_planes-bf16-128x2816x1024x1024":
        lambda: _attend_two_planes(128, 2816, 1024, 1024, 32),
    # Olmo-Hybrid's multi-head layers (30 heads over 30 of 128): rows of
    # 3 840 + 3 840, blocks of 256 rows — 7.9 MB of tiles where the 768
    # rows the table also divides into would ask for 23.6 MB (ISSUE 62)
    "attend_pages-two_planes-bf16-8x8448x3840x3840":
        lambda: _attend_two_planes(8, 8448, 3840, 3840, 30),
    "embed_cache-gather-w128": lambda: _cache_gather(128),
    "embed_cache-gather-w256": lambda: _cache_gather(256),
    "embed_cache-scatter-w128": lambda: _cache_scatter(128),
    "embed_cache-scatter-w256": lambda: _cache_scatter(256),
    "embed_pool-w128": lambda: _embed_pool(128),
    "embed_pool-w256": lambda: _embed_pool(256),
    "flash-fwd+bwd-T512-d128": lambda: _flash(512, 128),
    "flash-fwd+bwd-T2048-d64": lambda: _flash(2048, 64),
    "flash-fwd+bwd-T8192-dqk192-dv128": _flash_latent,
    "flash_pairs-fwd+bwd-T512-d64": lambda: _flash_pairs(0.0),
    "flash_pairs-fwd+bwd-T512-d64-dropout": lambda: _flash_pairs(0.3),
    "fused_ce-fwd+bwd-8192x512x32000": _fused_ce,
    "fused_lstm-fwd+bwd-100x64x512": _fused_lstm,
    "kda_state-f32-128x64x128x128": _kda_state,
    "kda_state-f32-8x30x96x192-head-decay": _gdn_state,
    "s6_scan-f32-1024x5120x16-chunk64": lambda: _s6_scan(1024),
    "s6_scan-f32-512x5120x16-chunk64": lambda: _s6_scan(512),
    "s6_state-f32-256x16x5120": _s6_state,
    # GLM-5's expert layer (16 of 256 held) and Trinity's (all 128)
    "hit_experts-bf16-32x6144x2048x16": lambda: _hit_experts(32, 6144, 2048,
                                                              16),
    "hit_experts-bf16-32x2048x1024x128": lambda: _hit_experts(32, 2048, 1024,
                                                               128),
    # LFM2's prefills of 4 096 and 2 048 tokens (4 picks, all 32 held),
    # Granite's of 2 048 and 1 024 (18 of 72 held), JoyAI's trained
    # sequence (16 of 256), GLM-5's and Trinity's priming prefills
    "grouped_products-bf16-16384x2048x1792x32":
        lambda: _grouped_products(16384, 2048, 1792, 32),
    "grouped_products-bf16-8192x2048x1792x32":
        lambda: _grouped_products(8192, 2048, 1792, 32),
    "grouped_products-bf16-6400x4096x768x18":
        lambda: _grouped_products(6400, 4096, 768, 18),
    "grouped_products-bf16-3328x4096x768x18":
        lambda: _grouped_products(3328, 4096, 768, 18),
    "grouped_products-bf16-5120x2048x768x16":
        lambda: _grouped_products(5120, 2048, 768, 16),
    "grouped_products-bf16-5120x6144x2048x16":
        lambda: _grouped_products(5120, 6144, 2048, 16),
    "grouped_products-bf16-32768x2048x1024x128":
        lambda: _grouped_products(32768, 2048, 1024, 128),
    "grouped_products-f32-1024x256x384x4":
        lambda: _grouped_products(1024, 256, 384, 4, F32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if case.startswith("flash_pairs"):      # forward AND backward kernel
        assert _count_opcode(text, "custom-call") == 2


def _attention_step_text(n_head, dist, chip, monkeypatch, compiled=True,
                         train=False):
    """The text of a one-block step (T = 512, d_model 256, mean of a
    causal ``fused_multi_head_attention``; with ``train`` its backward
    and an SGD update too) lowered for a described v5e: on ``chip``
    alone, or under ``dist``'s mesh. ``on_tpu`` is steered because
    trace-time gates ask ``jax.default_backend()``, which is the CPU in
    this process."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.lowering import CompiledBlock
    from paddle_tpu.ops import pallas as pk

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[512, 256], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fused_multi_head_attention(
            x, x, d_model=256, n_head=n_head, causal=True))
        if train:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    cb = CompiledBlock(main.desc, 0, ["x"], [loss.name], dist=dist)
    # under a mesh the jit's own in_shardings place the arguments
    where = {} if dist is not None else {"sharding": chip}

    def struct(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, **where)
    state, consts = cb._gather_state(scope)
    lowered = cb.fn.lower(
        jax.tree_util.tree_map(struct, state),
        jax.tree_util.tree_map(struct, consts),
        {"x": jax.ShapeDtypeStruct((8, 512, 256), F32, **where)},
        jax.ShapeDtypeStruct((), jnp.uint32, **where))
    return lowered.compile().as_text() if compiled else lowered.as_text()


@pytest.mark.parametrize("d_head", [128, 64])
def test_mesh_step_compiles_with_kernels_gated(v5e, chip, monkeypatch,
                                               d_head):
    """XLA refuses to partition a Mosaic call ("wrap the call in a
    shard_map"). At heads of 128 a step lowered under a mesh therefore
    takes the composed block where a lone chip takes ``flash_attention``
    (T = 512 engages it). At heads of 64 the emitter does wrap the pair
    kernels (PR 45): under a ``dp`` mesh the compiled step holds exactly
    two Mosaic calls a block — forward and backward; the ``__vjp__``
    op's re-traced forward is dropped inside the manual region as it is
    off a mesh — beside the gradients' all-reduce, both under the op's
    scope (the backward's under ``grad/``). The counter says which in
    both."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.ops import nn_ops
    from paddle_tpu.parallel import DistributeConfig

    def lowered(path):
        return nn_ops._ATTENTION_BLOCK_LOWERED.labels(
            path=path, d_head=str(d_head)).value

    def calls(text):
        return text.count('custom_call_target="tpu_custom_call"')

    mapped = d_head == 64
    was = lowered("flash"), lowered("composed")
    text = _attention_step_text(256 // d_head, None, chip, monkeypatch,
                                train=mapped)
    assert calls(text) == (2 if mapped else 1)
    assert (lowered("flash"), lowered("composed")) == (was[0] + 1, was[1])
    mesh = Mesh(np.asarray(v5e), ("dp",))
    text = _attention_step_text(
        256 // d_head, DistributeConfig(mesh=mesh, data_axis="dp"), chip,
        monkeypatch, train=mapped)
    assert calls(text) == (2 if mapped else 0)
    assert "all-reduce" in text     # the mean (and dW) over the dp split
    assert (lowered("flash"), lowered("composed")) == (
        was[0] + 1 + mapped, was[1] + 1 - mapped)
    # a manual region must not lose the scope the trace's readers go by
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]*)"', text)
    assert all("fused_attention_block" in n for n in names), names
    assert sum("grad/" in n for n in names) == mapped, names


def _scrubbed_sha(text):
    """sha256 of a program's text without what names the checkout or the
    process: a kernel's serialized body (``backend_config``, which
    carries source paths), source locations in a jaxpr, the module's
    name (blocks are numbered as a process builds them)."""
    import hashlib
    text = re.sub(r"module @\w+", "module", text)
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', "", text)
    text = re.sub(r" at [^\s]+:\d+", "", text)
    text = re.sub(r"/[\w/.\-]+\.py(:\d+)?", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _kernel_shas(fn, *shapes):
    """sha256 of each ``pallas_call`` of a trace, in order: its body,
    grid and block mappings, operands and results, without what names
    the checkout (``tests/test_pallas_kernels.py`` has its twin)."""
    import hashlib

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
    out = []
    for e in walk(jax.make_jaxpr(fn)(*shapes).jaxpr):
        if e.primitive.name != "pallas_call":
            continue
        text = (f"{e.params['jaxpr']}\n{e.params['grid_mapping']}\n"
                f"{[v.aval for v in e.invars]}\n"
                f"{[v.aval for v in e.outvars]}")
        text = re.sub(r" at [^\s]+:\d+", "", text)
        text = re.sub(r"/[\w/.\-]+\.py(:\d+)?", "", text)
        text = re.sub(r"0x[0-9a-f]+", "", text)
        out.append(hashlib.sha256(text.encode()).hexdigest()[:12])
    return out


@pytest.mark.parametrize("train,want", [(False, "1a1e6cc5ff39e3c8"),
                                        (True, "9ace96be8a2d6254")])
def test_one_chip_step_at_heads_of_64_lowers_as_before_the_wrap(
        chip, monkeypatch, train, want):
    """PR 45 maps the pair kernels over a dp mesh; off a mesh the block
    is the parent's op for op: the step's StableHLO around the kernels
    (forward alone, and with backward and update), held as recorded at
    the parent commit."""
    step = _attention_step_text(4, None, chip, monkeypatch, compiled=False,
                                train=train)
    assert step.count("tpu_custom_call") == 1 + train
    assert _scrubbed_sha(step) == want


def test_head_size_128_lowers_as_before_the_pair_kernels(chip, monkeypatch):
    """PR 41 gave heads of 64 kernels of their own; heads of 128 keep
    ``flash_attention`` op for op: the step's StableHLO around the
    kernels, and the three kernels of forward + backward — their bodies,
    grids and operands (``_kernel_shas``; until PR 50 the jaxpr's whole
    text, which a ``name`` equation renumbers: the hashes are the new
    helper's reading of PR 48's commit) — with and without dropout. NON-causal they are held as the parent of
    PR 48 traced them (PR 41 regrouped the hash helpers, not their
    operations; PR 48 left the dense grid's text alone). Causal they are
    PR 48's:
    the grid over the visible tiles (here one), two tables in SMEM
    ahead of the seed."""
    from paddle_tpu.ops.pallas import flash_attention
    step = _attention_step_text(2, None, chip, monkeypatch, compiled=False)
    assert step.count("tpu_custom_call") == 1
    assert _scrubbed_sha(step) == "a68d8bc779c38f5b"
    seed = jnp.asarray([3], I32)
    shape = jax.ShapeDtypeStruct((2, 2, 512, 128), BF16)
    for causal, dropout_p, want in (
            (False, 0.0, "13f4cc280efa 7db3bfe61acb 450d78b31f6e"),
            (False, 0.3, "c004b8a681df 9cede643060d bdff9fdc6e2c"),
            (True, 0.0, "9f053599830c 5de0e7a0d043 41a798012b0b"),
            (True, 0.3, "54ec76aa14dc 03619f19e166 278b3dbfe92b")):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal, None, 512, 512, False, dropout_p,
                seed if dropout_p > 0 else None).astype(F32))
        assert " ".join(_kernel_shas(
            jax.grad(loss, argnums=(0, 1, 2)), shape, shape, shape)) \
            == want, (causal, dropout_p)


# ---------------------------------------------------------------------------
# the paged KV pool's layout at rest (PR 28): a property only the TPU
# compiler decides, read from the HLO it emits for the benchmark's pool
# ---------------------------------------------------------------------------

_POOL = dict(n_pages=3072, ps=16, h=16, dk=64, slots=48, table=64)


def _lower_paged_op(op, store, chip, bucket=512):
    """Compile one paged op at the serving cell's geometry (3072 pages
    of 16 x 1024, 48 slots x 64 table entries; batch-1 prefill of
    ``bucket`` tokens; a verify window of 5, spec_k 4) with its pools
    donated -> optimized HLO text."""
    import types
    from paddle_tpu.core.registry import get_op
    g = _POOL
    m = g["h"] * g["dk"]
    b, t = {"kv_attention_prefill_paged": (1, bucket),
            "kv_attention_verify_paged": (g["slots"], 5)}.get(
                op, (g["slots"], 1))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    pools = {n: struct((g["n_pages"], g["ps"], m), store)
             for n in ("PageK", "PageV")}
    col = struct((b, 1), I32)
    ins = {"X": struct((b, t, m), F32)}
    ins.update({w: struct((m, m), F32) for w in ("Wq", "Wk", "Wv", "Wo")})
    if op == "kv_attention_prefill_paged":
        ins["Rows"] = struct((b * t, 1), I32)
    else:
        ins.update(PageTable=struct((b, g["table"]), I32), Pos=col,
                   SeqLen=col, GenStart=col, Active=col)
        if op == "kv_attention_verify_paged":
            ins["WinLen"] = col
    attrs = {"n_head": g["h"],
             "codec": "bf16" if store == BF16 else "none"}

    def step(pools, ins):
        slots = {k: [v] for k, v in {**ins, **pools}.items()}
        out = get_op(op).emit(types.SimpleNamespace(mesh=None), slots,
                              attrs)
        return {k: v[0] for k, v in out.items()}
    return jax.jit(step, donate_argnums=(0,)).lower(pools, ins)\
        .compile().as_text()


def _hlo_ops(text):
    """name -> (opcode, element count of the result, operand names,
    the line) for the instructions of a compiled module's text."""
    import math
    import re
    ops = {}
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \(?\w+\[([\d,]*)\]\S* "
                       r"([\w\-]+)\((.*)", line)
        if hit:
            name, dims, opcode, rest = hit.groups()
            count = math.prod(int(d) for d in dims.split(",") if d)
            ops[name] = (opcode, count, re.findall(r"%([\w.\-]+)", rest),
                         line)
    return ops


def _count_opcode(text, opcode):
    """Instructions of ``opcode`` in a module's text, those with a
    tuple for a result (``while``, ``conditional``, a variadic ``sort``)
    among them, which ``_hlo_ops`` does not parse."""
    import re
    return len(re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = .*?[\]})] "
                          + re.escape(opcode) + r"\(", text, re.M))


def _comes_from(ops, name, name_prefix):
    """Does instruction ``name`` depend, through its pool-sized
    operands, on one whose name starts with ``name_prefix`` (XLA names
    an instruction after its opcode or its kernel: ``copy.13``,
    ``gather_pages.2``)?"""
    size = ops[name][1]
    seen, todo = set(), [name]
    while todo:
        cur = todo.pop()
        if cur.startswith(name_prefix):
            return True
        for src in ops[cur][2]:
            if src in ops and src not in seen and ops[src][1] == size:
                seen.add(src)
                todo.append(src)
    return False


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
@pytest.mark.parametrize("op", ["kv_attention_prefill_paged",
                                "kv_attention_decode_paged",
                                "kv_attention_verify_paged"])
def test_paged_pool_is_row_major_at_rest_on_v5e(chip, monkeypatch, op,
                                                store):
    """The pool variable [n_pages, page_size, H*Dk] is row-major at
    rest: (a) parameters and results carry the descending layout, (b)
    no program holds a copy or transpose of a pool's size, (c) the
    decode and the verify window reach ``gather_pages`` without one and
    relay nothing it gathered ([48*1024, 1024], by chance the pool's
    size): K and V are contracted as the gather leaves them, through a
    block-diagonal query (kv_attention._decode_contract), (d) each
    pool's input and output share a buffer. With a 64-wide minor
    dimension ([.., H, Dk]) the same programs transposed every pool in
    and out, 109 of a 136 ms decode step, and a per-head contraction
    relaid each gathered cache twice, 29.4 of a 55.8 ms step (PERF.md,
    PRs 25, 28 and 30). This guard forbids their return."""
    import re
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    g = _POOL
    text = _lower_paged_op(op, jnp.dtype(store), chip)
    head = text.splitlines()[0]
    short = {"float32": "f32", "bfloat16": "bf16"}[store]
    pool = rf"{short}\[{g['n_pages']},{g['ps']},{g['h'] * g['dk']}\]"
    at_rest = re.findall(pool + r"\{([\d,]+)", head)
    assert len(at_rest) == 4 and set(at_rest) == {"2,1,0"}, head  # (a)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    assert aliased and aliased.group(1).count("-alias") == 2, head  # (d)

    ops = _hlo_ops(text)
    n_pool = g["n_pages"] * g["ps"] * g["h"] * g["dk"]
    copies = [n for n, (opcode, count, _, _) in ops.items()
              if opcode in ("copy", "transpose") and count == n_pool]
    gathers = [n for n in ops if n.startswith("gather_pages")]
    if op == "kv_attention_prefill_paged":
        assert not gathers
    else:
        assert len(gathers) == 2 and \
            all("tpu_custom_call" in ops[n][3] for n in gathers)
        for n in gathers:                                       # (c)
            assert not _comes_from(ops, n, "copy"), ops[n][3][:200]
    assert not copies, [ops[n][3][:160] for n in copies]    # (b), (c)


# ---------------------------------------------------------------------------
# the hybrid sparse block (PR 31): the whole decode step of
# solar_open2_250b_ep8_d4 at the cell's sizes, as the chip compiles it
# ---------------------------------------------------------------------------

def test_hybrid_decode_step_compiles_for_v5e(chip, monkeypatch):
    """The decode step of the benchmark's hybrid configuration — 128
    slots, bf16, 4 layers (gqa, kda, kda, kda), 40 of 320 experts — for a
    described v5e: it fits one chip's 16 GB with room for the gathered
    caches; it holds the two page gathers and, per expert layer, the
    dense gate and up products over all tokens (results
    ``[128,40,1280]``, the shape the benchmark's reader selects) with no
    copy of the experts' weights; each KDA layer's state is read by ONE
    instruction, the ``kda_state_update`` kernel, whose first result is
    the state (``f32[128,64,128,128]``, the shape the reader selects) —
    no fusion touches it — and no ``copy`` or ``transpose`` of a state's
    or a pool's size is in it (one would mean the kernel's alias was
    lost: 1.6 GB and ~4 ms a step); the recurrent state is donated and
    aliased in place."""
    import json
    import os
    import re
    import numpy as np
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops import pallas as pk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "solar_open2_250b_ep8_d4.json")) as f:
        cfg = json.load(f)
    build = cfg["build"]
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    programs = T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes(cfg["kv_layout"]),
        kv_codec=cfg["kv_codec"],
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})
    eng = serving.make_slot_model("lm", programs, init=False)
    cb = eng._cb_decode
    gvars = programs["decode_paged"][0].desc.global_block.vars

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    state = {n: struct(gvars[n].shape, gvars[n].dtype)
             for n in cb.sig.state_names}
    consts = {n: struct(gvars[n].shape, gvars[n].dtype)
              for n in cb.sig.const_names}
    feeds = {k: struct(v.shape, I32 if v.dtype == np.int64 else v.dtype)
             for k, v in eng._decode_feeds().items()}
    compiled = cb.fn.lower(state, consts, feeds,
                           struct((), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    # the pools and the recurrent state are updated in place
    state_bytes = 3 * 128 * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes > state_bytes + 2 * 16384 * 16 * 1024 * 2
    text = compiled.as_text()
    ops = _hlo_ops(text)
    entry = text[text.index("ENTRY "):]
    assert entry.count("gather_pages") >= 2
    # 128 tokens are under expert_ffn.DENSE_MAX_TOKENS: every held
    # expert multiplies every token (results [128, 40, 1280], the shape
    # the benchmark's reader selects) and no grouped product is in it
    assert not [n for n in ops if n.startswith("ragged-dot")]
    assert len([line for _o, _c, _a, line in ops.values()
                if "[128,40,1280]" in line.split(" = ")[1].split(" ")[0]
                and "fusion(" in line]) >= 8
    # nor a copy of an expert layer's weights (40 x 4096 x 1280)
    assert not [line for opcode, count, _a, line in ops.values()
                if opcode == "copy" and count >= 40 * 4096 * 1280]
    big = 128 * 64 * 128 * 128          # a state; a pool is half of it
    # a layer's state is read once, by the kernel that writes it back
    kernels = re.findall(
        r"%(kda_state_update[\w.]*) = \((f32\[[\d,]+\])\S* (f32\[[\d,]+\])"
        r"\S* custom-call\(", entry)
    assert len(kernels) == 3 and all(
        # o as the kernel writes it: [slots, blocks, a block's heads, Dv]
        k[1:] == ("f32[128,64,128,128]", "f32[128,2,32,128]")
        for k in kernels), kernels
    assert not [n for n, (opcode, _c, args, _l) in ops.items()
                if opcode == "fusion"
                and any(ops.get(a, ("", 0))[1] == big for a in args)]
    assert not [line for opcode, count, _a, line in ops.values()
                if opcode in ("copy", "transpose") and count >= big // 2]
    # token_sample (PR 32): the sampled branch sits under a conditional,
    # nothing sorts the vocabulary on either side of it (the step's
    # sorts are the four routers' top 8 of 320), and it brought no
    # kernel: the step's are the two page gathers and the three state
    # updates (``kv_gather_*``, which takes every tpu_custom_call of a
    # decode execution for the page gather, reads gpt2_medium_d12's
    # cell alone)
    assert _count_opcode(text, "conditional") == 1
    sorts = re.findall(r"= (\(.*?\)|\S+) sort\(", text)
    assert len(sorts) == 4 and all("[128,320]" in r for r in sorts), sorts
    assert text.count('custom_call_target="tpu_custom_call"') == 5


def test_hybrid_startup_writes_every_draw_into_its_result_for_v5e(chip):
    """The start-up of the benchmark's hybrid configuration (65 matrices
    drawn by ``hash_normal_random``, 9.36 GB of parameters, pools and
    state) for a described v5e: ``hash_normal`` is jitted, so the module
    calls ONE private function a (shape, dtype, std) and not a body a
    parameter — and XLA still inlines each call before it fuses: the
    program keeps under a megabyte beside its outputs (the bit buffers of
    ``jax.random.normal`` were 10.5 GB beside them)."""
    import json
    import os
    import re
    from paddle_tpu.core.lowering import CompiledBlock
    from paddle_tpu.models import transformer as T

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "solar_open2_250b_ep8_d4.json")) as f:
        cfg = json.load(f)
    build = cfg["build"]
    programs = T.build_decoder_lm_programs(
        name="lm", modes=("decode_paged",), kv_codec=cfg["kv_codec"],
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           "layer_kinds": tuple(build["layer_kinds"])})
    startup = programs["decode_paged"][1]
    draws = [op for op in startup.desc.global_block.ops
             if op.type == "hash_normal_random"]
    signatures = {(tuple(op.attrs["shape"]), op.attrs["dtype"],
                   op.attrs["std"]) for op in draws}
    assert len(draws) == 65 and len(signatures) < len(draws) // 3
    cb = CompiledBlock(startup.desc, 0, [], [], is_test=False)
    lowered = cb.fn.lower({}, {}, {}, jax.ShapeDtypeStruct(
        (), jnp.uint32, sharding=chip))
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @hash_normal", text)) \
        == len(signatures)
    assert len(re.findall(r"call @hash_normal", text)) == len(draws)
    mem = lowered.compile().memory_analysis()
    assert mem.output_size_in_bytes > 9.3e9
    assert mem.temp_size_in_bytes < 1e6


# ---------------------------------------------------------------------------
# token_sample (PR 32): what a greedy batch pays for, as the chip
# compiles it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,vocab", [(48, 50257), (128, 24576),
                                        (1, 50257), (48 * 5, 50257)])
def test_token_sample_holds_a_conditional_and_no_sort_on_v5e(chip, rows,
                                                             vocab):
    """token_sample at the benchmark's decode shapes ([48, 50257] of
    gpt2_medium_d12, [128, 24576] of the hybrid cut), the batch-1
    prefill and a verify window of 5: the sampled branch is a
    device-side ``conditional`` (XLA does not flatten it into a select:
    an all-greedy batch runs the argmax alone), the top-k threshold's
    32 passes are a ``while`` inside it, and no ``sort`` and no kernel
    is anywhere — the full-vocabulary sort was the hottest single op of
    both gpt2 serve cells, 2.8 ms of a 27 ms decode step (PERF.md, PR
    32)."""
    import types
    from paddle_tpu.core.registry import get_op

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    col = struct((rows, 1), I32)
    ins = {"Logits": struct((rows, vocab), F32),
           "Temperature": struct((rows, 1), F32),
           "TopK": col, "Seed": col, "StepIdx": col}
    text = jax.jit(lambda i: get_op("token_sample").emit(
        types.SimpleNamespace(mesh=None), {k: [v] for k, v in i.items()},
        {})["Out"][0]).lower(ins).compile().as_text()
    assert _count_opcode(text, "conditional") == 1
    assert _count_opcode(text, "while") == 1
    assert _count_opcode(text, "sort") == 0
    assert "tpu_custom_call" not in text
    # the loop belongs to the sampled branch, not to the entry
    entry = text[text.index("ENTRY "):]
    assert _count_opcode(entry, "while") == 0
    assert _count_opcode(entry, "conditional") == 1


# ---------------------------------------------------------------------------
# the latent-attention block (PR 33): the decode step and the largest
# prefill of glm5_744b_ep16_d5 at the cell's sizes, as the chip compiles
# them
# ---------------------------------------------------------------------------

def _cell_engine(name):
    """(the slot engine, its program family) of the benchmark's
    configuration ``name``, lowered as on the chip (kernels on), nothing
    initialised or run."""
    import json
    import os
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops import pallas as pk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    build = cfg["build"]
    was, pk.on_tpu = pk.on_tpu, lambda: True
    try:
        programs = T.build_decoder_lm_programs(
            name="lm", modes=T.slot_modes(cfg["kv_layout"]),
            kv_codec=cfg["kv_codec"],
            **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
               "layer_kinds": tuple(build["layer_kinds"])})
        yield serving.make_slot_model("lm", programs, init=False), programs
    finally:
        pk.on_tpu = was


@pytest.fixture(scope="module")
def glm5_engine():
    """The program family of the benchmark's latent-attention
    configuration, lowered as on the chip (kernels on), nothing run."""
    yield from _cell_engine("glm5_744b_ep16_d5")


def _flash_forward_calls(text, result):
    """The Mosaic calls of a compiled prefill whose first result has the
    shape ``result`` ([B * H, T, D]: the grouped-KV prefill's flash
    forward, kv_attention._gqa_attend)."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and f"= ({result}" in line]


def _grouped_kernel_calls(text):
    """{kernel: its Mosaic calls in a compiled module} for the two
    bodies of ``ops/pallas/grouped_matmul.py`` (ISSUE 64)."""
    return {name: len(re.findall(rf"%{name}[.\d]* = \S+ custom-call\(",
                                 text))
            for name in ("grouped_swiglu", "grouped_matmul")}


def _compile_view(chip, programs, key, cb, feeds, monkeypatch):
    import numpy as np
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    gvars = programs[key][0].desc.global_block.vars

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)
    state = {n: struct(gvars[n].shape, gvars[n].dtype)
             for n in cb.sig.state_names}
    consts = {n: struct(gvars[n].shape, gvars[n].dtype)
              for n in cb.sig.const_names}
    feeds = {k: struct(np.shape(v), I32 if np.asarray(v).dtype == np.int64
                       else np.asarray(v).dtype) for k, v in feeds.items()}
    return cb.fn.lower(state, consts, feeds,
                       struct((), jnp.uint32)).compile()


@pytest.mark.parametrize("path", ["pages", "rows"])
def test_latent_decode_step_compiles_for_v5e(chip, glm5_engine,
                                             monkeypatch, path):
    """32 slots of 12 288 rows, bf16, five ``mla`` layers (one dense, four
    with 16 of 256 experts): the step fits one chip with the 3.02 GB of
    latent and index planes donated and aliased in place. Per layer the
    indexer's scores are ONE ``score_pages`` call over the index plane's
    live pages where they lie (ISSUE 66), its result a table block a row
    (``f32[32,12,1024]``): no ``gather_pages`` of the plane's 393 216
    rows and no scoring of a copy (``f32[32,96,128]``) is left. The
    cache is 6 x index_topk long, so the selection is a mask found by
    threshold and ``attend_pages`` reads the latent plane's live pages
    under it (``pages``, PR 34): a second kernel a layer whose result is
    the attended latent ``bf16[32,64,640]``, no ``sort`` of a slot's
    12 288 scores (nor of the mask's positions: ``Selected`` is not
    fetched, and gone), no array of the 65 536 selected rows. Past
    ``ATTEND_PAGES_MAX_RATIO`` (``rows``: the same geometry under a
    smaller ratio) what follows the scores is PR 33's:
    ``jax.lax.top_k``, the chosen rows ordered by position and gathered
    ``bf16[65536,640]`` — the scores still come in place, the indexer
    has no ratio. Neither way copies or gathers a whole plane. Each of
    the four expert layers (32 tokens of 8 picks over a router 256 wide:
    a uniform draw leaves 36 % of the held experts unpicked) streams its
    HIT experts' weights through ONE kernel whose result is the float32
    sum ``f32[32,6144]`` (ISSUE 54): no product of every held expert
    over every token (``[32,16,2048]``) is left."""
    from paddle_tpu.ops import expert_ffn, mla
    eng, programs = glm5_engine
    if path == "rows":
        monkeypatch.setattr(mla, "ATTEND_PAGES_MAX_RATIO", 4)
        # the view was traced under the other ratio, and ``lower`` would
        # find that trace: drop this one function's, not the worker's
        eng._cb_decode.fn.clear_cache()
    lowered = {p: mla.MLA_DECODE_LOWERED.labels(path=p).value
               for p in ("pages", "rows")}
    index = {p: mla.DSA_INDEX_LOWERED.labels(path=p).value
             for p in ("pages", "rows")}
    experts = {p: expert_ffn.EXPERT_DENSE_LOWERED.labels(path=p).value
               for p in ("skip", "all")}
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    for p, was in lowered.items():
        assert mla.MLA_DECODE_LOWERED.labels(path=p).value - was \
            == (5 if p == path else 0)
    for p, was in index.items():
        assert mla.DSA_INDEX_LOWERED.labels(path=p).value - was \
            == (5 if p == "pages" else 0)
    for p, was in experts.items():
        assert expert_ffn.EXPERT_DENSE_LOWERED.labels(path=p).value - was \
            == (4 if p == "skip" else 0)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.5e9
    planes = 5 * 24576 * 16 * (640 + 128) * 2
    assert mem.alias_size_in_bytes >= planes
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    ops = _hlo_ops(text)
    in_place = path == "pages"
    assert entry.count("custom_call_target=\"tpu_custom_call\"") \
        == (14 if in_place else 9)
    assert len(re.findall(r"= f32\[32,6144\]\S* custom-call\(", entry)) == 4
    assert "[32,16,2048]" not in text
    # the indexer: five ``score_pages``, no copy of an index plane and
    # no scoring of one
    assert len(re.findall(r"= f32\[32,12,1024\]\S* custom-call\(", entry)) \
        == 5
    assert not re.findall(r"= bf16\[393216,128\]\S* custom-call\(", entry)
    assert "[32,96,128]" not in text
    assert len(re.findall(r"= bf16\[32,64,640\]\S* custom-call\(", entry)) \
        == (5 if in_place else 0)
    rows = len(re.findall(r"= bf16\[65536,640\]\S* fusion\(", entry))
    # the selection's five full sorts, or none
    sorts = len(re.findall(r"= \(f32\[32,12288\]\S*, s32\[32,12288\]\S*\) "
                           r"sort\(", entry))
    if in_place:
        assert rows == 0 and "[65536,640]" not in text
        assert sorts == 0 and not re.findall(r"\[32,12288\][^=]* sort\(",
                                             text)
        # the four routers' top-8 over 256 scores are all that sorts
        assert _count_opcode(entry, "sort") == 4
    else:
        assert rows >= 5 and sorts == 5
        # ... and the five orderings of the 2 048 chosen rows by position
        assert _count_opcode(entry, "sort") == 14
    plane = 24576 * 16 * 128
    assert not [line for opcode, count, _a, line in ops.values()
                if opcode in ("copy", "transpose", "gather")
                and count >= plane]
    assert not re.findall(r"= bf16\[393216,640\]\S* custom-call\(", entry)


def test_latent_prefill_compiles_for_v5e(chip, glm5_engine, monkeypatch):
    """The 8 192-token prefill beside the weights and the planes: under
    the chip's 16 GB, and with no window reduction in it — the row
    maximum of the blocked softmax, fused with its subtraction, became a
    ``reduce-window`` of 16 383 taps for every score (7.5 of a prefill's
    8.2 s on the chip: PERF.md, PR 33; ``ops/mla.py:_softmax_rows``)."""
    eng, programs = glm5_engine
    compiled = _compile_view(chip, programs, "prefill_paged@8192",
                             eng._cb_prefill[8192],
                             eng._prefill_feeds(8192), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    text = compiled.as_text()
    assert "reduce-window" not in text
    # the four expert layers' router and two argsorts; the indexer's
    # selection over [256, 8192] scores a block is by threshold, no sort
    assert _count_opcode(text, "sort") == 12
    assert not re.findall(r"\[256,8192\][^=]* sort\(", text)


# ---------------------------------------------------------------------------
# window and full attention layers in one page pool (PR 37): the decode
# step and the largest prefill of trinity_mini_26b_d5 at the cell's sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trinity_engine():
    yield from _cell_engine("trinity_mini_26b_d5")


def test_window_decode_step_compiles_for_v5e(chip, trinity_engine,
                                             monkeypatch):
    """32 slots, bf16, four window layers and one full layer, all 128
    experts a layer: the step fits one chip with both page groups (1.34
    GB the full layer's 20 480 rows a slot, 0.54 GB the four window
    layers' 2 064) donated and aliased in place. A window layer gathers
    its ring alone (``bf16[66048,512]``: 32 slots x 129 pages x 16
    rows); the full layer gathers NOTHING — its table of 20 480 rows is
    attended in place (ISSUE 62: one ``attend_pages`` call, the context
    ``bf16[32,32,512]``, no ``bf16[655360,512]`` copy of either plane)
    — and neither group is copied or transposed."""
    eng, programs = trinity_engine
    assert (eng.window, eng.window_ring, eng.n_window_pages) \
        == (2048, 129, 32 * 129)
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10e9 < peak < 15e9
    pages = (40960 + 4 * 4128) * 16 * 512 * 2 * 2
    assert mem.alias_size_in_bytes >= pages
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    assert len(re.findall(r"= bf16\[66048,512\]\S* custom-call\(", entry)) \
        == 8
    # (the pool's own flat view is bf16[655360,512] too: 40 960 pages)
    assert not re.findall(r"= bf16\[655360,512\]\S* custom-call\(", entry)
    assert len(re.findall(r"= bf16\[32,32,512\]\S* custom-call\(", entry)) \
        == 1
    # 32 tokens of 8 picks over 128 experts leave 12.7 % of them
    # unpicked: each of the four expert layers streams its hit experts
    # alone through one kernel (ISSUE 54)
    assert len(re.findall(r"= f32\[32,2048\]\S* custom-call\(", entry)) == 4
    assert "[32,128,1024]" not in text
    plane = 4128 * 16 * 512
    assert not [line for opcode, count, _a, line in _hlo_ops(text).values()
                if opcode in ("copy", "transpose", "gather")
                and count >= plane]
    # the module's name carries the window variant's row
    assert text.startswith("HloModule jit_lm_decode_paged_s367a,")


def test_window_prefill_compiles_for_v5e(chip, trinity_engine, monkeypatch):
    """The 16 384-token prefill beside the weights and both page groups:
    under 15 GB (the window layers attend in blocks of 512 queries
    over their band, the full layer through the flash kernel: the whole
    prompt's scores would be 34 GB), with no window reduction in it
    (``kv_attention._softmax_rows``)."""
    eng, programs = trinity_engine
    compiled = _compile_view(chip, programs, "prefill_paged@16384",
                             eng._cb_prefill[16384],
                             eng._prefill_feeds(16384), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    text = compiled.as_text()
    assert "reduce-window" not in text
    # the one full layer takes the flash forward (ISSUE 52), the four
    # window layers keep their loop of query blocks over the band
    assert len(_flash_forward_calls(text, "bf16[32,16384,128]")) == 1
    assert "f32[4,8,512,16384]" not in text
    assert _count_opcode(text, "while") >= 4


# ---------------------------------------------------------------------------
# grouped attention with a geometry per layer kind (PR 56): the decode step
# and the 32768-token prefill of mimo_v2_flash_ep16_d7 at the cell's sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mimo_engine():
    yield from _cell_engine("mimo_v2_flash_ep16_d7")


def test_grouped_kv_decode_step_compiles_for_v5e(chip, mimo_engine,
                                                 monkeypatch):
    """24 slots, bf16, two full layers of 4 KV heads and five window
    layers of 8, keys of 192 beside values of 128: the step fits one
    chip beside both page groups, donated and aliased in place. A full
    layer attends its slots' pages IN PLACE (ISSUE 62: one
    ``attend_pages`` call over the K plane's rows of 768 and the V
    plane's of 512, the context ``bf16[24,64,512]``; no copy of a
    table's 884 736 rows) and a window layer gathers its ring of 9 pages
    alone (rows of 1536 and
    1024); no plane is copied or transposed; each of the six expert
    layers streams its hit experts through one kernel (24 tokens padded
    to 32 rows)."""
    eng, programs = mimo_engine
    assert (eng.window, eng.window_ring, eng.n_window_pages) \
        == (128, 9, 24 * 9)
    assert (eng.n_pages, eng.max_pages) == (29184, 2304)
    assert eng.row_bytes == {"full": 2 * 4 * 320 * 2,
                             "window": 5 * 8 * 320 * 2}
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 9e9 < peak < 13e9
    pages = (2 * 29184 * 16 * 4 + 5 * 216 * 16 * 8) * 320 * 2
    assert mem.alias_size_in_bytes >= pages
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    rows, ring = 24 * 2304 * 16, 24 * 9 * 16
    for shape, count in ((f"{rows},768", 0), (f"{rows},512", 0),
                         ("24,64,512", 2),
                         (f"{ring},1536", 5), (f"{ring},1024", 5)):
        assert len(re.findall(rf"= bf16\[{shape}\]\S* custom-call\(",
                              entry)) == count, shape
    assert f"[{rows}," not in text
    assert len(re.findall(r"= f32\[32,4096\]\S* custom-call\(", entry)) == 6
    # no PLANE is copied (the compiler does re-lay the rotated
    # projections' weights, wq and wk, every step: PERF.md section 7)
    plane = 216 * 16 * 1024
    moved = [line for opcode, count, _a, line in _hlo_ops(text).values()
             if opcode in ("copy", "transpose", "gather") and count >= plane]
    assert all(re.search(r"bf16\[4096,(12288|1536)\]", line)
               for line in moved), moved
    # the module's name carries the window variant's row, as Trinity's
    assert text.startswith("HloModule jit_lm_decode_paged_s367a,")


def test_grouped_kv_prefill_compiles_for_v5e(chip, mimo_engine, monkeypatch):
    """The 32 768-token prefill beside the weights and both page groups:
    under 14.5 GB of the chip's 15.75 — the dense layer's hidden rows,
    the rotated projections' float32 heads and the grouped way's combine
    run a block at a time (with all three whole it is 16.7 GB and does
    not compile). The two full layers take the flash forward at 64 / 4
    heads of 192 / 128, the five window layers their loop of query
    blocks over a band of 640 keys with the sink in its softmax."""
    eng, programs = mimo_engine
    compiled = _compile_view(chip, programs, "prefill_paged@32768",
                             eng._cb_prefill[32768],
                             eng._prefill_feeds(32768), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    text = compiled.as_text()
    assert "reduce-window" not in text
    assert len(_flash_forward_calls(text, "bf16[64,32768,128]")) == 2
    assert "f32[32768,16384]" not in text
    assert "f32[1,32768,64,192]" not in text
    assert "f32[8,8,512,32768]" not in text
    assert _count_opcode(text, "while") >= 5


# ---------------------------------------------------------------------------
# state-space layers beside one attention layer (PR 42): the decode step
# and the 2048-token prefill of granite4_h_small_ep4_d10 at the cell's sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def granite_engine():
    yield from _cell_engine("granite4_h_small_ep4_d10")


def test_ssd_decode_step_compiles_for_v5e(chip, granite_engine, monkeypatch):
    """128 slots, bf16, nine SSD layers and one attention layer, 18 of 72
    experts: the step's arguments are the 12.28 GB the configuration's
    file counts and it fits one chip with its temporaries; each SSD
    layer's state (``f32[128,128,8192]``, 537 MB) is touched by ONE
    instruction — a fusion with two results, the new state and ``y
    f32[128,8192]``: one read and one write of the state — and no
    ``copy`` or ``transpose`` of its size is in the step; the state and
    the pages are donated and aliased in place. 128 tokens take the
    experts' dense way; the attention layer reads its live pages in
    place."""
    eng, programs = granite_engine
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    assert 12.2e9 < mem.argument_size_in_bytes < 12.35e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    state = 9 * 128 * 128 * 8192 * 4
    assert mem.alias_size_in_bytes >= state + 2 * 22528 * 16 * 1024 * 2
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    ops = _hlo_ops(text)
    touching = [line for line in entry.splitlines()[1:]
                if "f32[128,128,8192]" in line.split(" = ", 1)[-1]
                and not re.search(r"\b(parameter|get-tuple-element|bitcast|"
                                  r"tuple)\(", line)]
    assert len(touching) == 9, touching[:3]
    assert all(re.search(r"= \(f32\[128,128,8192\]\S*, f32\[128,8192\]\S*\) "
                         r"fusion\(", line) for line in touching)
    assert not [n for n in ops if n.startswith("ragged-dot")]
    # the one attention layer's table of 2 816 rows is attended in place
    # (ISSUE 62: blocks of 256 rows), neither plane gathered
    assert "gather_pages" not in entry
    assert not re.findall(r"= bf16\[360448,1024\]\S* custom-call\(", entry)
    assert len(re.findall(r"= bf16\[128,32,1024\]\S* custom-call\(",
                          entry)) == 1
    # the module's name carries ``ssd_decode``'s row of the phases
    assert text.startswith("HloModule jit_lm_decode_paged_s8ff8,")


def test_ssd_prefill_compiles_for_v5e(chip, granite_engine, monkeypatch):
    """The 2048-token prefill beside the weights, the state and the
    pages: under 14.5 GB (the bound under which the configuration keeps
    128 slots); the scan is a ``while`` over the chunks a prompt fills;
    the slot's state lands by an in-place update of the donated variable
    (no copy of a state's size); 2048 tokens take the experts' grouped
    way, its products through the row-tiled kernel (ISSUE 64: two calls
    an expert layer, no ``ragged-dot``)."""
    eng, programs = granite_engine
    compiled = _compile_view(chip, programs, "prefill_paged@2048",
                             eng._cb_prefill[2048],
                             eng._prefill_feeds(2048), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert mem.alias_size_in_bytes >= 9 * 128 * 128 * 8192 * 4
    text = compiled.as_text()
    ops = _hlo_ops(text)
    assert _count_opcode(text, "while") >= 9
    assert not [line for opcode, count, _a, line in ops.values()
                if opcode in ("copy", "transpose")
                and count >= 128 * 128 * 8192]
    assert len(_flash_forward_calls(text, "bf16[32,2048,128]")) == 1
    assert _grouped_kernel_calls(text) == {"grouped_swiglu": 10,
                                           "grouped_matmul": 10}
    assert "ragged_dot_tiling" not in text
    assert text.startswith("HloModule jit_lm_prefill_paged_2048_s0b8b,")


# ---------------------------------------------------------------------------
# gated short convolutions beside rotary attention layers (PR 51): the
# decode step and the 4096-token prefill of lfm2_8b_a1b_d12 at the cell's
# sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lfm2_engine():
    yield from _cell_engine("lfm2_8b_a1b_d12")


def test_shortconv_decode_step_compiles_for_v5e(chip, lfm2_engine,
                                                monkeypatch):
    """64 slots, bf16, nine conv layers and three rotary attention
    layers, all 32 experts: the step's arguments are the 9.55 GB the
    configuration's file counts (7.86 of weights, 1.69 of pages, 4.7 MB
    of conv windows) and it fits one chip with its temporaries; the
    pages are donated and aliased in place; 64 tokens take the experts'
    dense way; three layers gather K and V."""
    eng, programs = lfm2_engine
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    assert 9.5e9 < mem.argument_size_in_bytes < 9.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    assert mem.alias_size_in_bytes >= 2 * 3 * 17152 * 16 * 512 * 2
    text = compiled.as_text()
    assert not [n for n in _hlo_ops(text) if n.startswith("ragged-dot")]
    assert text[text.index("ENTRY "):].count("gather_pages") >= 6
    # the module's name carries ``shortconv_decode``'s row of the phases
    assert text.startswith("HloModule jit_lm_decode_paged_se045,")


def test_shortconv_prefill_compiles_for_v5e(chip, lfm2_engine, monkeypatch):
    """The 4096-token prefill beside the weights and the pages: under
    10.5 GB; 4096 tokens take the experts' grouped way over buffers of
    every assignment (the member holds every expert: no loop of turns),
    its products through the row-tiled kernel (ISSUE 64: gate and up in
    one call, down in a second, ten expert layers; no ``ragged-dot``);
    each of the three attention
    layers attends through ONE causal flash forward kernel over 32
    query heads of 64 (ISSUE 52: 8 key heads, read by the index maps) —
    no loop of query blocks, no float32 block of scores."""
    eng, programs = lfm2_engine
    compiled = _compile_view(chip, programs, "prefill_paged@4096",
                             eng._cb_prefill[4096],
                             eng._prefill_feeds(4096), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    text = compiled.as_text()
    assert "reduce-window" not in text
    assert _grouped_kernel_calls(text) == {"grouped_swiglu": 10,
                                           "grouped_matmul": 10}
    assert "ragged_dot_tiling" not in text
    assert len(_flash_forward_calls(text, "bf16[32,4096,64]")) == 3
    assert "f32[8,4,512,4096]" not in text       # a block's scores
    assert text.startswith("HloModule jit_lm_prefill_paged_4096_sfa9e,")


# ---------------------------------------------------------------------------
# a multi-head layer of 30 KV heads beside Gated DeltaNet layers (PR 59):
# the decode step of olmo_hybrid_7b_pp2_d16 at the cell's sizes, its four
# full layers in place in blocks that follow the row width (ISSUE 62)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def olmo_engine():
    yield from _cell_engine("olmo_hybrid_7b_pp2_d16")


def test_dense_decode_step_compiles_for_v5e(chip, olmo_engine, monkeypatch):
    """8 slots, bf16, twelve Gated DeltaNet layers and four multi-head
    layers of 30 KV heads of 128 (a group of ONE: rows of 3 840 beside
    rows of 3 840), tables of 528 pages. Each full layer attends its
    slots' pages IN PLACE — one ``attend_pages`` call, the context
    ``bf16[8,30,3840]``, in blocks of 16 pages = 256 rows whose tiles
    take 7.9 MB of VMEM (the 48 pages the table also divides into would
    take 23.6 MB and do not compile) — and no plane's table of 67 584
    rows is gathered; the step fits the chip beside the 8.2 GB of
    weights and the 4.15 GB of pages, donated and aliased in place."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    eng, programs = olmo_engine
    assert (eng.n_slots, eng.max_pages, eng.page_size) == (8, 528, 16)
    assert eng._full_layers == 4 and eng._in_place_blocks == {16: 4}
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    assert 12.5e9 < mem.argument_size_in_bytes < 12.8e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
    assert mem.alias_size_in_bytes >= 8 * eng.n_pages * 16 * 3840 * 2
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    assert "gather_pages" not in entry
    assert not re.findall(r"= bf16\[67584,3840\]\S* custom-call\(", entry)
    assert len(re.findall(r"= bf16\[8,30,3840\]\S* custom-call\(",
                          entry)) == 4
    # the bias rides in blocks of the kernel's rows: 33 of 256 a slot
    assert "f32[8,33,256]" in entry
    assert 2 * 256 * pa.attend_row_bytes(
        jax.ShapeDtypeStruct((1, 3840), BF16),
        jax.ShapeDtypeStruct((1, 3840), BF16)) <= pa._ATTEND_TILE_BYTES


def test_dense_prefill_compiles_for_v5e(chip, olmo_engine, monkeypatch):
    """The 8192-token prefill beside the weights and the pages, in the
    cell nearest the chip's memory (``peak_hbm_gb.decode`` 14.53 of 16).
    Arguments and temporaries were 12.644 + 1.549 GB on the parent of
    PR 67 and are 12.644 + 1.586 since: the block turns of the blocked
    solve bring ONE more ``[16,30,64,288]`` float32 buffer (36 MB, the
    same whether three, seven or fourteen products make the turns) and
    nothing else — a layout of q, k, v or o made once a layer brought
    0.11-0.39 GB and was not kept. Each Gated DeltaNet layer solves a
    chunk's triangular system in diagonal blocks of 16 rows
    (``ops/gdn.py:_unit_lower_solve``): the substitution's loop carries
    ``f32[16,30,4,16,16]``, and the only loop left that carries a
    ``[16,30,64,64]`` array is the scan over a block's chunks (its
    attention), one a layer."""
    eng, programs = olmo_engine
    compiled = _compile_view(chip, programs, "prefill_paged@8192",
                             eng._cb_prefill[8192],
                             eng._prefill_feeds(8192), monkeypatch)
    mem = compiled.memory_analysis()
    assert 12.6e9 < mem.argument_size_in_bytes < 12.7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.25e9
    loops = [line.split(" while(")[0]
             for line in compiled.as_text().splitlines()
             if re.match(r"\s*%while[.\d]* = \(.* while\(", line)]
    assert sum("f32[16,30,4,16,16]" in carried for carried in loops) >= 12
    assert sum("f32[16,30,64,64]" in carried for carried in loops) == 12


# ---------------------------------------------------------------------------
# the expert layer's two ways (PR 44): the dense way's text is the
# parent's, the grouped way's optimised module holds no buffer of the
# worst case
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Mamba-1's selective scan beside multi-query attention (PR 65): the decode
# step and the 1024-token prefill of jamba2_3b — the WHOLE model — at the
# cell's sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jamba2_engine():
    yield from _cell_engine("jamba2_3b")


def test_s6_decode_step_compiles_for_v5e(chip, jamba2_engine, monkeypatch):
    """256 slots, bf16, all 28 layers (26 Mamba, 2 multi-query), the
    whole tied vocabulary: the step's arguments are the 9.52 GB the
    configuration's file counts and it fits one chip with its
    temporaries; each Mamba layer's state (``f32[256,16,5120]``, 84 MB)
    is touched by ONE instruction, the ``s6_state_update`` kernel, whose
    results are the state and ``y f32[256,5120]`` — no fusion, copy or
    transpose of its size is in the step; state, windows and pages are
    donated and aliased in place; each attention layer reads its one KV
    head's live pages in place."""
    eng, programs = jamba2_engine
    assert (eng.n_slots, eng.max_pages, eng.page_size) == (256, 256, 16)
    compiled = _compile_view(chip, programs, "decode_paged", eng._cb_decode,
                             eng._decode_feeds(), monkeypatch)
    mem = compiled.memory_analysis()
    assert 9.45e9 < mem.argument_size_in_bytes < 9.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    state = 26 * 256 * 16 * 5120 * 4
    assert mem.alias_size_in_bytes >= state + 4 * 65536 * 16 * 128 * 2
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    touching = [line for line in entry.splitlines()[1:]
                if "f32[256,16,5120]" in line.split(" = ", 1)[-1]
                and not re.search(r"\b(parameter|get-tuple-element|bitcast|"
                                  r"tuple)\(", line)]
    assert len(touching) == 26, touching[:3]
    assert all(re.search(r"%s6_state_update[\w.]* = \(f32\[256,16,5120\]\S*, "
                         r"f32\[256,5120\]\S*\) custom-call\(", line)
               for line in touching)
    assert "gather_pages" not in entry
    assert len(re.findall(r"= bf16\[256,20,128\]\S* custom-call\(",
                          entry)) == 2
    # the module's name carries ``s6_decode``'s row of the phases
    assert text.startswith("HloModule jit_lm_decode_paged_s6ffc,")


def test_s6_prefill_compiles_for_v5e(chip, jamba2_engine, monkeypatch):
    """The 1024-token prefill beside the weights, the state and the
    pages: each Mamba layer's scan is ONE ``s6_scan`` call (``y
    f32[1024,5120]`` and the state ``f32[16,5120]``), no array of the
    bucket's states (``[1024,16,5120]``) and no loop over tokens is in
    it; the slot's state lands by an in-place update of the donated
    variable (no copy of a state's size); the largest values are the
    bucket's rows of the inner width and of the feed-forward."""
    eng, programs = jamba2_engine
    compiled = _compile_view(chip, programs, "prefill_paged@1024",
                             eng._cb_prefill[1024],
                             eng._prefill_feeds(1024), monkeypatch)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    assert mem.alias_size_in_bytes >= 26 * 256 * 16 * 5120 * 4
    text = compiled.as_text()
    ops = _hlo_ops(text)
    scans = re.findall(r"%s6_scan[\w.]* = \((f32\[[\d,]+\])\S*, "
                       r"(f32\[[\d,]+\])\S*\) custom-call\(", text)
    assert scans == [("f32[1024,5120]", "f32[16,5120]")] * 26
    assert not re.search(r"f32\[1024,16,5120\]|f32\[1024,5120,16\]", text)
    assert not [line for opcode, count, _a, line in ops.values()
                if opcode in ("copy", "transpose")
                and count >= 256 * 16 * 5120]
    # what a prefill holds at most: [1024, 10240] and [1024, 8192] rows
    biggest = max(count for opcode, count, _a, _l in ops.values()
                  if opcode not in ("parameter", "get-tuple-element",
                                    "tuple", "bitcast", "dynamic-update-slice")
                  and count < 256 * 3 * 5120)
    assert biggest <= 1024 * 10240, biggest
    assert len(_flash_forward_calls(text, "bf16[20,1024,128]")) == 2
    assert text.startswith("HloModule jit_lm_prefill_paged_1024_s617b,")


def _expert_layer(chip, tokens, n_experts, n_held, top_k, m, f, attrs, told):
    """``expert_ffn_held`` alone, lowered for a described v5e: X
    [1, tokens, m] over a router ``n_experts`` wide, ``n_held`` experts
    of width ``f`` and a shared one of 2 f; ``told(struct)`` gives the
    optional inputs."""
    from paddle_tpu.ops import expert_ffn

    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    ins = {"X": s((1, tokens, m)), "RouterW": s((m, n_experts)),
           "WGate": s((n_held, m, f)), "WUp": s((n_held, m, f)),
           "WDown": s((n_held, f, m)), "SGate": s((m, 2 * f)),
           "SUp": s((m, 2 * f)), "SDown": s((2 * f, m)), **told(s)}

    def layer(ins):
        out = expert_ffn._expert_ffn_held(
            None, {k: [v] for k, v in ins.items()},
            {"top_k": top_k, **attrs})
        return {k: v[0] for k, v in out.items()}
    return jax.jit(layer).lower(ins)


# (tokens, router, held, picks, d_model, d_expert, attributes, optional
# inputs) -> the text's hash as recorded at the parent commit c5e5c19
DENSE_WAY_AT_THE_PARENT = {
    "granite_step": (
        (128, 72, 18, 10, 4096, 768,
         {"scoring": "softmax_topk", "held_start": 0},
         lambda s: {"Valid": s((128, 1), I32), "Counts": s((2, 18), I32)}),
        "5138a4510503f09b"),
    "solar_prefill_512": (
        (512, 320, 40, 8, 4096, 1280, {"norm_topk": True, "scaling": 1.0},
         lambda s: {"SeqLen": s((1, 1), I32)}),
        "cfc40f81379cddfd"),
    "glm5_step": (
        (32, 256, 16, 8, 6144, 2048, {"norm_topk": True, "scaling": 2.5},
         lambda s: {"Valid": s((32, 1), I32), "Counts": s((2, 16), I32),
                    "RouterBias": s((1, 256), F32)}),
        "830f75c81b33a4e1"),
}


@pytest.mark.parametrize("on_chip", [False, True], ids=["refer", "chip"])
@pytest.mark.parametrize("case", sorted(DENSE_WAY_AT_THE_PARENT))
def test_the_dense_way_lowers_as_at_the_parent(chip, case, on_chip,
                                               monkeypatch):
    """Up to ``DENSE_MAX_TOKENS`` tokens the op lowers op for op as
    before the grouped way changed: a step of Granite's and of GLM-5's
    view, Solar's largest prefill — the text's hash without source
    locations, as recorded in the parent's checkout by this function.
    On a chip (steered) as off it, but for GLM-5's step there: 32 tokens
    of 8 picks over 256 experts engage the kernel that streams the hit
    experts alone (ISSUE 54, ``expert_ffn.dense_tier``), Granite's and
    Solar's shapes do not."""
    from paddle_tpu.ops import expert_ffn
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: on_chip)
    args, want = DENSE_WAY_AT_THE_PARENT[case]
    assert args[0] <= expert_ffn.DENSE_MAX_TOKENS == 512
    text = _expert_layer(chip, *args).as_text()
    if on_chip and case == "glm5_step":
        assert text.count("tpu_custom_call") == 1
        assert _scrubbed_sha(text) != want
    else:
        assert "tpu_custom_call" not in text
        assert _scrubbed_sha(text) == want


@pytest.mark.parametrize("on_chip", [False, True], ids=["refer", "chip"])
def test_the_grouped_way_holds_no_worst_case_buffer_on_v5e(chip, on_chip,
                                                           monkeypatch):
    """Granite's 2 048-token prefill, 18 of 72 experts held, 10 picks a
    token: the optimised module holds the three grouped products over a
    buffer of the HELD share (6 400 rows: a quarter of the 20 480
    assignments and a quarter more), inside a loop whose turns the
    draw's held rows decide, and nothing of the worst case's size — no
    ``[N*K, M]`` value in float32 or in the storage dtype, no
    ``[N, K, M]`` value. On a chip (steered) the products are the
    row-tiled kernel's two calls (ISSUE 64), off it three
    ``ragged-dot``."""
    from paddle_tpu.ops import expert_ffn
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: on_chip)
    assert expert_ffn.grouped_rows(2048, 10, 18, 72) == 6400
    text = _expert_layer(
        chip, 2048, 72, 18, 10, 4096, 768,
        {"scoring": "softmax_topk", "held_start": 0},
        lambda s: {"SeqLen": s((1, 1), I32)}).compile().as_text()
    if on_chip:
        assert _grouped_kernel_calls(text) == {"grouped_swiglu": 1,
                                               "grouped_matmul": 1}
        assert "ragged-dot-none" not in text
        # the two float32 products of gate and up are never written
        assert "f32[6400,768]" not in text
    else:
        assert sum(_grouped_kernel_calls(text).values()) == 0
        assert text.count("ragged-dot-none") >= 3
    assert "f32[6400,4096]" in text and "bf16[6400,4096]" in text
    assert _count_opcode(text, "while") == 1
    for shape in ("[20480,4096]", "[20480,768]", "[2048,10,4096]",
                  "[10,2048,4096]"):
        assert shape not in text, shape


@pytest.mark.parametrize("on_chip", [False, True], ids=["refer", "chip"])
def test_the_grouped_ways_backward_is_grouped_on_v5e(chip, on_chip,
                                                     monkeypatch):
    """A training step's expert layer at JoyAI-LLM-Flash's share (8 192
    tokens, 8 picks over a router of 256, 16 experts of 768 held, float32
    master weights under bfloat16 activations): forward and backward
    hold grouped products alone — three forward, and in the backward the
    three made again, their four transposes to the rows and three to the
    weights — over the HELD share's 5 120 rows; nothing of every expert
    times every token (``[8192,16,768]``), nothing of the worst case's
    size (65 536 assignments), and one loop each way whose turns the
    draw decides. On a chip (steered) the forward's products and the
    backward's recompute of them are the row-tiled kernel's (ISSUE 64:
    gate and up in one call and down forward, three plain calls again
    backward); the transposes stay ``ragged_dot_general``."""
    from paddle_tpu.ops import expert_ffn
    from paddle_tpu.ops import pallas as pk
    monkeypatch.setattr(pk, "on_tpu", lambda: on_chip)
    n, e, held, k, m, f = 8192, 256, 16, 8, 2048, 768
    assert expert_ffn.grouped_rows(n, k, held, e) == 5120

    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def step(x, router, bias, wg, wu, wd, g):
        def part(x, wg, wu, wd):
            combine, idx = expert_ffn.route(x, router, k, True, 2.5, bias)
            return expert_ffn.held_experts_part(x, combine, idx, wg, wu, wd,
                                                0, None, e)[0]
        y, pull = jax.vjp(part, x, wg, wu, wd)
        return (y,) + pull(g)
    text = jax.jit(step).lower(
        s((n, m)), s((m, e), F32), s((1, e), F32), s((held, m, f), F32),
        s((held, m, f), F32), s((held, f, m), F32),
        s((n, m), F32)).compile().as_text()
    if on_chip:
        assert _grouped_kernel_calls(text) == {"grouped_swiglu": 1,
                                               "grouped_matmul": 4}
        assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3
    else:
        assert sum(_grouped_kernel_calls(text).values()) == 0
        assert text.count("ragged-dot-none") >= 13
    assert "[5120,2048]" in text and "[5120,768]" in text
    assert _count_opcode(text, "while") == 2
    for shape in ("[8192,16,768]", "[16,8192,768]", "[65536,2048]",
                  "[65536,768]", "[8192,8,2048]", "[8,8192,2048]"):
        assert shape not in text, shape


def test_joyai_train_step_compiles_for_v5e(chip, monkeypatch):
    """The trainer's whole step of ``train_joyai_seq8k_1chip`` (build_lm
    at the configuration's sizes: five layers and the MTP module, one
    8 192-token sequence, the pass pipeline, the mixed-precision rewrite
    and the configuration's recomputation) for a described v5e: it fits
    the chip beside its 8.17 GB of float32 state, holds the flash
    kernels of every layer — the forward once, the backward's two — and
    the grouped products, and no ``[.., T, T]`` array."""
    import json
    import os
    import numpy as np
    from paddle_tpu.core.lowering import CompiledBlock
    from paddle_tpu.ops import pallas as pk
    from chipbench.runners import train_lm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "joyai_llm_flash_ep16_d6.json")) as f:
        cfg = json.load(f)
    t = cfg["build"]["seq_len"]
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    main, _startup, loss, _totals = train_lm.build_program(cfg, 1)
    feeds = ["ids", "lbl_ids", "lbl2_ids"]
    cb = CompiledBlock(main.desc, 0, feeds, [loss.name])
    gvars = main.desc.global_block.vars

    def struct(n):
        v = gvars[n]
        return jax.ShapeDtypeStruct(tuple(v.shape), jnp.dtype(v.dtype),
                                    sharding=chip)
    state = {n: struct(n) for n in cb.sig.state_names}
    assert 8.1e9 < sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for s in state.values()) < 8.2e9
    from paddle_tpu.ops import grad_ops

    def kept():
        return {op: (grad_ops.KEPT_VALUES.labels(op=op).value,
                     grad_ops.KEPT_BYTES.labels(op=op).value)
                for op in cfg["recompute"]}
    before = kept()
    compiled = cb.fn.lower(
        state, {n: struct(n) for n in cb.sig.const_names},
        {n: jax.ShapeDtypeStruct((1, t, 1), jnp.int64, sharding=chip)
         for n in feeds},
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=chip)).compile()
    # what the three recomputed op types keep beside their inputs: the
    # six latent layers their bf16[1,32,8192,128] out and f32[1,32,8192]
    # lse, the experts and the dense layer nothing
    assert {op: tuple(a - b for a, b in zip(kept()[op], before[op]))
            for op in before} == {
        "mla_full": (12, 6 * (32 * t * 128 * 2 + 32 * t * 4)),
        "expert_ffn_held": (0, 0), "swiglu_ffn": (0, 0)}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # six layers: the flash forward ONCE — the recomputed op keeps its
    # out and lse (PR 50; four calls a layer before) — dq and dkv
    attend = [m.groups() for m in map(re.compile(
        r'= (.*?) custom-call\(.*custom_call_target="tpu_custom_call"'
        r'.*?op_name="([^"]*mla_full[^"]*)"').search, text.splitlines())
        if m]
    forward = [n for shape, n in attend if "f32[32,8192,1]" in shape]
    assert len(forward) == 6 and not any("grad/" in n for n in forward)
    assert len(attend) == 18
    assert sum("grad/mtp/mla_full" in n for _, n in attend) == 2
    # five expert layers: the forward's two kernel calls (ISSUE 64), the
    # backward's three for its recompute, and the three transposes to
    # the weights on ``ragged_dot_general``
    calls = _grouped_kernel_calls(text)
    assert calls["grouped_swiglu"] >= 5 and calls["grouped_matmul"] >= 5 * 4
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 5 * 3
    for m in re.finditer(r"(?:f32|bf16)\[([\d,]+)\]", text):
        dims = [int(d) for d in m.group(1).split(",")]
        assert dims.count(t) < 2, m.group(0)
