"""SPMD execution-path tests (ISSUE 18): one jax.jit dispatch under
Mesh + NamedSharding as the product path.

Contracts certified here, all on the 8-virtual-device CPU mesh
(conftest.py):

- dp=8 loss parity (rtol <= 1e-6, finiteness checked separately —
  assert_allclose treats NaN == NaN) against the single-device oracle
  for >= 2 zoo models;
- training state stays DEVICE-RESIDENT across steps: the per-step
  host round-trip (``_gather_state``) happens once, and only an
  external scope write re-triggers it;
- PartitionSpec derivation edge cases: RowSparseGrad embedding
  pytrees, padding_idx rows, and non-divisible batch dims riding the
  utils/padding.py pad-and-slice path exactly;
- the FLAGS_hbm_bytes budget ladder (as-configured -> ZeRO -> tp)
  records its decision on ``CompiledBlock.hbm_plan`` and the chosen
  plan actually shards what it promised;
- the SPMD observability surface: ``paddle_spmd_mesh_devices`` and a
  ``paddle_spmd_resharding_bytes_total`` that goes FLAT once steady
  state is reached (the device-residency witness);
- FLAGS_grad_allreduce_codec: the explicit shard_map-island gradient
  exchange (parallel/collective.py grad_all_reduce) is exact for
  'none' and parity-window-close for 'bf16'/'int8' (EQuARX-style
  per-row scales, arXiv:2506.17615).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import flags
from paddle_tpu.fluid import layers
from paddle_tpu.parallel import DistributeConfig, make_mesh


@pytest.fixture(autouse=True)
def _reset_spmd_flags():
    yield
    flags.set("hbm_bytes", 0.0)
    flags.set("grad_allreduce_codec", "none")


def _dist(mesh=None):
    return DistributeConfig(mesh=mesh or make_mesh(), data_axis="dp")


# -- zoo-model parity: dp=8 one dispatch vs the single-device oracle ------

_ZOO_FEEDS = {
    "mnist": lambda rng, bs: {
        "pixel": rng.rand(bs, 1, 28, 28).astype(np.float32),
        "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)},
    "smallnet": lambda rng, bs: {
        "data": rng.rand(bs, 3, 32, 32).astype(np.float32),
        "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)},
}


def _zoo_losses(model_name, mesh, steps=3, bs=16):
    from paddle_tpu import models
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    startup.random_seed = 1
    with fluid.program_guard(main, startup):
        loss, _, specs = getattr(models, model_name).build()
    feed_fn = _ZOO_FEEDS[model_name]
    prog = main
    if mesh is not None:
        prog = fluid.CompiledProgram(main).with_sharding(_dist(mesh))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    out = []
    for s in range(steps):
        feeds = feed_fn(np.random.RandomState(100 + s), bs)
        out.append(np.asarray(exe.run(prog, feed=feeds, fetch_list=[loss],
                                      scope=scope)[0]))
    return np.asarray(out)


@pytest.mark.parametrize("model_name", ["mnist", "smallnet"])
def test_zoo_dp8_parity(model_name):
    """dp=8 must reproduce the single-device loss curve (rtol <= 1e-6;
    the acceptance contract of ISSUE 18)."""
    ref = _zoo_losses(model_name, None)
    got = _zoo_losses(model_name, make_mesh())
    assert np.all(np.isfinite(ref)), ref
    assert np.all(np.isfinite(got)), got
    np.testing.assert_allclose(got, ref, rtol=1e-6)


# -- device-resident state across steps -----------------------------------

def _build_mlp(seed=5, opt="sgd"):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[32], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="int64")
        # explicit param names: the layer-name counter is process-global,
        # so auto names (fc_0.w_0) drift with test order
        h = layers.fc(input=x, size=64, act="relu",
                      param_attr=fluid.ParamAttr(name="mlp_w1"))
        logits = layers.fc(input=h, size=4,
                           param_attr=fluid.ParamAttr(name="mlp_w2"))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        if opt == "adam":
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        else:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


_PROJ = np.random.RandomState(42).rand(32, 4).astype(np.float32)


def _mlp_feeds(step, bs=32):
    rng = np.random.RandomState(100 + step)
    xv = rng.rand(bs, 32).astype(np.float32)
    yv = np.argmax(xv @ _PROJ, axis=1).astype(np.int64)[:, None]
    return {"x": xv, "y": yv}


def test_state_stays_device_resident():
    """The per-step host round-trip is gone: ``_gather_state`` runs once
    to arm the residency cache, then every subsequent dispatch reuses
    the device arrays. An EXTERNAL scope write (a checkpoint restore, a
    manual set_var) is the one thing that re-triggers the walk."""
    from paddle_tpu.core.lowering import CompiledBlock
    main, startup, loss = _build_mlp(seed=7)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    cb = CompiledBlock(main.desc, 0, ["x", "y"], [loss.name],
                       dist=_dist())
    for s in range(4):
        cb(scope, _mlp_feeds(s), s)
    assert cb.gather_state_calls == 1, cb.gather_state_calls
    # fetch coherence: the scope writeback still carries every step's
    # result, so an explicit fetch needs no extra transfer machinery
    w = np.asarray(scope.find_var("mlp_w1"))
    assert np.all(np.isfinite(w))
    # external mutation invalidates the residency cache exactly once
    scope.set_var("mlp_w1", np.zeros_like(w))
    cb(scope, _mlp_feeds(9), 9)
    assert cb.gather_state_calls == 2, cb.gather_state_calls
    cb(scope, _mlp_feeds(10), 10)
    assert cb.gather_state_calls == 2, cb.gather_state_calls


# -- PartitionSpec derivation edge cases ----------------------------------

V, D = 40, 8


def _build_embed(seed=11, padding_idx=None):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        ids = layers.data(name="ids", shape=[6, 1], dtype="int64")
        y = layers.data(name="y", shape=[1], dtype="float32")
        emb = layers.embedding(ids, size=[V, D], padding_idx=padding_idx,
                               param_attr=fluid.ParamAttr(name="emb_w"))
        pooled = layers.reduce_sum(emb, dim=1)
        pred = layers.fc(pooled, size=1)
        loss = layers.mean(layers.square(layers.elementwise_sub(pred, y)))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _embed_batches(n, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ids = rng.randint(0, V, (bs, 6, 1)).astype(np.int64)
        ids[0, :3] = 3                      # duplicate rows in one batch
        out.append({"ids": ids, "y": rng.rand(bs, 1).astype(np.float32)})
    return out


def _train_embed(mesh, padding_idx=None, steps=4):
    main, startup, loss = _build_embed(padding_idx=padding_idx)
    prog = main
    if mesh is not None:
        prog = fluid.CompiledProgram(main).with_sharding(_dist(mesh))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    losses = [np.asarray(exe.run(prog, feed=f, fetch_list=[loss],
                                 scope=scope)[0])
              for f in _embed_batches(steps)]
    return np.asarray(losses), np.asarray(scope.find_var("emb_w"))


def test_row_sparse_grad_under_mesh():
    """The lookup_table VJP carries a RowSparseGrad pytree
    (core/selected_rows.py) through the jitted step — the SPMD specs
    must traverse it without densifying or crashing, and the dp=8 run
    must match the single-device table bit-for-bit-close."""
    ref_losses, ref_table = _train_embed(None)
    got_losses, got_table = _train_embed(make_mesh())
    assert np.all(np.isfinite(got_losses)), got_losses
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6)
    np.testing.assert_allclose(got_table, ref_table, rtol=1e-5,
                               atol=1e-7)


def test_padding_idx_rows_under_mesh():
    """padding_idx rows take no gradient: under the mesh the padded
    row must stay at its initial value exactly as it does on one
    device."""
    ref_losses, ref_table = _train_embed(None, padding_idx=0)
    got_losses, got_table = _train_embed(make_mesh(), padding_idx=0)
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6)
    np.testing.assert_allclose(got_table[0], ref_table[0], rtol=1e-6)


def test_non_divisible_batch_pads_and_slices_exactly():
    """A batch of 12 over 8 devices rides pad-and-slice
    (utils/padding.py): row-shaped fetches come back with exactly 12
    rows and bit-match the single-device forward."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 2
    startup.random_seed = 2
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[32], dtype="float32")
        h = layers.fc(input=x, size=16, act="relu")
        pred = layers.fc(input=h, size=4)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    feeds = {"x": np.random.RandomState(0).rand(12, 32).astype(np.float32)}
    ref = np.asarray(exe.run(main, feed=feeds, fetch_list=[pred],
                             scope=scope)[0])
    prog = fluid.CompiledProgram(main).with_sharding(_dist())
    got = np.asarray(exe.run(prog, feed=feeds, fetch_list=[pred],
                             scope=scope)[0])
    assert got.shape == (12, 4), got.shape
    np.testing.assert_allclose(got, ref, rtol=1e-6)


# -- HBM budget ladder ----------------------------------------------------

def test_hbm_budget_ladder_picks_zero():
    """An Adam MLP whose replicated state blows a tiny budget must walk
    to the ZeRO rung: moments shard over dp, the decision is recorded,
    and training still runs."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core.lowering import CompiledBlock
    main, startup, loss = _build_mlp(seed=3, opt="adam")
    flags.set("hbm_bytes", 15_000.0)
    cb = CompiledBlock(main.desc, 0, ["x", "y"], [loss.name],
                       dist=_dist())
    plan = cb.hbm_plan
    assert plan is not None
    assert plan["chosen"] == "zero", plan
    assert plan["fits"] is True, plan
    assert plan["must_shard"], plan
    assert [r["rung"] for r in plan["ladder"]] == ["as-configured",
                                                   "zero"]
    assert plan["ladder"][0]["fits"] is False
    # the promise is kept: every must-shard var really is sharded now
    for n in plan["must_shard"]:
        assert tuple(cb.param_sharding(n).spec), n
    m = cb.param_sharding("mlp_w1_moment1_0")
    assert m.spec == P("dp", None), m
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    for s in range(3):
        out = cb(scope, _mlp_feeds(s), s)[0]
    assert np.isfinite(np.asarray(out)).all()


def test_hbm_budget_big_enough_keeps_configured():
    from paddle_tpu.core.lowering import CompiledBlock
    main, startup, loss = _build_mlp(seed=3, opt="adam")
    flags.set("hbm_bytes", 1e12)
    cb = CompiledBlock(main.desc, 0, ["x", "y"], [loss.name],
                       dist=_dist())
    assert cb.hbm_plan["chosen"] == "as-configured"
    assert cb.hbm_plan["fits"] is True
    assert cb.hbm_plan["must_shard"] == []


def test_hbm_budget_no_fit_warns_and_keeps_cheapest():
    from paddle_tpu.core.lowering import CompiledBlock
    main, startup, loss = _build_mlp(seed=3, opt="adam")
    flags.set("hbm_bytes", 10.0)
    with pytest.warns(UserWarning, match="no sharding plan fits"):
        cb = CompiledBlock(main.desc, 0, ["x", "y"], [loss.name],
                           dist=_dist())
    assert cb.hbm_plan["fits"] is False
    assert cb.hbm_plan["chosen"] == "zero"    # cheapest rung available


# -- SPMD observability ---------------------------------------------------

def test_spmd_metrics_mesh_gauge_and_flat_resharding():
    """paddle_spmd_mesh_devices reports the mesh size; the resharding
    counter moves on the FIRST dispatch (host arrays take on the
    training layout) and stays flat afterwards — the metric-level
    witness that steady state moves no state bytes."""
    from paddle_tpu.core.lowering import CompiledBlock
    from paddle_tpu.observability import spmd as obs_spmd
    main, startup, loss = _build_mlp(seed=13)
    main.desc._obs_name = "spmd_metric_probe"
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    cb = CompiledBlock(main.desc, 0, ["x", "y"], [loss.name],
                       dist=_dist())
    assert obs_spmd.MESH_DEVICES.value == 8.0
    handle = obs_spmd.RESHARD_BYTES.labels(program=cb.obs_label)
    cb(scope, _mlp_feeds(0), 0)
    first = handle.value
    assert first > 0, "first dispatch must note the startup->training " \
                      "layout change"
    for s in range(1, 4):
        cb(scope, _mlp_feeds(s), s)
    assert handle.value == first, "steady state reshards"


# -- FLAGS_grad_allreduce_codec -------------------------------------------

def _shard_map_sum(x_local, codec):
    """Per-device addends reduced over 'dp' with the flagged codec."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import collective
    mesh = make_mesh()

    def f(xs):
        return collective.grad_all_reduce(xs[0], "dp", codec=codec)

    return shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                     check_vma=False)(x_local)


def test_grad_allreduce_codec_parity():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16, 32).astype(np.float32)
    exact = x.sum(axis=0)
    dense = np.asarray(_shard_map_sum(x, "none"))
    np.testing.assert_allclose(dense, exact, rtol=1e-6)
    for codec, tol in (("bf16", 0.02), ("int8", 0.04)):
        got = np.asarray(_shard_map_sum(x, codec))
        assert np.all(np.isfinite(got))
        rel = (np.linalg.norm(got - exact)
               / max(np.linalg.norm(exact), 1e-30))
        assert rel < tol, (codec, rel)


def test_grad_allreduce_codec_flag_default():
    """codec=None reads FLAGS_grad_allreduce_codec."""
    flags.set("grad_allreduce_codec", "int8")
    rng = np.random.RandomState(1)
    x = rng.randn(8, 4, 8).astype(np.float32)
    got = np.asarray(_shard_map_sum(x, None))
    exact = x.sum(axis=0)
    assert not np.allclose(got, exact, rtol=1e-7), \
        "int8 flag value was ignored (result is bit-exact)"
    rel = (np.linalg.norm(got - exact)
           / max(np.linalg.norm(exact), 1e-30))
    assert rel < 0.04, rel


def test_grad_allreduce_codec_unknown_raises():
    from paddle_tpu.parallel import collective
    with pytest.raises(ValueError, match="unknown grad allreduce codec"):
        collective.grad_all_reduce(jnp.zeros((2, 2)), "dp",
                                   codec="fp4")


def test_grad_allreduce_codec_training_window():
    """Parity window (the FLAGS_embed_exchange_codec contract applied
    to gradients): a dp=8 shard_map training loop whose gradient
    exchange rides the int8 codec must track the exact-codec loss
    curve within rtol 1e-2 and stay finite."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import collective
    mesh = make_mesh()
    rng = np.random.RandomState(3)
    w_true = rng.randn(16, 1).astype(np.float32)
    xs = rng.randn(64, 16).astype(np.float32)
    ys = (xs @ w_true).astype(np.float32)

    def window(codec, steps=20, lr=0.05):
        w = jnp.zeros((16, 1), jnp.float32)

        def local_grad(x_sh, y_sh, w_rep):
            def loss_fn(w):
                err = x_sh @ w - y_sh
                return jnp.mean(err * err)
            g = jax.grad(loss_fn)(w_rep)
            # SUM over dp, then 1/n for the mean — the caller-side
            # scaling grad_all_reduce documents
            return collective.grad_all_reduce(g, "dp", codec=codec) / 8.0

        step = shard_map(local_grad, mesh=mesh,
                         in_specs=(P("dp"), P("dp"), P()),
                         out_specs=P(), check_vma=False)
        losses = []
        for _ in range(steps):
            g = step(xs, ys, w)
            w = w - lr * g
            losses.append(float(jnp.mean((xs @ w - ys) ** 2)))
        return np.asarray(losses)

    ref = window("none")
    got = window("int8")
    assert np.all(np.isfinite(ref)), ref
    assert np.all(np.isfinite(got)), got
    assert ref[-1] < ref[0]            # it actually trains
    np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-4)
