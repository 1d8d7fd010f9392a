"""End-to-end smoke tests for the IR → lowering → Executor slice."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_fill_constant_and_fetch():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.fill_constant(shape=[2, 3], dtype="float32", value=5.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (out,) = exe.run(main, fetch_list=[x])
    np.testing.assert_allclose(out, np.full((2, 3), 5.0), rtol=1e-6)


def test_feed_fetch_elementwise():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        a = layers.data(name="a", shape=[3], dtype="float32")
        b = layers.data(name="b", shape=[3], dtype="float32")
        c = layers.elementwise_add(a, b)
        d = layers.relu(c)
    exe = fluid.Executor(fluid.CPUPlace())
    av = np.array([[1.0, -2.0, 3.0]], dtype=np.float32)
    bv = np.array([[0.5, 1.0, -4.0]], dtype=np.float32)
    out_c, out_d = exe.run(main, feed={"a": av, "b": bv}, fetch_list=[c, d])
    np.testing.assert_allclose(out_c, av + bv, rtol=1e-6)
    np.testing.assert_allclose(out_d, np.maximum(av + bv, 0), rtol=1e-6)


def test_param_init_and_fc_forward():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.fc(input=x, size=2, act=None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.random.RandomState(0).rand(5, 4).astype(np.float32)
    (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    assert out.shape == (5, 2)


def test_batch_dim_is_dynamic():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.fc(input=x, size=3)
    assert x.shape == (-1, 4)
    assert y.shape == (-1, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    for bs in (2, 7):
        (out,) = exe.run(main, feed={"x": np.ones((bs, 4), np.float32)},
                         fetch_list=[y])
        assert out.shape == (bs, 3)


def test_program_serialization_roundtrip():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        y = layers.fc(input=x, size=2)
    from paddle_tpu.core.ir import ProgramDesc
    blob = main.desc.serialize_to_string()
    restored = ProgramDesc.parse_from_string(blob)
    assert restored.serialize_to_string() == blob


def test_scope_hierarchy():
    from paddle_tpu.core.scope import Scope
    s = Scope()
    s.set_var("a", 1)
    kid = s.new_scope()
    assert kid.find_var("a") == 1
    kid.set_var("b", 2)
    assert s.find_var("b") is None


def test_persistable_state_updates():
    """Optimizer-style in-place update: persistable var read+written."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[2], dtype="float32")
        w = main.global_block().create_var(
            name="w_state", shape=[1, 2], dtype="float32", persistable=True)
        sv = startup.global_block().create_var(
            name="w_state", shape=[1, 2], dtype="float32", persistable=True)
        from paddle_tpu.fluid.initializer import ConstantInitializer
        ConstantInitializer(1.0)(sv, startup.global_block())
        new_w = layers.elementwise_add(w, x)
        main.global_block().append_op(
            "assign", inputs={"X": [new_w]}, outputs={"Out": [w]})
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.ones((1, 2), np.float32)
    exe.run(main, feed={"x": xv}, fetch_list=[])
    exe.run(main, feed={"x": xv}, fetch_list=[])
    (wv,) = exe.run(main, feed={"x": xv}, fetch_list=["w_state"])
    np.testing.assert_allclose(wv, np.full((1, 2), 4.0), rtol=1e-6)


@pytest.mark.parametrize("shape,dtype,std", [
    ((24, 16), "bfloat16", 0.02),       # a stored dtype narrower than the draw
    ((1,), "float32", 1.0),             # a single element
    ((300, 256), "float32", 0.5)])      # past 2**16 elements
def test_the_jitted_draw_is_the_bare_functions_bit_for_bit(shape, dtype, std):
    """``hash_normal`` sits behind one ``jax.jit`` (shape, dtype and std
    static) so that its body is traced once a signature: the values are
    what the bare function gives."""
    from paddle_tpu.ops.basic import hash_normal
    for seed, salt in ((0, 1), (0x9E3779B9, 0xFFFFFFFF)):
        seed, salt = np.uint32(seed), np.uint32(salt)
        got = hash_normal(shape, dtype, std, seed, salt)
        want = hash_normal.__wrapped__(shape, dtype, std, seed, salt)
        assert got.shape == shape and got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        assert np.asarray(got, np.float32).std() > 0 or shape == (1,)


def test_a_second_parameter_of_a_seen_shape_traces_no_draw(monkeypatch):
    """Eight parameters of one (shape, dtype, std): the draw's Python
    body runs for the first one's shape inference and never again — not
    for the other seven, not when the start-up program is lowered — and
    each parameter is still its own draw (the op's key salts it)."""
    import jax
    from paddle_tpu.fluid.initializer import HashNormalInitializer
    from paddle_tpu.ops.basic import hash_normal
    bodies, real = [], jax.lax.iota

    def counted(*args, **kwargs):       # the body's first call
        bodies.append(args)
        return real(*args, **kwargs)
    monkeypatch.setattr(jax.lax, "iota", counted)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup):
        for i in range(8):
            # a signature no other test of the worker draws
            layers.create_parameter(
                [13, 21], "float32", name=f"w{i}",
                default_initializer=HashNormalInitializer(0.0371))
            assert len(bodies) == 1, i
    size = hash_normal._cache_size()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    assert len(bodies) == 1 and hash_normal._cache_size() == size
    drawn = [np.asarray(scope.find_var(f"w{i}")) for i in range(8)]
    assert all(w.shape == (13, 21) and 0.02 < w.std() < 0.06 for w in drawn)
    assert len({w.tobytes() for w in drawn}) == 8
