"""The gated short convolution's two ops (``ops/shortconv.py``:
``shortconv_prefill``, ``shortconv_decode``) against the plain full
forward over the whole sequence — no window, no bucket, no slots — in
float32 on the CPU. What separates the two is the order of three-term
sums: ``TOL``. A window taken from the bucket's padded end, a row of a
neighbouring slot or a missing shift moves a result by 0.1 or more."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import OPS, slot_state_vars
from paddle_tpu.ops import shortconv  # noqa: F401  (registers the ops)

TOL = 1e-5
M, TAPS, BUCKET, SLOTS = 16, 3, 16, 4


def weights(seed=0):
    rng = np.random.RandomState(seed)
    return {"WIn": rng.randn(M, 3 * M).astype(np.float32) * M ** -0.5,
            "ConvW": rng.randn(TAPS, M).astype(np.float32) * TAPS ** -0.5,
            "WOut": rng.randn(M, M).astype(np.float32) * M ** -0.5}


def plain(u, w):
    """u [L, M] -> (out [L, M], z = B * x [L, M]): the equations of the
    module's docstring over the whole sequence at once."""
    bcx = u @ w["WIn"]
    b, c, x = bcx[:, :M], bcx[:, M:2 * M], bcx[:, 2 * M:]
    z = b * x
    padded = np.concatenate([np.zeros((TAPS - 1, M), np.float32), z])
    conv = sum(w["ConvW"][j] * padded[j:j + len(u)] for j in range(TAPS))
    return (c * conv) @ w["WOut"], z


def last_rows(z):
    """The window a slot holds after ``z``: its last TAPS - 1 rows,
    zeros where the sequence is shorter."""
    padded = np.concatenate([np.zeros((TAPS - 1, M), np.float32), z])
    return padded[len(z):]


def emit(op, **ins):
    out = OPS[op].emit(None, {k: [jnp.asarray(v)] for k, v in ins.items()},
                       {})
    return {k: np.asarray(v[0]) for k, v in out.items()}


def prefill(u, w, conv, slot):
    """The prefill op over ``u`` [n, M] padded to the bucket with rows
    that are NOT zero (a padded position's token has an embedding)."""
    n = len(u)
    pad = np.random.RandomState(99).randn(BUCKET - n, M).astype(np.float32)
    x = np.concatenate([u, pad])[None]
    return emit("shortconv_prefill", X=x, Conv=conv, **w,
                SeqLen=np.asarray([[n]], np.int64),
                Slot=np.asarray([[slot]], np.int64))


@pytest.mark.parametrize("n", [1, 2, 3, 7, 11, BUCKET])
def test_prefill_then_decode_matches_the_full_forward(n):
    """A prompt of ``n`` true tokens under a bucket of 16 (1 and 2:
    shorter than the conv's taps), then five decode steps: every output
    row and the window after every call are the full forward's."""
    w = weights()
    rng = np.random.RandomState(n)
    seq = rng.randn(n + 5, M).astype(np.float32)
    want, z = plain(seq, w)
    conv = rng.randn(SLOTS, TAPS - 1, M).astype(np.float32)  # a last tenant's
    got = prefill(seq[:n], w, conv, slot=2)
    np.testing.assert_allclose(got["Out"][0, :n], want[:n], atol=TOL)
    np.testing.assert_allclose(got["ConvOut"][2], last_rows(z[:n]),
                               atol=TOL)
    others = [0, 1, 3]
    np.testing.assert_array_equal(got["ConvOut"][others], conv[others])
    conv = got["ConvOut"]
    active = np.asarray([[0], [0], [1], [0]], np.int64)
    for t in range(n, n + 5):
        x = rng.randn(SLOTS, 1, M).astype(np.float32)
        x[2, 0] = seq[t]
        got = emit("shortconv_decode", X=x, Conv=conv, **w, Active=active)
        np.testing.assert_allclose(got["Out"][2, 0], want[t], atol=TOL)
        np.testing.assert_allclose(got["ConvOut"][2],
                                   last_rows(z[:t + 1]), atol=TOL)
        # inactive slots ride along: their windows bit for bit
        np.testing.assert_array_equal(got["ConvOut"][others], conv[others])
        conv = got["ConvOut"]


def test_a_slot_reused_by_a_shorter_prompt_keeps_nothing_of_the_last():
    w = weights(1)
    rng = np.random.RandomState(4)
    conv = np.zeros((SLOTS, TAPS - 1, M), np.float32)
    conv = prefill(rng.randn(9, M).astype(np.float32), w, conv, 1)["ConvOut"]
    assert np.abs(conv[1]).min() > 0
    short = rng.randn(1, M).astype(np.float32)
    conv = prefill(short, w, conv, 1)["ConvOut"]
    _out, z = plain(short, w)
    assert not conv[1, 0].any()                  # before the prompt: zeros
    np.testing.assert_allclose(conv[1, 1], z[0], atol=TOL)


def test_a_slot_past_the_pool_writes_nothing():
    """The warm-up's dispatch names slot ``n_slots``: every window stays."""
    w = weights(2)
    conv = np.random.RandomState(5).randn(SLOTS, TAPS - 1, M).astype(
        np.float32)
    got = prefill(np.ones((6, M), np.float32), w, conv, SLOTS)
    np.testing.assert_array_equal(got["ConvOut"], conv)


def test_slots_step_together_each_on_its_own_window():
    """Four sequences of different lengths decoding in one batch."""
    w = weights(3)
    rng = np.random.RandomState(6)
    seqs = [rng.randn(n, M).astype(np.float32) for n in (4, 9, 1, 6)]
    conv = np.zeros((SLOTS, TAPS - 1, M), np.float32)
    for s, seq in enumerate(seqs):
        conv = prefill(seq[:-1], w, conv, s)["ConvOut"] if len(seq) > 1 \
            else conv
    x = np.stack([seq[-1] for seq in seqs])[:, None]
    got = emit("shortconv_decode", X=x, Conv=conv, **w,
               Active=np.ones((SLOTS, 1), np.int64))
    for s, seq in enumerate(seqs):
        want, z = plain(seq, w)
        np.testing.assert_allclose(got["Out"][s, 0], want[-1], atol=TOL)
        np.testing.assert_allclose(got["ConvOut"][s], last_rows(z),
                                   atol=TOL)


def test_the_ops_declare_their_window_as_per_slot_state():
    for op in ("shortconv_prefill", "shortconv_decode"):
        assert OPS[op].slot_state == ("shortconv", ("ConvOut",))
        assert OPS[op].no_grad


def test_the_window_is_found_by_role_in_a_program():
    """A decode view with conv layers: ``slot_state_vars`` names their
    windows under the kind ``shortconv`` whatever they are called."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [SLOTS, 1, M], append_batch_size=False)
        active = layers.data("active", [SLOTS, 1], dtype="int64",
                             append_batch_size=False)
        conv = main.global_block().create_var(
            name="anything_at_all", shape=[SLOTS, TAPS - 1, M],
            dtype="float32", persistable=True)
        layers.shortconv(x, conv, M, TAPS, "l0", None, active=active)
    assert slot_state_vars(main.desc.global_block) == {
        "shortconv": {"ConvOut": ["anything_at_all"]}}
