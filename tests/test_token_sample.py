"""token_sample (ops/kv_attention.py) against the body it had while it
still sorted the vocabulary (PR 32): the sampled branch is under a
device-side conditional and the top-k threshold is a selection, and
neither may change one token. The reference below IS that older body,
sort and all — kept here as the oracle, not as a second path."""
import types
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import get_op
from paddle_tpu.ops.kv_attention import _kth_largest

V = 257          # not a multiple of anything the chip tiles by


def _reference(logits, temp, topk, seed, stepi):
    """The parent's body, verbatim but for the slot plumbing."""
    temp = jnp.asarray(temp).reshape(-1).astype(jnp.float32)
    topk = jnp.asarray(topk).reshape(-1).astype(jnp.int32)
    seed = jnp.asarray(seed).reshape(-1).astype(jnp.int32)
    stepi = jnp.asarray(stepi).reshape(-1).astype(jnp.int32)
    v = logits.shape[-1]
    lg = jnp.asarray(logits).reshape(-1, v).astype(jnp.float32)

    greedy = jnp.argmax(lg, axis=-1)

    scaled = lg / jnp.maximum(temp, 1e-6)[:, None]
    k = jnp.clip(topk, 1, v)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    keep = (scaled >= kth) | (topk <= 0)[:, None]
    masked = jnp.where(keep, scaled, -jnp.inf)

    j = jnp.arange(v, dtype=jnp.uint32)[None, :]
    x = (j * jnp.uint32(0x9E3779B9)
         ^ seed.astype(jnp.uint32)[:, None] * jnp.uint32(0x85EBCA6B))
    x = x ^ (stepi.astype(jnp.uint32)[:, None] * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    u = ((x >> jnp.uint32(8)).astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))
    noise = -jnp.log(-jnp.log(u))

    sampled = jnp.argmax(masked + noise, axis=-1)
    use_greedy = (temp <= 0.0) | (topk == 1)
    out = jnp.where(use_greedy, greedy, sampled).astype(jnp.int64)
    return out[:, None]


def _emit(logits, temp, topk, seed, stepi):
    ins = {"Logits": [logits], "Temperature": [temp], "TopK": [topk],
           "Seed": [seed], "StepIdx": [stepi]}
    return get_op("token_sample").emit(
        types.SimpleNamespace(mesh=None), ins, {})["Out"][0]


# jitted, as every caller runs it: the conditional stays a conditional
# and both branches are one executable
_op = jax.jit(_emit)
_ref = jax.jit(_reference)


def _logits(kind, b, rng):
    x = rng.standard_normal((b, V)).astype(np.float32) * 4.0
    if kind == "ties":
        # a handful of distinct values: every k-th value is tied
        x = np.round(x).astype(np.float32)
    elif kind == "zeros":
        x = np.round(x).astype(np.float32)
        x[x == 1.0] = -0.0
        x[x == -1.0] = 0.0
    elif kind == "inf":
        x[:, ::7] = -np.inf
        x[:, 3] = np.inf
        x[0, :] = -np.inf          # a row with nothing finite
    elif kind == "equal":
        x[:] = 1.5
    elif kind == "tiny":
        # denormals and the smallest normals beside ordinary logits
        x[:, ::5] = np.float32(1e-42)
        x[:, 1::5] = np.float32(-1e-42)
    elif kind == "nan":
        x[:, 5] = np.nan
        x[-1, :] = np.nan
    else:
        assert kind == "normal"
    return x


def _rows(batch, b, k, rng):
    """Temperature and top_k per row for a batch of ``batch`` kind."""
    temp = rng.uniform(0.3, 1.7, b).astype(np.float32)
    topk = np.full(b, k, np.int64)
    if batch == "greedy":
        temp[::2] = 0.0            # greedy by temperature ...
        topk[1::2] = 1             # ... or by top_k == 1
    elif batch == "mixed":
        temp[::3] = 0.0
        topk[1::3] = 1
    elif batch == "one_sampling":
        temp[:] = 0.0
        temp[b // 2] = 0.8
    else:
        assert batch == "sampling"
    return temp[:, None], topk[:, None]


@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "inf",
                                  "equal", "tiny", "nan"])
@pytest.mark.parametrize("k", [0, 1, 2, 40, V, V + 9])
@pytest.mark.parametrize("batch", ["greedy", "sampling", "mixed",
                                   "one_sampling"])
@pytest.mark.parametrize("b", [1, 48])
def test_out_is_the_sorting_bodys_bit_for_bit(kind, k, batch, b):
    rng = np.random.default_rng(
        zlib.crc32(repr((kind, k, batch, b)).encode()))
    logits = _logits(kind, b, rng)
    temp, topk = _rows(batch, b, k, rng)
    seed = rng.integers(0, 2**31 - 1, (b, 1))
    stepi = rng.integers(0, 4096, (b, 1))
    want = _ref(logits, temp, topk, seed, stepi)
    got = _op(logits, temp, topk, seed, stepi)
    assert got.dtype == want.dtype and got.shape == (b, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_verify_window_batch_is_the_sorting_bodys():
    # the verify view flattens [n_slots, K1] windows to n_slots*K1 rows:
    # temperature / top_k / seed tiled per slot, the step index running
    # along the window (engine._verify_feeds)
    s, k1 = 6, 5
    rng = np.random.default_rng(19)
    logits = _logits("ties", s * k1, rng)
    temp = np.repeat(np.array([0.0, 0.8, 1.3, 0.0, 0.5, 0.9],
                              np.float32), k1)[:, None]
    topk = np.repeat(np.array([0, 40, 1, 5, 0, 7]), k1)[:, None]
    seed = np.repeat(rng.integers(0, 2**31 - 1, s), k1)[:, None]
    stepi = (np.repeat(rng.integers(1, 99, s), k1)
             + np.tile(np.arange(k1), s))[:, None]
    want = _ref(logits, temp, topk, seed, stepi)
    got = _op(logits, temp, topk, seed, stepi)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and a row's token does not depend on the rows around it
    alone = _op(logits[7:8], temp[7:8], topk[7:8], seed[7:8], stepi[7:8])
    assert int(alone[0, 0]) == int(got[7, 0])


@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "inf",
                                  "equal", "tiny", "nan"])
def test_selected_kth_value_is_the_sorted_one(kind):
    rng = np.random.default_rng(7)
    b = 48
    x = _logits(kind, b, rng)
    ks = np.array([1, 2, 3, 40, V - 1, V] * 8, np.int32)
    want = np.take_along_axis(np.asarray(-jnp.sort(-jnp.asarray(x), axis=-1)),
                              (ks - 1)[:, None], axis=-1)[:, 0]
    got = np.asarray(jax.jit(_kth_largest)(x, ks))
    # equal as VALUES (-0.0 == 0.0: the sort's own order does not tell
    # them apart, and ``scaled >= kth`` does not either) ...
    np.testing.assert_array_equal(got, want)
    # ... and to the bit wherever the value is neither a zero nor a NaN
    # (a row with fewer than k numbers: nothing is >= NaN either way)
    nz = ~(want == 0) & ~np.isnan(want)
    np.testing.assert_array_equal(got[nz].view(np.uint32),
                                  want[nz].view(np.uint32))
    # so the kept set is the sorting body's
    np.testing.assert_array_equal(x >= got[:, None], x >= want[:, None])


def test_an_all_greedy_batch_skips_the_sampled_branch():
    # the lowered op holds ONE conditional whose predicate comes from
    # Temperature / TopK, no sort anywhere, and the selection's loop
    # only inside the conditional's sampled branch
    b = 4
    args = (jnp.zeros((b, V)), jnp.zeros((b, 1)), jnp.zeros((b, 1), int),
            jnp.zeros((b, 1), int), jnp.zeros((b, 1), int))
    jaxpr = jax.make_jaxpr(_emit)(*args)
    top = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert top.count("cond") == 1
    assert not {"sort", "scan", "while", "log"} & set(top)

    def names(jp):
        for e in jp.eqns:
            yield e.primitive.name
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from names(sub)
    everything = list(names(jaxpr.jaxpr))
    assert "sort" not in everything
    cond = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond")
    per_branch = [set(names(br.jaxpr)) for br in cond.params["branches"]]
    assert sum("scan" in n for n in per_branch) == 1   # the 32 passes
    assert sum("log" in n for n in per_branch) == 1
    assert any(not n - {"pjit"} for n in per_branch)   # greedy: empty
