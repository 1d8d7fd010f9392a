"""Sharded embedding tables (ISSUE 14): vocab-range partitioning over
the shard fleet + the trainer-side hot-rows device cache.

Covers the acceptance contract end to end:
- ShardSpec routing edge cases (ids exactly on a range split, padding
  rows at shard boundaries) and RowSparseGrad.deduped() edge cases
  (all-duplicate ids, K > unique rows).
- The wire codec arms (none/bf16/int8-per-row-scale) roundtrip within
  their advertised tolerances.
- The hot-rows cache's hit/miss/eviction/occupancy counters asserted
  against a KNOWN id schedule, and per-shard wire-bytes accounting.
- deepfm trained sharded across 2 shards matches the single-table
  baseline loss-for-loss (rtol=1e-4, fixed seed) with ZERO steady-state
  recompiles (the backend_compile_duration witness), both with a
  no-eviction cache and an eviction-forcing cache.
- The Pallas gather/scatter kernels in interpreter mode.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.distributed import sharded_table as st
from paddle_tpu.distributed.sharded_table import (ShardSpec,
                                                  ShardedTableClient,
                                                  TableShardServer)
from paddle_tpu.ops import embed_cache as ec
from _dist_utils import bound_listener, build_deepfm_small


# ---------------------------------------------------------------------------
# ShardSpec routing
# ---------------------------------------------------------------------------

def test_shardspec_balanced_bounds():
    # 10 rows / 3 shards: first 10 % 3 = 1 shard gets the extra row
    spec = ShardSpec(10, 3)
    assert spec.bounds == [(0, 4), (4, 7), (7, 10)]
    sizes = [hi - lo for lo, hi in spec.bounds]
    assert max(sizes) - min(sizes) <= 1
    # degenerate single shard: everything local
    one = ShardSpec(10, 1)
    assert one.bounds == [(0, 10)]
    assert list(one.owner_of([0, 9])) == [0, 0]


def test_shardspec_ids_exactly_on_a_split():
    spec = ShardSpec(10, 3)          # splits at 4 and 7
    # a row sitting exactly ON a split belongs to the shard whose range
    # STARTS there ([lo, hi) ranges)
    assert list(spec.owner_of([3, 4, 6, 7, 9])) == [0, 1, 1, 2, 2]
    routed = spec.route([4, 7, 0])
    assert set(routed) == {0, 1, 2}
    pos0, loc0 = routed[0]
    pos1, loc1 = routed[1]
    pos2, loc2 = routed[2]
    # local indices are range-relative: the boundary rows are row 0 of
    # their owning shard
    assert list(loc1) == [0] and list(loc2) == [0] and list(loc0) == [0]
    # positions reassemble input order
    back = np.empty(3, dtype=np.int64)
    for s, (pos, loc) in routed.items():
        back[pos] = loc + spec.bounds[s][0]
    assert list(back) == [4, 7, 0]


def test_shardspec_padding_rows_at_shard_boundaries():
    # a padding_idx row that happens to sit exactly at a shard boundary
    # must route like any other row — to the shard starting there — and
    # the sparse-grad path must still drop the out-of-range padding
    # bucket (rows == height) rather than ever routing it
    spec = ShardSpec(8, 2)           # split at 4
    padding_idx = 4                  # boundary row as padding
    assert int(spec.owner_of([padding_idx])[0]) == 1
    with pytest.raises(IndexError):
        spec.owner_of([8])           # the padding BUCKET is never routed
    with pytest.raises(IndexError):
        spec.owner_of([-1])


def test_shardspec_rejects_more_shards_than_rows():
    with pytest.raises(ValueError):
        ShardSpec(2, 3)


# ---------------------------------------------------------------------------
# RowSparseGrad.deduped() edge cases
# ---------------------------------------------------------------------------

def test_deduped_all_duplicate_ids():
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import RowSparseGrad
    g = RowSparseGrad(jnp.asarray([5, 5, 5, 5], jnp.int32),
                      jnp.ones((4, 3), jnp.float32), height=16)
    d = g.deduped()
    assert d.unique and d.nnz_rows == 4           # static K preserved
    rows = np.asarray(d.rows)
    vals = np.asarray(d.values)
    assert rows[0] == 5 and np.all(rows[1:] == 16)  # padding = height
    np.testing.assert_allclose(vals[0], 4.0 * np.ones(3))  # summed
    np.testing.assert_allclose(vals[1:], 0.0)
    # dense semantics preserved exactly
    np.testing.assert_allclose(np.asarray(d.densify()),
                               np.asarray(g.densify()))


def test_deduped_k_exceeds_unique_rows():
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import RowSparseGrad
    rows = jnp.asarray([2, 0, 2, 0, 1, 2], jnp.int32)
    vals = jnp.arange(18, dtype=jnp.float32).reshape(6, 3)
    g = RowSparseGrad(rows, vals, height=8)
    d = g.deduped()
    assert d.nnz_rows == 6
    r = np.asarray(d.rows)
    v = np.asarray(d.values)
    assert sorted(r[r < 8].tolist()) == [0, 1, 2]
    assert np.all(r[3:] == 8)                     # 3 padding slots
    dense = np.asarray(g.densify())
    for i in range(3):
        np.testing.assert_allclose(v[list(r).index(i)], dense[i])
    # a second dedup is a no-op (already unique)
    assert d.deduped() is d


# ---------------------------------------------------------------------------
# Wire codec arms
# ---------------------------------------------------------------------------

def test_codec_roundtrips():
    rng = np.random.RandomState(0)
    v = rng.randn(6, 5).astype(np.float32) * 3.0
    v[2] = 0.0                                     # all-zero row
    exact = st.decode_rows(st.encode_rows(v, "none"))
    np.testing.assert_array_equal(exact, v)
    bf = st.decode_rows(st.encode_rows(v, "bf16"))
    np.testing.assert_allclose(bf, v, rtol=1e-2, atol=1e-6)
    q = st.decode_rows(st.encode_rows(v, "int8"))
    # per-row scale: error bounded by half a quantization step of each
    # row's own max-abs
    step = np.abs(v).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(q - v) <= 0.5 * step + 1e-7)
    np.testing.assert_array_equal(q[2], 0.0)
    # int8 payload is ~4x smaller than fp32 (codes + one scale per row)
    assert st.payload_nbytes(st.encode_rows(v, "int8")) < \
        st.payload_nbytes(st.encode_rows(v, "none")) // 2
    with pytest.raises(ValueError):
        st.encode_rows(v, "fp4")


# ---------------------------------------------------------------------------
# Shard server + client plumbing
# ---------------------------------------------------------------------------

def _fleet(height, num_shards, codec="none"):
    spec = ShardSpec(height, num_shards)
    servers, eps = [], []
    for i in range(num_shards):
        lis, port = bound_listener()
        s = TableShardServer(i)
        s.serve(listener=lis)
        servers.append(s)
        eps.append(("127.0.0.1", port))
    client = ShardedTableClient(eps, spec, codec=codec)
    return spec, servers, client


def test_pull_zero_fills_unknown_families_and_push_overwrites():
    spec, servers, client = _fleet(10, 3)
    try:
        seed = np.arange(40, dtype=np.float32).reshape(10, 4)
        client.seed_from_value("emb", seed)
        got = client.pull_rows("emb", [9, 0, 4, 7],
                               families=[("param", 4), ("moment1", 4)])
        np.testing.assert_array_equal(got["param"], seed[[9, 0, 4, 7]])
        # moments were never pushed: lazily zero-filled at the asked width
        np.testing.assert_array_equal(got["moment1"], 0.0)
        # overwrite rows spanning all three shards in one logical push
        newv = -np.ones((3, 4), np.float32)
        applied = client.push_rows("emb", [0, 4, 7],
                                   {"param": newv, "moment1": newv * 2},
                                   push_id="p1")
        assert applied == 3                        # one per owning shard
        back = client.pull_rows("emb", [0, 4, 7],
                                families=[("param", 4), ("moment1", 4)])
        np.testing.assert_array_equal(back["param"], newv)
        np.testing.assert_array_equal(back["moment1"], newv * 2)
        # a replay of the same push_id is refused by every shard
        deduped0 = st.SHARD_PUSHES_DEDUPED.value
        assert client.push_rows("emb", [0, 4, 7], {"param": newv * 9},
                                push_id="p1") == 0
        assert st.SHARD_PUSHES_DEDUPED.value - deduped0 == 3
        np.testing.assert_array_equal(
            client.pull_rows("emb", [0], families=[("param", 4)])["param"],
            newv[:1])                              # replay did not apply
    finally:
        client.stop_servers()
        client.close()


def test_push_sparse_grad_ships_deduped_rows_only():
    import jax.numpy as jnp
    from paddle_tpu.core.selected_rows import RowSparseGrad
    spec, servers, client = _fleet(8, 2)
    try:
        client.create_table("emb")
        g = RowSparseGrad(jnp.asarray([1, 6, 1, 6], jnp.int32),
                          jnp.ones((4, 2), jnp.float32), height=8)
        pushed = client.push_sparse_grad("emb", g, push_id="g0")
        assert pushed == 2                         # rows 1 and 6: 2 owners
        got = client.pull_rows("emb", [1, 6], families=[("grad", 2)])
        np.testing.assert_allclose(got["grad"], 2.0)  # duplicates summed
        # the dedup padding bucket (rows == height) never hit the wire:
        # both shards saw exactly one applied push
        for s in (0, 1):
            assert client.stats(s)["applied"] >= 1
    finally:
        client.stop_servers()
        client.close()


def test_shard_bytes_metric_counts_both_directions():
    spec, servers, client = _fleet(8, 2)
    try:
        pull0 = [st.SHARD_BYTES.labels(direction="pull", shard=str(s)).value
                 for s in (0, 1)]
        push0 = [st.SHARD_BYTES.labels(direction="push", shard=str(s)).value
                 for s in (0, 1)]
        seed = np.ones((8, 4), np.float32)
        client.seed_from_value("emb", seed)        # 4 rows x 16B per shard
        client.pull_rows("emb", [0, 7], families=[("param", 4)])
        for s in (0, 1):
            assert st.SHARD_BYTES.labels(direction="push",
                                         shard=str(s)).value \
                - push0[s] == 4 * 4 * 4            # seed: 4 rows fp32
            assert st.SHARD_BYTES.labels(direction="pull",
                                         shard=str(s)).value \
                - pull0[s] == 4 * 4                # one row fp32 each
    finally:
        client.stop_servers()
        client.close()


# ---------------------------------------------------------------------------
# Hot-rows cache: counters against a KNOWN id schedule
# ---------------------------------------------------------------------------

def test_cache_counters_match_known_schedule():
    import jax.numpy as jnp
    spec, servers, client = _fleet(16, 2)
    try:
        seed = np.arange(64, dtype=np.float32).reshape(16, 4)
        client.seed_from_value("tbl", seed)
        scope = Scope()
        capacity = 4
        scope.set_var("tbl", jnp.zeros((capacity + 1, 4), jnp.float32))
        cache = ec.HotRowsCache("tbl", 16, capacity, client, scope,
                                families={"param": ("tbl", 4)},
                                padding_idx=7)
        h0 = ec.CACHE_HITS.labels(param="tbl").value
        m0 = ec.CACHE_MISSES.labels(param="tbl").value
        e0 = ec.CACHE_EVICTIONS.labels(param="tbl").value

        # schedule: [0,1,2] -> 3 misses; [0,1,3] -> 2 hits 1 miss (full);
        # [4] -> 1 miss, evicts the LRU-oldest (row 2); [7] is padding
        # and never counts
        s1 = cache.translate(np.asarray([0, 1, 2]), train=False)
        s2 = cache.translate(np.asarray([0, 1, 3, 7]), train=False)
        s3 = cache.translate(np.asarray([4]), train=False)
        assert ec.CACHE_MISSES.labels(param="tbl").value - m0 == 5
        assert ec.CACHE_HITS.labels(param="tbl").value - h0 == 2
        assert ec.CACHE_EVICTIONS.labels(param="tbl").value - e0 == 1
        assert ec.CACHE_OCCUPANCY.labels(param="tbl").value == 1.0
        assert cache.resident == capacity

        # translated slots index the right device rows
        assert s2[3] == cache.pad_slot            # padding -> pad slot
        got = cache._device_get_rows("param", np.asarray(s1[:2]))
        np.testing.assert_array_equal(got, seed[[0, 1]])
        # row 2 was evicted: its lut entry is free again
        assert cache._slot_lut[2] == -1 and cache._slot_lut[4] >= 0

        # a batch whose hits would be evicted by its own misses keeps
        # the hits pinned (the current-batch working set never thrashes)
        s4 = cache.translate(np.asarray([0, 1, 5, 6]), train=False)
        assert cache._slot_lut[0] >= 0 and cache._slot_lut[1] >= 0
        np.testing.assert_array_equal(
            cache._device_get_rows("param", np.asarray(s4)),
            seed[[0, 1, 5, 6]])

        # over-capacity batches fail loudly with the sizing hint
        with pytest.raises(ValueError, match="cache capacity"):
            cache.translate(np.asarray([0, 1, 2, 3, 4]), train=False)
    finally:
        client.stop_servers()
        client.close()


def test_cache_writeback_on_eviction_and_flush():
    import jax.numpy as jnp
    spec, servers, client = _fleet(16, 2)
    try:
        client.seed_from_value("tbl", np.zeros((16, 4), np.float32))
        scope = Scope()
        capacity = 2
        scope.set_var("tbl", jnp.zeros((capacity + 1, 4), jnp.float32))
        cache = ec.HotRowsCache("tbl", 16, capacity, client, scope,
                                families={"param": ("tbl", 4)})
        s = cache.translate(np.asarray([3]), train=True)   # dirty row 3
        # mutate the device row as a training step would
        cache._device_set_rows("param", np.asarray(s),
                               7.0 * np.ones((1, 4), np.float32))
        cache.translate(np.asarray([8, 9]), train=True)    # evicts row 3
        got = client.pull_rows("tbl", [3], families=[("param", 4)])
        np.testing.assert_array_equal(got["param"], 7.0)   # written back
        assert cache.flush() == 2                          # rows 8, 9
        assert cache.flush() == 0                          # now clean
    finally:
        client.stop_servers()
        client.close()


# ---------------------------------------------------------------------------
# Pallas kernels (interpreter mode on the CPU backend)
# ---------------------------------------------------------------------------

def test_pallas_gather_scatter_rows_interpret():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import embed_cache as pk
    rng = np.random.RandomState(1)
    cache = jnp.asarray(rng.randn(12, 8).astype(np.float32))
    ref = np.asarray(cache)
    slots = jnp.asarray([0, 11, 3, 3, 7], jnp.int32)
    out = pk.gather_rows(cache, slots, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  ref[[0, 11, 3, 3, 7]])
    rows = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    # slot 12 (== capacity) and slot -1 are out of range -> dropped,
    # not written (and not clamped onto an edge row)
    new = pk.scatter_rows(cache, jnp.asarray([2, 5, 12, -1], jnp.int32),
                          rows, interpret=True)
    got = np.asarray(new)
    np.testing.assert_array_equal(got[2], np.asarray(rows)[0])
    np.testing.assert_array_equal(got[5], np.asarray(rows)[1])
    untouched = [i for i in range(12) if i not in (2, 5)]
    np.testing.assert_array_equal(got[untouched], ref[untouched])


# ---------------------------------------------------------------------------
# Acceptance: deepfm sharded across 2 shards — loss parity with the
# single-table baseline under zero steady-state recompiles
# ---------------------------------------------------------------------------

def _deepfm_feeds(steps=14, batch=16, seed=7):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(steps):
        ids = rng.randint(0, 64, size=(batch, 4, 1)).astype("int64")
        lab = (ids[:, 0, 0] % 2).astype("float32")[:, None]
        out.append({"feat_ids": ids, "label": lab})
    return out


def _run_deepfm_baseline():
    main, startup, loss = build_deepfm_small()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return [float(exe.run(main, feed=f, fetch_list=[loss], scope=scope)[0])
            for f in _deepfm_feeds()]


def _run_deepfm_sharded(capacity, codec="none"):
    main, startup, loss = build_deepfm_small()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    seed_val = np.asarray(scope.find_var("deepfm_emb"))
    spec, servers, client = _fleet(64, 2, codec=codec)
    try:
        client.seed_from_value("deepfm_emb", seed_val)
        cache = ec.enable_sharded_table(main, scope, "deepfm_emb",
                                        client=client, capacity=capacity)
        losses, steady0 = [], None
        for i, f in enumerate(_deepfm_feeds()):
            if i == 2:                 # steps 0-1 warm the jit caches
                steady0 = ec.compile_count()
            (lv,) = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
            losses.append(float(lv))
        steady_compiles = ec.compile_count() - steady0
        cache.flush()
        # final param state on the fleet matches the cache's view
        pulled = client.pull_rows("deepfm_emb", np.arange(64),
                                  families=[("param", 9)])["param"]
        resident = np.asarray(sorted(cache._lru))
        dev = cache._device_get_rows("param",
                                     cache._slot_lut[resident])
        np.testing.assert_allclose(pulled[resident], dev, rtol=1e-6)
        return losses, steady_compiles
    finally:
        client.stop_servers()
        client.close()


def test_deepfm_sharded_parity_and_zero_steady_state_recompiles():
    base = _run_deepfm_baseline()
    # capacity 64 = whole vocab resident (no evictions)
    full, compiles_full = _run_deepfm_sharded(capacity=64)
    np.testing.assert_allclose(full, base, rtol=1e-4)
    assert compiles_full == 0, \
        f"{compiles_full} steady-state recompiles with full cache"
    # capacity 48 < per-step worst case working set of ~42..48 unique
    # rows: evictions + writebacks every step, still bitwise-stable
    small, compiles_small = _run_deepfm_sharded(capacity=48)
    np.testing.assert_allclose(small, base, rtol=1e-4)
    assert compiles_small == 0, \
        f"{compiles_small} steady-state recompiles under eviction"
