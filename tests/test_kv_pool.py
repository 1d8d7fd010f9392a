"""Paged KV-cache subsystem tests (ISSUE 17, docs/serving.md "Paged KV
cache"): the PagePool allocator + prefix radix tree, the page-pool
metric gauges asserted against a known admission schedule, the Pallas
page-gather kernels in interpret mode, and the SlotGenerativeModel
engine — greedy bit-parity with the sequential full-forward oracle,
prefix sharing witnessed by refcounts with bit-identical COW divergence,
zero steady-state recompiles, the int8 KV codec's sampling-replay
determinism, and the pages-before-slots admission discipline through
the server scheduler."""

import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving import engine as seng
from paddle_tpu.serving import kv_pool
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.models import transformer as T
from paddle_tpu.observability import memory as obs_memory


_LM_CFG = dict(prompt_len=8, max_new=8, vocab=32, d_model=16,
               d_inner=32, n_head=2, n_layer=2)

_CACHE = {}


def _paged_lm(codec="none"):
    """One warmed SlotGenerativeModel per codec, shared by the
    engine tests (same config/seed discipline as test_serving's
    ``_shared_slot_lm`` — warmup costs several jit compiles on CPU)."""
    key = "paged_" + codec
    m = _CACHE.get(key)
    if m is None:
        m = seng.make_slot_model(
            "lm_" + key,
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=("prefill_paged", "decode_paged"), n_slots=4,
                page_size=4, kv_codec=codec))
        m.warmup()
        _CACHE[key] = m
    m.reset()
    return m


def _tiny_paged():
    """A page-starved engine (4 pages = ONE bucket-8 admission) shared
    by the exhaustion-message and server put-back tests: pages run out
    while slots stay free — the shed only a page economy can hit."""
    m = _CACHE.get("tiny")
    if m is None:
        m = seng.make_slot_model(
            "lm_paged_tiny",
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=("prefill_paged", "decode_paged"), n_slots=4,
                page_size=4, n_pages=4))
        m.warmup()
        _CACHE["tiny"] = m
    m.reset()
    return m


def _oracle_lm():
    gm = _CACHE.get("oracle")
    if gm is None:
        gm = serving.GenerativeModel(
            "lm_paged_oracle", T.build_decoder_lm_programs(**_LM_CFG),
            serving.BucketPolicy((2, 4)))
        _CACHE["oracle"] = gm
    return gm


# ---------------------------------------------------------------------------
# PagePool: allocator + prefix radix tree
# ---------------------------------------------------------------------------

def test_pool_geometry_and_span():
    p = kv_pool.PagePool(8, 4)
    assert p.span_for(1) == 1 and p.span_for(4) == 1
    assert p.span_for(5) == 2 and p.span_for(16) == 4
    with pytest.raises(ValueError):
        kv_pool.PagePool(0, 4)
    with pytest.raises(ValueError):
        kv_pool.PagePool(4, 0)


def test_pool_acquire_release_accounting():
    p = kv_pool.PagePool(8, 4)
    pages, n_shared = p.acquire(0, [1, 2, 3, 4, 5], 3)
    assert len(pages) == 3 and len(set(pages)) == 3
    assert n_shared == 0
    assert p.free_count() == 5
    with pytest.raises(ValueError):          # double lease
        p.acquire(0, [7], 1)
    p.release(0)
    # the full prompt page [1,2,3,4] stays RESIDENT as prefix cache;
    # the partial-prompt + generation tail goes back to the free list
    assert p.free_count() == 7
    assert p.cached_count() == 1
    assert p.available_count() == 8


def test_pool_prefix_sharing_refcounts():
    p = kv_pool.PagePool(16, 4)
    a, sa = p.acquire(0, [5, 6, 7, 8, 1, 2], 3)
    b, sb = p.acquire(1, [5, 6, 7, 8, 9], 3)
    assert sa == 0 and sb == 1
    assert b[0] == a[0]                      # physical sharing
    assert set(b[1:]).isdisjoint(a)          # COW: divergent pages private
    assert p.page_refs(a[0]) == 2
    assert p.shared_count() == 1
    # releasing ONE sharer must not free pages the other references
    free0 = p.free_count()
    p.release(0)
    assert p.page_refs(a[0]) == 1            # still referenced by slot 1
    assert p.free_count() == free0 + 2       # only slot 0's private tail
    p.release(1)
    assert p.page_refs(a[0]) == 0            # cached, still resident
    assert p.cached_count() == 1


def test_pool_prefix_cache_hit_and_failed_admission_is_noop():
    p = kv_pool.PagePool(4, 4)
    p.acquire(0, [1, 2, 3, 4, 5], 2)
    p.release(0)                             # [1,2,3,4] cached
    assert p.free_count() == 3 and p.cached_count() == 1
    # cache hit: the resident page is re-shared without allocation
    pages, n_shared = p.acquire(1, [1, 2, 3, 4, 9], 2)
    assert n_shared == 1 and p.cached_count() == 0
    # over-ask fails cleanly: no refcount moves, no pages taken
    before = (p.free_count(), p.page_refs(pages[0]))
    with pytest.raises(kv_pool.PagesExhaustedError):
        p.acquire(2, [8, 8, 8, 8], 99)
    assert (p.free_count(), p.page_refs(pages[0])) == before


def test_pool_lru_capacity_eviction():
    p = kv_pool.PagePool(4, 4, model="kvp_evict")
    ev0 = smetrics.KV_PAGE_EVICTIONS.labels(
        model="kvp_evict", cause="capacity").value
    p.acquire(0, [1, 2, 3, 4], 1)
    p.release(0)                             # cached page A (older)
    p.acquire(1, [9, 9, 9, 9], 1)
    p.release(1)                             # cached page B (newer)
    assert p.free_count() == 2 and p.cached_count() == 2
    # a 3-page admission must reclaim the LRU cached page (A): the
    # newer prefix [9,9,9,9] survives and is still shareable
    p.acquire(2, [7, 7, 7, 7, 7, 7, 7, 7, 7], 3)
    assert smetrics.KV_PAGE_EVICTIONS.labels(
        model="kvp_evict", cause="capacity").value == ev0 + 1
    _, n_shared = p.acquire(3, [9, 9, 9, 9], 1)
    assert n_shared == 1                     # B survived the eviction


def test_pool_eviction_never_reclaims_admissions_own_prefix():
    """Regression (REVIEW r05): under pressure, _take_pages could LRU-
    evict a refcount-0 node IN the admission's own shared chain and
    hand its page back as a private page of the same lease — one
    physical page backing both the shared prefix and a prefill-written
    page (pages=[0,0,2]). The chain must be pinned before allocation."""
    p = kv_pool.PagePool(3, 4)
    p.acquire(0, [1, 2, 3, 4], 1)
    p.release(0)                             # prefix A cached, LRU-oldest
    p.acquire(1, [9, 9, 9, 9], 1)
    p.release(1)                             # prefix B cached, newer
    pages, n_shared = p.acquire(2, [1, 2, 3, 4, 5, 6, 7, 8, 9], 3)
    assert n_shared == 1
    assert len(set(pages)) == 3              # no page backs two positions
    assert p.page_refs(pages[0]) == 1        # A pinned, still shared
    p.release(2)
    # B (the true LRU candidate once A is pinned) was the one evicted
    _, ns = p.acquire(3, [9, 9, 9, 9], 1)
    assert ns == 0


def test_pool_failed_admission_unpins_shared_chain():
    """An over-ask that shares a cached prefix must roll the pin back:
    no refcount moves, the prefix stays an evictable cache entry."""
    p = kv_pool.PagePool(4, 4)
    p.acquire(0, [1, 2, 3, 4, 5], 2)
    p.release(0)                             # [1,2,3,4] cached on page 0
    assert p.page_refs(0) == 0 and p.cached_count() == 1
    with pytest.raises(kv_pool.PagesExhaustedError):
        p.acquire(1, [1, 2, 3, 4, 9], 99)
    assert p.page_refs(0) == 0               # unpinned
    assert p.cached_count() == 1 and p.free_count() == 3
    _, ns = p.acquire(1, [1, 2, 3, 4, 9], 2)
    assert ns == 1                           # still shareable afterwards


def test_pool_abort_discards_unwritten_inserted_pages():
    """abort() (failed prefill dispatch) must NOT leave the lease's own
    inserted nodes resident as prefix cache — their pages were never
    written — while pre-existing shared nodes survive as cache."""
    p = kv_pool.PagePool(8, 4)
    p.acquire(0, [1, 2, 3, 4, 5], 2)
    p.release(0)                             # [1,2,3,4] cached (written)
    pages, ns = p.acquire(1, [1, 2, 3, 4, 5, 6, 7, 8, 9], 3)
    assert ns == 1
    p.abort(1)
    assert p.free_count() == 7               # inserted + tail pages freed
    assert p.cached_count() == 1             # only the written prefix
    _, ns2 = p.acquire(2, [1, 2, 3, 4, 5, 6, 7, 8, 9], 3)
    assert ns2 == 1                          # unwritten page NOT re-shared


def test_pool_metrics_track_known_admission_schedule():
    """Satellite: the paged gauges asserted step-by-step against a
    known admission schedule."""
    name = "kvp_sched"

    def gauges():
        return (smetrics.KV_PAGES_TOTAL.labels(model=name).value,
                smetrics.KV_PAGES_FREE.labels(model=name).value,
                smetrics.KV_PREFIX_SHARED_PAGES.labels(model=name).value)

    p = kv_pool.PagePool(8, 4, model=name)
    assert gauges() == (8, 8, 0)
    p.acquire(0, [1, 2, 3, 4, 5], 3)         # 3 pages, nothing shared
    assert gauges() == (8, 5, 0)
    p.acquire(1, [1, 2, 3, 4, 9], 3)         # shares [1,2,3,4] -> 2 new
    assert gauges() == (8, 3, 1)
    p.release(0)                             # tail back; shared page held
    assert gauges() == (8, 5, 0)
    rst0 = smetrics.KV_PAGE_EVICTIONS.labels(
        model=name, cause="reset").value
    p.reset()                                # evicts the whole tree
    assert gauges() == (8, 8, 0)
    # the tree held ONE node ([1,2,3,4]); tail pages are not tree pages
    assert smetrics.KV_PAGE_EVICTIONS.labels(
        model=name, cause="reset").value == rst0 + 1


def test_kv_gauges_preregistered_in_exporter_catalog():
    # importing serving.metrics (done above) must be enough for the
    # scrape endpoint to list the paged families — no traffic required
    from paddle_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.default_registry().snapshot()
    for fam in ("paddle_kv_pages_total", "paddle_kv_pages_free",
                "paddle_kv_prefix_shared_pages",
                "paddle_kv_page_evictions_total",
                "paddle_kv_group_pages_total", "paddle_kv_group_pages_free",
                "paddle_kv_window_pages_released_total",
                "paddle_kv_window_rows_attended_total",
                "paddle_kv_full_rows_attended_total",
                "paddle_kv_full_rows_gathered_total",
                "paddle_kv_row_bytes"):
        assert fam in snap, fam


# ---------------------------------------------------------------------------
# the window group (PR 37): a ring of pages a slot, beside the full group
# ---------------------------------------------------------------------------

def _window_pool(window_pages=9, model=""):
    # pages of 4 rows, a window of 8 positions: a ring of 3 pages
    return kv_pool.PagePool(16, 4, model=model, window_pages=window_pages,
                            window=8)


def _window_invariants(p):
    """No page of the window group both free and leased, none leased
    twice, none lost."""
    leased = [pg for lease in p._wslots.values()
              for pg in lease.ring if pg >= 0]
    assert len(leased) == len(set(leased))
    assert not set(leased) & set(p._wfree)
    assert len(leased) + len(p._wfree) == p.window_pages
    for lease in p._wslots.values():
        assert lease.held() == sum(pg >= 0 for pg in lease.ring) \
            <= p.window_ring


def test_window_ring_geometry():
    assert kv_pool.window_ring(2048, 16) == 129
    assert kv_pool.window_ring(8, 4) == 3
    assert kv_pool.window_ring(9, 4) == 4
    assert _window_pool().window_ring == 3
    with pytest.raises(ValueError, match="ring"):
        _window_pool(window_pages=2)
    with pytest.raises(ValueError, match="total_len"):
        _window_pool().acquire(0, [1, 2, 3], 2)


@pytest.mark.parametrize("prompt_len,total,lo,held", [
    (3, 5, 0, 2),        # two pages cover all it will ever write
    (3, 40, 0, 3),       # a ring's worth, however long it runs
    (8, 9, 0, 3),        # the prompt fills the window
    (21, 23, 3, 3),      # a long prompt: pages 0-2 are never taken
    (21, 22, 3, 3)])
def test_window_admission_takes_the_prompts_last_pages(prompt_len, total,
                                                       lo, held):
    p = _window_pool()
    p.acquire(0, list(range(prompt_len)), -(-total // 4), total_len=total)
    lease = p.window_lease(0)
    assert (lease.lo, lease.held()) == (lo, held)
    # the ring's entry of a logical page is page % ring
    for lp in range(lease.lo, lease.hi):
        assert lease.ring[lp % 3] >= 0
    _window_invariants(p)
    p.release(0)
    assert p.window_free_count() == 9 and p.window_lease(0) is None


def test_window_release_behind_the_window_is_idempotent():
    """A decoding slot returns the pages behind its window when it
    enters a page its ring has no room for, and takes one; a second
    call for the same position changes nothing; a position inside a
    held page changes nothing."""
    name = "kvp_window"
    p = _window_pool(model=name)
    counter = smetrics.KV_WINDOW_PAGES_RELEASED.labels(model=name)
    p.acquire(0, [1, 2, 3], 10, total_len=40)
    assert p.window_lease(0).ring.count(-1) == 0
    free = p.window_free_count()
    for position in range(3, 12):             # pages 0-2: all held
        assert not p.window_advance(0, position)
    assert (counter.value, p.window_free_count()) == (0, free)
    # position 12 opens page 3; the window [5, 12] leaves page 0 behind
    assert p.window_advance(0, 12)
    assert (counter.value, p.window_free_count()) == (1, free)
    lease = p.window_lease(0)
    assert (lease.lo, lease.hi) == (1, 4)
    ring = list(lease.ring)
    assert not p.window_advance(0, 12)        # again: nothing to do
    assert not p.window_advance(0, 13)
    assert (counter.value, list(lease.ring)) == (1, ring)
    _window_invariants(p)
    for position in range(14, 40):
        p.window_advance(0, position)
        _window_invariants(p)
        assert p.window_lease(0).held() == 3
    assert counter.value == 7                 # pages 0-6, one each
    assert not p.window_advance(5, 12)        # a slot with no lease


def test_window_admission_is_refused_when_either_group_lacks_pages():
    p = _window_pool(window_pages=4)
    p.acquire(0, [1, 2, 3], 4, total_len=40)       # 3 of 4 window pages
    before = p.stats()
    with pytest.raises(kv_pool.PagesExhaustedError, match="window"):
        p.acquire(1, [4, 5, 6], 2, total_len=8)    # needs 2, 1 free
    assert p.stats() == before and p.lease(1) is None
    with pytest.raises(kv_pool.PagesExhaustedError, match="private"):
        p.acquire(1, [4, 5, 6], 14, total_len=4)   # 12 of 16 full pages
    assert p.stats() == before and p.window_lease(1) is None
    p.acquire(1, [4, 5, 6], 2, total_len=4)        # one window page: fits
    _window_invariants(p)


@pytest.mark.parametrize("end", ["release", "abort", "reset"])
def test_window_cancel_and_finish_return_both_groups(end):
    name = "kvp_window_" + end
    p = _window_pool(model=name)
    p.acquire(0, [1, 2, 3, 4, 5], 4, total_len=14)
    p.acquire(1, [1, 2, 3, 4, 9], 3, total_len=12)
    p.window_advance(0, 12)
    gauges = lambda group: (                               # noqa: E731
        smetrics.KV_GROUP_PAGES_TOTAL.labels(model=name, group=group).value,
        smetrics.KV_GROUP_PAGES_FREE.labels(model=name, group=group).value)
    assert gauges("window") == (9, 3) and gauges("full")[0] == 16
    # the unlabelled pair counts both groups
    assert smetrics.KV_PAGES_TOTAL.labels(model=name).value == 25
    assert smetrics.KV_PAGES_FREE.labels(model=name).value \
        == p.free_count() + 3
    if end == "reset":
        p.reset()
    else:
        getattr(p, end)(0)
        getattr(p, end)(1)
    assert gauges("window") == (9, 9)
    assert p.available_count() == 16 and not p._wslots
    _window_invariants(p)


def test_window_group_leaves_the_full_groups_sharing_as_it_was():
    """The radix tree shares the full group's prompt pages exactly as a
    pool without a window group does; the window group's are private."""
    plain, both = kv_pool.PagePool(16, 4), _window_pool()
    for slot, tokens in enumerate(([1, 2, 3, 4, 5, 6, 7, 8, 9],
                                   [1, 2, 3, 4, 5, 6, 7, 8, 7],
                                   [1, 2, 3, 4, 0])):
        want = plain.acquire(slot, tokens, 4)
        assert both.acquire(slot, tokens, 4, total_len=16) == want
    assert both.shared_count() == plain.shared_count() == 2
    rings = [pg for s in range(3) for pg in both.window_lease(s).ring
             if pg >= 0]
    assert len(rings) == len(set(rings)) == 9
    _window_invariants(both)


# ---------------------------------------------------------------------------
# Pallas page-gather kernels (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_paged_gather_kernel_interpret(dtype):
    """Every storage dtype of FLAGS_kv_cache_codec: 24 rows is a whole
    number of fp32 tiles (8) but a ragged last tile for bf16 (16) and
    int8 (32) — the padded-group path."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(24, 16) * 50).astype(dtype)
    if dtype != "int8":
        pool = pool.at[5, 3].set(-0.0)       # the select is bit-exact
    rows = rng.randint(0, 30, size=13)       # includes sentinel overflow
    rows[0] = 5
    got = pa.gather_rows(pool, jnp.asarray(rows), interpret=True)
    assert got.dtype == pool.dtype
    want = np.asarray(pool.astype(jnp.float32))[np.minimum(rows, 23)]
    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(want))


def test_paged_gather_dequant_kernel_interpret():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(1)
    codes = rng.randint(-127, 128, size=(24, 16)).astype(np.int8)
    scales = np.abs(rng.randn(24, 4)).astype(np.float32)
    rows = rng.randint(0, 30, size=11)
    got = np.asarray(pa.gather_rows_dequant(
        jnp.asarray(codes), jnp.asarray(scales), jnp.asarray(rows),
        heads=4, interpret=True))
    c = np.minimum(rows, 23)
    want = (codes[c].astype(np.float32).reshape(-1, 4, 4)
            * scales[c][:, :, None]).reshape(-1, 16)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def _bits(x):
    """An array's raw bits (uint of its width): the comparison that
    tells -0.0 from 0.0 and one NaN payload from another."""
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.itemsize])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_gather_pages_kernel_interpret(dtype):
    """gather_pages == pool.reshape(n_pages, ps, D)[clip(pages)] BIT
    for bit: a -0.0 and a NaN with a payload travel unchanged (no
    select, no arithmetic), sentinel ids >= n_pages read the last
    page, and 45 table entries are neither a multiple of the copies in
    flight (32) nor fewer than them — the rolling window wraps."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    n_pages, ps, d = 10, 16, 128
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(n_pages * ps, d) * 50).astype(dtype)
    pool = pool.at[5 * ps + 3, 7].set(-0.0)
    uint = jnp.uint32 if dtype == "float32" else jnp.uint16
    nan = jax.lax.bitcast_convert_type(
        jnp.asarray(0x7FC12345 if dtype == "float32" else 0x7FC5, uint),
        pool.dtype)
    pool = pool.at[9 * ps + 15, 0].set(nan)
    pages = rng.randint(0, n_pages + 3, size=45)    # sentinels >= n_pages
    pages[:3] = (5, 9, n_pages)
    assert pages.shape[0] % pa._WINDOW and pages.shape[0] > pa._WINDOW
    got = pa.gather_pages(pool, jnp.asarray(pages), ps, interpret=True)
    assert got.dtype == pool.dtype and got.shape == (45 * ps, d)
    want = _bits(pool).reshape(n_pages, ps, d)[
        np.clip(pages, 0, n_pages - 1)].reshape(-1, d)
    np.testing.assert_array_equal(_bits(got), want)
    # fewer entries than copies in flight: the window is cut to them
    got = pa.gather_pages(pool, jnp.asarray(pages[:5]), ps, interpret=True)
    np.testing.assert_array_equal(_bits(got), want[:5 * ps])


def _paged_op_inputs(codec, op="decode", seed=0):
    """Random inputs of kv_attention_prefill_paged / _decode_paged /
    _verify_paged at a tiny geometry whose pages are whole tiles of
    every float storage dtype (16 rows), pools in the shape they are
    declared in, [n_pages, ps, H * Dk]: 3 slots (one inactive), 6
    pages, tables with sentinel entries past each slot's span; the
    prefill writes one 16-token prompt with a sentinel (shared-prefix)
    stretch in the middle."""
    import jax.numpy as jnp
    from paddle_tpu.ops import kv_attention as kva
    rng = np.random.RandomState(seed)
    b, h, dk, n_pages, ps, mp = 3, 2, 8, 6, 16, 3
    m = h * dk
    b, t = {"prefill": (1, 16), "decode": (b, 1), "verify": (b, 3)}[op]
    store = {"none": jnp.float32, "bf16": jnp.bfloat16}.get(codec)
    ins = {"X": [jnp.asarray(rng.randn(b, t, m), jnp.float32)]}
    for w in ("Wq", "Wk", "Wv", "Wo"):
        ins[w] = [jnp.asarray(rng.randn(m, m) * 0.3, jnp.float32)]
    for name in ("PageK", "PageV"):
        rows = jnp.asarray(rng.randn(n_pages, ps, h, dk), jnp.float32)
        if codec == "int8":
            codes, scale = kva._kv_quant(rows)
            ins[name], ins[name + "S"] = [codes.reshape(n_pages, ps, m)], \
                [scale]
        else:
            ins[name] = [rows.reshape(n_pages, ps, m).astype(store)]
    attrs = {"n_head": h, "codec": codec}
    if op == "prefill":
        rows = 4 * ps + np.arange(t)
        rows[5:9] = n_pages * ps            # skipped: dropped writes
        ins["Rows"] = [jnp.asarray(rows, jnp.int32).reshape(-1, 1)]
        return ins, attrs
    ins["PageTable"] = [jnp.asarray(
        [[4, 1, n_pages], [0, 5, 2], [n_pages] * mp], jnp.int32)]
    col = lambda *v: [jnp.asarray(v, jnp.int32).reshape(-1, 1)]
    ins.update(Pos=col(20, 33, 0), SeqLen=col(13, 16, 0),
               GenStart=col(16, 16, 0), Active=col(1, 1, 0))
    if op == "verify":
        ins["WinLen"] = col(3, 2, 1)
    return ins, attrs


@pytest.mark.parametrize("codec", ["none", "bf16", "int8"])
@pytest.mark.parametrize("op", ["prefill", "decode", "verify"])
def test_paged_ops_identical_through_every_gather_tier(op, codec,
                                                       monkeypatch):
    """The page kernel, the row kernel and jnp.take move the same bits
    wherever the mask looks, so the decode and verify ops give
    identical outputs AND pools through each (the tier is forced HERE,
    by patching the function that chooses it; the product has no
    switch). They differ only in what a SENTINEL entry reads — the last
    page against the last row, 16 times — which no active slot attends.
    int8 pages never take the page kernel (no dequant there). The
    prefill gathers nothing — whichever tier is named, it lowers no
    gather and writes the same pools, in the declared 3-D shape."""
    import types
    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import kv_attention as kva
    ins, attrs = _paged_op_inputs(codec, op)
    op = f"kv_attention_{op}_paged"
    gathers = 0 if op == "kv_attention_prefill_paged" else 2
    ctx = types.SimpleNamespace(mesh=None)
    results = {}
    for tier in ("take", "rows") + (("pages",) if codec != "int8" else ()):
        monkeypatch.setattr(kva, "_gather_tier", lambda *a, t=tier: t)
        before = kva.KV_GATHER_LOWERED.labels(path=tier).value
        results[tier] = get_op(op).emit(ctx, ins, attrs)
        assert kva.KV_GATHER_LOWERED.labels(path=tier).value == \
            before + gathers
    want = results.pop("take")
    assert np.all(np.isfinite(np.asarray(want["Out"][0])))
    for name in ("PageK", "PageV", "PageKS", "PageVS"):
        if name in ins:             # pools leave as they came
            got = want[name + "Out"][0]
            assert (got.shape, got.dtype) == \
                (ins[name][0].shape, ins[name][0].dtype)
    active = np.asarray(ins["Active"][0]).reshape(-1) > 0 \
        if "Active" in ins else slice(None)
    for tier, got in results.items():
        assert sorted(got) == sorted(want)
        for slot in want:
            g, w = _bits(got[slot][0]), _bits(want[slot][0])
            if slot == "Out":       # a free slot's output row is
                g, w = g[active], w[active]     # meaningless by contract
            np.testing.assert_array_equal(
                g, w, err_msg=f"{op} {codec}: {slot}, {tier} vs take")


@pytest.mark.parametrize("op", ["decode", "verify"])
def test_paged_fp32_ops_match_the_plain_window_attention(op):
    """fp32 paged decode and verify == a plain float32 statement of the
    window attention written here (``_window_attention_reference``; a
    decode step is a window of one) over the cache the page table
    describes, on every row the host reads (rtol 1e-6: the same products
    and exact zeros, the sums in another order), and the rows they write
    are the reference's BIT for bit — the page indirection adds nothing
    to what lands in the pool. Token-level identity of the verify window
    with sequential paged decode is tests/test_spec_decode.py::
    test_spec_greedy_bit_identical_zero_recompiles."""
    import types
    import jax.numpy as jnp
    from paddle_tpu.core.registry import get_op
    ins, attrs = _paged_op_inputs("none", op, seed=3)
    ctx = types.SimpleNamespace(mesh=None)
    got = get_op(f"kv_attention_{op}_paged").emit(ctx, ins, attrs)
    n_pages, ps, m = ins["PageK"][0].shape
    h = attrs["n_head"]
    table = np.minimum(np.asarray(ins["PageTable"][0]), n_pages - 1)
    rows = (table[:, :, None] * ps + np.arange(ps)).reshape(
        table.shape[0], -1)                             # [B, S]

    def cache(pool):
        return jnp.asarray(np.asarray(pool).reshape(-1, h, m // h)[rows])
    cins = {k: v for k, v in ins.items()
            if not k.startswith("Page")}
    cins["CacheK"] = [cache(ins["PageK"][0])]
    cins["CacheV"] = [cache(ins["PageV"][0])]
    cins.setdefault("WinLen", [jnp.ones_like(ins["Pos"][0])])
    active = np.asarray(ins["Active"][0]).reshape(-1) > 0
    assert active.any() and not active.all()
    want = _window_attention_reference(cins, h)
    assert want["seen"].any(axis=1).tolist() == active.tolist()
    np.testing.assert_allclose(np.asarray(got["Out"][0])[want["seen"]],
                               np.asarray(want["Out"][0])[want["seen"]],
                               rtol=1e-6, atol=1e-6)
    for pool, c in (("PageKOut", "CacheKOut"), ("PageVOut", "CacheVOut")):
        np.testing.assert_array_equal(
            _bits(cache(got[pool][0]))[active],
            _bits(want[c][0])[active])


def _contract_case(k1, h, d, seed):
    """Inputs of ``kv_attention._decode_contract``: q [B, K1, H, Dk],
    k / v [B, S, H*Dk] with huge garbage in every masked row, a causal
    window mask with per-row lengths, and one slot (row 1) that is
    inactive: nothing of it is valid."""
    b, s_len = 3, 40
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, k1, h, d)).astype(np.float32)
    k, v = (rng.standard_normal((b, s_len, h * d)).astype(np.float32)
            for _ in range(2))
    lens = np.array([17, 0, 31])
    j = np.arange(s_len)
    valid = j[None, None, :] < (lens[:, None] + np.arange(k1))[:, :, None]
    valid[1] = False
    dead = ~valid.any(axis=1)                            # [B, S]
    k[dead] = 1e30
    v[dead] = -1e30
    return q, k, v, valid


@pytest.mark.parametrize("k1, h, d", [(1, 16, 64), (5, 16, 64),
                                      (1, 4, 128)])
def test_decode_contract_equals_per_head_contraction(k1, h, d):
    """The block-diagonal contraction over [B, S, H*Dk] is the per-head
    attention over [B, S, H, Dk], written here plainly in float32: the
    same products plus exact zeros, the sums in another order (rtol
    1e-6). Garbage in masked rows and an inactive slot change nothing
    a reader sees."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kv_attention as kva
    q, k, v, valid = _contract_case(k1, h, d, seed=k1 * h + d)
    got = np.asarray(kva._decode_contract(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(valid), jnp.float32))
    assert got.shape == (3, k1, h, d) and got.dtype == np.float32
    k4, v4 = (a.reshape(3, -1, h, d) for a in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", q, k4) * d ** -0.5
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    want = np.asarray(jnp.einsum("bhij,bjhd->bihd", p, v4))
    live = valid.any(axis=2)                             # [B, K1]
    assert live[0].all() and not live[1].any()
    assert np.all(np.isfinite(got[live]))
    np.testing.assert_allclose(got[live], want[live], rtol=1e-6,
                               atol=1e-6)


def test_decode_contract_heads_do_not_mix():
    """A head's context reads its own lanes of K and V and nothing
    else: rewriting every OTHER head's lanes, with values large enough
    to swamp any leak, leaves its bits as they were — the zeros of the
    block-diagonal query and of the pick of a head's own lanes are
    selected (``where``), not multiplied, so they are exact."""
    import jax.numpy as jnp
    from paddle_tpu.ops import kv_attention as kva
    k1, h, d = 2, 16, 64
    q, k, v, valid = _contract_case(k1, h, d, seed=11)

    def run(k, v):
        return np.asarray(kva._decode_contract(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(valid), jnp.float32))
    base = run(k, v)
    keep = 5
    others = np.ones(h * d, bool)
    others[keep * d:(keep + 1) * d] = False
    k2, v2 = k.copy(), v.copy()
    k2[:, :, others] = 1e4 * (1 + np.arange(others.sum()) % 7)
    v2[:, :, others] = -3e4
    moved = run(k2, v2)
    live = valid.any(axis=2)
    np.testing.assert_array_equal(_bits(moved[:, :, keep])[live],
                                  _bits(base[:, :, keep])[live])
    assert not np.array_equal(moved[:, :, keep - 1][live],
                              base[:, :, keep - 1][live])


def _window_attention_reference(ins, h):
    """The verify window (a decode step: a window of one) in plain float32
    ``jax.numpy`` over [B, S, H, Dk] caches: window position i of row b writes its k/v at cache row
    pos + i (where active, i < win_len and the row exists) and attends
    over {j < seq_len} ∪ {gen_start <= j <= pos + i}. ``seen`` marks the
    [B, K1] outputs the host reads (active rows, i < win_len)."""
    import jax
    import jax.numpy as jnp
    x = ins["X"][0]
    b, k1, m = x.shape
    d = m // h
    q, k, v = ((x @ ins[w][0]).reshape(b, k1, h, d)
               for w in ("Wq", "Wk", "Wv"))
    pos, lens, gen0, act, wlen = (
        np.asarray(ins[n][0]).reshape(-1)
        for n in ("Pos", "SeqLen", "GenStart", "Active", "WinLen"))
    ck, cv = ins["CacheK"][0], ins["CacheV"][0]
    s_len = ck.shape[1]
    seen = np.zeros((b, k1), bool)
    for r in range(b):
        for i in range(k1):
            if act[r] and i < wlen[r] and pos[r] + i < s_len:
                ck = ck.at[r, pos[r] + i].set(k[r, i])
                cv = cv.at[r, pos[r] + i].set(v[r, i])
                seen[r, i] = True
    j = np.arange(s_len)
    valid = (j[None, None, :] < lens[:, None, None]) | (
        (j[None, None, :] >= gen0[:, None, None])
        & (j[None, None, :] <= (pos[:, None] + np.arange(k1))[:, :, None]))
    s = jnp.einsum("bihd,bjhd->bhij", q, ck) * d ** -0.5
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    c = jnp.einsum("bhij,bjhd->bihd", p, cv).reshape(b, k1, m)
    return {"Out": [c @ ins["Wo"][0]], "CacheKOut": [ck],
            "CacheVOut": [cv], "seen": seen}


@pytest.mark.parametrize("case, want", [
    ("cpu-f32-ps16", "take"),           # no kernel tier off the chip
    ("kernel-f32-ps16", "pages"),       # the benchmark's pool
    ("kernel-bf16-ps16", "pages"),
    ("kernel-bf16-ps8", "rows"),        # half a bf16 tile
    ("kernel-f32-ps4", "rows"),
    ("kernel-int8-ps16", "rows"),       # 32-row tile, and the dequant
])
def test_kv_gather_lowering_counter_names_the_tier(case, want,
                                                   monkeypatch):
    """Lowering a paged decode counts one K and one V gather under the
    tier the pool's dtype, page size and codec select:
    paddle_kv_gather_lowered_total{path}. ``kernel_enabled`` is steered
    here because it asks for a TPU backend."""
    import types
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.registry import get_op
    from paddle_tpu.ops import kv_attention as kva
    from paddle_tpu.ops import pallas as plk
    tier, storage, ps = case.split("-")
    ps = int(ps[2:])
    codec = {"f32": "none", "bf16": "bf16", "int8": "int8"}[storage]
    ins, attrs = _paged_op_inputs(codec)
    for name in ("PageK", "PageV", "PageKS", "PageVS"):
        if name in ins:             # same rows, pages of ``ps``
            a = ins[name][0]
            ins[name] = [a.reshape((-1, ps) + a.shape[2:])]
    ins["PageTable"] = [jnp.zeros((3, 48 // ps), jnp.int32)]
    if tier == "kernel":
        monkeypatch.setattr(plk, "kernel_enabled",
                            lambda *a, **k: True)
    fam = kva.KV_GATHER_LOWERED
    before = {t: fam.labels(path=t).value
              for t in ("pages", "rows", "take")}
    jax.eval_shape(lambda i: get_op("kv_attention_decode_paged").emit(
        types.SimpleNamespace(mesh=None), i, attrs), ins)
    grew = {t: fam.labels(path=t).value - before[t] for t in before}
    assert grew == {t: (2 if t == want else 0) for t in before}


def test_kv_gather_counter_counts_a_real_program_and_is_cataloged():
    """Warming the paged engine lowers its decode program on this CPU:
    K and V of both layers take the refer path, and the family is in
    the scrape's catalog at zero traffic."""
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    from paddle_tpu.ops import kv_attention as kva
    _paged_lm()
    assert kva.KV_GATHER_LOWERED.labels(path="take").value >= 4
    exporters._preregister_catalog()
    assert "paddle_kv_gather_lowered_total" in \
        obs_metrics.default_registry().snapshot()


# ---------------------------------------------------------------------------
# engine: paged views vs the sequential oracle
# ---------------------------------------------------------------------------

def test_make_slot_model_factory_and_geometry():
    m = _paged_lm()
    assert type(m) is seng.SlotGenerativeModel
    assert not seng.SlotGenerativeModel.__subclasses__()
    assert not hasattr(seng, "PagedSlotGenerativeModel")
    # one engine: the plumbing is inherited, not borrowed
    assert seng.SlotGenerativeModel.__mro__[1] is seng.GenerativeModel
    assert "_launch" not in vars(seng.SlotGenerativeModel)
    assert (m.n_pages, m.page_size, m.max_pages) == (16, 4, 4)
    assert m.cache_len == 16 and m.n_slots == 4
    assert m.free_pages() == 16


def test_paged_build_validation():
    with pytest.raises(ValueError):          # page_size must divide S
        T.build_decoder_lm_programs(
            **_LM_CFG, modes=("decode_paged",), n_slots=2, page_size=3)
    with pytest.raises(ValueError):          # pool < one worst-case span
        T.build_decoder_lm_programs(
            **_LM_CFG, modes=("decode_paged",), n_slots=2, page_size=4,
            n_pages=2)


def test_paged_greedy_matches_sequential_oracle_zero_recompiles():
    m = _paged_lm()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 32, (int(n),)) for n in (3, 4, 7, 8, 5, 2)]
    gm = _oracle_lm()                        # chunk: oracle buckets top at 4
    want = (gm.full_forward_generate(prompts[:3], max_new=6)
            + gm.full_forward_generate(prompts[3:], max_new=6))
    with smetrics.forbid_compiles():
        got = m.generate(prompts, max_new=6)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_paged_slot_layout_helper():
    """One layout: the helper takes no flag, and the contiguous layout
    (removed at PR 29) is refused by name, as is its flag."""
    from paddle_tpu import flags
    assert T.slot_modes() == T.slot_modes("paged") == (
        "prefill_paged", "decode_paged")
    with pytest.raises(ValueError, match="removed at PR 29"):
        T.slot_modes("contiguous")
    with pytest.raises(ValueError):
        T.slot_modes("ragged")
    with pytest.raises(KeyError):
        flags.get("kv_cache_layout")


def test_make_slot_model_refuses_a_family_without_paged_views():
    """The oracle's family (the ``full`` view alone, the builder's
    default) is not a slot family: the constructor says which views it
    wants."""
    full_only = T.build_decoder_lm_programs(**_LM_CFG)
    assert sorted(full_only) == ["full"]
    with pytest.raises(ValueError, match="prefill_paged.*decode_paged"):
        seng.make_slot_model("lm_full_only", full_only, init=False)
    progs = T.build_decoder_lm_programs(
        **_LM_CFG, modes=("decode_paged",), n_slots=2)
    with pytest.raises(ValueError, match="prefill_paged"):
        seng.make_slot_model("lm_no_prefill", progs, init=False)


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_the_contiguous_cache_views_are_refused_by_name(mode):
    """The two views of the batch-at-a-time engine (removed at PR 46)
    are refused with where they went; four modes are left."""
    from paddle_tpu.analysis.contracts import DECODER_LM_MODES
    assert DECODER_LM_MODES == ("full",) + T.slot_modes(spec=True)
    with pytest.raises(ValueError, match="removed at PR 46.*slot_modes"):
        T.build_decoder_lm_programs(**_LM_CFG, modes=(mode,))
    from paddle_tpu.core.registry import get_op
    with pytest.raises(KeyError, match="no emitter registered"):
        get_op(f"kv_attention_{mode}")


def test_replica_refuses_a_spec_without_slots():
    """``"slots": false`` asked for the batch-at-a-time engine: refused
    by name (a spec comes from outside the program)."""
    from paddle_tpu.serving import replica
    with pytest.raises(ValueError, match="slots.*slot engine alone"):
        replica.build_engine(
            {"kind": "decoder_lm", "name": "lm_replica_noslots",
             "slots": False, "params": dict(_LM_CFG)})


def test_replica_decoder_lm_spec_builds_the_paged_engine():
    """A replica's ``decoder_lm`` spec with ``slots: true`` builds the
    paged slot engine at validate_geometry's defaults (page_size 4,
    every slot at full length) and serves the oracle's tokens."""
    from paddle_tpu.serving import replica
    eng = replica.build_engine(
        {"kind": "decoder_lm", "name": "lm_replica_paged", "slots": True,
         "params": dict(_LM_CFG, n_slots=2)})
    assert type(eng) is seng.SlotGenerativeModel
    assert (eng.page_size, eng.n_pages, eng.n_slots) == (4, 8, 2)
    server = serving.ModelServer()
    try:
        server.add_model(eng)
        prompts = [np.asarray([5, 9, 2]), np.asarray([7, 1, 30, 4, 12])]
        got = [server.generate("lm_replica_paged", [p], max_new=6,
                               timeout=120)[0] for p in prompts]
    finally:
        server.stop()
    want = _oracle_lm().full_forward_generate(prompts, max_new=6)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_engine_prefix_sharing_cow_bit_identical():
    """Satellite: same system-prompt prefix -> physically shared pages
    (refcount witnessed); divergence is copy-on-write with greedy
    output bit-identical to the unshared run; releasing one sharer
    keeps the other's pages."""
    m = _paged_lm()
    pa = [5, 6, 7, 8, 1, 2]                  # shared full page [5,6,7,8]
    pb = [5, 6, 7, 8, 3]
    # unshared references: each prompt alone on an empty tree
    ref = {}
    for key, pr in (("a", pa), ("b", pb)):
        m.reset()
        ref[key] = m.generate([pr], max_new=5)[0]
    m.reset()
    sa, first_a, _ = m.admit(pa, max_new=5)
    sb, first_b, _ = m.admit(pb, max_new=5)
    shared_page = m.pool.lease(sa).pages[0]
    assert m.pool.lease(sb).pages[0] == shared_page
    assert m.pool.page_refs(shared_page) == 2
    assert m.pool.shared_count() == 1
    assert first_a == ref["a"][0] and first_b == ref["b"][0]
    toks = {sa: [first_a], sb: [first_b]}
    done = set()
    while len(done) < 2:
        for slot, tok, d in m.step():
            toks[slot].append(tok)
            if d:
                done.add(slot)
    np.testing.assert_array_equal(toks[sa], ref["a"])
    np.testing.assert_array_equal(toks[sb], ref["b"])
    assert m.pool.page_refs(shared_page) == 0   # cached, resident
    m.reset()


def test_engine_release_one_sharer_keeps_pages():
    m = _paged_lm()
    pa = [9, 9, 9, 9, 1]
    pb = [9, 9, 9, 9, 2]
    m.reset()
    ref_b = m.generate([pb], max_new=6)[0]
    m.reset()
    sa, _, _ = m.admit(pa, max_new=6)
    sb, fb, _ = m.admit(pb, max_new=6)
    shared_page = m.pool.lease(sb).pages[0]
    assert m.pool.page_refs(shared_page) == 2
    m.release(sa, cause="cancelled")         # leave B in flight
    assert m.pool.page_refs(shared_page) == 1
    toks = [fb]
    while True:
        ev = {s: (t, d) for s, t, d in m.step()}
        t, d = ev[sb]
        toks.append(t)
        if d:
            break
    np.testing.assert_array_equal(toks, ref_b)
    m.reset()


def test_paged_admission_by_pages_and_exhaustion_message():
    # slot-side shed (pool sized n_slots * max_pages: slots run out
    # exactly when pages do) — the base message, counts included
    m = _paged_lm()
    for tok in (7, 3, 2, 6):
        m.admit([tok, tok, 1, 2, 3], max_new=8)   # bucket 8 -> span 4
    assert m.free_pages() == 0 and m.free_count() == 0
    with pytest.raises(seng.SlotExhaustedError) as ei:
        m.admit([4, 4, 4], max_new=8)
    assert "free_slots=0" in str(ei.value)
    assert "active_slots=4" in str(ei.value)
    m.reset()
    # page-side shed (satellite 2): the page-starved engine runs out of
    # PAGES with 3 slots still free, and the error says so in numbers
    t = _tiny_paged()
    t.admit([9, 9, 9, 9, 9], max_new=8)           # span 4 = whole pool
    assert t.free_pages() == 0 and t.free_count() == 3
    with pytest.raises(seng.SlotExhaustedError) as ei:
        t.admit([4, 4, 4], max_new=8)
    msg = str(ei.value)
    assert "free_pages=0" in msg
    assert "pages_total=4" in msg
    assert "free_slots=3" in msg
    t.reset()


def test_admit_prefill_failure_releases_lease():
    """Regression (REVIEW r05): a prefill dispatch that dies after
    _reserve_capacity leaked the page lease — the slot never went
    active, release() skipped the pool, and since admit always picks
    the lowest free slot every later admission retried it and tripped
    'already holds a page lease' forever. The failure path must return
    the lease, scrub the table row, and clear pending write rows."""
    m = _paged_lm()
    ref = m.generate([[1, 2, 3]], max_new=4)[0]
    m.reset()
    armed = {"on": True}
    orig = m._run

    def boom(cb, key, feeds):
        if armed["on"] and key[0] == m.PREFILL:
            armed["on"] = False
            raise RuntimeError("injected prefill dispatch failure")
        return orig(cb, key, feeds)

    m._run = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            m.admit([1, 2, 3], max_new=4)
        assert m.pool.lease(0) is None       # no leaked lease
        assert m.free_pages() == m.n_pages
        assert m._pending_rows is None
        assert (m._table[0] == m.n_pages).all()
        # the same slot admits again, and output is uncorrupted
        got = m.generate([[1, 2, 3]], max_new=4)[0]
        np.testing.assert_array_equal(got, ref)
    finally:
        del m.__dict__["_run"]
        m.reset()


def test_paged_int8_sampling_replay_deterministic():
    m = _paged_lm("int8")
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 32, (int(n),)) for n in (3, 5, 8, 4)]
    kw = dict(max_new=6, temperature=0.8, top_k=4, seeds=[11, 12, 13, 14])
    with smetrics.forbid_compiles():
        a = m.generate(prompts, **kw)
    # interleave unrelated traffic, then replay: streams keyed only by
    # (seed, step index) must reproduce bit-identically
    m.generate([[1, 2]], max_new=3, temperature=0.5, seeds=[99])
    b = m.generate(prompts, **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_paged_int8_deterministic_across_engines():
    # the codec is lossy (no greedy-bit-parity claim vs fp32) but must
    # be DETERMINISTIC: a fresh engine with the same weights replays
    # the same greedy streams bit-for-bit
    m = _paged_lm("int8")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 32, (int(n),)) for n in (4, 6, 8)]
    a = m.generate(prompts, max_new=5)
    m.reset()
    b = m.generate(prompts, max_new=5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# observability + server integration
# ---------------------------------------------------------------------------

def test_page_pool_census_classification():
    assert obs_memory.classify("decoder_paged_attn_0_page_k_0") == "kv_cache"
    assert obs_memory.classify("decoder_paged_attn_0_page_vs_1") == "kv_cache"
    m = _paged_lm()
    assert obs_memory.kv_pool_bytes(m.scope) > 0
    cen = obs_memory.census([m.scope])
    page_bufs = [b for b in cen["buffers"] if "_page_" in b["name"]]
    assert page_bufs
    assert all(b["family"] == "kv_cache" for b in page_bufs)


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_pool_variable_is_3d_and_engine_and_census_read_it(codec):
    """Every paged mode and codec declares ``*_page_k/v_*`` as
    [n_pages, page_size, n_head * d_k] in the storage dtype (row-major
    at rest on the chip, PERF.md PR 28; the int8 scale planes stay
    [n_pages, page_size, n_head]); ``_discover_pool`` reads n_pages and
    page_size from its two leading dimensions, the device arrays have
    the declared shape, and the census counts exactly their bytes."""
    m = _paged_lm(codec)
    cfg, store = _LM_CFG, {"none": np.float32, "int8": np.int8}[codec]
    assert (m.n_pages, m.page_size) == (16, 4)

    def declared(name):         # (shape, dtype) a *_page_* variable has
        if "_page_ks_" in name or "_page_vs_" in name:
            return (16, 4, cfg["n_head"]), np.float32
        return (16, 4, cfg["d_model"]), store
    programs = T.build_decoder_lm_programs(
        **cfg, prompt_buckets=(4, 8), n_slots=4, page_size=4,
        kv_codec=codec, spec_k=2,
        modes=("prefill_paged", "decode_paged", "decode_verify_paged"))
    assert len(programs) == 5           # two buckets + their alias
    for mode in programs:
        block = programs[mode][0].desc.global_block
        pools = {n: v for n, v in block.vars.items() if "_page_" in n}
        assert len(pools) == cfg["n_layer"] * (4 if codec == "int8" else 2)
        for n, v in pools.items():
            assert tuple(v.shape) == declared(n)[0], (mode, n)
    kv_bytes = 0
    for n, arr in m.scope.iter_vars():
        if "_page_" in n:
            assert (arr.shape, arr.dtype) == declared(n), n
            kv_bytes += arr.nbytes
    rows = m.n_pages * m.page_size * cfg["n_layer"] * 2
    per_row = {"none": 4 * cfg["d_model"],
               "int8": cfg["d_model"] + 4 * cfg["n_head"]}[codec]
    assert kv_bytes == rows * per_row
    assert obs_memory.kv_pool_bytes(m.scope) == kv_bytes


def test_verify_paged_flops_read_page_size_not_n_pages():
    """utils/flops.py credits kv_attention_verify_paged with dots over
    the cache length max_pages * page_size — the pool's SECOND
    dimension; with the 3-D pool ``shape[-3]`` is n_pages."""
    from paddle_tpu.utils import flops
    progs = T.build_decoder_lm_programs(
        **_LM_CFG, prompt_buckets=(8,), n_slots=4, page_size=4,
        n_pages=40, spec_k=2, modes=("decode_verify_paged",))
    main = progs["decode_verify_paged"][0]
    block = main.desc.global_block
    ops = [op for op in block.ops
           if op.type == "kv_attention_verify_paged"]
    assert len(ops) == _LM_CFG["n_layer"]
    b, k1, m, s = 4, 3, _LM_CFG["d_model"], 16      # cache_len 8 + 8
    want = 2.0 * b * m * m * 4 * k1 + 2.0 * b * k1 * s * m * 2
    assert flops._op_flops(main.desc, block, ops[0], 4) == want


def test_server_maps_exhaustion_to_typed_wire_kind():
    from paddle_tpu.serving import server as srv
    assert srv._ERROR_KINDS[seng.SlotExhaustedError] == "exhausted"
    # the isinstance scan must hit the specific kind, not RuntimeError
    err = seng.SlotExhaustedError("x")
    kind = next(k for klass, k in srv._ERROR_KINDS.items()
                if isinstance(err, klass))
    assert kind == "exhausted"


def test_server_queues_when_pages_exhausted():
    """Pages-before-slots admission through the scheduler: a pool too
    small for the offered load must QUEUE the overflow (put-back), not
    fail it — every request completes."""
    m = _tiny_paged()                        # one span-3 request at a time
    server = serving.ModelServer(linger_s=0.001, max_queue_depth=64)
    server.add_model(m, warmup=False)        # _tiny_paged is warm already
    try:
        futs = [server.submit_generate("lm_paged_tiny", [[i + 1, 2, 3]],
                                       max_new=8)
                for i in range(5)]
        outs = [f.result(120) for f in futs]
        assert all(len(o[0]) == 8 for o in outs)
        assert m.pool.free_count() == 4      # everything released
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# speculative decoding over the paged pool (ISSUE 19 satellites)
# ---------------------------------------------------------------------------

def _spec_paged_lm():
    """One warmed draft-verify paged engine (spec_k=3) shared by the
    speculative satellites; tests swap ``m.drafter`` per schedule."""
    m = _CACHE.get("spec_paged")
    if m is None:
        m = seng.make_slot_model(
            "lm_spec_paged_kvp",
            T.build_decoder_lm_programs(
                **_LM_CFG, prompt_buckets=(4, 8),
                modes=T.slot_modes("paged", spec=True), n_slots=4,
                page_size=4, spec_k=3))
        m.warmup()
        _CACHE["spec_paged"] = m
    m.reset()
    m.drafter = seng.NgramDrafter()
    return m


class _ScriptedDrafter:
    """Proposes the true continuation of ``target``, corrupting window
    positions >= sched[call] — a deterministic accept/reject schedule
    (see tests/test_spec_decode.py)."""

    def __init__(self, target, sched=None):
        self.target = [int(t) for t in target]
        self.sched = sched
        self.calls = 0

    def propose(self, tokens, k):
        n = len(tokens)
        d = self.target[n:n + k]
        keep = len(d) if self.sched is None else self.sched[self.calls]
        self.calls += 1
        return [t if i < keep else (t + 1) % 32
                for i, t in enumerate(d)]


def test_span_for_draft_window_off_by_k_regression():
    """Satellite regression: at the max_new boundary an engine that
    drafts a FULL window writes up to draft_window rows past
    total_len; when total_len is page-aligned that overshoot needs one
    extra page — the off-by-K span_for(total) alone would miss."""
    pool = kv_pool.PagePool(n_pages=16, page_size=4)
    assert pool.span_for(16) == 4
    assert pool.span_for(16, draft_window=0) == 4
    assert pool.span_for(16, draft_window=1) == 5      # the off-by-K
    assert pool.span_for(16, draft_window=3) == 5
    assert pool.span_for(16, draft_window=5) == 6
    assert pool.span_for(15, draft_window=1) == 4      # unaligned: free
    assert pool.span_for(13, draft_window=3) == 4


def test_spec_window_kv_append_crosses_page_boundary():
    """A multi-token KV append crossing a page boundary MID-window:
    prompt bucket 4 (page 1 = rows 4..7), the first window commits 3
    tokens (accept 2 + bonus) so the second window writes rows 7..10 —
    row 7 in page 1, rows 8..10 in page 2 — and the stream must stay
    bit-identical to the sequential engine."""
    m = _spec_paged_lm()
    prompt = [3, 12, 26]
    ref = _paged_lm().generate([prompt], max_new=8)[0]
    m.reset()
    m.drafter = _ScriptedDrafter(list(prompt) + list(ref),
                                 sched=[2, 3])
    d0 = smetrics.DECODE_STEPS.labels(model=m.name).value
    got = m.generate([prompt], max_new=8)[0]
    np.testing.assert_array_equal(got, ref)
    # admit commits 1, dispatch 1 commits 3 (frontier row 6), then the
    # boundary window 7..10 accepts all 3 drafts and commits 4 — the
    # whole budget-8 request drains in TWO verify dispatches
    assert smetrics.DECODE_STEPS.labels(model=m.name).value - d0 == 2
    m.reset()
    assert m.pool.free_count() + m.pool.cached_count() == m.n_pages


def test_spec_rollback_across_page_boundary():
    """Rejected drafts whose KV rows landed in the NEXT page: the
    logical frontier rewinds (pages stay leased), the stale rows are
    never attended, and later windows overwrite them — witnessed by
    bit-parity with the sequential stream after a reject-all window
    that straddled the boundary."""
    m = _spec_paged_lm()
    prompt = [8, 8, 21]
    ref = _paged_lm().generate([prompt], max_new=8)[0]
    m.reset()
    # dispatch 1: accept 2 of 3 -> frontier at row 6 (page 1);
    # dispatch 2: window rows 7..10 straddles pages 1|2, REJECT ALL ->
    # rows 8..10 in page 2 are stale, only row 7's token committed +
    # bonus; the remaining dispatches must still replay the reference
    m.drafter = _ScriptedDrafter(list(prompt) + list(ref),
                                 sched=[2, 0, 3, 3, 3])
    got = m.generate([prompt], max_new=8)[0]
    np.testing.assert_array_equal(got, ref)
    st = m.pool.stats()
    assert st["slots"] == 0                  # lease released at done


def test_spec_shared_prefix_refcount_safety():
    """Prefix sharing under speculation: two in-flight requests share a
    full prompt page while their verify windows write ONLY private
    generated pages — refcount 2 while both live, decremented on
    release, and both streams bit-match their unshared references."""
    m = _spec_paged_lm()
    pa = [5, 6, 7, 8, 1, 2]                  # shared full page [5,6,7,8]
    pb = [5, 6, 7, 8, 3]
    ref = {}
    for key, pr in (("a", pa), ("b", pb)):
        m.reset()
        ref[key] = m.generate([pr], max_new=5)[0]
    m.reset()
    sa, first_a, _ = m.admit(pa, max_new=5)
    sb, first_b, _ = m.admit(pb, max_new=5)
    shared_page = m.pool.lease(sa).pages[0]
    assert m.pool.lease(sb).pages[0] == shared_page
    assert m.pool.page_refs(shared_page) == 2
    assert first_a == ref["a"][0] and first_b == ref["b"][0]
    toks = {sa: [first_a], sb: [first_b]}
    done = set()
    while len(done) < 2:
        for slot, tok, d in m.step():
            toks[slot].append(tok)
            if d:
                done.add(slot)
    np.testing.assert_array_equal(toks[sa], ref["a"])
    np.testing.assert_array_equal(toks[sb], ref["b"])
    assert m.pool.page_refs(shared_page) == 0    # cached, resident
    m.reset()


# ---------------------------------------------------------------------------
# decode steps dispatched ahead (step(ahead=True): PR 31)
# ---------------------------------------------------------------------------

_AHEAD_PROMPTS = ([3, 9, 4, 1, 7], [8, 2], [6, 6, 1, 5, 2, 9, 4], [7, 3, 3])
_AHEAD_BUDGETS = (6, 3, 8, 5)


def _drive(m, ahead, eos=None, late=True):
    """Three requests at once, a fourth admitted into the first slot
    that frees: every event in order, with the step it came in."""
    m.reset()
    for prompt, budget in zip(_AHEAD_PROMPTS[:3], _AHEAD_BUDGETS):
        m.admit(prompt, max_new=budget, eos_id=eos)
    events, step = [], 0
    while m.active_count():
        step += 1
        for ev in m.step(ahead=ahead):
            events.append((step,) + ev)
        if late and m.free_count() and step < 50:
            m.admit(_AHEAD_PROMPTS[3], max_new=_AHEAD_BUDGETS[3],
                    eos_id=eos)
            late = False
    assert not m._flights or not m.active_count()
    assert m.pool.free_count() + m.pool.cached_count() == m.n_pages
    return events


def test_steps_dispatched_ahead_emit_the_same_events():
    """``step(ahead=True)`` queues the next decode step before this
    one's tokens are fetched: the events are those of one step at a
    time, to the token, the cause, the order and the step — budgets
    that end at different steps."""
    m = _paged_lm()
    plain = _drive(m, ahead=False, late=False)
    assert [e for e in plain if e[3]], "some request ends"
    assert _drive(m, ahead=True, late=False) == plain

    def streams(events):
        out = {}
        for _step, slot, tok, done in events:
            out.setdefault(slot, []).append((tok, done))
        return out
    # a request admitted between two steps joins the step after the one
    # in flight: a step later, the same stream
    late = _drive(m, ahead=False)
    assert len(late) == len(plain) + _AHEAD_BUDGETS[3] - 1
    assert streams(_drive(m, ahead=True)) == streams(late)


def test_a_step_ahead_drops_the_token_after_an_eos():
    """EOS is seen a step late when steps run ahead: the slot has run
    one step more than it needed, and that token is nobody's — the
    events end at the EOS as they do one step at a time."""
    m = _paged_lm()
    plain = _drive(m, ahead=False, late=False)
    # a token some request emits mid-way, as everybody's EOS
    eos = next(tok for _step, _slot, tok, done in plain[3:] if not done)
    with_eos = _drive(m, ahead=False, eos=eos, late=False)
    assert [e for e in with_eos if e[3] == "eos"]
    assert len(with_eos) < len(plain)
    assert _drive(m, ahead=True, eos=eos, late=False) == with_eos


def test_a_release_drops_the_step_in_flight_for_that_slot():
    """A cancel between two steps ahead: the step in flight ran the
    slot, the slot has a new owner when its tokens come back, and the
    new owner's stream is the one it has alone."""
    m = _paged_lm()
    m.reset()
    alone = m.generate([_AHEAD_PROMPTS[1]], max_new=4)[0]
    m.reset()
    a, _t, _d = m.admit(_AHEAD_PROMPTS[0], max_new=8)
    b, _t, _d = m.admit(_AHEAD_PROMPTS[2], max_new=8)
    m.step(ahead=True)                   # a second step is in flight
    assert len(m._flights) == 1 and a in m._flights[0].slots
    m.release(a)                         # the server's cancel
    a2, first, _d = m.admit(_AHEAD_PROMPTS[1], max_new=4)
    assert a2 == a
    # the admission queued the step after the one in flight behind its
    # prefill, without the slot it was filling
    assert len(m._flights) == 2 and not m._flights[1].ran[a]
    toks = [first]
    while m._active[a2]:
        toks += [tok for slot, tok, _d in m.step(ahead=True) if slot == a2]
    np.testing.assert_array_equal(toks, alone)
    # a call without ``ahead`` commits what is in flight, dispatches
    # nothing, and the next one is an ordinary step
    assert len(m._flights) == 1
    assert {s for s, _t, _d in m.step()} == {b}
    assert not m._flights
    m.reset()


def test_the_token_merge_is_part_of_the_decode_executable():
    """The step's tokens come from the previous dispatch's output where
    the host does not know better, merged INSIDE the decode executable
    (``CompiledBlock(feed_transform=...)``): the program and its feeds
    are what they were (``_fingerprint`` hashes them), the engine hands
    two entries more, and no dispatch of its own is spent on the merge
    (the benchmark's readers count program executions a decode step)."""
    m = _paged_lm()
    m.reset()
    program_feeds = set(m._cb_decode.sig.feed_names)
    handed = set(m._decode_feeds())
    assert handed - program_feeds == {"tok_prev", "tok_use_host"}
    assert "tok" in program_feeds
    # the host's token wins where the mask says so, the device's elsewhere
    import jax.numpy as jnp
    merged = seng._merge_tokens({
        "tok": jnp.asarray([[[1]], [[2]], [[3]]]),
        "tok_prev": jnp.asarray([[7], [8], [9]]),
        "tok_use_host": jnp.asarray([True, False, True])})
    assert set(merged) == {"tok"}
    np.testing.assert_array_equal(np.asarray(merged["tok"]).reshape(-1),
                                  [1, 8, 3])
    # a step fed a stale host token for a slot the step in flight ran
    # still reads the device's: poison the host mirror between two steps
    plain = _drive(m, ahead=False, late=False)
    m.reset()
    for prompt, budget in zip(_AHEAD_PROMPTS[:3], _AHEAD_BUDGETS):
        m.admit(prompt, max_new=budget)
    events, step = [], 0
    while m.active_count():
        step += 1
        events += [(step,) + ev for ev in m.step(ahead=True)]
        if m._flights:
            m._tok[m._flights[-1].slots] = 0  # the host's copy is not read
    assert events == plain
    m.reset()


def test_pool_evicts_many_pages_in_one_walk_lru_leaf_first(monkeypatch):
    """An admission that needs many pages of a pool full of cached
    prompts walks the radix tree ONCE (a walk a page was a 150 ms stall
    of the scheduler on the chip: PERF.md, PR 31), and still reclaims
    the least recently used refcount-0 LEAF first, a parent only once
    its last child is gone."""
    p = kv_pool.PagePool(12, 2, model="kvp_walk")
    old = [1, 2, 3, 4, 5, 6]                 # a chain of 3 pages
    mid = [1, 2, 9, 9]                       # shares the first, adds one
    new = [7, 7, 8, 8, 6, 6]                 # a chain of its own
    for slot, toks in enumerate((old, mid, new)):
        p.acquire(slot, toks, span=len(toks) // 2 + 1)
    for slot in (0, 1, 2):
        p.release(slot)
    assert p.cached_count() == 7 and p.free_count() == 5
    walks = []
    real = p._iter_nodes
    monkeypatch.setattr(p, "_iter_nodes",
                        lambda: walks.append(1) or real())
    before = len(walks)
    p.acquire(0, [5, 5], span=5)             # what is free covers it
    p.release(0)
    plain = len(walks) - before              # the gauges' own walks
    assert p.free_count() == 4               # [5, 5] stays cached too
    before = len(walks)
    _pages, n_shared = p.acquire(0, [6, 6], span=10)   # 6 to reclaim
    assert n_shared == 0 and len(walks) - before <= plain // 2 + 1
    # gone, the oldest leaf first and a parent once its last child is:
    # old's tail two (used at 1), mid's own page and then the first
    # page they shared (used at 2), new's tail two (used at 3)
    assert p.cached_count() == 2             # new's head, and [5, 5]
    assert p.acquire(1, [5, 5], span=1)[1] == 1
    p.reset()
