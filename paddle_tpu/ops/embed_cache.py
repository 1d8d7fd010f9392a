"""Trainer-side hot-rows HBM cache for sharded embedding tables
(ISSUE 14 tentpole; reference capability: the distributed lookup_table
prefetch path, nn.py:345-359 — here the prefetch becomes a
fixed-capacity device-resident row cache).

The construction that makes the jitted step recompile-free:

- The cache is a ``[capacity + 1, D]`` array living in the Scope UNDER
  THE TABLE'S NAME (the var desc still says ``[V, D]``; lowering traces
  from the runtime array, so the whole step — lookup, row-sparse VJP,
  lazy-adam apply — comes out sized to the cache with no program
  rewrite). Row ``capacity`` is the pinned-zero PAD slot;
  ``core/lowering.py`` rewrites marked lookup sites' ``padding_idx`` to
  it, so padding semantics survive the id translation exactly.
- The HOST translates vocab ids to cache slot ids in the feed before
  every dispatch (``Executor.run`` calls :meth:`HotRowsCache.translate`
  for registered feeds). The jitted step then only ever sees in-range
  slot ids over a static-shape table: a cache HIT costs one on-device
  gather and nothing else. By construction there is NOTHING
  shape-dynamic in the step function — zero steady-state recompiles
  (witnessed by :func:`compile_count`, a ``jax.monitoring`` listener
  counting real backend compiles).
- MISSES are handled host-side before the dispatch: cold rows (param +
  row-aligned optimizer-state rows, lazily zero-filled by the shard for
  never-pushed rows) are pulled from the owning shard
  (``distributed/sharded_table.py``), installed into LRU-assigned slots
  through a pow2-bucketed jitted scatter (padded with out-of-range
  slots, ``mode="drop"`` — a handful of install shapes total, all
  compiled during warmup), and evicted DIRTY rows are written back to
  their shard first. Optimizer state rides along param rows on both
  writeback and pull, so lazy-adam momentum is exact across evictions.

Device gather/scatter primitives: ``jnp`` by default;
``ops/pallas/embed_cache.py`` kernels (HBM-resident, row-DMA) when
``use_pallas`` — the TPP-style reusable primitive pair.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observability import metrics as _metrics

# exporter-catalog families (docs/observability.md; preregistered via
# exporters._preregister_catalog importing this module). hits/misses
# count UNIQUE ids per translate() call (misses == rows pulled over the
# wire, hits == resident unique ids touched), so the hit RATE is a row
# -traffic ratio, not an occurrence ratio — the quantity that prices
# the DCN exchange.
CACHE_HITS = _metrics.counter(
    "paddle_embed_cache_hits_total",
    "Unique ids found resident per translate() call",
    labelnames=("param",))
CACHE_MISSES = _metrics.counter(
    "paddle_embed_cache_misses_total",
    "Unique ids pulled from their owning shard (cold rows)",
    labelnames=("param",))
CACHE_EVICTIONS = _metrics.counter(
    "paddle_embed_cache_evictions_total",
    "LRU evictions (dirty rows write back to their shard first)",
    labelnames=("param",))
CACHE_OCCUPANCY = _metrics.gauge(
    "paddle_embed_cache_occupancy_ratio",
    "Resident rows / capacity after the last translate()",
    labelnames=("param",))


# -- compile-counter witness -------------------------------------------------

def compile_count() -> int:
    """jit-cache misses observed process-wide (the program's one
    ``jax.monitoring`` listener, ``observability.runtime``): a
    ``backend_compile_duration`` event fires once per compile request
    and never on a cache-hit dispatch, so a flat count across a training
    window IS the zero-steady-state-recompiles witness. Counting starts
    at the first call or the first ``CompiledBlock``, whichever comes
    first: take a delta."""
    from paddle_tpu.observability import runtime as _obs_runtime
    return _obs_runtime.backend_compile_count()


# -- pow2-bucketed device row ops -------------------------------------------

_MIN_BUCKET = 8


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_rows(arr, idx, vals):
    # out-of-range idx (the bucket padding) drops — never clamps onto a
    # live row
    return arr.at[idx].set(vals.astype(arr.dtype), mode="drop")


@jax.jit
def _get_rows(arr, idx):
    return arr[idx]


class HotRowsCache:
    """Fixed-capacity row cache for ONE sharded table.

    ``families`` maps family name -> (scope var name, row width); the
    ``param`` family is the table itself, the rest are its row-aligned
    optimizer-state accumulators (lazy-adam moment1/moment2). All of
    them live in the scope as ``[capacity + 1, width]`` arrays whose
    LAST row is the pinned-zero pad slot."""

    def __init__(self, table: str, height: int, capacity: int,
                 client, scope,
                 families: Dict[str, Tuple[str, int]],
                 padding_idx: int = -1,
                 use_pallas: bool = False,
                 pallas_interpret: bool = False):
        if capacity < 1 or capacity > height:
            raise ValueError(f"capacity {capacity} not in [1, {height}]")
        if "param" not in families:
            raise ValueError("families must include 'param'")
        self.table = table
        self.height = int(height)
        self.capacity = int(capacity)
        self.pad_slot = int(capacity)
        self.client = client
        self.scope = scope
        self.families = dict(families)
        self.padding_idx = int(padding_idx) if padding_idx is not None \
            else -1
        self._use_pallas = bool(use_pallas)
        self._pallas_interpret = bool(pallas_interpret)
        # host index: vocab id -> slot (LUT for vectorized translate),
        # slot -> vocab id, LRU order, dirty vocab ids
        self._slot_lut = np.full(self.height, -1, dtype=np.int64)
        self._lru: "OrderedDict[int, int]" = OrderedDict()  # vocab->slot
        self._free = list(range(self.capacity - 1, -1, -1))
        self._dirty: set = set()
        self._hits = CACHE_HITS.labels(param=table)
        self._misses = CACHE_MISSES.labels(param=table)
        self._evictions = CACHE_EVICTIONS.labels(param=table)
        self._occupancy = CACHE_OCCUPANCY.labels(param=table)

    # -- device plumbing ---------------------------------------------------

    def _arr(self, fam: str):
        name = self.families[fam][0]
        arr = self.scope.find_var(name)
        if arr is None:
            raise KeyError(f"scope has no var {name!r} for cache family "
                           f"{fam!r} of table {self.table!r}")
        return arr

    def _device_set_rows(self, fam: str, slots: np.ndarray,
                         vals: np.ndarray) -> None:
        """Install rows at slots via a pow2-padded jitted scatter (a
        fixed small set of shapes -> no steady-state compiles)."""
        name, width = self.families[fam]
        b = _bucket(slots.size)
        idx = np.full(b, self.capacity + 1, dtype=np.int64)  # OOB: drop
        idx[:slots.size] = slots
        v = np.zeros((b, width), dtype=np.float32)
        v[:slots.size] = vals
        arr = self._arr(fam)
        if self._use_pallas:
            from paddle_tpu.ops.pallas import embed_cache as pk
            out = pk.scatter_rows(arr, jnp.asarray(idx),
                                  jnp.asarray(v),
                                  interpret=self._pallas_interpret)
        else:
            out = _set_rows(arr, jnp.asarray(idx), jnp.asarray(v))
        self.scope.set_var(name, out)

    def _device_get_rows(self, fam: str, slots: np.ndarray) -> np.ndarray:
        """Read rows at slots via a pow2-padded jitted gather (padding
        points at the pad slot; those rows are sliced off host-side)."""
        b = _bucket(slots.size)
        idx = np.full(b, self.pad_slot, dtype=np.int64)
        idx[:slots.size] = slots
        arr = self._arr(fam)
        if self._use_pallas:
            from paddle_tpu.ops.pallas import embed_cache as pk
            out = pk.gather_rows(arr, jnp.asarray(idx),
                                 interpret=self._pallas_interpret)
        else:
            out = _get_rows(arr, jnp.asarray(idx))
        return np.asarray(out)[:slots.size]

    # -- the hot path ------------------------------------------------------

    def translate(self, ids, train: bool = True) -> np.ndarray:
        """Vocab ids (any shape) -> cache slot ids (same shape/dtype),
        after ensuring every id is resident. ``padding_idx`` ids map to
        the pinned-zero pad slot. ``train=True`` marks every touched
        row dirty (the dispatch that follows will update it)."""
        a = np.asarray(ids)
        flat = a.reshape(-1).astype(np.int64)
        pad_mask = (flat == self.padding_idx) if self.padding_idx >= 0 \
            else None
        valid = flat[~pad_mask] if pad_mask is not None else flat
        uniq = np.unique(valid)
        if uniq.size and (uniq[0] < 0 or uniq[-1] >= self.height):
            raise IndexError(
                f"{self.table}: ids outside [0, {self.height})")
        miss = uniq[self._slot_lut[uniq] < 0] if uniq.size else uniq
        self._hits.inc(int(uniq.size - miss.size))
        if miss.size:
            self._misses.inc(int(miss.size))
            self._ensure(miss, keep=uniq)
        # LRU touch in id order (one batch = one recency tick)
        for vid in uniq.tolist():
            self._lru.move_to_end(vid)
        if train:
            self._dirty.update(uniq.tolist())
        slots = self._slot_lut[flat]
        if pad_mask is not None:
            slots[pad_mask] = self.pad_slot
        self._occupancy.set(len(self._lru) / self.capacity)
        return slots.reshape(a.shape).astype(a.dtype)

    def _ensure(self, miss: np.ndarray, keep: np.ndarray) -> None:
        if keep.size > self.capacity:
            raise ValueError(
                f"{self.table}: one batch touches {keep.size} unique "
                f"rows > cache capacity {self.capacity} — size the "
                f"cache above the per-step working set "
                f"(docs/performance.md 'Sharded embedding tables')")
        # evict (oldest-first) until the misses fit; rows the CURRENT
        # batch hits are pinned (rotated to MRU, never evicted), and
        # dirty victims are written back BEFORE their slots are reused
        pinned = set(keep.tolist())
        evict_ids, evict_slots = [], []
        while len(self._free) < miss.size:
            vid, slot = self._lru.popitem(last=False)
            if vid in pinned:
                self._lru[vid] = slot        # re-insert at MRU end
                continue
            self._slot_lut[vid] = -1
            self._free.append(slot)
            self._evictions.inc()
            if vid in self._dirty:
                self._dirty.discard(vid)
                evict_ids.append(vid)
                evict_slots.append(slot)
        if evict_ids:
            self._writeback(np.asarray(evict_ids, dtype=np.int64),
                            np.asarray(evict_slots, dtype=np.int64))
        pulled = self.client.pull_rows(
            self.table, miss,
            families=[(fam, width) for fam, (_, width)
                      in sorted(self.families.items())])
        slots = np.asarray([self._free.pop() for _ in range(miss.size)],
                           dtype=np.int64)
        for fam in self.families:
            self._device_set_rows(fam, slots, pulled[fam])
        self._slot_lut[miss] = slots
        for vid, slot in zip(miss.tolist(), slots.tolist()):
            self._lru[vid] = slot

    def _writeback(self, vocab_rows: np.ndarray,
                   slots: np.ndarray) -> None:
        values = {fam: self._device_get_rows(fam, slots)
                  for fam in sorted(self.families)}
        self.client.push_rows(self.table, vocab_rows, values)

    def flush(self) -> int:
        """Write every dirty resident row back to its owning shard
        (end of training / before checkpointing the fleet). Returns the
        number of rows written."""
        if not self._dirty:
            return 0
        ids = np.asarray(sorted(self._dirty), dtype=np.int64)
        self._writeback(ids, self._slot_lut[ids])
        self._dirty.clear()
        return int(ids.size)

    def drop_all(self) -> int:
        """Flush dirty rows and forget every resident row (the index
        resets; device slots become reusable). The next translate pulls
        everything cold — the cache-off control arm of
        ``tools/embed_bench.py``, and the recovery path after mutating
        the fleet's rows behind the cache's back."""
        n = self.flush()
        for vid in self._lru:
            self._slot_lut[vid] = -1
        self._free = list(range(self.capacity - 1, -1, -1))
        self._lru.clear()
        self._occupancy.set(0.0)
        return n

    def warmup(self) -> None:
        """Compile the install/gather kernels for every pow2 bucket up
        to the capacity, so no steady-state step ever hits a fresh
        compile (the zero-recompile witness counts from here on)."""
        b = _bucket(1)
        top = _bucket(self.capacity)
        while b <= top:
            drop = np.full(b, self.capacity + 1, dtype=np.int64)
            pad = np.full(b, self.pad_slot, dtype=np.int64)
            for fam, (_, width) in self.families.items():
                self._device_set_rows(
                    fam, drop, np.zeros((b, width), dtype=np.float32))
                self._device_get_rows(fam, pad)
            b *= 2

    @property
    def resident(self) -> int:
        return len(self._lru)


# ---------------------------------------------------------------------------
# wiring: mark the program, swap the scope, register the cache
# ---------------------------------------------------------------------------

LOOKUP_OPS = ("lookup_table", "fused_embedding_seq_pool")

# optimizer op -> row-aligned state slots that must ride along rows on
# eviction/pull (per-row accumulators ONLY: beta-pow scalars advance
# globally and stay trainer-resident)
_ROW_STATE_SLOTS = {
    "adam": (("Moment1", "moment1"), ("Moment2", "moment2")),
    "momentum": (("Velocity", "velocity"),),
    "sgd": (),
}


def enable_sharded_table(program, scope, param_name: str, client,
                         capacity: int, use_pallas: bool = False,
                         pallas_interpret: bool = False) -> HotRowsCache:
    """Turn ``param_name`` in ``program`` into a sharded table backed by
    ``client`` (a ``ShardedTableClient`` whose shards already hold the
    seed rows — see ``ShardedTableClient.seed_from_value``) with a
    ``capacity``-row hot cache. No model change: the var desc keeps its
    ``[V, D]`` shape; this swaps the RUNTIME arrays (param + row-aligned
    optimizer state) for ``[capacity + 1, D]`` cache arrays, marks the
    var ``__sharded__`` (lowering patches marked lookup sites'
    ``padding_idx`` to the pad slot), and registers the id-feed
    translation hook the executor runs before every dispatch."""
    desc = program.desc if hasattr(program, "desc") else program
    gblock = desc.global_block
    if param_name not in gblock.vars:
        raise KeyError(f"no var {param_name!r} in program")
    v_desc = gblock.vars[param_name]
    height = int(v_desc.shape[0])
    if client.spec.height != height:
        raise ValueError(f"client spec height {client.spec.height} != "
                         f"table height {height}")

    # the lookup sites: which feed carries the ids, and padding_idx
    feed_names, paddings = set(), set()
    for block in desc.blocks:
        for op in block.ops:
            if op.type in LOOKUP_OPS and \
                    (op.inputs.get("W") or [None])[0] == param_name:
                feed_names.update(op.inputs.get("Ids") or ())
                paddings.add(op.attrs.get("padding_idx", -1))
    if not feed_names:
        raise ValueError(f"no lookup site over {param_name!r}")
    paddings.discard(None)
    paddings = {int(p) for p in paddings}
    real_pads = {p for p in paddings if p >= 0}
    if len(real_pads) > 1:
        raise ValueError(f"lookup sites over {param_name!r} disagree on "
                         f"padding_idx: {sorted(real_pads)}")
    padding_idx = real_pads.pop() if real_pads else -1

    # row-aligned optimizer state (found from the apply op, so the
    # accumulator NAMES need no convention)
    families: Dict[str, Tuple[str, int]] = {}
    for op in gblock.ops:
        if op.type in _ROW_STATE_SLOTS and \
                (op.inputs.get("Param") or [None])[0] == param_name:
            for slot, fam in _ROW_STATE_SLOTS[op.type]:
                families[fam] = ((op.inputs.get(slot) or [None])[0], None)
    widths = {}
    for fam, (name, _) in list(families.items()):
        fv = gblock.vars.get(name)
        if fv is None or name is None:
            raise ValueError(f"optimizer state {fam!r} of {param_name!r} "
                             f"has no var desc")
        widths[fam] = int(fv.shape[-1])
        families[fam] = (name, widths[fam])
    families["param"] = (param_name, int(v_desc.shape[-1]))

    # swap the runtime arrays: [capacity + 1, width] zeros, pad row last.
    # device_put COMMITS the array — every later version is a jit output
    # with the same committed sharding, so the warmup-compiled install/
    # gather kernels keep cache-hitting (uncommitted zeros here would
    # recompile each bucket once the step fn's outputs take over).
    dev = jax.devices()[0]
    from paddle_tpu.observability import memory as _obs_memory
    for fam, (name, width) in families.items():
        scope.set_var(name, jax.device_put(
            jnp.zeros((capacity + 1, width), dtype=jnp.float32), dev))
        # census: the device arrays keep the TABLE/accumulator names
        # (which would classify as param/optimizer_moment) but are the
        # hot-rows cache — pin them to the embed_cache family
        _obs_memory.register_buffer_family(name, "embed_cache")
    _obs_memory.note_scope(scope)

    cache = HotRowsCache(param_name, height, capacity, client, scope,
                         families, padding_idx=padding_idx,
                         use_pallas=use_pallas,
                         pallas_interpret=pallas_interpret)

    # program-side registration: the lowering pad-slot registry + the
    # executor feed-translation registry ride the desc (the same
    # desc-attached-registry pattern as desc._sparse_sites)
    pads = getattr(desc, "_sharded_pad_slots", None) or {}
    pads[param_name] = cache.pad_slot
    desc._sharded_pad_slots = pads
    caches = getattr(desc, "_embed_caches", None) or {}
    for fn in feed_names:
        caches[fn] = cache
    desc._embed_caches = caches
    from paddle_tpu.distributed.sharded_table import mark_sharded
    mark_sharded(desc, param_name, client.spec.num_shards)
    cache.warmup()
    return cache
