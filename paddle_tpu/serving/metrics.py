"""Serving telemetry: every metric family the model server exports,
declared in one place and preregistered in the exporter catalog
(observability/exporters.py imports this module, so a scrape shows the
full serving surface at zero before the first request).

Label conventions follow docs/observability.md: ``model`` carries the
operator-chosen model tag (bounded — the hosted-model set), ``cause`` /
``outcome`` are enum-like strings, never ids or paths.

The ``paddle_serving_compilations_total`` counter is serving's analogue
of the autotune cache's measurement counter: warmup compiles count, and
AFTER warmup the counter must stay flat across any mixed-shape load —
batches land on compiled buckets via pad-and-slice, autoregressive
decoding reuses one static-shape executable per bucket. The
:func:`forbid_compiles` guard turns that contract from observed into
ENFORCED (tests/test_serving.py), exactly like
``passes.autotune.forbid_measurement`` does for timing.
"""

from __future__ import annotations

import contextlib
import threading

from paddle_tpu.observability import metrics as _metrics

REQUEST_LATENCY = _metrics.histogram(
    "paddle_serving_request_latency_seconds",
    "End-to-end request latency (enqueue to reply ready); p50/p99 come "
    "from the bucket counts", labelnames=("model",))
REQUESTS = _metrics.counter(
    "paddle_serving_requests_total",
    "Requests by terminal outcome: ok | shed | error",
    labelnames=("model", "outcome"))
REQUESTS_APPLIED = _metrics.counter(
    "paddle_serving_requests_applied_total",
    "Requests actually EXECUTED (dedup-visible: a client retry answered "
    "from the idempotency cache does not count — the at-most-once "
    "witness the chaos suite asserts)", labelnames=("model",))
QUEUE_DEPTH = _metrics.gauge(
    "paddle_serving_queue_depth",
    "Requests waiting in the model's admission queue",
    labelnames=("model",))
QUEUE_WAIT = _metrics.histogram(
    "paddle_serving_queue_wait_seconds",
    "Admission-to-dispatch wait (enqueue until the batcher coalesces "
    "the request into a batch, or the slot scheduler pops it for "
    "admission) — the queueing-delay component the depth gauge cannot "
    "show",
    labelnames=("model",))
BATCH_OCCUPANCY = _metrics.gauge(
    "paddle_serving_batch_occupancy_ratio",
    "Real rows / bucket rows of the last dispatched batch (padding "
    "waste is 1 - occupancy)", labelnames=("model",))
BATCHES = _metrics.counter(
    "paddle_serving_batches_total",
    "Coalesced batches dispatched to an executable",
    labelnames=("model",))
COMPILATIONS = _metrics.counter(
    "paddle_serving_compilations_total",
    "Executable builds (bucket warmup, AOT-miss JIT). Must stay FLAT "
    "after warmup — the zero-steady-state-compile contract "
    "(forbid_compiles turns it into an error)",
    labelnames=("model", "kind"))
AOT_FALLBACK = _metrics.counter(
    "paddle_serving_aot_fallback_total",
    "PaddlePredictor.run dispatches that missed the AOT executable set "
    "and fell back to JIT, by cause: no_artifact | shape_miss | "
    "backend_error", labelnames=("model", "cause"))
TOKENS_GENERATED = _metrics.counter(
    "paddle_serving_tokens_generated_total",
    "Tokens emitted by the KV-cache decode path", labelnames=("model",))
DECODE_STEPS = _metrics.counter(
    "paddle_serving_decode_steps_total",
    "Single-token decode executable dispatches", labelnames=("model",))
DISPATCH_STARVED = _metrics.counter(
    "paddle_serving_dispatch_starved_total",
    "Dispatches of an engine that found the device DRY: the output of "
    "the engine's previous dispatch was already ready (one is_ready() a "
    "dispatch, no wait and no transfer), so the in-order stream had run "
    "everything queued and this dispatch starts late by what the host "
    "still had to do. view = decode | prefill. The first dispatch after "
    "the engine was empty is dry by construction; never asked under a "
    "mesh", labelnames=("model", "view"))
SCHEDULER_ERRORS = _metrics.counter(
    "paddle_serving_scheduler_errors_total",
    "Exceptions the slot scheduler's loop swallowed outside a step (a "
    "bookkeeping error): each one backs the loop off for 50 ms, which a "
    "trace shows as the span serving.sched.error", labelnames=("model",))
SAMPLING_STEPS = _metrics.counter(
    "paddle_sampling_steps_total",
    "Slot-engine decode (or verify) steps dispatched with at least one "
    "live row that samples (temperature > 0 and top_k != 1): the steps "
    "whose token_sample ran its sampled branch. Over "
    "paddle_serving_decode_steps_total it is the share of steps that "
    "paid for more than an argmax", labelnames=("model",))
PREFILLS = _metrics.counter(
    "paddle_serving_prefills_total",
    "Prefill executable dispatches (one per slot admission)",
    labelnames=("model",))
TTFT = _metrics.histogram(
    "paddle_serving_ttft_seconds",
    "Time to first token: submit to the first generated token of a "
    "request: bounded by queue wait + one prefill",
    labelnames=("model",))
INTER_TOKEN = _metrics.histogram(
    "paddle_serving_inter_token_latency_seconds",
    "Per-token gap after the first token (one observation per emitted "
    "token on the slot scheduler — the decode-step cadence)",
    labelnames=("model",))
SLOT_OCCUPANCY = _metrics.gauge(
    "paddle_serving_decode_slot_occupancy_ratio",
    "In-flight requests / decode slots of the slot pool (the in-flight "
    "batching analogue of batch occupancy)", labelnames=("model",))
SLOT_ADMISSIONS = _metrics.counter(
    "paddle_serving_slot_admissions_total",
    "Requests that JOINED a decode slot mid-flight (one per prompt "
    "prefilled into the pool)", labelnames=("model",))
SLOT_EVICTIONS = _metrics.counter(
    "paddle_serving_slot_evictions_total",
    "Slots freed, by cause: eos | max_new | cancelled | error",
    labelnames=("model", "cause"))

# -- speculative decoding families (draft-verify slot engine) -----------
# The acceptance economy of the draft-verify step: proposed counts every
# DRAFT token placed in a verify window, accepted counts the drafts the
# target model kept (accepted <= proposed; the acceptance RATE is their
# ratio). tokens_per_step observes the COMMITTED token count of each
# live slot per verify dispatch (accepted drafts + 1 bonus token), so
# sum/count is the mean acceptance length — the speedup witness
# SERVE_r06 reports. Non-speculative decode observes 1.0 per emitted
# token, keeping the family comparable across arms.
SPEC_PROPOSED = _metrics.counter(
    "paddle_serving_spec_proposed_tokens_total",
    "Draft tokens proposed into verify windows (speculative decoding)",
    labelnames=("model",))
SPEC_ACCEPTED = _metrics.counter(
    "paddle_serving_spec_accepted_tokens_total",
    "Draft tokens the target model accepted (longest-prefix match of "
    "the verify dispatch; always <= proposed)", labelnames=("model",))
TOKENS_PER_STEP = _metrics.histogram(
    "paddle_serving_tokens_per_step",
    "Tokens committed per slot per decode dispatch (1.0 on the "
    "sequential path; up to spec_k + 1 under draft-verify — sum/count "
    "is the mean acceptance length)", labelnames=("model",),
    buckets=(1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 12.0, 16.0, 24.0,
             32.0))

# -- paged KV pool families (serving/kv_pool.py) ------------------------
# The paged layout replaces the single worst-case reservation the
# paddle_hbm_kv_pool_bytes gauge reports with a page economy; these
# three gauges + the eviction counter ARE its accounting (total is
# static per model, free moves with admissions/releases, shared counts
# pages referenced by MORE THAN ONE in-flight slot — the prefix-sharing
# witness the tests refcount against).
KV_PAGES_TOTAL = _metrics.gauge(
    "paddle_kv_pages_total",
    "Pages in the model's KV page pool (static: n_pages per layer "
    "group — the capacity side of the admission rule)",
    labelnames=("model",))
KV_PAGES_FREE = _metrics.gauge(
    "paddle_kv_pages_free",
    "Pages on the free list right now (admission takes "
    "span - shared_prefix_pages of these; cached prefix pages are NOT "
    "free — they evict on demand)", labelnames=("model",))
# a pool with a window group (serving/kv_pool.py "Window group")
# publishes each group beside the pair above, which counts both
KV_GROUP_PAGES_TOTAL = _metrics.gauge(
    "paddle_kv_group_pages_total",
    "Pages of one layer group of the model's KV page pool (group: "
    "full | window; only for a pool that has a window group)",
    labelnames=("model", "group"))
KV_GROUP_PAGES_FREE = _metrics.gauge(
    "paddle_kv_group_pages_free",
    "Pages of one layer group on its free list right now",
    labelnames=("model", "group"))
KV_WINDOW_PAGES_RELEASED = _metrics.counter(
    "paddle_kv_window_pages_released_total",
    "Window-group pages a LIVE request returned because every row of "
    "theirs lies behind its window (a page taken in their place is "
    "not counted)", labelnames=("model",))
KV_WINDOW_ROWS_ATTENDED = _metrics.counter(
    "paddle_kv_window_rows_attended_total",
    "Cache rows the window layers' decode steps attended: per step, "
    "slot and window layer min(the slot's positions, the window), "
    "counted on the host from the slots' own lengths",
    labelnames=("model",))
KV_FULL_ROWS_ATTENDED = _metrics.counter(
    "paddle_kv_full_rows_attended_total",
    "Cache rows the full-attention layers' decode steps attended: per "
    "step, slot and full layer the slot's LIVE rows (its positions), "
    "counted on the host from the slots' own lengths",
    labelnames=("model",))
KV_FULL_ROWS_GATHERED = _metrics.counter(
    "paddle_kv_full_rows_gathered_total",
    "Cache rows the full-attention layers' decode steps READ: per step "
    "and full layer every row of every slot's page table, live or not, "
    "where the layer gathers its cache, and the running slots' live "
    "blocks where it attends its pages in place "
    "(paddle_kv_decode_attend_lowered_total says which; attended / "
    "gathered is the share of what a step read that held a token)",
    labelnames=("model",))
KV_ROW_BYTES = _metrics.gauge(
    "paddle_kv_row_bytes",
    "Bytes one cache position costs in one layer group of the pool, all "
    "the group's layers and both planes, codec scales included (group: "
    "full | window): the groups' layers may differ in KV heads and a "
    "value head in size from a key head, so pages turn into bytes per "
    "group", labelnames=("model", "group"))
KV_PREFIX_SHARED_PAGES = _metrics.gauge(
    "paddle_kv_prefix_shared_pages",
    "Pages physically referenced by >= 2 in-flight slots via the "
    "prompt-prefix radix tree (each counted once)",
    labelnames=("model",))
KV_PAGE_EVICTIONS = _metrics.counter(
    "paddle_kv_page_evictions_total",
    "Cached prefix pages dropped from the radix tree, by cause: "
    "capacity (LRU reclaim to satisfy an admission) | reset (engine "
    "reset/warmup scrub)", labelnames=("model", "cause"))

# -- hybrid models: recurrent state and expert layers -------------------
RECURRENT_STATE_BYTES = _metrics.gauge(
    "paddle_recurrent_state_bytes",
    "Bytes of per-slot recurrent and conv state a hybrid model keeps "
    "beside its KV pages, by the kind of mixer that keeps it "
    "(kda|gdn|ssd|s6|shortconv; "
    "static: fixed-size per slot, n_slots of them; no sample for a "
    "model with none)", labelnames=("model", "kind"))
SSD_TOKENS_SCANNED = _metrics.counter(
    "paddle_ssd_tokens_scanned_total",
    "True prompt tokens through ssd_prefill's scan, summed over the "
    "model's SSD layers (counted on the host at admission)",
    labelnames=("model",))
SSD_CHUNK_ROWS = _metrics.counter(
    "paddle_ssd_chunk_rows_total",
    "Rows ssd_prefill's chunked scan computed: the whole chunks a "
    "prompt's true length fills, summed over the model's SSD layers",
    labelnames=("model",))
GDN_TOKENS_SCANNED = _metrics.counter(
    "paddle_gdn_tokens_scanned_total",
    "True prompt tokens through gdn_prefill's chunked scan, summed over "
    "the model's GDN layers (counted on the host at admission)",
    labelnames=("model",))
GDN_CHUNK_ROWS = _metrics.counter(
    "paddle_gdn_chunk_rows_total",
    "Rows gdn_prefill's chunked scan computed: the whole blocks of chunks "
    "a prompt's true length fills (ops/gdn.py:scan_rows), summed over the "
    "model's GDN layers", labelnames=("model",))
S6_TOKENS_SCANNED = _metrics.counter(
    "paddle_s6_tokens_scanned_total",
    "True prompt tokens through s6_prefill's selective scan, summed over "
    "the model's S6 layers (counted on the host at admission)",
    labelnames=("model",))
S6_CHUNK_ROWS = _metrics.counter(
    "paddle_s6_chunk_rows_total",
    "Rows s6_prefill's scan walked: the whole chunks a prompt's true "
    "length fills, summed over the model's S6 layers",
    labelnames=("model",))
SHORTCONV_TOKENS = _metrics.counter(
    "paddle_shortconv_tokens_total",
    "True tokens through the gated short convolutions, summed over the "
    "model's conv layers, by view: a prompt's length at its prefill, the "
    "slots a decode step ran (counted on the host)",
    labelnames=("model", "view"))
LATENT_CACHE_BYTES = _metrics.gauge(
    "paddle_latent_cache_bytes",
    "Bytes of the latent-attention layers' latent planes in the page "
    "pool (one [cKV ; kR] row a position and layer, padded to whole "
    "lane tiles; static; 0 for a model with none)", labelnames=("model",))
INDEX_CACHE_BYTES = _metrics.gauge(
    "paddle_index_cache_bytes",
    "Bytes of the sparse-attention indexer's key planes in the page "
    "pool, beside the latent planes (static; 0 for a model with none)",
    labelnames=("model",))
DSA_ROWS_SCORED = _metrics.counter(
    "paddle_dsa_rows_scored_total",
    "Cache rows the sparse-attention indexer scored: per decode step "
    "the live rows of the slots it ran, times the latent-attention "
    "layers (from the engine's own lengths; nothing is fetched)",
    labelnames=("model",))
DSA_ROWS_SELECTED = _metrics.counter(
    "paddle_dsa_rows_selected_total",
    "Cache rows the sparse attention attended: per decode step and slot "
    "min(live rows, index_topk), times the latent-attention layers",
    labelnames=("model",))
MOE_EXPERT_TOKENS = _metrics.counter(
    "paddle_moe_expert_tokens_total",
    "Tokens the decode steps routed to each expert this program holds, "
    "counted on the device and brought here by "
    "SlotGenerativeModel.expert_token_counts() (off the step's path: "
    "current as of the last call)",
    labelnames=("model", "layer", "expert"))

MOE_GROUPED_ROWS = _metrics.counter(
    "paddle_moe_grouped_rows_total",
    "Assignment rows of the expert layers' grouped way (a prefill of "
    "more than ops/expert_ffn.py:DENSE_MAX_TOKENS tokens), summed over "
    "the expert layers and the prefills: rows=given is bucket tokens x "
    "top_k (the worst case, what every buffer was once sized to), "
    "rows=held what this program's experts were routed and computed "
    "(counted on the device; a padded position is routed nowhere). "
    "Brought here with paddle_moe_expert_tokens_total, both rows as of "
    "the same snapshot", labelnames=("model", "rows"))

# -- router families (serving/router.py) -------------------------------
# ``replica`` is the router-assigned slot index ("0".."N-1") — bounded
# by the pool size, stable across restarts of the replica in that slot.
ROUTER_REPLICA_UP = _metrics.gauge(
    "paddle_router_replica_up",
    "1 while the replica in this pool slot is alive AND ready (readyz "
    "true), else 0 — the router's routing-eligibility view",
    labelnames=("replica",))
ROUTER_REQUESTS = _metrics.counter(
    "paddle_router_requests_total",
    "Requests routed, by terminal outcome: ok | typed_error | "
    "unavailable", labelnames=("outcome",))
ROUTER_FAILOVERS = _metrics.counter(
    "paddle_router_failovers_total",
    "Re-dispatches of a request to another replica, by cause: "
    "conn_error | breaker_open | dead_sticky | draining",
    labelnames=("cause",))
ROUTER_DRAIN_DURATION = _metrics.histogram(
    "paddle_router_drain_duration_seconds",
    "Observed drain time of a replica (drain RPC begin to in-flight "
    "settled) during restart_replica / rolling restart")
ROUTER_RESTARTS = _metrics.counter(
    "paddle_router_replica_restarts_total",
    "Replica respawns, by cause: crash (supervisor restart-with-"
    "backoff) | rolling (operator-driven drain+replace) | oom "
    "(memdump-witnessed death, replaced with the fallback spec) | "
    "quarantine_retry (cooldown expired on a FAILED slot)",
    labelnames=("cause",))
ROUTER_REPLICA_INFLIGHT = _metrics.gauge(
    "paddle_router_replica_inflight",
    "Requests the router currently has outstanding against this pool "
    "slot — the router-side congestion view the autoscaler reads "
    "instead of object internals", labelnames=("replica",))
ROUTER_REPLICA_QUEUE_DEPTH = _metrics.gauge(
    "paddle_router_replica_queue_depth",
    "Queued requests on the replica (summed over its hosted models, "
    "polled via the stats RPC by the router's monitor thread)",
    labelnames=("replica",))
ROUTER_REPLICA_STATE = _metrics.gauge(
    "paddle_router_replica_state",
    "One-hot replica lifecycle state per pool slot: exactly one of "
    "starting | ready | draining | down | failed is 1",
    labelnames=("replica", "state"))

# -- autoscaler families (serving/autoscaler.py) ------------------------
AUTOSCALER_DECISIONS = _metrics.counter(
    "paddle_autoscaler_decisions_total",
    "Control-loop verdicts, by action: hold | scale_up | scale_down "
    "(one per step; scale actions also appear in the fleet-size trace)",
    labelnames=("action",))
AUTOSCALER_FLEET_SIZE = _metrics.gauge(
    "paddle_autoscaler_fleet_size",
    "Replica counts the reconciler sees, by kind: desired (the "
    "policy's target) | ready (routable now) | total (pool slots "
    "incl. starting/draining)", labelnames=("kind",))
AUTOSCALER_SIGNAL = _metrics.gauge(
    "paddle_autoscaler_signal",
    "The scaling signals of the last step: queue_wait_p99_s (windowed "
    "across the fleet) | queue_depth (summed)", labelnames=("signal",))
AUTOSCALER_SLO_ATTAINMENT = _metrics.gauge(
    "paddle_autoscaler_slo_attainment_ratio",
    "Fraction of windowed queue-wait observations at or under the "
    "policy SLO (1.0 with an empty window — no evidence of breach)")


class CompileForbiddenError(RuntimeError):
    """An executable build was attempted under :func:`forbid_compiles` —
    steady-state serving hit an unwarmed (model, bucket) signature."""


# PROCESS-global (depth counter + lock), NOT thread-local: the server's
# per-model batcher threads do the actual dispatching, so a guard taken
# on the caller's thread must bind them too — same shape as
# passes.autotune.forbid_measurement
_forbid_lock = threading.Lock()
_forbid_depth = 0


def compiles_forbidden() -> bool:
    return _forbid_depth > 0


@contextlib.contextmanager
def forbid_compiles():
    """Turn any serving-layer executable build inside the with-block into
    a :class:`CompileForbiddenError` — the enforcement arm of the
    zero-steady-state-compilation contract (count_compile call sites).
    Process-wide: builds attempted by the server's batcher threads while
    the guard is held are rejected too."""
    global _forbid_depth
    with _forbid_lock:
        _forbid_depth += 1
    try:
        yield
    finally:
        with _forbid_lock:
            _forbid_depth -= 1


def count_compile(model: str, kind: str):
    """Record (and, under :func:`forbid_compiles`, reject) an executable
    build. Call BEFORE the build so the forbidden case never compiles.

    What it counts is the FIRST USE of a (model, bucket) key by an
    engine — a warm-up dispatch, or an unwarmed signature at steady
    state (``kind`` then starts with ``steady_``) — not a compile: a
    warmed key the persistent cache serves still counts once, and a jit
    recompile of a warmed key (a new layout or committed-ness) does not
    count at all. Compiles themselves, by stage and by program, are
    ``paddle_compile_events_total`` / ``paddle_compile_seconds_total``
    (``observability.runtime``, the process's one ``jax.monitoring``
    listener). The chip benchmark reads this counter's delta over the
    window next to that listener's."""
    if compiles_forbidden():
        raise CompileForbiddenError(
            f"serving executable build ({kind}) for model {model!r} "
            f"attempted after warmup — steady-state serving must land "
            f"every dispatch on a warmed bucket (docs/serving.md)")
    COMPILATIONS.labels(model=model, kind=kind).inc()


def histogram_percentile(family, q: float, **labels) -> float:
    """Percentile estimate (upper bucket bound) from an exported
    histogram — how the load tests assert p50/p99 without a client-side
    timer array. Returns 0.0 with no observations."""
    hist = family.labels(**labels)
    buckets, _, count = hist.snapshot()
    if count <= 0:
        return 0.0
    target = q * count
    for ub, cum in buckets:
        if cum >= target:
            return ub
    return buckets[-1][0]


def latency_percentile(model: str, q: float) -> float:
    """Request-latency percentile (see :func:`histogram_percentile`)."""
    return histogram_percentile(REQUEST_LATENCY, q, model=model)


def queue_wait_percentile(model: str, q: float) -> float:
    """Queue-wait percentile (see :func:`histogram_percentile`)."""
    return histogram_percentile(QUEUE_WAIT, q, model=model)


def histogram_exemplar(family, bucket: str = "top", **labels):
    """The trace_id last recorded for a bucket of an exported histogram
    — ``bucket="top"`` returns the exemplar of the HIGHEST bucket that
    has one (the p99-outlier lookup recipe in docs/observability.md:
    slow sample → trace_id → grep the merged trace). Returns None when
    no exemplar was recorded."""
    ex = family.labels(**labels).exemplars()
    if not ex:
        return None
    if bucket == "top":
        return ex[max(ex)]
    return ex.get(float(bucket))
