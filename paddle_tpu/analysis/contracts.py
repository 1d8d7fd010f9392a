"""Cross-view program contracts: one scope, many executables, one truth.

The decoder_lm serving family emits several program views (full,
prefill_paged@P, decode_paged, decode_verify_paged) that all
dispatch against ONE scope — the weights and page pools are
shared state. Nothing in the per-program verifier can see the
hazards that live BETWEEN views: a persistable whose shape/dtype drifts
across builders, a startup whose rng-salted initializers slid to
different op indices (two views would disagree on the weights they
"share"), a buffer donated in-place by one view while a sibling still
treats it as a local temp, or geometry constants (n_slots, page_size,
spec_k, prompt buckets) copy-pasted out of sync.

Two surfaces:

- :func:`validate_geometry` — THE geometry record. Every decoder_lm
  view builder normalizes and validates its constants through this one
  function (satellite: the ad-hoc checks formerly inlined in
  ``models/transformer.py``) and stamps the resulting
  :class:`GeometryRecord` on the program, where the family verifier
  cross-checks it.
- :func:`verify_family` — given ``{key: (main, startup, feed_specs,
  fetch_name)}`` (the :func:`build_decoder_lm_programs` shape), run the
  cross-view contract rules and return ``Diagnostic`` records:

  ========================  =================================================
  rule                      contract
  ========================  =================================================
  ctr-view-var-drift        every shared persistable agrees on shape/dtype/
                            persistable/sharding mark across views
  ctr-salt-misalignment     rng-bearing startup initializers for shared
                            params sit at the same startup op index (rng is
                            salted per index — drift = different weights)
  ctr-stale-donation-read   a var mutated-in-place (donated state) by one
                            view is persistable scope state in EVERY sibling
                            that touches it — never a local temp or feed
                            (which would read a stale or freed buffer)
  ctr-geometry-drift        all views' stamped GeometryRecords agree, and
                            each view's feeds/pools are consistent with its
                            record (page_table width, K+1 window, slot count,
                            one row of recurrent state per slot)
  ========================  =================================================

CLI: ``tools/proglint.py --contracts`` (default family:
``paddle_tpu.models.transformer:contracts_lint_family``). Checks are
counted in ``paddle_analysis_contract_checks_total{check}``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Tuple

from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
from paddle_tpu.analysis.rules import register_rule

DECODER_LM_MODES = ("full", "prefill_paged", "decode_paged",
                    "decode_verify_paged")

_KV_CODECS = ("none", "bf16", "int8")
_STORE_DTYPES = {"none": "float32", "bf16": "bfloat16", "int8": "int8"}


def declare_metrics():
    """Get-or-create the contract-check counter (also called from the
    exporters' catalog preregistration so a scrape shows it at zero)."""
    from paddle_tpu.observability import metrics as obs_metrics
    return obs_metrics.counter(
        "paddle_analysis_contract_checks_total",
        "cross-view program-contract checks performed (geometry "
        "normalizations and family-verifier rule runs)", ("check",))


def _count(check: str):
    try:
        declare_metrics().labels(check=check).inc()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# the geometry record
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GeometryRecord:
    """Normalized serving-geometry constants for ONE decoder_lm view.

    All derived values (cache_len default, paged pool sizing, the codec
    storage dtype, the verify window) come out of
    :func:`validate_geometry` — view builders consume this record
    instead of re-deriving, so the constants cannot drift apart."""

    mode: str
    prompt_len: int
    max_new: int
    cache_len: int
    n_slots: Optional[int] = None
    spec_k: Optional[int] = None          # verify views only
    page_size: Optional[int] = None       # paged views only
    n_pages: Optional[int] = None
    max_pages: Optional[int] = None       # pages of one worst-case slot
    kv_codec: Optional[str] = None
    store_dtype: Optional[str] = None

    @property
    def window(self) -> Optional[int]:
        """K+1: the verify window width, when this is a verify view."""
        return None if self.spec_k is None else self.spec_k + 1

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in (
            "mode", "prompt_len", "max_new", "cache_len", "n_slots",
            "spec_k", "page_size", "n_pages", "max_pages", "kv_codec",
            "store_dtype")}

    # fields every view of one family must agree on (prompt_len varies
    # per bucket; spec_k/page fields compare where present)
    SHARED_FIELDS = ("cache_len", "n_slots", "spec_k", "page_size",
                     "n_pages", "kv_codec")


def validate_geometry(mode: str, prompt_len: int, max_new: int,
                      cache_len: Optional[int] = None,
                      n_slots: Optional[int] = None,
                      page_size: Optional[int] = None,
                      n_pages: Optional[int] = None,
                      kv_codec: Optional[str] = None,
                      spec_k: Optional[int] = None) -> GeometryRecord:
    """Validate + normalize one view's geometry constants; raises
    ``ValueError`` with the same contracts the view builders used to
    enforce inline. The single source of truth for defaults: cache_len
    (prompt_len + max_new), spec_k (4), page_size (4), n_pages (every
    slot at full length) and kv_codec (FLAGS_kv_cache_codec)."""
    _count("geometry")
    if mode not in DECODER_LM_MODES:
        gone = (" — the contiguous-cache views 'prefill' and 'decode' "
                "were removed at PR 46: the slot views prefill_paged and "
                "decode_paged serve (transformer.slot_modes()), 'full' is "
                "the greedy oracle") if mode in ("prefill", "decode") else ""
        raise ValueError(f"decoder_lm mode {mode!r} not in "
                         f"{DECODER_LM_MODES}{gone}")
    if mode.endswith("_paged") and not n_slots:
        raise ValueError(f"mode {mode!r} needs n_slots")
    prompt_len = int(prompt_len)
    max_new = int(max_new)
    cache_len = int(cache_len) if cache_len else prompt_len + max_new
    if prompt_len > cache_len:
        raise ValueError(f"prompt_len {prompt_len} > cache_len "
                         f"{cache_len}")
    n_slots = int(n_slots) if n_slots else None

    if mode == "decode_verify_paged":
        # verify-window geometry: K >= 1 (K = 0 is plain decode — use
        # decode_paged), and the K+1 window must fit the
        # generated region it could commit into
        spec_k = int(spec_k) if spec_k else 4
        if spec_k < 1:
            raise ValueError(f"spec_k {spec_k} < 1 — the verify view "
                             f"needs at least one drafted token")
        if spec_k + 1 > cache_len - prompt_len + 1:
            raise ValueError(
                f"spec_k {spec_k}: the K+1={spec_k + 1} verify window "
                f"exceeds the generated region "
                f"(cache_len {cache_len} - prompt_len {prompt_len})")
    else:
        spec_k = int(spec_k) if spec_k else None

    max_pages = store_dtype = None
    if mode.endswith("_paged"):
        from paddle_tpu import flags as _flags
        page_size = int(page_size) if page_size else 4
        if cache_len % page_size:
            raise ValueError(f"page_size {page_size} must divide "
                             f"cache_len {cache_len}")
        max_pages = cache_len // page_size
        n_pages = int(n_pages) if n_pages else int(n_slots) * max_pages
        if n_pages < max_pages:
            raise ValueError(f"n_pages {n_pages} < one slot's span "
                             f"{max_pages} — no request could admit")
        kv_codec = (kv_codec if kv_codec is not None
                    else _flags.get("kv_cache_codec")) or "none"
        if kv_codec not in _KV_CODECS:
            raise ValueError(f"kv_codec {kv_codec!r} not in "
                             f"{_KV_CODECS}")
        store_dtype = _STORE_DTYPES[kv_codec]
    else:
        page_size = n_pages = kv_codec = None

    return GeometryRecord(
        mode=mode, prompt_len=prompt_len, max_new=max_new,
        cache_len=cache_len, n_slots=n_slots, spec_k=spec_k,
        page_size=page_size, n_pages=n_pages, max_pages=max_pages,
        kv_codec=kv_codec, store_dtype=store_dtype)


# ---------------------------------------------------------------------------
# the family verifier
# ---------------------------------------------------------------------------

@dataclass
class _View:
    key: str
    desc: Any                       # ir.ProgramDesc of the main program
    startup: Any                    # ir.ProgramDesc of the startup
    feed_specs: Dict[str, Any]
    fetch_name: Optional[str]
    geometry: Optional[GeometryRecord]
    sig: Any = None                 # lowering.BlockSignature


class FamilyContext:
    """What every contract rule reads: the de-aliased views of one
    program family plus their block signatures (state vs const vs feed
    classification — ``lowering.analyze_block``, no lowering or
    execution involved). Rules registered in the shared catalog no-op
    when handed the per-program ``AnalysisContext`` instead."""

    def __init__(self, family: Dict[str, tuple]):
        from paddle_tpu.core.lowering import analyze_block
        self.views: List[_View] = []
        seen_ids = set()
        for key, (main, startup, feed_specs, fetch_name) in \
                family.items():
            if id(main) in seen_ids:       # bucket aliases ("prefill_paged"
                continue                   # -> "prefill_paged@P_max")
            seen_ids.add(id(main))
            desc = main.desc if hasattr(main, "desc") else main
            sdesc = (startup.desc if hasattr(startup, "desc")
                     else startup)
            geom = getattr(main, "_geometry", None)
            v = _View(key=key, desc=desc, startup=sdesc,
                      feed_specs=dict(feed_specs or {}),
                      fetch_name=fetch_name, geometry=geom)
            try:
                v.sig = analyze_block(
                    desc.global_block, sorted(v.feed_specs),
                    [fetch_name] if fetch_name else [])
            except Exception:
                v.sig = None
            self.views.append(v)


def _var_spec(v) -> Tuple:
    shape = tuple(int(d) for d in (v.shape or []))
    return (shape, v.dtype, bool(v.persistable),
            bool((v.attrs or {}).get("__sharded__")))


@register_rule(
    "ctr-view-var-drift", Severity.ERROR,
    "a persistable shared across program views disagrees on shape/"
    "dtype/persistable/sharding mark between views", category="contracts")
def rule_view_var_drift(ctx) -> Iterable[Diagnostic]:
    if not isinstance(ctx, FamilyContext):
        return
    _count("view-var-drift")
    by_name: Dict[str, List[Tuple[str, Tuple]]] = {}
    for v in ctx.views:
        for name, vd in v.desc.global_block.vars.items():
            if vd.persistable:
                by_name.setdefault(name, []).append((v.key,
                                                     _var_spec(vd)))
    for name, specs in sorted(by_name.items()):
        if len(specs) < 2:
            continue
        distinct = {}
        for key, spec in specs:
            distinct.setdefault(spec, []).append(key)
        if len(distinct) > 1:
            rendered = "; ".join(
                f"{spec[0]}/{spec[1]}"
                f"{'/sharded' if spec[3] else ''}"
                f" in {sorted(keys)}"
                for spec, keys in distinct.items())
            yield Diagnostic(
                rule="ctr-view-var-drift", severity=Severity.ERROR,
                message=f"shared persistable {name!r} drifts across "
                        f"views: {rendered}",
                var=name,
                details={"views": {k: list(map(str, s))
                                   for s, ks in distinct.items()
                                   for k in ks}})


def _rng_inits(startup_desc) -> Dict[str, Tuple[int, str]]:
    """param name -> (startup op index, op type) for rng-bearing
    initializer ops (the per-index salt makes the index part of the
    weight's identity)."""
    out: Dict[str, Tuple[int, str]] = {}
    for i, op in enumerate(startup_desc.global_block.ops):
        if "random" not in op.type:
            continue
        for name in op.output_names():
            out.setdefault(name, (i, op.type))
    return out


@register_rule(
    "ctr-salt-misalignment", Severity.ERROR,
    "a shared parameter's rng initializer sits at different startup op "
    "indices across views — per-index rng salting would give the views "
    "different weights", category="contracts")
def rule_salt_misalignment(ctx) -> Iterable[Diagnostic]:
    if not isinstance(ctx, FamilyContext):
        return
    _count("salt-alignment")
    per_view = [(v.key, _rng_inits(v.startup)) for v in ctx.views
                if v.startup is not None]
    names: Dict[str, List[Tuple[str, Tuple[int, str]]]] = {}
    for key, inits in per_view:
        for name, where in inits.items():
            names.setdefault(name, []).append((key, where))
    for name, sites in sorted(names.items()):
        if len(sites) < 2:
            continue
        distinct = sorted({w for _k, w in sites})
        if len(distinct) > 1:
            rendered = "; ".join(
                f"op {w[0]} ({w[1]}) in "
                f"{sorted(k for k, w2 in sites if w2 == w)}"
                for w in distinct)
            yield Diagnostic(
                rule="ctr-salt-misalignment", severity=Severity.ERROR,
                message=f"rng initializer for shared param {name!r} is "
                        f"salted differently across views: {rendered}",
                var=name,
                details={"sites": {k: list(map(str, w))
                                   for k, w in sites}})


@register_rule(
    "ctr-stale-donation-read", Severity.ERROR,
    "a var mutated in place (donated state) by one view is a local "
    "temp or feed in a sibling view — the sibling reads a stale or "
    "freed buffer instead of the shared scope state",
    category="contracts")
def rule_stale_donation_read(ctx) -> Iterable[Diagnostic]:
    if not isinstance(ctx, FamilyContext):
        return
    _count("donation-coherence")
    state_in: Dict[str, List[str]] = {}
    for v in ctx.views:
        if v.sig is None:
            continue
        for name in v.sig.state_names:
            state_in.setdefault(name, []).append(v.key)
    for name, owners in sorted(state_in.items()):
        for v in ctx.views:
            if v.key in owners:
                continue
            blk = v.desc.global_block
            referenced = any(
                name in op.input_names() or name in op.output_names()
                for op in blk.ops)
            if not referenced:
                continue
            vd = blk.vars.get(name)
            as_feed = name in v.feed_specs
            as_temp = vd is not None and not vd.persistable
            if as_feed or as_temp:
                how = "a feed" if as_feed else "a non-persistable temp"
                yield Diagnostic(
                    rule="ctr-stale-donation-read",
                    severity=Severity.ERROR,
                    message=f"{name!r} is donated state (mutated in "
                            f"place) in view(s) {sorted(owners)} but "
                            f"{how} in view {v.key!r} — that view "
                            f"never observes the in-place update",
                    var=name,
                    details={"state_views": sorted(owners),
                             "offending_view": v.key, "as": how})


@register_rule(
    "ctr-geometry-drift", Severity.ERROR,
    "the views' stamped GeometryRecords disagree, or a view's feeds/"
    "pools are inconsistent with its own record", category="contracts")
def rule_geometry_drift(ctx) -> Iterable[Diagnostic]:
    if not isinstance(ctx, FamilyContext):
        return
    _count("geometry-drift")
    stamped = [(v.key, v.geometry) for v in ctx.views
               if v.geometry is not None]
    # cross-view agreement on the shared fields
    for fieldname in GeometryRecord.SHARED_FIELDS:
        values: Dict[Any, List[str]] = {}
        for key, g in stamped:
            val = getattr(g, fieldname)
            if val is not None:
                values.setdefault(val, []).append(key)
        if len(values) > 1:
            rendered = "; ".join(f"{val} in {sorted(keys)}"
                                 for val, keys in values.items())
            yield Diagnostic(
                rule="ctr-geometry-drift", severity=Severity.ERROR,
                message=f"geometry constant {fieldname!r} drifts "
                        f"across views: {rendered}",
                var=fieldname,
                details={str(v): sorted(k) for v, k in values.items()})
    # per-view internal consistency: record vs declared feeds
    for key, g in stamped:
        v = next(vv for vv in ctx.views if vv.key == key)
        pt = v.feed_specs.get("page_table")
        if pt is not None and g.page_size:
            width = int(pt[0][1])
            want = g.cache_len // g.page_size
            if width != want:
                yield Diagnostic(
                    rule="ctr-geometry-drift", severity=Severity.ERROR,
                    message=f"view {key!r}: page_table feed width "
                            f"{width} != cache_len/page_size "
                            f"({g.cache_len}/{g.page_size}={want})",
                    var="page_table", details={"view": key})
        tok = v.feed_specs.get("tok")
        if g.mode == "decode_verify_paged" and tok is not None:
            k1 = int(tok[0][1])
            if g.window is not None and k1 != g.window:
                yield Diagnostic(
                    rule="ctr-geometry-drift", severity=Severity.ERROR,
                    message=f"view {key!r}: tok window width {k1} != "
                            f"spec_k+1 ({g.window})",
                    var="tok", details={"view": key})
        # the second kind of per-slot state (a hybrid family's recurrent
        # and conv state): one row per slot, whichever view declares it
        from paddle_tpu.core.registry import slot_state_vars
        declared = {n for slots in slot_state_vars(
            v.desc.global_block).values() for names in slots.values()
            for n in names}
        for name, vd in v.desc.global_block.vars.items():
            if g.n_slots and vd.persistable and vd.shape \
                    and name in declared \
                    and int(vd.shape[0]) != g.n_slots:
                yield Diagnostic(
                    rule="ctr-geometry-drift", severity=Severity.ERROR,
                    message=f"view {key!r}: per-slot state {name!r} holds "
                            f"{vd.shape[0]} slots, the view's n_slots is "
                            f"{g.n_slots}",
                    var=name, details={"view": key})
        if g.n_slots and tok is not None and g.mode in (
                "decode_paged", "decode_verify_paged"):
            s = int(tok[0][0])
            if s != g.n_slots:
                yield Diagnostic(
                    rule="ctr-geometry-drift", severity=Severity.ERROR,
                    message=f"view {key!r}: tok slot dim {s} != "
                            f"n_slots {g.n_slots}",
                    var="tok", details={"view": key})


_CONTRACT_RULES = (
    rule_view_var_drift,
    rule_salt_misalignment,
    rule_stale_donation_read,
    rule_geometry_drift,
)


def verify_family(family: Dict[str, tuple]) -> List[Diagnostic]:
    """Run every cross-view contract rule over one program family
    (``{key: (main, startup, feed_specs, fetch_name)}``) and return
    the diagnostics, errors first."""
    import time as _time
    t0 = _time.perf_counter()
    ctx = FamilyContext(family)
    diags: List[Diagnostic] = []
    for rule in _CONTRACT_RULES:
        diags.extend(rule(ctx))
    diags.sort(key=lambda d: (-int(d.severity), d.rule, d.var or ""))
    from paddle_tpu.analysis.rules import _publish_metrics
    _publish_metrics(diags, _time.perf_counter() - t0)
    return diags
