"""HBM memory observability: compiled breakdowns, live-buffer census,
donation audit, OOM forensics.

`contrib/memory_usage.py` is a static per-var estimator, and fusion-era
XLA reuses buffers aggressively enough that static sums are only a
band — the *compiled* numbers are the truth. XLA exposes them per
executable (``lower(...).compile().memory_analysis()``, the memory twin
of the cost-analysis FLOPs the MFU gauge rides), so this module makes
memory a first-class telemetry layer:

- **compiled breakdown** — argument/output/temp/alias/generated-code
  bytes per jit signature, cached exactly like ``analyzed_flops``
  (:func:`compiled_memory`), exported as
  ``paddle_hbm_compiled_bytes{program,kind}``;
- **live-buffer census** — walk the noted scopes and classify every
  device-resident array by family (param, optimizer moment, KV cache,
  embed hot-rows cache, activation, other) with per-family gauges and a
  process watermark (:func:`census` / :func:`record_census`);
- **donation audit** — parse the compiled HLO's
  ``input_output_alias`` header and verify every mutated state var the
  runtime donates actually aliases (:func:`donation_audit`), counting
  ``paddle_donation_violations_total{program}``;
- **OOM forensics** — :func:`oom_dump` writes an atomic
  ``<role>.<pid>.memdump.json`` through the flight-recorder directory:
  top-N live buffers with names/families, the failing program's
  compiled breakdown, and the watermark history.

Off by default: ``FLAGS_memory_stats`` (or :func:`enable`) gates
everything, and the executor pays exactly ONE flag lookup per dispatch
when it is off — the same contract as the step sampler. CLI probes:
``tools/mem_probe.py`` (zoo sweep → MEM_r01.json) and
``tools/proglint.py --memory`` (donation-audit CI gate).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from paddle_tpu.observability import metrics
from paddle_tpu.observability.spool import default_role, wall_us

HBM_COMPILED = metrics.gauge(
    "paddle_hbm_compiled_bytes", "Compiled-executable memory breakdown "
    "from XLA memory_analysis(), per program and kind (argument/output/"
    "temp/alias/generated_code/peak; peak = argument + output - alias + "
    "temp + generated_code)", ("program", "kind"))
HBM_LIVE = metrics.gauge(
    "paddle_hbm_live_bytes", "Live device-resident bytes by buffer "
    "family from the scope census (param, optimizer_moment, kv_cache, "
    "embed_cache, activation, other)", ("family",))
HBM_WATERMARK = metrics.gauge(
    "paddle_hbm_watermark_bytes", "Process high-watermark of total "
    "census bytes since start")
HBM_KV_POOL = metrics.gauge(
    "paddle_hbm_kv_pool_bytes", "Exact KV-cache pool bytes resident for "
    "a serving model (sum of its *_cache_/*_page_ k/v arrays "
    "incl. codec scale planes); the page pool's economy is the "
    "paddle_kv_pages_* family (serving/metrics.py)", ("model",))
DONATION_VIOLATIONS = metrics.counter(
    "paddle_donation_violations_total", "State vars the runtime donated "
    "that the compiled executable did NOT alias in input_output_alias — "
    "each one is a silently-doubled buffer", ("program",))
OOM_EVENTS = metrics.counter(
    "paddle_oom_events_total", "Device OOMs (RESOURCE_EXHAUSTED at "
    "dispatch) caught by the executor's forensics path", ("program",))

# every census family renders even at 0, so a scrape shows the catalog
FAMILIES = ("param", "optimizer_moment", "kv_cache", "embed_cache",
            "activation", "other")

_force = False


def enable():
    """Switch memory telemetry on for this process (flag-free path)."""
    global _force
    _force = True


def disable():
    global _force
    _force = False


def enabled() -> bool:
    """One module bool + one flag lookup — the executor's entire
    per-dispatch cost when memory telemetry is off."""
    if _force:
        return True
    from paddle_tpu import flags
    return bool(flags.get("memory_stats"))


# -- compiled memory breakdown (cached per jit signature) -----------------

_MEM_CACHE: Dict[Any, Optional[dict]] = {}
_MEM_LOCK = threading.Lock()
_MEM_CACHE_MAX = 4096      # FIFO eviction, same bound/rationale as the
# compiled-cost cache (per-shape serving compiles must not grow forever)


def memory_cache_peek(key: Any):
    """(hit, value) — lets CompiledBlock.analyzed_memory skip argument
    gathering once a signature is resolved (per-dispatch telemetry)."""
    with _MEM_LOCK:
        if key in _MEM_CACHE:
            return True, _MEM_CACHE[key]
    return False, None


def _cache_put(key: Any, value):
    with _MEM_LOCK:
        while len(_MEM_CACHE) >= _MEM_CACHE_MAX:
            _MEM_CACHE.pop(next(iter(_MEM_CACHE)))
        _MEM_CACHE[key] = value


def compiled_memory(jit_fn, *args, cache_key: Any = None,
                    compiled=None) -> Optional[dict]:
    """Memory breakdown of ``jit_fn`` specialized to ``args`` from XLA's
    ``memory_analysis()``: {argument,output,temp,alias,generated_code,
    peak}_bytes. The lower/compile round trip runs once per ``cache_key``
    (jax's executable caches make it cheap after a real dispatch), or
    not at all where ``compiled``, a callable that returns the
    executable, is given in place of ``jit_fn`` and ``args``
    (``CompiledBlock._compile_thunk``: one kept per signature).
    None when the backend reports nothing."""
    key = cache_key if cache_key is not None else id(jit_fn)
    hit, val = memory_cache_peek(key)
    if hit:
        return val
    out: Optional[dict] = None
    try:
        exe = compiled() if compiled is not None \
            else jit_fn.lower(*args).compile()
        ma = exe.memory_analysis()
        if isinstance(ma, (list, tuple)):   # older jax: one per device
            ma = ma[0] if ma else None
        if ma is not None:
            out = {
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0) or 0),
                "output_bytes": int(
                    getattr(ma, "output_size_in_bytes", 0) or 0),
                "temp_bytes": int(
                    getattr(ma, "temp_size_in_bytes", 0) or 0),
                "alias_bytes": int(
                    getattr(ma, "alias_size_in_bytes", 0) or 0),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0) or 0),
            }
            # donated buffers alias: they are counted in argument_bytes
            # AND output_bytes but occupy HBM once
            out["peak_bytes"] = (
                out["argument_bytes"] + out["output_bytes"]
                - out["alias_bytes"] + out["temp_bytes"]
                + out["generated_code_bytes"])
    except Exception:
        out = None
    _cache_put(key, out)
    return out


def sharded_state_bytes(block, shardings: Dict[str, Any]) -> int:
    """Analytic PER-DEVICE bytes of a sharded state/const set: for each
    var, total bytes divided by the product of the mesh-axis sizes its
    PartitionSpec names. This is the cheap pre-compile estimator the
    HBM-budget ladder (core/lowering.py CompiledBlock._plan_under_budget)
    ranks plans with — params + optimizer moments dominate a training
    step's footprint; activations/temps are confirmed post-hoc by
    :func:`compiled_memory`. Vars with dynamic dims are skipped."""
    import numpy as np
    total = 0
    for name, sh in shardings.items():
        if not block.has_var(name):
            continue
        v = block.var(name)
        shape = v.shape or ()
        if not shape or any(d is None or d <= 0 for d in shape):
            continue
        try:
            itemsize = np.dtype(v.dtype or "float32").itemsize
        except TypeError:
            itemsize = 4
        nbytes = int(np.prod(shape)) * itemsize
        mesh = getattr(sh, "mesh", None)
        spec = tuple(getattr(sh, "spec", ()) or ())
        factor = 1
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                if ax is not None and mesh is not None \
                        and ax in mesh.shape:
                    factor *= int(mesh.shape[ax])
        total += nbytes // max(factor, 1)
    return total


def set_compiled_gauges(program: str, breakdown: Optional[dict]):
    if not breakdown:
        return
    for k, v in breakdown.items():
        kind = k[:-len("_bytes")] if k.endswith("_bytes") else k
        HBM_COMPILED.labels(program=program, kind=kind).set(v)


# -- donation audit -------------------------------------------------------

# ENTRY parameter lines carry jax's pytree arg paths as op_name
# metadata — fn(state, consts, feeds, step_seed) names them
# "state['w']" / "feeds['x']". Inner fusion-computation parameters have
# unrelated or absent op_name, so the (state|consts|feeds)[ anchor plus
# the ENTRY-region scan below keeps them out.
_HLO_PARAM_RE = re.compile(
    r"parameter\((\d+)\)[^\n]*?op_name=\"(state|consts|feeds)"
    r"\[\\?['\"]([^'\"\\\]]+)")


def parse_hlo_aliasing(hlo_text: str
                       ) -> Tuple[Dict[Tuple[str, str], int], set]:
    """({(tree, var_name): entry_param_number}, {aliased_param_numbers})
    from compiled HLO text. The alias header looks like
    ``input_output_alias={ {1}: (0, {}, may-alias), ... }`` — output
    tuple index → (parameter number, index path)."""
    aliased = set()
    i = hlo_text.find("input_output_alias={")
    if i >= 0:
        j = i + len("input_output_alias={")
        depth, k = 1, j
        while k < len(hlo_text) and depth:
            c = hlo_text[k]
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
            k += 1
        body = hlo_text[j:k - 1]
        aliased = {int(g) for g in re.findall(r"\((\d+),\s*\{", body)}
    params: Dict[Tuple[str, str], int] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            m = _HLO_PARAM_RE.search(line)
            if m:
                params[(m.group(2), m.group(3))] = int(m.group(1))
    return params, aliased


def donation_audit(lower_text: Callable[[], str],
                   state_names: Iterable[str], program: str = "",
                   cache_key: Any = None) -> dict:
    """Verify the donated state vars actually alias in the compiled
    executable. ``lower_text`` produces the HLO text lazily (the
    lower/compile trip only runs on a cache miss). A state var jit
    pruned entirely (keep_unused=False drops unused args) has no ENTRY
    parameter and is *skipped*, not flagged. Returns {program, expected,
    aliased, violations, skipped} and counts
    paddle_donation_violations_total once per cache fill."""
    if cache_key is not None:
        hit, val = memory_cache_peek(cache_key)
        if hit:
            return val
    names = list(state_names)
    try:
        params, aliased_nums = parse_hlo_aliasing(lower_text())
    except Exception as e:
        result = {"program": program, "error": str(e)[:200],
                  "expected": names, "aliased": [], "violations": [],
                  "skipped": names}
        if cache_key is not None:
            _cache_put(cache_key, result)
        return result
    ok, violations, skipped = [], [], []
    for n in names:
        pnum = params.get(("state", n))
        if pnum is None:
            skipped.append(n)
        elif pnum in aliased_nums:
            ok.append(n)
        else:
            violations.append(n)
    result = {"program": program, "expected": names, "aliased": ok,
              "violations": violations, "skipped": skipped}
    if violations:
        DONATION_VIOLATIONS.labels(
            program=program or "unknown").inc(len(violations))
    if cache_key is not None:
        _cache_put(cache_key, result)
    return result


# -- live-buffer census ---------------------------------------------------

_SCOPES: "weakref.WeakSet" = weakref.WeakSet()
_FAMILY_OVERRIDES: Dict[str, str] = {}
_PARAM_NAMES: set = set()
_WATERMARK_HIST: deque = deque(maxlen=256)
_watermark_peak = 0
_CENSUS_LOCK = threading.Lock()

# matches the slot engine's page pools (_page_k_0), the paged codec's
# scale planes (_page_ks_0 / _page_vs_0), a latent-attention layer's
# latent and indexer-key planes (_page_c_0 / _page_i_0) — all kv_cache
# family
_KV_RE = re.compile(r"_page_((k|v)s?|c|i)_\d+$")
# optimizer accumulators are '<param>_<kind>_N' (fluid/optimizer.py
# _add_accumulator); the kinds below are every _add_accumulator call site
_ACC_RE = re.compile(
    r"_(velocity|moment1|moment2|beta1_pow_acc|beta2_pow_acc|moment|"
    r"inf_norm|avg_squared_grad|avg_squared_update|mean_square|momentum|"
    r"mean_grad|squared|linear)_\d+$")
_PARAM_NAME_RE = re.compile(r"\.(w|b)_\d+$")


def note_scope(scope):
    """Register a scope for the census walk (weakly held)."""
    _SCOPES.add(scope)


def register_buffer_family(name: str, family: str):
    """Pin a scope var name to a census family — the embed hot-rows
    cache registers its device arrays here (their names are the TABLE's,
    which would otherwise classify as a parameter)."""
    _FAMILY_OVERRIDES[name] = family


def note_params(names: Iterable[str]):
    """Teach the classifier which names are parameters (the executor
    feeds each compiled block's is_parameter vars through here)."""
    _PARAM_NAMES.update(names)


def classify(name: str) -> str:
    fam = _FAMILY_OVERRIDES.get(name)
    if fam:
        return fam
    if _KV_RE.search(name):
        return "kv_cache"
    if _ACC_RE.search(name):
        return "optimizer_moment"
    if name.endswith("@GRAD"):
        return "activation"
    if name in _PARAM_NAMES or _PARAM_NAME_RE.search(name):
        return "param"
    return "other"


def census(scopes=None) -> dict:
    """Walk scopes (noted ones by default) and classify every array:
    {families: {family: bytes}, total_bytes, buffers: [...desc, largest
    first]}. Arrays are deduped by identity — a var visible in a parent
    and child scope counts once."""
    if scopes is None:
        scopes = list(_SCOPES)
    seen = set()
    fams: Dict[str, int] = {}
    buffers: List[dict] = []
    for sc in scopes:
        if sc is None:
            continue
        it = getattr(sc, "iter_vars", None)
        items = it() if it is not None else getattr(sc, "_vars", {}).items()
        for name, v in items:
            nb = int(getattr(v, "nbytes", 0) or 0)
            if nb <= 0:
                continue
            key = id(v)
            if key in seen:
                continue
            seen.add(key)
            fam = classify(name)
            fams[fam] = fams.get(fam, 0) + nb
            buffers.append({
                "name": name, "family": fam, "bytes": nb,
                "shape": [int(d) for d in (getattr(v, "shape", ()) or ())],
                "dtype": str(getattr(v, "dtype", ""))})
    buffers.sort(key=lambda b: -b["bytes"])
    return {"families": fams,
            "total_bytes": sum(fams.values()),
            "buffers": buffers}


def record_census(scope=None) -> dict:
    """Take a census (noting ``scope`` first) and publish it: per-family
    gauges, the watermark gauge, and a history sample."""
    global _watermark_peak
    if scope is not None:
        note_scope(scope)
    cen = census()
    fams = cen["families"]
    for fam in set(FAMILIES) | set(fams):
        HBM_LIVE.labels(family=fam).set(fams.get(fam, 0))
    total = cen["total_bytes"]
    with _CENSUS_LOCK:
        _WATERMARK_HIST.append(
            {"t": wall_us(time.perf_counter()), "bytes": total})
        if total > _watermark_peak:
            _watermark_peak = total
    HBM_WATERMARK.set(_watermark_peak)
    return cen


def watermark() -> int:
    return _watermark_peak


def kv_pool_bytes(scope, model: str = "") -> int:
    """Sum the KV-cache/slot-pool arrays resident in ``scope`` and set
    the exact-bytes gauge for ``model``. Serving engines call this after
    their pools exist (post-startup / post-first-prefill)."""
    total = 0
    it = getattr(scope, "iter_vars", None)
    items = it() if it is not None else getattr(scope, "_vars", {}).items()
    for name, v in items:
        if _KV_RE.search(name) or _FAMILY_OVERRIDES.get(name) == "kv_cache":
            total += int(getattr(v, "nbytes", 0) or 0)
    if model:
        HBM_KV_POOL.labels(model=model).set(total)
    return total


def dump_section() -> dict:
    """The ``memory`` block flight-recorder dumps embed: census
    families + top buffers + watermark history."""
    cen = census()
    with _CENSUS_LOCK:
        hist = list(_WATERMARK_HIST)
    return {"families": cen["families"],
            "total_bytes": cen["total_bytes"],
            "top_buffers": cen["buffers"][:10],
            "watermark_bytes": _watermark_peak,
            "watermark_history": hist[-32:]}


def snapshot() -> dict:
    """The JSON document the /memory scrape route serves."""
    cen = census()
    with _CENSUS_LOCK:
        hist = list(_WATERMARK_HIST)
    return {"families": cen["families"],
            "total_bytes": cen["total_bytes"],
            "top_buffers": cen["buffers"][:20],
            "watermark_bytes": _watermark_peak,
            "watermark_history": hist}


# -- OOM forensics --------------------------------------------------------

def is_oom_error(e: BaseException) -> bool:
    """Device OOM (XLA RESOURCE_EXHAUSTED) or the host analogue the
    chaos harness injects (MemoryError)."""
    if isinstance(e, MemoryError):
        return True
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def oom_dump(cb, scope, exc, feeds=None, iterations: int = 1,
             stacked=False) -> Optional[str]:
    """Write ``<role>.<pid>.memdump.json`` (atomic: tmp + fsync +
    replace) into the flight-recorder directory: the failing program's
    compiled breakdown, top live buffers by bytes with families, and
    the watermark history. Gated on the flight recorder / its dir flag
    or :func:`enabled` — and NEVER raises (it runs inside the
    executor's except path; the original error must propagate)."""
    try:
        from paddle_tpu import flags
        from paddle_tpu.observability import flight_recorder
        rec = flight_recorder.current()
        dirpath = (os.path.dirname(rec.dump_path) if rec is not None
                   else (flags.get("flight_recorder_dir") or None))
        if dirpath is None and not enabled():
            return None
        program = getattr(cb, "obs_label", None) or "unknown"
        OOM_EVENTS.labels(program=program).inc()
        cen = census(list(_SCOPES) + ([scope] if scope is not None
                                      else []))
        breakdown = None
        try:
            # memory_analysis is compiler-side (allocates no device
            # buffers) and usually already cached from telemetry
            breakdown = cb.analyzed_memory(scope, feeds or {},
                                           iterations, stacked)
        except Exception:
            breakdown = None
        with _CENSUS_LOCK:
            hist = list(_WATERMARK_HIST)
        role = rec.role if rec is not None else default_role()
        doc = {"role": role, "pid": os.getpid(), "reason": "oom",
               "wall_us": wall_us(time.perf_counter()),
               "program": program, "error": str(exc)[:500],
               "exc_type": type(exc).__name__,
               "compiled": breakdown,
               "families": cen["families"],
               "total_bytes": cen["total_bytes"],
               "top_buffers": cen["buffers"][:20],
               "watermark_bytes": _watermark_peak,
               "watermark_history": hist}
        path = None
        if dirpath:
            os.makedirs(dirpath, exist_ok=True)
            path = os.path.join(dirpath,
                                f"{role}.{os.getpid()}.memdump.json")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        flight_recorder.note("oom", program=program,
                             total_bytes=cen["total_bytes"],
                             memdump=path or "")
        if rec is not None:
            rec.dump("oom")
        return path
    except Exception:
        return None


def _reset_for_tests():
    """Test isolation: clear registries, caches, and watermark state."""
    global _watermark_peak, _force
    _force = False
    _FAMILY_OVERRIDES.clear()
    _PARAM_NAMES.clear()
    with _MEM_LOCK:
        _MEM_CACHE.clear()
    with _CENSUS_LOCK:
        _WATERMARK_HIST.clear()
        _watermark_peak = 0
    for sc in list(_SCOPES):
        _SCOPES.discard(sc)
