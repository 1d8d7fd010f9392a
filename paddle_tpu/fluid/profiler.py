"""Profiler (reference: python/paddle/fluid/profiler.py — profiler() context
manager :221, start/stop_profiler :125,165, cuda_profiler :39, reset_profiler;
C++ side platform/profiler.cc + CUPTI DeviceTracer + tools/timeline.py).

TPU-native design: device-side tracing is jax.profiler (XPlane → TensorBoard
/ Perfetto, replacing the CUPTI→chrome-trace path); host-side span
recording delegates to ``paddle_tpu.observability.tracing`` (the
process-default :class:`Tracer`) — lock-protected and thread-id-aware,
fixing the old module-global ``_events``/``_spans`` lists that raced the
DataLoader's produce thread and stacked every span on tid 0. The public
API here is unchanged; the sorted-summary report keeps the reference's
shape (EventSortingKey profiler.h:114)."""

from __future__ import annotations

import contextlib
from typing import Optional

from paddle_tpu.observability import tracing as _tracing

_tracer = _tracing.default_tracer()


def record_event(name: str):
    """Host-side RAII event (reference: platform/profiler.h:27 RecordEvent).
    Thread-safe: aggregates update under the tracer's lock and spans carry
    the recording thread's real id."""
    return _tracer.span(name)


def reset_profiler():
    _tracer.reset()


def export_spans(path: str):
    """Write (name, start, end, tid) span rows (csv-quoted — names are
    arbitrary caller strings) — input for ``tools/trace_collect.py
    --profile_path``."""
    import csv
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        for s in _tracer.spans():
            w.writerow([s.name, s.start_s, s.end_s, s.tid])


def spans_to_chrome_trace(spans, pid=0):
    """(name, start_s, end_s[, tid]) rows → chrome://tracing JSON dict
    (reference capability: tools/timeline.py output format; here
    ``tools/trace_collect.merge_span_files``). Rows from
    :func:`export_spans` carry the real thread id in column 4."""
    events = []
    for row in spans:
        name, start, end = row[0], float(row[1]), float(row[2])
        tid = int(row[3]) if len(row) > 3 else 0
        events.append({"name": name, "cat": "host", "ph": "X",
                       "ts": start * 1e6, "dur": (end - start) * 1e6,
                       "pid": pid, "tid": tid})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str):
    _tracer.export_chrome_trace(path)


def start_profiler(state: str = "All", tracer_option: Optional[str] = None,
                   trace_dir: Optional[str] = None):
    """reference: profiler.py:125. state/tracer_option accepted for parity;
    device tracing delegates to jax.profiler when a trace_dir is given."""
    _tracer.start()
    if trace_dir:
        import jax
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: Optional[str] = None, trace_dir=None):
    """reference: profiler.py:165 — prints the per-event summary table."""
    if trace_dir:
        import jax
        jax.profiler.stop_trace()
    if not _tracer.enabled:
        return
    _tracer.stop()
    rows = []
    for name, e in _tracer.event_stats().items():
        ave = e["total"] / max(e["calls"], 1)
        rows.append((name, e["calls"], e["total"], ave, e["min"], e["max"]))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key or "total", 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    if rows:
        print(f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Ave(s)':>12}"
              f"{'Min(s)':>12}{'Max(s)':>12}")
        for r in rows:
            print(f"{r[0]:<40}{r[1]:>8}{r[2]:>12.6f}{r[3]:>12.6f}"
                  f"{r[4]:>12.6f}{r[5]:>12.6f}")
    if profile_path:
        with open(profile_path, "w") as f:
            for r in rows:
                f.write(",".join(str(x) for x in r) + "\n")


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: str = "total",
             profile_path: Optional[str] = None,
             trace_dir: Optional[str] = None):
    """reference: profiler.py:221 fluid.profiler.profiler()."""
    reset_profiler()
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path, trace_dir=trace_dir)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """reference: profiler.py:39 — nvprof passthrough; no TPU analogue
    (use trace_dir→TensorBoard instead). Accepted as a no-op for parity."""
    yield


def device_op_stats(trace_dir: str, top: int = 0):
    """Per-HLO-op DEVICE time attribution from a jax.profiler trace
    captured via start_profiler(trace_dir=...) — the TPU delivery of the
    reference's CUPTI DeviceTracer per-op device table
    (platform/device_tracer.h:39 correlates device events back to ops;
    here the XPlane protos are parsed through xprof's hlo_stats tool).

    With multi-step device loops (exe.run iterations=N) host-side spans
    can no longer attribute time per op — the whole window is one
    dispatch; this is the device-side view that can. Returns rows of
    {name, category, self_time_us, occurrences, flop_rate, bound_by,
    bandwidth_gbs}, sorted by self time (top rows if top > 0)."""
    import glob
    import json as _json

    try:
        from xprof.convert import raw_to_tool_data as _rtd
    except ImportError as e:                       # pragma: no cover
        raise RuntimeError(
            "device_op_stats needs the xprof package (baked into this "
            "environment; pip install xprof elsewhere)") from e

    run_dirs = sorted(glob.glob(trace_dir + "/plugins/profile/*"))
    if not run_dirs:
        raise FileNotFoundError(
            f"no profile runs under {trace_dir!r} — call "
            f"start_profiler(trace_dir=...) / stop_profiler first")
    files = glob.glob(run_dirs[-1] + "/*.xplane.pb")
    if not files:
        raise FileNotFoundError(
            f"profile run {run_dirs[-1]!r} has no .xplane.pb — the "
            f"capture was interrupted before stop_profiler flushed it; "
            f"re-capture the trace")
    data, _ = _rtd.xspace_to_tool_data(files, "hlo_stats", {})
    raw = _json.loads(data)
    cols = [c["label"] for c in raw["cols"]]
    idx = {c: i for i, c in enumerate(cols)}

    def col(row, label, default=None):
        cell = row["c"][idx[label]] if label in idx else None
        return cell.get("v", default) if cell else default

    rows = []
    for r in raw["rows"]:
        rows.append({
            "name": col(r, "HLO op name", ""),
            "category": col(r, "HLO op category", ""),
            "self_time_us": float(col(r, "Total self time (us)", 0.0) or 0),
            "occurrences": int(col(r, "#Occurrences", 0) or 0),
            "flop_rate": col(r, "Model GFLOP/s"),
            "bound_by": col(r, "Bound by"),
            "bandwidth_gbs": col(r, "Measured memory BW (GiB/s)"),
        })
    rows.sort(key=lambda x: -x["self_time_us"])
    return rows[:top] if top else rows


def print_device_op_stats(trace_dir: str, top: int = 20):
    """Sorted per-op device-time table (the reference's sorted profiler
    report, but for DEVICE time — EventSortingKey profiler.h:114)."""
    all_rows = device_op_stats(trace_dir)      # parse ONCE
    total = sum(r["self_time_us"] for r in all_rows)
    rows = all_rows[:top] if top else all_rows
    print(f"{'HLO op':<44}{'Category':<22}{'Self(us)':>10}{'%':>7}"
          f"{'Bound':>9}")
    for r in rows:
        pct = 100.0 * r["self_time_us"] / total if total else 0.0
        print(f"{r['name'][:43]:<44}{r['category'][:21]:<22}"
              f"{r['self_time_us']:>10.0f}{pct:>6.1f}%"
              f"{str(r['bound_by'] or ''):>9}")
    return rows
