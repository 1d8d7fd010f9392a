#!/usr/bin/env python
"""Merge per-process span spools into ONE Perfetto/chrome trace.

The distributed-tracing flow (docs/observability.md "Distributed
tracing & flight recorder"): every traced process appends finished
spans to ``FLAGS_trace_spool_dir/<role>.<pid>.jsonl`` (observability/
spool.py — wall-clock microseconds, flushed per line, so a SIGKILLed
process still leaves a complete file up to its last whole line). This
tool is the read side:

    python tools/trace_collect.py /tmp/spools          # -> spools/trace.json
    python tools/trace_collect.py /tmp/spools -o merged.json
    python tools/trace_collect.py /tmp/spools --check  # validate, no output

The merged trace gives each spool file its own process track (named
``<role> <pid>`` via process_name metadata), keeps real thread ids
within a track, and stitches CROSS-PROCESS parent edges with chrome
flow events (ph "s" at the parent span, ph "f"/bp "e" at the child),
so ui.perfetto.dev draws an arrow from the client's request span into
the server's admission/prefill/decode spans of the same trace_id.

``--check`` is the integrity gate ``tools/test_runner.py`` runs over a
smoke spool: per-file record order must be time-monotonic (completion
order, small slack for thread races), durations non-negative, every
span's ``parent_id`` must resolve to a recorded span, and every flow
id in the merged trace must pair up (one "s", one "f").
``--check --chain client,router,replica`` additionally requires one
request's span ancestry to cross those roles in order — the replicated
serving deployment's three-hop stitch (client span -> router.route ->
replica handler; docs/serving.md "Deployment").

Profiler span CSVs (``fluid.profiler.export_spans``, one per process)
merge here too, one pid lane per file (the reference's
``tools/timeline.py --profile_path trainer0=a.csv,trainer1=b.csv``
grammar; :func:`parse_profile_paths`, :func:`merge_span_files`):

    python tools/trace_collect.py --profile_path spans.csv -o out.json
    python tools/trace_collect.py \
        --profile_path trainer0=a.csv,trainer1=b.csv -o merged.json

A lane carries every span its process recorded: a rank's own events
and, because the executor records ``executor.run`` (with
``executor.prepare`` / ``executor.dispatch``) whenever a profiler is
active, those too.
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# record-order (= completion-order) timestamps may interleave slightly
# across threads: t_end is captured before the spool lock is taken, so
# a thread can finish first but write second. Anything beyond this
# slack is a real clock problem, not a race.
MONOTONIC_SLACK_US = 250_000.0


def load_spool(path: str) -> Tuple[Optional[dict], List[dict], int]:
    """Read one spool file -> (meta, spans, torn_lines).

    A torn/garbage line (the process died mid-write) is skipped and
    counted, never fatal — crash tolerance is the point of the spool.
    """
    meta = None
    spans: List[dict] = []
    torn = 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                torn += 1
                continue
            k = rec.get("k")
            if k == "meta" and meta is None:
                meta = rec
            elif k == "span":
                spans.append(rec)
    return meta, spans, torn


def find_spools(target: str) -> List[str]:
    """A directory -> its ``*.jsonl`` spool files (sorted); a file ->
    itself. Flight-recorder black boxes (``*.blackbox.jsonl``) share
    the directory when both captures point at the same place — they
    are event logs, not span spools, and are skipped."""
    if os.path.isdir(target):
        return sorted(
            os.path.join(target, n) for n in os.listdir(target)
            if n.endswith(".jsonl")
            and not n.endswith(".blackbox.jsonl"))
    return [target]


def merge(paths: List[str]) -> dict:
    """Spool files -> one chrome-trace dict (Perfetto opens it natively).

    One chrome ``pid`` lane per spool file; real thread ids inside the
    lane; span args carry trace/span/parent ids so a trace_id returned
    to a client (``ServingClient.last_trace_id``) greps straight to its
    spans; flow events stitch parent edges that cross files.
    """
    events: List[dict] = []
    # span_id -> (file index, record) across ALL files, for flow edges
    by_span_id: Dict[str, Tuple[int, dict]] = {}
    loaded = []
    for idx, path in enumerate(paths):
        meta, spans, _torn = load_spool(path)
        loaded.append((idx, path, meta, spans))
        for rec in spans:
            sid = rec.get("span_id")
            if sid:
                by_span_id[sid] = (idx, rec)

    flow_n = 0
    for idx, path, meta, spans in loaded:
        role = (meta or {}).get("role") or os.path.basename(path)
        pid = (meta or {}).get("pid", idx)
        events.append({"name": "process_name", "ph": "M", "pid": idx,
                       "args": {"name": f"{role} {pid}"}})
        tids_named = set()
        for rec in spans:
            tid = rec.get("tid", 0)
            if tid not in tids_named:
                tids_named.add(tid)
                events.append(
                    {"name": "thread_name", "ph": "M", "pid": idx,
                     "tid": tid, "args": {"name": f"thread {tid}"}})
            args = dict(rec.get("args") or {})
            for key in ("trace_id", "span_id", "parent_id"):
                if rec.get(key):
                    args[key] = rec[key]
            ev = {"name": rec["name"], "cat": "host", "ph": "X",
                  "ts": rec["ts"], "dur": rec["dur"],
                  "pid": idx, "tid": tid}
            if args:
                ev["args"] = args
            events.append(ev)
            parent = rec.get("parent_id")
            if parent and parent in by_span_id:
                p_idx, p_rec = by_span_id[parent]
                if p_idx != idx:       # a cross-process edge: draw it
                    flow_n += 1
                    common = {"name": "rpc", "cat": "trace",
                              "id": flow_n}
                    events.append(dict(
                        common, ph="s", pid=p_idx,
                        tid=p_rec.get("tid", 0), ts=p_rec["ts"]))
                    events.append(dict(
                        common, ph="f", bp="e", pid=idx, tid=tid,
                        ts=rec["ts"]))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- profiler span CSVs (one process each) ------------------------------

def parse_profile_paths(arg: str) -> List[Tuple[Optional[str], str]]:
    """'file' -> [(None, file)]; 'n1=f1,n2=f2' -> [(n1, f1), (n2, f2)]
    (the reference's argument grammar, tools/timeline.py:27-30)."""
    if "=" not in arg:
        return [(None, arg)]
    out = []
    for part in arg.split(","):
        if not part:
            continue
        name, _, path = part.partition("=")
        if not path:
            raise ValueError(
                f"bad --profile_path segment {part!r}: want name=file")
        out.append((name, path))
    return out


def merge_span_files(named_paths) -> dict:
    """[(label, span_csv_path), ...] -> one chrome trace dict with one
    pid lane per input file, labeled via process_name metadata events."""
    from paddle_tpu.fluid.profiler import spans_to_chrome_trace
    events: List[dict] = []
    for pid, (label, path) in enumerate(named_paths):
        with open(path, newline="") as f:
            rows = [row for row in csv.reader(f) if len(row) >= 3]
        events.extend(spans_to_chrome_trace(rows, pid=pid)["traceEvents"])
        if label is not None:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": label}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def check_chain(paths: List[str], chain: List[str]) -> List[str]:
    """Require at least one request whose span ancestry crosses the
    given roles in order (e.g. ``client,router,replica``): walking a
    leaf span's parents in a ``chain[-1]``-role spool must pass through
    every earlier role. This is how the router deployment proves its
    three-hop trace stitches — a broken inject/extract at any hop
    breaks the ancestry and fails the gate."""
    role_of: Dict[str, str] = {}       # span_id -> role of its spool
    recs: Dict[str, dict] = {}         # span_id -> record
    leaves: List[str] = []
    for path in paths:
        meta, spans, _ = load_spool(path)
        role = (meta or {}).get("role") or os.path.basename(path)
        for rec in spans:
            sid = rec.get("span_id")
            if not sid:
                continue
            role_of[sid] = role
            recs[sid] = rec
            if role == chain[-1]:
                leaves.append(sid)
    for sid in leaves:
        # roles along the ancestry, leaf -> root, deduping repeats
        seq: List[str] = []
        cur: Optional[str] = sid
        hops = 0
        while cur is not None and hops < 64:
            r = role_of.get(cur)
            if r is not None and (not seq or seq[-1] != r):
                seq.append(r)
            cur = (recs.get(cur) or {}).get("parent_id")
            hops += 1
        seq.reverse()                  # root -> leaf
        it = iter(seq)
        if all(role in it for role in chain):   # subsequence match
            return []
    return [f"no span chain matching {'->'.join(chain)} "
            f"(roles found: {sorted(set(role_of.values()))})"]


def check(paths: List[str],
          chain: Optional[List[str]] = None) -> List[str]:
    """Validate spools + the merged trace; returns problem strings
    (empty = pass). The test_runner gate fails on any problem."""
    problems: List[str] = []
    all_span_ids = set()
    parented = []          # (file, record) with a parent_id to resolve
    any_spans = False
    for path in paths:
        meta, spans, torn = load_spool(path)
        base = os.path.basename(path)
        if meta is None:
            problems.append(f"{base}: no meta header line")
        if torn:
            # informational only when it is the FINAL line of a killed
            # process; more than one torn line means corruption
            if torn > 1:
                problems.append(f"{base}: {torn} unparseable lines")
        last_end = None
        for i, rec in enumerate(spans):
            any_spans = True
            ts, dur = rec.get("ts"), rec.get("dur")
            if not isinstance(ts, (int, float)) or \
                    not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{base}[{i}]: bad ts/dur "
                                f"({ts!r}/{dur!r})")
                continue
            end = ts + dur
            if last_end is not None and \
                    end < last_end - MONOTONIC_SLACK_US:
                problems.append(
                    f"{base}[{i}]: non-monotonic completion time "
                    f"({end:.0f}us after {last_end:.0f}us)")
            last_end = max(last_end, end) if last_end is not None \
                else end
            sid = rec.get("span_id")
            if sid:
                all_span_ids.add(sid)
            if rec.get("parent_id"):
                parented.append((base, i, rec))
    if not any_spans:
        problems.append("no spans in any spool")
    for base, i, rec in parented:
        if rec["parent_id"] not in all_span_ids:
            problems.append(
                f"{base}[{i}]: span {rec.get('span_id')!r} "
                f"({rec['name']}) has unresolved parent "
                f"{rec['parent_id']!r}")
    # flow pairing on the merged trace: every flow id exactly one "s"
    # and one "f" (they are emitted together, so this guards merge()
    # regressions more than the data)
    flows: Dict[int, List[str]] = {}
    for ev in merge(paths)["traceEvents"]:
        if ev.get("ph") in ("s", "f"):
            flows.setdefault(ev["id"], []).append(ev["ph"])
    for fid, phs in sorted(flows.items()):
        if sorted(phs) != ["f", "s"]:
            problems.append(f"flow id {fid}: unpaired events {phs}")
    if chain:
        problems.extend(check_chain(paths, chain))
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="merge span spools into one Perfetto trace")
    ap.add_argument("spool_dir", nargs="?",
                    help="FLAGS_trace_spool_dir of the run (or one "
                         ".jsonl spool file)")
    ap.add_argument("--profile_path", default=None,
                    help="instead of spools: a span csv from "
                         "profiler.export_spans, or a comma list "
                         "trainer0=a.csv,trainer1=b.csv, merged into "
                         "one timeline with a lane per file (needs -o)")
    ap.add_argument("-o", "--out", default=None,
                    help="output path (default: <spool_dir>/trace.json)")
    ap.add_argument("--check", action="store_true",
                    help="validate spools (monotonic ts, parents "
                         "resolve, flows pair up); write nothing")
    ap.add_argument("--chain", default=None,
                    help="with --check: comma-separated roles at least "
                         "one request's span ancestry must cross in "
                         "order (e.g. client,router,replica)")
    args = ap.parse_args(argv)

    if args.profile_path:
        if not args.out or args.spool_dir or args.check:
            ap.error("--profile_path takes -o and nothing else")
        named = parse_profile_paths(args.profile_path)
        trace = merge_span_files(named)
        with open(args.out, "w") as f:
            json.dump(trace, f)
        n_x = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
        print(f"wrote {args.out} ({n_x} spans, {len(named)} process "
              f"lane{'s' if len(named) != 1 else ''}) — open in "
              f"ui.perfetto.dev")
        return 0
    if not args.spool_dir:
        ap.error("a spool directory (or --profile_path) is required")

    paths = find_spools(args.spool_dir)
    if not paths:
        print(f"no .jsonl spools under {args.spool_dir}",
              file=sys.stderr)
        return 2

    if args.check:
        chain = ([r.strip() for r in args.chain.split(",") if r.strip()]
                 if args.chain else None)
        problems = check(paths, chain=chain)
        if problems:
            for p in problems:
                print(f"CHECK FAIL: {p}", file=sys.stderr)
            return 1
        n = sum(len(load_spool(p)[1]) for p in paths)
        print(f"ok: {len(paths)} spool(s), {n} spans, all checks pass")
        return 0

    trace = merge(paths)
    out = args.out
    if out is None:
        base = args.spool_dir if os.path.isdir(args.spool_dir) \
            else os.path.dirname(args.spool_dir) or "."
        out = os.path.join(base, "trace.json")
    with open(out, "w") as f:
        json.dump(trace, f)
    n_x = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    n_flow = sum(1 for e in trace["traceEvents"] if e.get("ph") == "s")
    print(f"wrote {out} ({n_x} spans, {len(paths)} process track"
          f"{'s' if len(paths) != 1 else ''}, {n_flow} cross-process "
          f"flow edge{'s' if n_flow != 1 else ''}) — open in "
          f"ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
