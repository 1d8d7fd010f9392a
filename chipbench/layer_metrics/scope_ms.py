"""Device time under the program's own names: milliseconds a step of
the ops that lie in a named scope of the program, whatever implements
it.

The program lowers every Fluid op inside ``jax.named_scope(<op type>)``
(``grad/<type>`` for the backward ops) and the fused serving ops name
their mechanisms below that (``mla_decode_paged/index``); XLA carries
the scope into each instruction's ``op_name`` and the program reads it
back as ``{module: {instruction: scope path}}``
(``paddle_tpu.observability.device_scopes.scopes()``, built here, AFTER
the window: a lower / compile round trip per executable, the backend's
part a cache hit). The trace gives ``[<instruction> <opcode> <shape>,
start, duration]`` per device and the program executions by module name
(``jit_lm_decode_paged_s<digest>(<id>)``), so an op is selected by
(module, instruction) -> scope, never by its result shape.

``read(obs, what, module, scopes, unit)``: among the executions wholly
inside the window of the modules whose name matches ``module`` (a
regular expression, the whole name),

- ``what="busy"``: the union of their ops' intervals over the number of
  those executions — the step as the device sees it; it needs no host
  span, so no clock offset, and the engine's ``jit_copy`` snapshots of
  the expert counters are other modules, not steps;
- ``what="ms"``: the durations of the ops whose scope path contains one
  of ``scopes`` (whole components: ``mla_decode_paged/index`` is in
  ``mla_decode_paged/index`` and not in ``mla_decode_paged``), summed,
  over the PROGRAM's count of steps ``obs["units"][unit]``;
- ``what="unscoped_pct"``: the share of the ops' summed durations that
  lies under no scope of the program.

Each is taken per device and averaged over the devices. A fusion is one
instruction and carries its root's scope: attribution is per fusion.
0.0 where the program gave a map for the module and nothing matches;
None where it gave none (a program without device scopes: the metric is
left out of the line) or no such execution lies in the window.

In a traced run of a cell of ``BENCHMARK.json`` the first call also
writes ``chiprun_out/scopes/<cell>.json``: every module with its
executions and busy time, and for each mapped module every scope path
with its op groups (instruction stem, opcode, shape), ms and instances
an execution. ``python -m chipbench.layer_metrics.scope_ms <file>``
prints it.
"""

from __future__ import annotations

import json
import os
import re
import sys

from chipbench import harness
from chipbench import trace_reduce as tr

_TABLE = "scope_ms.table"            # memo in ``obs``: one build a run
NOT_IN_MAP = "(not in map)"          # an instruction the module's map lacks
_PROGRAM_ID = re.compile(r"\(\d+\)$")
_NUMBERED = re.compile(r"\.\d+$")


def program_scopes():
    """(the program's map, what building it cost) — (None, None) for a
    program that has no device scopes."""
    try:
        from paddle_tpu.observability import device_scopes
    except ImportError:
        return None, None
    return device_scopes.scopes(), device_scopes.last_build()


def executions(red: dict) -> dict:
    """{device: [(module name, start, end)]} of the program executions
    wholly inside the window, by start."""
    out = {}
    for device, events in red["modules"].items():
        out[device] = [
            (_PROGRAM_ID.sub("", name), s, s + d)
            for name, s, d in sorted(events, key=lambda e: e[1])
            if d > 0 and s >= red["t0_ns"] and s + d <= red["t1_ns"]]
    return out


def table(obs: dict) -> dict:
    """{"map", "cost", "runs": {device: [(module, start, end)]}, "ops":
    {device: [(module, scope or None, event)]}}: each op of the window
    under the execution that contains its start, with the scope the
    program's map gives its instruction (None where the module has no
    map, "" where the instruction lies under no scope, ``NOT_IN_MAP``
    where the map does not know the instruction: unscoped too, and
    listed apart, since many of them mean that the profiler and the
    compiled text name instructions differently)."""
    if _TABLE in obs:
        return obs[_TABLE]
    red = obs["reduced"]
    scopes, cost = program_scopes()
    runs = executions(red)
    ops = {}
    for device, events in red["devices"].items():
        mine, i = [], 0
        spans = runs.get(device, [])
        for ev in events:                        # sorted by start
            while i < len(spans) and spans[i][2] <= ev[1]:
                i += 1
            if i < len(spans) and spans[i][1] <= ev[1]:
                module = spans[i][0]
                names = (scopes or {}).get(module)
                scope = None if names is None \
                    else names.get(ev[0].split()[0], NOT_IN_MAP)
                mine.append((module, scope, ev))
        ops[device] = mine
    built = obs[_TABLE] = {"map": scopes, "cost": cost, "runs": runs,
                           "ops": ops}
    cell = cell_of(obs)
    if cell:
        path = os.path.join(harness.ROOT, "chiprun_out", "scopes",
                            cell + ".json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(listing(obs, cell), f, indent=1)
    return built


def in_scope(path: str, scopes) -> bool:
    """Whether ``path`` holds one of ``scopes`` as whole components."""
    return any(f"/{s}/" in f"/{path}/" for s in scopes)


def read(obs, what, module, scopes=(), unit=None):
    tab = table(obs)
    wanted = re.compile(module)
    values = []
    for device, runs in tab["runs"].items():
        n = sum(1 for name, _s, _e in runs if wanted.fullmatch(name))
        ops = [(scope, ev) for name, scope, ev in tab["ops"][device]
               if wanted.fullmatch(name)]
        if not n:
            return None
        if what == "busy":
            values.append(tr.total((ev[1], ev[1] + ev[2])
                                   for _scope, ev in ops) / 1e6 / n)
            continue
        if any(scope is None for scope, _ev in ops):
            return None                 # the program gave no map for it
        if what == "ms":
            steps = obs["units"].get(unit)
            if not steps:
                return None
            values.append(sum(ev[2] for scope, ev in ops
                              if in_scope(scope, scopes)) / 1e6 / steps)
        elif what == "unscoped_pct":
            whole = sum(ev[2] for _scope, ev in ops)
            if not whole:
                return None
            values.append(100.0 * sum(ev[2] for scope, ev in ops
                                      if scope in ("", NOT_IN_MAP)) / whole)
        else:
            raise ValueError(f"scope_ms cannot read {what!r}")
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------- the listing

def cell_of(obs: dict):
    """The cell of ``BENCHMARK.json`` these observations are of (its
    configuration by name, its traffic file as loaded), or None."""
    config = (obs.get("config") or {}).get("name")
    for cell in harness.load_benchmark()["workloads"]:
        if cell["config"] == config and obs.get("traffic") == \
                harness.load_json("traffic", cell["traffic"] + ".json"):
            return cell["name"]
    return None


def listing(obs: dict, cell: str = "") -> dict:
    """What a builder used to rebuild by hand from a kept profile: every
    module of the window (executions, busy ms of the union of its ops,
    both averaged over the devices), and for each module the program
    mapped every scope path -> op groups ``[stem, opcode, shape, ms an
    execution, instances an execution]``, largest first."""
    tab = table(obs)
    n_dev = max(len(tab["runs"]), 1)
    modules = {}
    for device, runs in tab["runs"].items():
        for name, _s, _e in runs:
            entry = modules.setdefault(name, {
                "executions": 0.0, "busy_ms": 0.0, "ops_ms": 0.0,
                "mapped": name in (tab["map"] or {}), "scopes": {}})
            entry["executions"] += 1 / n_dev
        by_module = {}
        for name, scope, ev in tab["ops"][device]:
            by_module.setdefault(name, []).append((scope, ev))
        for name, ops in by_module.items():
            entry = modules[name]
            entry["busy_ms"] += tr.total(
                (ev[1], ev[1] + ev[2]) for _s, ev in ops) / 1e6 / n_dev
            for scope, ev in ops:
                words = ev[0].split()
                group = (_NUMBERED.sub("", words[0]), " ".join(words[1:]))
                row = entry["scopes"].setdefault(
                    "(no map)" if scope is None else scope or "(unscoped)",
                    {}).setdefault(group, [0.0, 0.0])
                row[0] += ev[2] / 1e6 / n_dev
                row[1] += 1 / n_dev
                entry["ops_ms"] += ev[2] / 1e6 / n_dev
    for entry in modules.values():
        n = entry["executions"]
        entry["scopes"] = {
            scope: {"ms": sum(r[0] for r in groups.values()) / n,
                    "groups": sorted(
                        ([stem, what, ms / n, count / n]
                         for (stem, what), (ms, count) in groups.items()),
                        key=lambda g: -g[2])}
            for scope, groups in sorted(
                entry["scopes"].items(),
                key=lambda kv: -sum(r[0] for r in kv[1].values()))}
        entry["busy_ms_per_execution"] = entry["busy_ms"] / n
        entry["ops_ms_per_execution"] = entry.pop("ops_ms") / n
    return {"cell": cell, "window_s": obs["reduced"]["window_s"],
            "units": obs.get("units"), "map_build": tab["cost"],
            "modules": dict(sorted(modules.items(),
                                   key=lambda kv: -kv[1]["busy_ms"]))}


def render(doc: dict, groups: int = 6) -> str:
    lines = [f"{doc['cell']}: window {doc['window_s']:.2f} s, units "
             f"{doc['units']}, map {doc['map_build']}"]
    for name, m in doc["modules"].items():
        lines.append(
            f"{name}: {m['executions']:.1f} executions, busy "
            f"{m['busy_ms']:.1f} ms, {m['busy_ms_per_execution']:.3f} ms "
            f"each (ops summed {m['ops_ms_per_execution']:.3f})"
            + ("" if m["mapped"] else "  [no map]"))
        for scope, s in m["scopes"].items():
            lines.append(f"  {s['ms']:9.4f} ms  {scope}")
            for stem, what, ms, count in s["groups"][:groups]:
                lines.append(f"      {ms:9.4f} ms x{count:7.1f}  "
                             f"{stem} {what}")
            if len(s["groups"]) > groups:
                rest = s["groups"][groups:]
                lines.append(f"      {sum(g[2] for g in rest):9.4f} ms  "
                             f"in {len(rest)} more groups")
    return "\n".join(lines)


if __name__ == "__main__":
    with open(sys.argv[1]) as _f:
        print(render(json.load(_f)))
