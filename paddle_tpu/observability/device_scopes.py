"""Device scopes: the device's time under the program's own names.

``core/lowering.py:emit_op_seq`` puts every Fluid op it lowers into a
``jax.named_scope`` of the op's type (``grad/<forward type>`` for a
``__vjp__`` op) and the fused serving ops put their mechanisms into the
phases of ``PHASES`` below. XLA carries the scope into every
instruction's ``op_name``, so the names live on the DEVICE's clock: a
profiler event ``%fusion.230`` of module ``jit_lm_decode_paged`` belongs
to whatever scope the compiled text gives ``fusion.230``.

This module reads that back: ``scopes()`` is ``{module name:
{instruction name: program scope path}}`` of the executables the program
ran, parsed from their compiled text. It is built on demand — the first
call lowers and compiles again (a compile-cache hit) and parses; nothing
here runs on a dispatch or with tracing off (docs/observability.md,
"Device scopes").

A fusion is one instruction: it carries ONE scope, its root's. Where XLA
fuses the tail of one op with the head of the next, the whole fusion's
time goes to the op that owns the root.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
import weakref
import zlib
from typing import Dict, Iterable, Optional, Tuple

import jax

# The phases the fused serving ops hold, in the order the op runs them.
# ``phase`` refuses a name that is not declared here, and a block's
# module name carries a digest of its ops' rows (``module_name``): the
# persistent compile cache's key leaves metadata out, so a PR that adds
# or renames a phase would otherwise be served the executable compiled
# under the old names.
PHASES = {
    "mla_decode_paged": ("project", "index", "select", "attend"),
    "kv_attention_decode_paged": ("write", "gather", "attend"),
    "kv_attention_verify_paged": ("write", "gather", "attend"),
    "kda_decode": ("conv", "state"),
    "gdn_decode": ("conv", "state", "gate"),
    "gdn_prefill": ("conv", "scan", "gate"),
    "ssd_decode": ("conv", "state"),
    "ssd_prefill": ("conv", "scan"),
    "s6_decode": ("conv", "project", "state"),
    "s6_prefill": ("conv", "project", "scan"),
    "shortconv_decode": ("project", "conv", "out"),
    "shortconv_prefill": ("project", "conv", "out"),
    "expert_ffn_held": ("route", "up", "down", "shared"),
    "mla_full": ("project", "attend"),
    # a VARIANT of an op (``<op type>/<variant>``): the scope an op
    # lowers under, below its own, where an attribute makes it another
    # mechanism — a layer that attends a window. A row of its own, and
    # in a module's digest only where an op of the block is the variant
    # (``scope_keys``), so the modules of programs without one keep
    # their names
    "kv_attention_decode_paged/window": ("write", "gather", "attend"),
    "kv_attention_prefill_paged/window": (),
}
GRAD = "grad"
# A module beside a model's stack (the trainer's multi-token-prediction
# module) lowers every one of its ops one component deeper:
# ``<LAYER_SCOPES member>/<op type>``, ``grad/<member>/<op type>`` for
# its backward — the op attribute ``LAYER_SCOPE_ATTR`` names the member.
LAYER_SCOPES = ("mtp",)
LAYER_SCOPE_ATTR = "__layer_scope__"


def phase(op_type: str, name: str):
    """``with phase("kda_decode", "state"):`` — the named scope of one
    declared mechanism inside a fused op (trace time only)."""
    if name not in PHASES[op_type]:
        raise ValueError(
            f"{name!r} is not a declared phase of {op_type!r} "
            f"({PHASES[op_type]}): add it to device_scopes.PHASES, so "
            f"that the module names, and with them the compile-cache "
            f"keys, change")
    return jax.named_scope(name)


def variant(op_type: str, name: str, on: bool = True):
    """``with variant("kv_attention_decode_paged", "window"):`` — the
    named scope of a declared variant of an op, below the op's own
    (nothing where ``on`` is false)."""
    if not on:
        return contextlib.nullcontext()
    if f"{op_type}/{name}" not in PHASES:
        raise ValueError(f"{name!r} is not a declared variant of "
                         f"{op_type!r}: add it to device_scopes.PHASES")
    return jax.named_scope(name)


def scope_keys(op) -> Tuple[str, ...]:
    """The rows of ``PHASES`` that ``op`` lowers under: its type, and
    the variant its attributes make it."""
    if op.attrs.get("window") and f"{op.type}/window" in PHASES:
        return op.type, f"{op.type}/window"
    return (op.type,)


def op_scope(op) -> str:
    """The scope ``emit_op_seq`` lowers ``op`` in."""
    if op.type == "__vjp__":
        fwd = op.attrs.get("fwd_op") or {}
        layer = (fwd.get("attrs") or {}).get(LAYER_SCOPE_ATTR)
        return "/".join(p for p in (GRAD, layer, fwd.get("type")) if p)
    layer = op.attrs.get(LAYER_SCOPE_ATTR)
    return f"{layer}/{op.type}" if layer else op.type


def module_name(label: str, op_types: Iterable[str]) -> str:
    """What a block's jitted function is called, so that XLA names the
    module ``jit_<name>``: the block's label in identifier characters,
    and ``_s<digest>`` of the declared phases of the phased ops among
    ``op_types`` (nothing for a block without one)."""
    name = re.sub(r"\W", "_", label)
    rows = sorted((t, PHASES[t]) for t in set(op_types) if t in PHASES)
    if rows:
        name += f"_s{zlib.crc32(repr(rows).encode()) & 0xffff:04x}"
    return name


# ------------------------------------------------------- compiled text

# '  ROOT %fusion.3 = f32[8]{0} fusion(...), kind=kLoop, calls=%fc.1,
#  metadata={op_name="jit(f)/mul/dot_general" stack_frame_id=3}'
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)]+)")
_REFERS = re.compile(r"%([^\s,(){}]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_MODULE = re.compile(r"^HloModule (\S+?),")
# the continuations JAX itself puts under its own `while` and `cond`
_JAX_BODY = re.compile(r"^(body|cond|branch_\d+_fun)$")


def parse_hlo(text: str) -> Tuple[str, Dict[str, str], Dict[str, list]]:
    """(module name, {instruction name: ``op_name``, "" where it has
    none}, {instruction name: the names its line refers to}) of one
    compiled module's text, in the text's order — every instruction of
    every computation, so a fusion, a kernel (``custom-call``) and the
    instructions of a ``while`` body are all found under their own
    names. A fusion without metadata of its own takes its root's."""
    module, names, refers, roots, calls = "", {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            elif not module:
                h = _MODULE.match(line)
                if h:
                    module = h.group(1)
            continue
        name = m.group(2)
        found = _OP_NAME.search(line)
        names[name] = found.group(1) if found else ""
        refers[name] = _REFERS.findall(line, m.end())
        if found and m.group(1):
            roots[computation] = found.group(1)
        elif not found:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, called in calls.items():
        names[name] = roots.get(called, "")
    return module, names, refers


def instruction_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: program scope}) of one compiled
    module's text. An instruction the compiler added on its own — the
    ``slice-start`` / ``slice-done`` of a prefetch into fast memory, a
    relayout ``copy``: no ``op_name``, or only an argument's name —
    works for whatever uses its result, and takes the scope of the
    first instruction that does."""
    module, names, refers = parse_hlo(text)
    scopes = {n: program_scope(p) for n, p in names.items()}
    users: Dict[str, list] = {}
    for name, operands in refers.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)
    for name in reversed(list(names)):       # a use comes after its def
        if not scopes[name] and "/" not in names[name]:
            scopes[name] = next((scopes[u] for u in users.get(name, ())
                                 if scopes.get(u)), "")
    return module, scopes


def program_scope(op_name: str) -> str:
    """The program's part of an instruction's ``op_name``: from the
    first component that is a registered op type (or ``grad``) on, the
    op types and declared phases alone — ``jit(lm)/jit(main)/
    mla_decode_paged/index/jit(_take)/gather`` reads ``mla_decode_paged/
    index``. JAX's own components are dropped: the primitive at the end,
    ``jit(..)``, and ``while/body``, ``cond/branch_0_fun`` (a Fluid
    ``while`` op's scope is followed by neither). An instruction XLA
    merged from several carries their names joined by ``;``: the first
    that is the program's counts. "" where none is."""
    from paddle_tpu.core.registry import OPS
    for name in op_name.split(";"):
        parts = name.split("/")
        out, phases, op_type = [], (), ""
        i, last = 0, len(parts) - 1
        while i < last:                  # the last one is the primitive
            part = parts[i]
            if part in ("while", "cond") and _JAX_BODY.match(parts[i + 1]):
                i += 2
                continue
            if part in phases:
                out.append(part)
            elif part in OPS or part == GRAD or part in LAYER_SCOPES:
                out.append(part)
                op_type, phases = part, PHASES.get(part, ())
            elif out and f"{op_type}/{part}" in PHASES:
                out.append(part)
                phases = PHASES[f"{op_type}/{part}"]
            i += 1
        if out:
            return "/".join(out)
    return ""


# ------------------------------------------------------------- the map

_sources: "weakref.WeakSet" = weakref.WeakSet()
_held: list = []      # the sources alive when the newest trace started
_lock = threading.Lock()
# id(executable) -> (executable, module name, {instruction: scope})
_parsed: Dict[int, tuple] = {}
_last_build: Optional[dict] = None


def register(source) -> None:
    """``source.device_executables()`` gives the compiled executables
    (``jax.stages.Compiled``) the source has run: a ``CompiledBlock``'s
    record of its jitted fns registers when the block is built, an
    engine's of what it loaded ahead of time. A source refers to no
    array. Weakly held; nothing is asked of it before ``scopes()``."""
    _sources.add(source)


def hold() -> None:
    """Keep the sources that are alive now until the next call: the
    default tracer calls this when it starts, because ``scopes()`` is
    asked AFTER the traced window, when the blocks that ran in it may be
    gone (a benchmark's runner has returned). With no trace started
    nothing is ever held."""
    global _held
    _held = list(_sources)


def scopes() -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: program scope path}}`` of
    every executable the registered sources ran. The first call pays the
    lower / compile round trips and the parse; later calls only parse
    what is new. Call it AFTER a measured window, never inside."""
    global _last_build
    t0 = time.perf_counter()
    out: Dict[str, Dict[str, str]] = {}
    fresh, conflicts, seen = 0, 0, {}
    with _lock:
        for source in list(_sources):
            for exe in source.device_executables():
                hit = _parsed.get(id(exe))
                if hit is None:
                    hit = (exe, *instruction_scopes(exe.as_text()))
                    fresh += 1
                seen[id(exe)] = hit
                into = out.setdefault(hit[1], {})
                # two executables of one module name (two feed shapes
                # behind one jitted fn) number their instructions apart
                conflicts += sum(1 for n, scope in hit[2].items()
                                 if into.get(n, scope) != scope)
                into.update(hit[2])
        # what no live source yields any more is let go
        _parsed.clear()
        _parsed.update(seen)
        if fresh or _last_build is None:
            _last_build = {
                "seconds": time.perf_counter() - t0,
                "executables_parsed": fresh,
                "modules": len(out),
                "instructions": sum(len(v) for v in out.values()),
                "conflicts": conflicts}
    return out


def last_build() -> Optional[dict]:
    """What the newest ``scopes()`` call that parsed anything cost:
    seconds (lowering, the cache-hit compile, ``as_text`` and the
    parse), executables, modules, instructions, and the instructions
    that two executables of one module name put under different scopes
    (the later one won: do not trust that module's split). None before
    any."""
    return _last_build
