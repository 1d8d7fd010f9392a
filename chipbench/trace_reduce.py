"""From a profiler trace to numbers: device busy and idle time, the
operations that took it, kernel and collective time, and the idle gaps
attributed to what the host was doing.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain dict
(``jax.profiler.ProfileData``, nothing else); every reduction below
works on that dict, so a small recorded trace kept as JSON checks them
(tests/chipbench). Times inside are nanoseconds on the profiler's clock.

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
CUSTOM_CALL = "tpu_custom_call"
COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)? ")
# '%fn.22 = f32[98304,1024]{1,0:T(8,128)} custom-call(...), custom_call_
# target="tpu_custom_call", ...' as the profiler names a device op
_HLO = re.compile(r"^%(\S+) = (.*?)\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
# ops that only hold other ops (a scan's `while` spans its whole body)
CONTAINER = re.compile(r"^\S+ (while|conditional|call) ")
UNATTRIBUTED = "unattributed"
BETWEEN_OPS = "between_ops_under_5us"
SHORT_GAP_NS = 5000.0


def load_xplane(path: str) -> dict:
    """The device planes' op lines and the host's annotated threads, as
    plain lists. The profiler names a device op by its whole HLO text;
    ``short_op_name`` keeps instruction, opcode and result shape, and
    ``tpu_custom_call`` where the op is a Pallas kernel, so that a kernel
    is recognisable whatever the compiler numbered it."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                            MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if device and line.name != MODULES_LINE:
                    name = short_op_name(name)
                elif device:
                    pass
                elif not name.startswith(("chipbench.", "serving.")):
                    continue
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_op_name(hlo: str) -> str:
    """``'fn.22 custom-call f32[98304,1024] tpu_custom_call'`` from the
    profiler's HLO text (unchanged if it is not HLO text)."""
    m = _HLO.match(hlo)
    if not m:
        return hlo
    shape = _SHAPE.search(m.group(2))
    short = f"{m.group(1)} {m.group(3)} {shape.group(0) if shape else ''}"
    if CUSTOM_CALL in hlo:
        short += " " + CUSTOM_CALL
    return short.strip() + " "


# ------------------------------------------------------------- selection

def device_ops(trace: dict, line_name: str = OPS_LINE) -> dict:
    """{device plane name: the events of one of its lines (the ops by
    default, or the program executions: ``MODULES_LINE``), by start}.
    Ops that only hold other ops are left out, so that time inside a
    scan in which no op of its body runs counts as idle."""
    out = {}
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        events = [e for line in plane["lines"] if line["name"] == line_name
                  for e in line["events"] if not CONTAINER.match(e[0])]
        out[plane["name"]] = sorted(events, key=lambda e: e[1])
    return out


def ops_of_spans(red: dict, spans) -> tuple:
    """({device: the ops of the program executions that ``spans``
    caused}, executions per device). The device's clock and the host's
    differ by a millisecond or two in one trace, so an op is not matched
    to a host span by its own start: a whole program execution (tens to
    hundreds of milliseconds) goes to the spans it overlaps for more
    than half its length, and an op to the execution that contains it —
    both on the device's clock. Only executions wholly inside the window
    count, so that time per execution is of whole executions."""
    spans = union(spans)
    out, n = {}, []
    for name, events in red["devices"].items():
        mine = [(s, s + d) for _n, s, d in red["modules"][name]
                if d > 0 and s >= red["t0_ns"] and s + d <= red["t1_ns"]
                and d - total(subtract([(s, s + d)], spans)) > 0.5 * d]
        out[name] = starting_within(events, mine)
        n.append(len(mine))
    return out, sum(n) / len(n)


def host_marks(trace: dict, names) -> dict:
    """Start of the first host event of each name (the window marks)."""
    found = {}
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, _dur in line["events"]:
                if name in names and name not in found:
                    found[name] = start
    missing = [n for n in names if n not in found]
    if missing:
        raise ValueError(f"the trace holds no host event named {missing}")
    return found


def clip(events, t0: float, t1: float):
    """[start, end) intervals of ``events`` cut to the window."""
    out = []
    for _name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((a, b))
    return out


def starting_within(events, spans):
    """The events that start inside any of the [a, b) ``spans``."""
    spans = sorted(spans)
    out, i = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1]:
            out.append(ev)
    return out


def is_custom_call(event) -> bool:
    return CUSTOM_CALL in event[0]


def is_collective(event) -> bool:
    return bool(COLLECTIVE.search(event[0]))


def collective_intervals(trace: dict, t0: float, t1: float) -> dict:
    """{device: [a, b) intervals in which a collective was in flight}:
    the synchronous ones from the op line, the asynchronous ones
    (``-start`` to ``-done``) from the async line."""
    out = {}
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        events = []
        for line in plane["lines"]:
            for ev in line["events"]:
                m = COLLECTIVE.search(ev[0])
                if m and (line["name"] == ASYNC_LINE or not m.group(2)):
                    events.append(ev)
        out[plane["name"]] = clip(events, t0, t1)
    return out


# ------------------------------------------------------------ arithmetic

def union(intervals):
    """Disjoint sorted union of [a, b) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(intervals, holes):
    """The part of ``intervals`` that no interval of ``holes`` covers."""
    out = []
    holes = union(holes)
    for a, b in union(intervals):
        for ha, hb in holes:
            if hb <= a or ha >= b:
                continue
            if ha > a:
                out.append([a, ha])
            a = max(a, hb)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def gaps(busy, t0: float, t1: float):
    """The idle intervals of the window, given the busy union."""
    return subtract([(t0, t1)], busy)


def attribute_gaps(idle, host_spans):
    """Seconds of idle time by what the host was doing: each part of a
    gap goes to the SHORTEST host span that covers it (the innermost of
    nested spans); what no span covers is ``unattributed``. Gaps under
    5 us are the device's own pauses between two ops of one program and
    are summed under one name."""
    seconds = {}

    def add(name, ns):
        if ns > 0:
            seconds[name] = seconds.get(name, 0.0) + ns / 1e9

    spans = sorted(set(map(tuple, host_spans)), key=lambda s: s[2] - s[1])
    for a, b in idle:
        if b - a < SHORT_GAP_NS:
            add(BETWEEN_OPS, b - a)
            continue
        rest = [[a, b]]
        for name, sa, sb in spans:
            if not rest:
                break
            if sb <= a or sa >= b:
                continue
            left = subtract(rest, [(sa, sb)])
            add(name, total(rest) - total(left))
            rest = left
        add(UNATTRIBUTED, total(rest))
    return seconds


def top(seconds_by_name: dict, n: int = 10):
    rows = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in rows]


def reduce_window(trace: dict, t0: float, t1: float, host_spans) -> dict:
    """What the result line's ``device`` and ``breakdown`` carry, and
    the per-device op lists the metric readers select from: busy time is
    the union of the op intervals of a device inside [t0, t1), averaged
    over the devices."""
    per_device = device_ops(trace)
    if not per_device:
        raise ValueError("the trace holds no device plane")
    busy_s, by_op, idle_by_span = [], {}, {}
    for events in per_device.values():
        busy = union(clip(events, t0, t1))
        busy_s.append(total(busy) / 1e9)
        for ev, (a, b) in _clipped(events, t0, t1):
            by_op[ev[0]] = by_op.get(ev[0], 0.0) + (b - a) / 1e9
        for name, sec in attribute_gaps(gaps(busy, t0, t1),
                                        host_spans).items():
            idle_by_span[name] = idle_by_span.get(name, 0.0) + sec
    n = len(per_device)
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy_s) / n,
            "top_ops": top({k: v / n for k, v in by_op.items()}),
            "idle_gaps": top({k: v / n for k, v in idle_by_span.items()}),
            "devices": per_device,
            "modules": device_ops(trace, MODULES_LINE),
            "t0_ns": t0, "t1_ns": t1,
            "host_spans": list(host_spans)}


def _clipped(events, t0, t1):
    for ev in events:
        a, b = max(ev[1], t0), min(ev[1] + ev[2], t1)
        if b > a:
            yield ev, (a, b)


def mean_seconds(red: dict, keep, spans=None) -> float:
    """Seconds of the selected ops (``keep(event)``) inside the window —
    only those of the program executions ``spans`` caused, if given — summed per device
    and averaged over the devices."""
    per_device = red["devices"] if spans is None \
        else ops_of_spans(red, spans)[0]
    sums = [sum(b - a for ev, (a, b) in
                _clipped(events, red["t0_ns"], red["t1_ns"])
                if keep(ev)) / 1e9 for events in per_device.values()]
    return sum(sums) / len(sums)


def busy_seconds(red: dict, spans=None) -> float:
    """Seconds in which any op ran (the union, so overlap counts once) —
    only ops of the program executions ``spans`` caused, if given —
    averaged over the devices."""
    per_device = red["devices"] if spans is None \
        else ops_of_spans(red, spans)[0]
    sums = [total(clip(events, red["t0_ns"], red["t1_ns"])) / 1e9
            for events in per_device.values()]
    return sum(sums) / len(sums)


def spans_named(red: dict, prefix: str):
    return [(a, b) for name, a, b in red["host_spans"]
            if name.startswith(prefix)]
