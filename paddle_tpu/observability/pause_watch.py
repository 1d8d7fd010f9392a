"""The host's pauses, seen from inside the program.

A device that runs dry for 100 ms while the scheduler sits inside
``serving.decode.dispatch`` reads, on a trace, as a dispatch that got
slow. It is usually nothing of the kind: the whole PROCESS stood still
(the host's CPU scheduling; PERF.md section 7 (14)), and the only span
that covers the gap is whichever one the scheduler happened to be in.

The watch is one daemon thread that sleeps a fixed tick on
``time.perf_counter()`` — the clock every span of the program is on —
and, when it wakes more than a fixed slack after it meant to, records
the LOST interval ``[intended wake, actual wake]``:

- counters ``paddle_host_pauses_total{cause}`` and
  ``paddle_host_pause_seconds_total{cause}``;
- while spans are captured, a span ``host.pause`` over the lost time
  alone — shorter than the scheduler span that contains the pause, so a
  reader that gives an idle gap to the shortest span over it gives it to
  the pause.

``cause`` is ``stopped`` when the process's CPU time advanced by less
than half the lost time — no thread of the process used the CPU: the
host did not run it, or the thread that held the GIL was BLOCKED in a
call that keeps it (the TPU runtime's start reads so for seconds) — and
``busy`` otherwise (the process ran and this thread could not: a C call
that computes with the GIL held, a collection).

The thread exists only while someone listens (:func:`hold` /
:func:`release`: the default tracer started, a span sink attached, step
telemetry or an exporter on). With everything off there is no thread
and no line on any hot path.
"""

from __future__ import annotations

import os
import resource
import threading
import time
from typing import Optional, Tuple

from paddle_tpu.observability import metrics

# A tick of 10 ms finds a pause within 10 ms of its end at 100 wake-ups
# a second; a slack of 25 ms is five times what a wake-up waits for the
# GIL at its worst (the interpreter's 5 ms switch interval) and a
# quarter of the stalls it is there to find. Constants, not flags.
TICK_S = 0.010
SLACK_S = 0.025

SPAN = "host.pause"
THREAD_NAME = "paddle-pause-watch"

PAUSES = metrics.counter(
    "paddle_host_pauses_total",
    "Times the pause watch's thread woke more than 25 ms late from a "
    "10 ms sleep, by cause: stopped (the process's CPU time stood still "
    "too: the process was not run, or the GIL's holder was blocked) | "
    "busy (the process ran and this thread could not: a C call "
    "computing with the GIL held, a collection). "
    "Counted only while the watch runs (tracing or telemetry on)",
    labelnames=("cause",))
PAUSE_SECONDS = metrics.counter(
    "paddle_host_pause_seconds_total",
    "Seconds lost in those pauses: actual wake minus intended wake",
    labelnames=("cause",))


def detect(intended: float, woke: float, cpu_before: float,
           cpu_after: float) -> Optional[Tuple[str, float]]:
    """``(cause, lost seconds)`` of a sleep that meant to end at
    ``intended`` and ended at ``woke``, the process's CPU clock read
    before and after it; None within the slack."""
    lost = woke - intended
    if lost <= SLACK_S:
        return None
    stood_still = cpu_after - cpu_before < 0.5 * lost
    return ("stopped" if stood_still else "busy"), lost


def _throttle_source() -> Optional[Tuple[str, str, float]]:
    """(file, key, milliseconds a unit) of this process's cgroup CPU
    throttling total: ``throttled_usec`` (v2) or ``throttled_time``
    (v1, nanoseconds); None where no such file is readable."""
    # a container sees its own cgroup at the mount's root whatever path
    # /proc names, so the roots are tried after the named directories
    roots = ["/sys/fs/cgroup", "/sys/fs/cgroup/cpu",
             "/sys/fs/cgroup/cpu,cpuacct"]
    named = []
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _id, controllers, path = line.rstrip("\n").split(":", 2)
                if not controllers:
                    named.append(roots[0] + path)
                elif "cpu" in controllers.split(","):
                    named += [root + path for root in roots[1:]]
    except (OSError, ValueError):
        pass
    for directory in named + roots:
        file = os.path.join(directory, "cpu.stat")
        try:
            with open(file) as f:
                keys = {ln.split()[0] for ln in f if ln.strip()}
        except OSError:
            continue
        if "throttled_usec" in keys:
            return file, "throttled_usec", 1e-3
        if "throttled_time" in keys:
            return file, "throttled_time", 1e-6
    return None


class PauseWatch:
    """The watch's thread and what it reads beside the clock."""

    def __init__(self):
        self._done = threading.Event()
        self._throttle = _throttle_source()
        self._read = self.totals()      # the reading before the newest
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=THREAD_NAME)

    def start(self):
        self._thread.start()

    def stop(self):
        self._done.set()
        self._thread.join(timeout=5)

    # -- what a detected pause is read beside ---------------------------
    def totals(self) -> dict:
        """The process's involuntary context switches and its cgroup's
        throttled time, both cumulative: read at the watch's start and
        at each detected pause, never per tick (a span carries the
        totals and their rise since the reading before it)."""
        throttled = None
        if self._throttle is not None:
            file, key, to_ms = self._throttle
            try:
                with open(file) as f:
                    for line in f:
                        if line.startswith(key + " "):
                            throttled = int(line.split()[1]) * to_ms
            except (OSError, ValueError):
                pass
        return {"nivcsw_total":
                resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw,
                "throttled_ms_total": throttled}

    def observe(self, intended: float, woke: float, cpu_before: float,
                cpu_after: float) -> Optional[str]:
        """Count and record one sleep's lost time, if it is a pause;
        its cause, or None."""
        found = detect(intended, woke, cpu_before, cpu_after)
        if found is None:
            return None
        cause, lost = found
        PAUSES.labels(cause=cause).inc()
        PAUSE_SECONDS.labels(cause=cause).inc(lost)
        tracer = _tracer()
        if tracer.active():
            before, now = self._read, self.totals()
            self._read = now
            throttled = now["throttled_ms_total"]
            tracer.record(SPAN, intended, woke, args={
                "cause": cause, "lost_ms": lost * 1e3,
                "cpu_ms": (cpu_after - cpu_before) * 1e3, **now,
                "nivcsw_rise": now["nivcsw_total"] - before["nivcsw_total"],
                "throttled_ms_rise": None if throttled is None
                else throttled - before["throttled_ms_total"]})
        return cause

    def _loop(self):
        cpu = time.process_time()
        while True:
            intended = time.perf_counter() + TICK_S
            if self._done.wait(TICK_S):
                return
            woke = time.perf_counter()
            cpu_before, cpu = cpu, time.process_time()
            self.observe(intended, woke, cpu_before, cpu)


def _tracer():
    from paddle_tpu.observability import tracing
    return tracing.default_tracer()


_lock = threading.Lock()
_holders: set = set()
_watch: Optional[PauseWatch] = None


def hold(who: str) -> None:
    """``who`` listens from now on: the first holder starts the thread."""
    global _watch
    with _lock:
        _holders.add(who)
        if _watch is None:
            _watch = PauseWatch()
            _watch.start()


def release(who: str) -> None:
    """``who`` stopped listening: the last holder's release joins the
    thread."""
    global _watch
    with _lock:
        _holders.discard(who)
        if _holders or _watch is None:
            return
        watch, _watch = _watch, None
    watch.stop()


def running() -> bool:
    return _watch is not None
