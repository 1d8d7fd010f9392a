"""What every runner shares: the cell's files found by name, the device
check, the compile cache and compile counters, the set-up phase clock,
the traced window with its clock alignment, and the one result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
those are files of their own (README.md).
"""

from __future__ import annotations

import contextlib
import glob
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PHASES = ("cache", "build", "startup", "check", "warm", "prime")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Refused(Exception):
    """The run may not produce a result (no accelerator, unknown device,
    a missing file): exit code 1 and no result line."""


def process_start_unix() -> float:
    """When the kernel started this process (10 ms ticks), so that the
    interpreter's own start-up counts as set-up."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(line.split()[1]) for line in f
                     if line.startswith("btime"))
    return btime + ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise Refused(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, name: str):
    """(cell, configuration, traffic) of the cell ``name``; the files
    are found by the names the cell gives."""
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; have "
                      f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metrics_of(bench: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` that the cell reports: those that list
    it, and those that list no cells at all."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def runner_of(config: dict):
    return importlib.import_module("chipbench.runners." + config["runner"])


def generator_of(traffic: dict):
    return importlib.import_module(
        "chipbench.generators." + traffic["generator"])


# ----------------------------------------------------------------- device

def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")["device_kinds"]
    if device_kind not in table:
        raise Refused(f"device_kind {device_kind!r} is not in "
                      f"chipbench/peaks.json ({sorted(table)})")
    return table[device_kind]


def attach(chips: int, allow_cpu: bool = False):
    """The devices the cell runs on. Anything but ``chips`` or more TPU
    devices of a known kind is refused; ``allow_cpu`` is for the unit
    tests' tiny runs alone (never reachable from the command line)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Refused(f"no accelerator: JAX reports platform "
                      f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX reports "
                      f"{len(devs)}")
    if devs[0].platform == "tpu":
        peaks_for(devs[0].device_kind)
    return devs[:chips]


def device_record(devices) -> dict:
    import jax
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak}


def place_compile_cache() -> str:
    """The program's own placement (``JAX_COMPILATION_CACHE_DIR`` if the
    machine sets it, else ``<checkout>/.jax_cache``) with JAX's size cap
    lifted: the directory then grows without bound (the four cells'
    executables took 381 MB; README.md tells the operator). A cap was
    tried and taken back (PERF.md, PR 23): under one JAX keeps an
    ``-atime`` file beside every entry, and in a directory that holds
    entries written without a cap (every machine these cells have run
    on) each write then fails, so every run compiles again; under the
    chip machine's own 192 MiB the trainer's ~240 MiB of executables
    evict each other."""
    import jax
    from paddle_tpu.utils import chip
    path = chip.compile_cache_dir()
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileWatch:
    """jit-cache misses (``requests``: each asks the backend for an
    executable, whether or not the persistent cache then serves it) and
    the persistent-cache hits among them."""

    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, _seconds, **_):
        self.requests += event == _COMPILE_EVENT

    def _on_event(self, event, **_):
        self.cache_hits += event == _CACHE_HIT_EVENT


# --------------------------------------------------------------- the run

class Run:
    """One run of one cell: what the command line gave, the phase clock,
    and the traced window. A runner fills ``obs`` and returns it.
    ``t_start`` is when the process started."""

    def __init__(self, bench, cell, config, traffic, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 allow_cpu: bool = False):
        self.bench, self.cell = bench, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.t_start = t_start
        self.phase_s = {p: 0.0 for p in PHASES}
        self.compiles = CompileWatch()
        self.devices = attach(cell["chips"], allow_cpu)
        # Set-up is timed from HERE: the interpreter's start, the import
        # of JAX and the TPU runtime's own start-up took 10 to 22 s on the
        # chip machine from one run to the next (PERF.md, PR 23), and no
        # change to this repository can move them. They are printed as
        # ``runtime_start_s``; everything the repository does follows.
        self.t_attached = time.time()
        self.runtime_start_s = self.t_attached - t_start
        self.cache_dir = None if allow_cpu else place_compile_cache()
        self.phase_s["cache"] = time.time() - self.t_attached
        self.setup_s = None
        self.setup_compiles = None
        self.spans = []              # the runner's own host spans

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.time()
        try:
            yield
        finally:
            self.phase_s[name] += time.time() - t0

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the runner's own, on the perf_counter clock
        (and in the profiler's trace, for a reader of the raw file)."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def open_window(self):
        """Set-up ends here. Prints the phase line (an EARLIER line of
        the run's output; the result is the last)."""
        self.setup_s = time.time() - self.t_attached
        self.setup_compiles = {
            "requests": self.compiles.requests,
            "served_by_cache": self.compiles.cache_hits,
            "compiled_afresh": self.compiles.requests
            - self.compiles.cache_hits}
        known = sum(self.phase_s.values())
        print("[setup] " + json.dumps({
            "workload": self.cell["name"], "seed": self.seed,
            "setup_s": self.setup_s,
            "runtime_start_s": self.runtime_start_s,
            "phases": {**self.phase_s, "other": self.setup_s - known},
            "compiles_during_setup": self.setup_compiles,
            "compile_cache": self.cache_dir}), flush=True)

    @contextlib.contextmanager
    def traced(self):
        """The measured window under the profiler (``--trace 1``), or
        plain. Yields a Window whose clock marks align the program's
        perf_counter spans with the profiler's nanoseconds."""
        win = Window(self)
        win.start()
        try:
            yield win
        finally:
            win.stop()


class Window:
    def __init__(self, run: Run):
        self.run = run
        self.trace = None
        self.p0 = self.p1 = None          # perf_counter at open / close
        self.t0_ns = self.t1_ns = None    # the same instants, trace clock
        self.compiles0 = None
        self._dir = None

    def start(self):
        import jax
        run = self.run
        if run.trace:
            from paddle_tpu.observability import tracing
            self._dir = os.path.join(ROOT, "chiprun_out", "trace",
                                     run.cell["name"])
            _rmtree(self._dir)
            os.makedirs(self._dir, exist_ok=True)
            tracing.default_tracer().reset()
            tracing.default_tracer().start()
            # no Python-function tracing: it slows the host threads that
            # the window is there to observe
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=options)
            with jax.profiler.TraceAnnotation("chipbench.window_open"):
                self.p0 = time.perf_counter()
                time.sleep(0.001)
        else:
            self.p0 = time.perf_counter()
        self.compiles0 = run.compiles.requests

    def stop(self):
        import jax
        run = self.run
        if not run.trace:
            self.p1 = time.perf_counter()
            return
        from chipbench import trace_reduce
        from paddle_tpu.observability import tracing
        with jax.profiler.TraceAnnotation("chipbench.window_close"):
            self.p1 = time.perf_counter()
            time.sleep(0.001)
        jax.profiler.stop_trace()
        tracer = tracing.default_tracer()
        tracer.stop()
        files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace to {self._dir}")
        self.trace = trace_reduce.load_xplane(max(files, key=os.path.getmtime))
        marks = trace_reduce.host_marks(
            self.trace, ("chipbench.window_open", "chipbench.window_close"))
        self.t0_ns = marks["chipbench.window_open"]
        self.t1_ns = marks["chipbench.window_close"]
        to_ns = lambda t: self.t0_ns + (t - self.p0) * 1e9   # noqa: E731
        self.host_spans = (
            [(s.name, to_ns(s.start_s), to_ns(s.end_s))
             for s in tracer.spans()]
            + [(n, to_ns(a), to_ns(b)) for n, a, b in run.spans])
        _rmtree(self._dir)

    @property
    def seconds(self) -> float:
        return self.p1 - self.p0

    @property
    def compiles(self) -> int:
        return self.run.compiles.requests - self.compiles0


def _rmtree(path: str):
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def add_device_observations(run: Run, win: Window, obs: dict) -> dict:
    """What every runner's observations end with: the runtime's start,
    the peak of device memory, and in a traced run the trace and its
    reduction."""
    from chipbench import trace_reduce
    obs["runtime_start_s"] = run.runtime_start_s
    obs.setdefault("notes", {})["runtime_start_s"] = run.runtime_start_s
    obs["peak_bytes"] = device_record(run.devices)["memory_peak_bytes"]
    if run.trace:
        obs["peaks"] = peaks_for(run.devices[0].device_kind)
        obs["trace"] = win.trace
        obs["reduced"] = trace_reduce.reduce_window(
            win.trace, win.t0_ns, win.t1_ns, win.host_spans)
    return obs


# ------------------------------------------------------------ the result

def read_layer_metrics(bench: dict, cell_name: str, obs: dict) -> dict:
    """Each per-layer metric of the cell through its own reader
    (``layer_metrics/<metric>.json`` names the reader module and its
    arguments). A reader that finds nothing returns None and the metric
    is left out of the line, and named on stderr: the check refuses a
    traced line that lacks a metric the cell lists (PERF.md, PR 23), so
    a metric with nothing to read in a cell does not belong to it."""
    out = {}
    for m in metrics_of(bench, "per_layer", cell_name):
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "chipbench.layer_metrics." + spec["reader"])
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            print(f"[chipbench] {cell_name}: nothing to read for "
                  f"{m['name']}; left out", file=sys.stderr)
    return out


def result_line(run: Run, obs: dict) -> str:
    bench, name = run.bench, run.cell["name"]
    device = device_record(run.devices)
    line = {"correct": bool(obs["correct"]),
            "attempted": int(obs["attempted"]),
            "failed": int(obs["failed"])}
    if run.trace:
        line["metrics"] = read_layer_metrics(bench, name, obs)
        red = obs["reduced"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["top_ops"],
                             "idle_gaps": red["idle_gaps"]}
    else:
        values = {**obs["end_to_end"], "setup_s": run.setup_s}
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in metrics_of(bench, "end_to_end", name)}
    line["device"] = device
    line["notes"] = obs.get("notes", {})
    return json.dumps(line)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python3 -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    t_start = process_start_unix()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        bench = load_benchmark()
        cell, config, traffic = load_cell(bench, args.workload)
        run = Run(bench, cell, config, traffic, args.seed, args.seconds,
                  bool(args.trace), t_start)
        obs = runner_of(config).run(run)
        line = result_line(run, obs)
    except BaseException:
        import traceback
        traceback.print_exc()
        sys.stderr.flush()
        return 1
    sys.stderr.flush()
    print(line, flush=True)
    return 0
