"""Unified runtime flag registry.

The reference re-exports a curated set of C++ gflags into Python and seeds
them from the environment at import (reference:
python/paddle/fluid/__init__.py:125-163 `__bootstrap__` collects
read_env_flags and calls core.init_gflags). TPU-native equivalent: typed
flag definitions with `FLAGS_<name>` environment override, queried at use
sites via `flags.get(...)` and settable programmatically via
`flags.set(...)` (tests) — one registry instead of ad-hoc os.environ
lookups scattered through the runtime.

Every flag the runtime honors is defined here, so `python -m
paddle_tpu.flags` prints the complete documented surface.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional


@dataclass(frozen=True)
class FlagDef:
    name: str
    type: type
    default: Any
    help: str


_DEFS: Dict[str, FlagDef] = {}
_OVERRIDES: Dict[str, Any] = {}


def define(name: str, type_, default, help_: str):
    if name in _DEFS:
        raise ValueError(f"flag {name!r} already defined")
    _DEFS[name] = FlagDef(name, type_, default, help_)


def _parse(d: FlagDef, raw: str):
    if d.type is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return d.type(raw)


def get(name: str):
    """Current value: programmatic override > FLAGS_<name> env > default."""
    d = _DEFS.get(name)
    if d is None:
        raise KeyError(f"unknown flag {name!r}; defined: {sorted(_DEFS)}")
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    raw = os.environ.get("FLAGS_" + name)
    if raw is not None:
        try:
            return _parse(d, raw)
        except ValueError:
            import warnings
            warnings.warn(f"FLAGS_{name}={raw!r} does not parse as "
                          f"{d.type.__name__}; using default {d.default!r}")
    return d.default


def set(name: str, value):   # noqa: A001 - mirrors gflags SetCommandLineOption
    d = _DEFS.get(name)
    if d is None:
        raise KeyError(f"unknown flag {name!r}")
    if value is None:
        _OVERRIDES[name] = None
    elif isinstance(value, str):
        # same parsing as the FLAGS_* env path — set('benchmark', '0')
        # must disable, not bool('0') == True
        _OVERRIDES[name] = _parse(d, value)
    else:
        _OVERRIDES[name] = d.type(value)


def reset(name: Optional[str] = None):
    if name is None:
        _OVERRIDES.clear()
    else:
        _OVERRIDES.pop(name, None)


def all_flags():
    return dict(_DEFS)


# --- runtime flag definitions (reference names kept where they exist) ----

define("check_nan_inf", bool, False,
       "Scan every fetch and updated state var for NaN/Inf after each "
       "executor run (reference: operator.cc FLAGS_check_nan_inf).")
define("debug_graphviz_path", str, "",
       "Write a graphviz dump of each compiled program here "
       "(reference: inference/analysis FLAGS_IA_graphviz_log_root "
       "capability; fluid/debugger.py draw_block_graphviz).")
define("benchmark", bool, False,
       "Print per-run compile/execute timing from the Executor "
       "(reference: FLAGS_benchmark executor timing).")
define("tpu_prng", str, "rbg",
       "JAX PRNG implementation: 'rbg' (TPU hardware path; default) or "
       "'threefry2x32'. Read once at import by paddle_tpu/__init__.py "
       "via PADDLE_TPU_PRNG (kept for compat) or FLAGS_tpu_prng.")
define("disable_pallas", bool, False,
       "Force the refer (jnp) tier instead of Pallas kernels "
       "(ops/pallas kernel_pool gate; PADDLE_TPU_DISABLE_PALLAS compat).")
define("disable_sparse_grad", bool, False,
       "Densify embedding-table gradients instead of carrying the "
       "SelectedRows-style (rows, values) pair from the lookup_table / "
       "fused_embedding_seq_pool VJP to the sparse optimizer apply "
       "(core/selected_rows.py). The sparse path is exact (parity suite "
       "tests/test_sparse_grad.py); this flag exists for A/B timing and "
       "as an escape hatch.")
define("eager_delete_tensor_gb", float, 0.0,
       "Accepted for API parity (reference: FLAGS_eager_delete_tensor_gb "
       "GC threshold) — XLA/PJRT owns buffer lifetime on TPU; no-op.")
define("fraction_of_gpu_memory_to_use", float, 1.0,
       "Accepted for API parity (reference allocator knob) — PJRT "
       "preallocation is controlled by XLA_PYTHON_CLIENT_* instead; "
       "no-op.")
define("fault_plan", str, "",
       "Deterministic fault-injection plan for the chaos harness "
       "(paddle_tpu.utils.faults): 'site:mode[@sched][:k=v]...' specs "
       "joined by ';', e.g. "
       "'master.rpc.send:raise@2:exc=ConnectionError;"
       "ckpt.write_shard:truncate@1:to=16'. Loaded lazily at the first "
       "instrumented site hit; see docs/robustness.md.")
define("fault_seed", int, 0,
       "Seed for probabilistic fault schedules ('p0.1'): per-site RNG "
       "streams are keyed by (seed, site) so chaos runs replay exactly.")
define("metrics_dump_path", str, "",
       "Directory the observability dump thread writes to: steps.jsonl "
       "(one record per executor dispatch: step_time, steps/s, "
       "examples/s, MFU) and metrics.prom (full registry, Prometheus "
       "text). Empty (default) disables the dump thread "
       "(paddle_tpu.observability.exporters; docs/observability.md).")
define("metrics_dump_interval", float, 10.0,
       "Seconds between observability dump-thread writes "
       "(FLAGS_metrics_dump_path). Records are queued per dispatch; the "
       "interval only controls disk-write frequency, and stop/atexit "
       "flushes the tail.")
define("metrics_port", int, -1,
       "Prometheus scrape endpoint (GET /metrics) on this port via a "
       "stdlib http.server thread. -1 (default) disables; 0 binds an "
       "ephemeral port (observability.exporters.active_server().port). "
       "Binds FLAGS_metrics_host (loopback by default).")
define("metrics_host", str, "127.0.0.1",
       "Interface the scrape endpoint binds. The loopback default is "
       "deliberate (the registry is unauthenticated); set 0.0.0.0 to "
       "expose it to an off-host Prometheus scraper.")
define("verify_program", bool, False,
       "Run the build-time program verifier (paddle_tpu.analysis) over "
       "every program before lowering: ERROR-severity diagnostics "
       "(dangling vars, shape/dtype drift, unknown ops, WAW hazards) "
       "raise ProgramVerificationError at CompiledBlock build with op "
       "provenance; warnings are counted in "
       "paddle_analysis_diagnostics_total. Standalone linting: "
       "tools/proglint.py; rule catalog: docs/static_analysis.md.")
define("trace_spool_dir", str, "",
       "Directory the per-process span spool appends to "
       "(<role>.<pid>.jsonl, one JSON span per line, flushed per span — "
       "crash-tolerant). Empty (default) disables. Merge every spool "
       "into one Perfetto trace with tools/trace_collect.py; see "
       "docs/observability.md 'Distributed tracing'.")
define("trace_role", str, "",
       "Role label naming this process's spool file and Perfetto "
       "process track ('server', 'client', 'trainer0'...). Defaults to "
       "the process name derived from sys.argv when empty.")
define("flight_recorder_dir", str, "",
       "Directory for the crash flight recorder: a bounded in-memory "
       "ring of recent spans, metric deltas and fault-site hits, dumped "
       "atomically (<role>.<pid>.dump.json) on unhandled exception, "
       "SIGTERM, or a fault-injection fire — plus an always-flushed "
       "blackbox JSONL that survives SIGKILL. Empty (default) disables "
       "(paddle_tpu.observability.flight_recorder).")
define("flight_recorder_capacity", int, 256,
       "Ring capacity (recent events kept) of the flight recorder.")
define("peak_flops", float, 0.0,
       "Override the peak-FLOP/s denominator of the MFU gauge "
       "(paddle_mfu_ratio). 0 (default) autodetects from the attached "
       "chip's spec sheet (utils.flops.device_peak_flops) — set this on "
       "CPU runs/tests to get a real MFU instead of none.")
define("peak_hbm", float, 0.0,
       "Override the peak HBM bytes/s denominator of the bandwidth "
       "gauge (bench bw_pct; utils.flops.device_peak_hbm). 0 (default) "
       "autodetects from the attached chip's spec sheet — set this on "
       "CPU runs/tests to get a real bw_pct instead of none.")
define("memory_stats", bool, False,
       "HBM memory telemetry (paddle_tpu.observability.memory): per-"
       "dispatch compiled memory breakdown (paddle_hbm_compiled_bytes), "
       "live-buffer census gauges (paddle_hbm_live_bytes) with a process "
       "watermark, and a one-time donation audit per compiled block "
       "(paddle_donation_violations_total). Off (default) costs one flag "
       "lookup per executor dispatch; OOM forensics (memdumps) also ride "
       "FLAGS_flight_recorder_dir independently of this flag.")
define("hbm_bytes", float, 0.0,
       "Override the device HBM capacity (bytes) used as the hbm_pct "
       "denominator in bench rows (utils.flops.device_hbm_bytes). 0 "
       "(default) autodetects from device.memory_stats()['bytes_limit'] "
       "or the chip spec sheet — set this on CPU runs/tests to get a "
       "real hbm_pct instead of none.")
define("embed_exchange_codec", str, "none",
       "Wire codec for the sharded-embedding row exchange "
       "(distributed/sharded_table.py): 'none' ships fp32 (the "
       "exact-dense control arm), 'bf16' truncates to 2 bytes/elem, "
       "'int8' ships int8 codes + one fp32 scale per row "
       "(EQuARX-style). Applies to pull_rows AND push_rows payloads.")
define("grad_allreduce_codec", str, "none",
       "Wire codec for the explicit gradient allreduce "
       "(parallel/collective.py grad_all_reduce — the shard_map-island "
       "exchange used when the data axis crosses DCN): 'none' reduces "
       "fp32 (the exact arm; GSPMD's implicit ICI psum is identical), "
       "'bf16' reduces in bfloat16 (2 bytes/elem on the wire), 'int8' "
       "ships int8 codes + one fp32 scale per row and dequant-sums "
       "locally — the per-row-scale discipline of "
       "FLAGS_embed_exchange_codec applied to gradients (EQuARX, "
       "arXiv:2506.17615). Parity contract: "
       "tests/test_spmd_exec.py codec window.")
define("kv_cache_codec", str, "none",
       "Storage codec for the slot server's paged KV pool "
       "(serving/engine.py, serving/kv_pool.py; docs/serving.md 'Paged "
       "KV cache'): 'none' stores fp32 (rows read back exactly as "
       "written), 'bf16' truncates to 2 bytes/elem, 'int8' stores int8 codes + one fp32 "
       "scale per (position, head) row — the per-row-scale discipline "
       "of FLAGS_embed_exchange_codec applied at rest. Quantize on "
       "page write, dequantize in the attention gather.")
define("lock_witness", bool, False,
       "Runtime lock-order witness (observability/lock_witness.py): "
       "ObservedLock records per-thread acquisition order and validates "
       "the global lock DAG online. A held->acquiring edge that closes "
       "a cycle is a witnessed inversion: it increments "
       "paddle_lock_witness_violations_total and dumps BOTH stacks "
       "(the inverted acquisition and the first-witnessed forward "
       "order) through the flight recorder. Off by default; the chaos "
       "suites run with it on and assert zero violations.")


def _main():
    print("paddle_tpu runtime flags (override with FLAGS_<name> env or "
          "paddle_tpu.flags.set):\n")
    for name, d in sorted(_DEFS.items()):
        cur = get(name)
        mark = "  [set]" if (name in _OVERRIDES
                             or ("FLAGS_" + name) in os.environ) else ""
        print(f"FLAGS_{name} ({d.type.__name__}, default {d.default!r}, "
              f"current {cur!r}){mark}\n    {d.help}\n")


if __name__ == "__main__":
    _main()
