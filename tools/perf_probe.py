"""Perf probe: per-step cost analysis, dispatch-vs-device-loop timing,
and per-op-region copy/relayout attribution.

The relayout report automates the manual analysis behind the
transformer_big "r4 copy band" (docs/performance.md): it walks the
OPTIMIZED HLO of the compiled step, collects every ``copy`` /
``transpose`` / ``bitcast-convert`` instruction, groups them by operand
shape (the op-region proxy — a relayout band is N copies of one logical
tensor), labels each band with the program vars whose sentinel shape
matches, and reports count + MB/step + the time bound at HBM peak.
Layout-pass wins are re-measurable with ONE command:

    python tools/perf_probe.py transformer_big --copy-band [--no-passes]

compares directly against the same invocation with the pass pipeline
disabled. Plain timing mode (the original probe) remains:

    python tools/perf_probe.py [model] [batch_size] [inner_steps]
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}

# `%copy.12 = bf16[16,512,4096]{2,1,0} copy(...)` — opcode + typed shape
_HLO_RE = re.compile(
    r"=\s+(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\][^ ]*\s+"
    r"(?P<opcode>copy|transpose|bitcast-convert)\(")

RELAYOUT_OPCODES = ("copy", "transpose", "bitcast-convert")


def collect_relayouts(hlo_text: str):
    """[(opcode, dtype, dims tuple, bytes)] for every relayout-family
    instruction in an optimized-HLO dump."""
    out = []
    for m in _HLO_RE.finditer(hlo_text):
        dims = tuple(int(d) for d in m.group("dims").split(",") if d)
        nbytes = _DTYPE_BYTES.get(m.group("dtype"), 4)
        for d in dims:
            nbytes *= d
        out.append((m.group("opcode"), m.group("dtype"), dims, nbytes))
    return out


def copy_band_report(hlo_text: str, block=None, batch_size=None,
                     hbm_gbps: float = 819.0, top: int = 12):
    """Group relayout instructions into per-region bands. Each band is
    one (dtype, shape) class — e.g. the transformer_big FFN hidden
    [16,512,4096] — with count, MB/step, the ms bound at HBM peak, and
    the program vars whose shape matches (region labels)."""
    bands = {}
    for opcode, dtype, dims, nbytes in collect_relayouts(hlo_text):
        key = (dtype, dims)
        b = bands.setdefault(key, {"count": 0, "bytes": 0,
                                   "opcodes": {}})
        b["count"] += 1
        b["bytes"] += nbytes
        b["opcodes"][opcode] = b["opcodes"].get(opcode, 0) + 1

    def region_labels(dims):
        if block is None:
            return []
        labels = []
        for name, v in getattr(block, "vars", {}).items():
            shape = list(v.shape or [])
            if not shape or len(shape) != len(dims):
                continue
            concrete = [batch_size if (d == -1 and batch_size) else d
                        for d in shape]
            if tuple(concrete) == dims:
                labels.append(name)
        return labels[:4]

    rows = []
    for (dtype, dims), b in bands.items():
        mb = b["bytes"] / 1e6
        rows.append({
            "region": f"{dtype}[{','.join(map(str, dims))}]",
            "count": b["count"],
            "opcodes": b["opcodes"],
            "mb_per_step": round(mb, 2),
            "ms_at_hbm_peak": round(b["bytes"] / (hbm_gbps * 1e9) * 1e3,
                                    3),
            "vars": region_labels(dims),
        })
    rows.sort(key=lambda r: -r["mb_per_step"])
    total_ms = round(sum(r["ms_at_hbm_peak"] for r in rows), 3)
    return {"relayout_bands": rows[:top],
            "relayout_total_ms_at_hbm_peak": total_ms,
            "relayout_total_count": sum(r["count"] for r in rows)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model", nargs="?", default="resnet50")
    ap.add_argument("batch_size", nargs="?", type=int, default=None)
    ap.add_argument("inner", nargs="?", type=int, default=10)
    ap.add_argument("--copy-band", action="store_true",
                    help="emit the per-region copy/relayout attribution "
                         "(JSON) from the optimized HLO and exit")
    ap.add_argument("--no-passes", dest="passes", action="store_const",
                    const="none", default=None,
                    help="disable the IR-pass pipeline (A/B arm)")
    ap.add_argument("--passes", dest="passes", default=None,
                    metavar="P1,P2", help="explicit pass list")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output for all sections")
    args = ap.parse_args()
    model, inner = args.model, args.inner

    import paddle_tpu.fluid as fluid
    from bench import (DEFAULT_BATCH_SIZES, _device_batch,
                       build_train_program)
    from paddle_tpu.core.lowering import CompiledBlock
    from paddle_tpu.utils import chip
    chip.compile_cache_dir()

    bs = args.batch_size or DEFAULT_BATCH_SIZES.get(model, 128)
    main_p, startup, loss, feed_specs, applied = build_train_program(
        model, bs, passes_spec=args.passes)
    if applied or args.passes:
        print(json.dumps({"passes": applied}), flush=True)

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feeds = _device_batch(exe, feed_specs, bs)

    desc = main_p.desc
    cb = CompiledBlock(desc, 0, sorted(feeds), [loss.name])
    from paddle_tpu.core.scope import global_scope
    scope = global_scope()
    state = {n: scope.find_var(n) for n in cb.sig.state_names}
    consts = {n: scope.find_var(n) for n in cb.sig.const_names}

    # ---- compile once; cost analysis + optimized HLO ----
    lowered = jax.jit(cb._step_fn, donate_argnums=(0,)).lower(
        state, consts, feeds, np.uint32(0))
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", 0.0)
    bytes_acc = ca.get("bytes accessed", 0.0)

    if args.copy_band:
        report = copy_band_report(compiled.as_text(),
                                  block=desc.global_block,
                                  batch_size=bs)
        report["model"] = model
        report["batch_size"] = bs
        report["passes"] = applied
        print(json.dumps(report, indent=None if args.json else 1))
        return

    print(f"XLA cost analysis: {flops/1e9:.1f} GFLOP/step, "
          f"{bytes_acc/1e9:.2f} GB accessed/step")
    print(f"  -> at 197 TFLOP/s peak: {flops/197e12*1e3:.2f} ms ideal")
    print(f"  -> at 819 GB/s HBM: {bytes_acc/819e9*1e3:.2f} ms ideal")

    # ---- single-step timing (per-dispatch) ----
    fetches, state = cb.fn(state, consts, feeds, np.uint32(1))
    print("single-step loss:",
          float(np.asarray(fetches[0]).reshape(())))
    t0 = time.time()
    N = 30
    for i in range(N):
        fetches, state = cb.fn(state, consts, feeds, np.uint32(2 + i))
    _ = float(np.asarray(fetches[0]).reshape(()))
    dt_disp = (time.time() - t0) / N
    print(f"per-dispatch step: {dt_disp*1e3:.2f} ms -> "
          f"{bs/dt_disp:.0f} examples/s")

    # ---- multi-step fori_loop ----
    from paddle_tpu.core.lowering import build_block_fn
    cb_fn = build_block_fn(desc, 0, cb.sig, is_test=False)

    def multi(state, consts, feeds, seed0):
        def body(i, carry):
            state, _ = carry
            fetches, state = cb_fn(state, consts, feeds, seed0 + i)
            return state, fetches[0]
        return jax.lax.fori_loop(0, inner, body,
                                 (state, jnp.zeros((), jnp.float32)))

    multi_j = jax.jit(multi, donate_argnums=(0,))
    state2, lv2 = multi_j(state, consts, feeds, np.uint32(100))
    print("multi-step loss:", float(np.asarray(lv2).reshape(())))
    t0 = time.time()
    R = 5
    for r in range(R):
        state2, lv2 = multi_j(state2, consts, feeds, np.uint32(200 + r))
    _ = float(np.asarray(lv2).reshape(()))
    dt_multi = (time.time() - t0) / (R * inner)
    print(f"fori_loop step:   {dt_multi*1e3:.2f} ms -> "
          f"{bs/dt_multi:.0f} examples/s")
    mfu = flops / dt_multi / 197e12
    print(f"MFU (XLA flops / 197 TFLOP/s): {mfu*100:.1f}%")


if __name__ == "__main__":
    main()
