"""mem_probe: compiled peak-HBM probe over the model zoo.

For every bench model this builds the default (small-config) training
graph, runs its startup into a fresh scope, and asks XLA's compiled
``memory_analysis()`` for the executable's breakdown (argument / output /
temp / alias / generated-code / peak bytes) — the ground truth the
static estimator (`paddle_tpu.contrib.memory_usage`) is reconciled
against:

    parameters_est <= peak_bytes          (params are resident)
    peak_bytes ~ total_high               (ratio recorded per model)

Each model also gets a donation audit (every donated state buffer must
alias in the compiled ``input_output_alias`` header — the zoo train
mains are the "optimizer-apply" programs), and the serving decode
program (tiny ``decoder_lm`` config) is audited the same way. Nothing
is executed beyond the startup programs: the probe is compile-only, so
it runs on the CPU backend (JAX_PLATFORMS=cpu) in CI.

    python tools/mem_probe.py                 # full zoo -> MEM_r01.json
    python tools/mem_probe.py --smoke         # mnist only, no artifact
    python tools/mem_probe.py --models mnist,smallnet --out MEM_r01.json

Exit is non-zero when any donation audit reports violations or a
model's estimator reconciliation fails (parameters > compiled peak).
Docs: docs/observability.md "Memory observability".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the bench table's model names (bench.py builders) — probed at each
# model's DEFAULT build() config: the probe reconciles reporting, it
# does not re-measure bench shapes, and default configs keep the
# CPU-backend compile sweep tractable
ZOO_MODELS = (
    "mnist", "smallnet", "alexnet", "vgg", "googlenet", "resnet50",
    "se_resnext", "deepfm", "roofline_probe", "machine_translation",
    "stacked_dynamic_lstm", "transformer", "transformer_big",
    "transformer_long",
)
SMOKE_MODELS = ("mnist",)

# bench rows that share a build() with a base zoo module; the base
# graph is probed once and the aliases marked, so the artifact still
# names every bench row
MODEL_ALIASES = {"transformer_big": "transformer",
                 "transformer_long": "transformer",
                 "resnet50": "resnet"}

DEFAULT_BATCH = 4


def _zero_feeds(feed_specs, batch):
    import numpy as np
    feeds = {}
    for name, (shape, dtype) in sorted(feed_specs.items()):
        sh = [batch if d is None or int(d) < 0 else int(d) for d in shape]
        np_dt = np.int32 if dtype.startswith("int") else np.float32
        feeds[name] = np.zeros(sh, np_dt)
    return feeds


def probe_model(name, batch=DEFAULT_BATCH):
    """One zoo model: compiled breakdown + estimator band + donation
    audit of the default-config training graph (optimizer included —
    build(is_train=True) minimizes, so the compiled step IS the
    optimizer-apply program)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib.memory_usage import memory_usage

    mod = getattr(models, name, None)
    if mod is None or not hasattr(mod, "build"):
        raise ValueError(f"no such zoo model {name!r}")
    t0 = time.time()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        loss, _, feed_specs = mod.build()
    main.desc._obs_name = name

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    feeds = _zero_feeds(feed_specs, batch)
    cb = exe._compiled(main, sorted(feeds), [loss.name], False)

    mem = cb.analyzed_memory(scope, feeds) or {}
    audit = cb.donation_audit(scope, feeds)
    est = memory_usage(main, batch)

    peak = mem.get("peak_bytes")
    row = {
        "batch_size": batch,
        "compiled": mem,
        "estimate": est,
        "donation": {k: audit.get(k) for k in
                     ("expected", "aliased", "violations", "skipped",
                      "error") if audit.get(k)},
        "donation_violations": len(audit.get("violations") or []),
        "probe_s": round(time.time() - t0, 1),
    }
    if peak:
        # reconciliation: resident parameters can never exceed the
        # compiled peak; the estimator band's high end vs peak is the
        # recorded calibration ratio (XLA liveness reuse keeps peak
        # below the straight per-var sum on activation-heavy graphs)
        row["peak_over_total_high"] = round(peak / est["total_high"], 3) \
            if est["total_high"] else None
        row["reconciled"] = est["parameters"] <= peak
    return row


def probe_serving_decode_paged():
    """Donation audit + census classification of the PAGED decode
    executable (ISSUE 17): the shared ``*_page_k/v_*`` pools must keep
    aliasing across the page-table gather/scatter rewrite, and the
    memory census must classify them as ``kv_cache``."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.transformer import build_decoder_lm_programs
    from paddle_tpu.observability import memory as obs_memory
    import proglint

    progs = build_decoder_lm_programs(
        prompt_len=8, max_new=8, vocab=64, d_model=32, d_inner=64,
        n_head=2, n_layer=2, modes=("decode_paged",), n_slots=4,
        page_size=4)
    main, startup, feed_specs, _fetch = progs["decode_paged"]
    audit = proglint._memory_audit("decoder_lm.decode_paged", main,
                                   startup, sorted(feed_specs))
    # census: run startup and make sure every page-pool buffer lands in
    # the kv_cache family (docs/observability.md; _KV_RE covers *_page_*)
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope)
    cen = obs_memory.census([scope])
    kv_bufs = [b for b in cen["buffers"] if b["family"] == "kv_cache"]
    misclassified = [b["name"] for b in cen["buffers"]
                     if "_page_" in b["name"]
                     and b["family"] != "kv_cache"]
    return {
        "program": "decoder_lm.decode_paged",
        "expected": len(audit.get("expected") or []),
        "aliased": len(audit.get("aliased") or []),
        "violations": (audit.get("violations") or []) + misclassified,
        "skipped": audit.get("skipped") or [],
        "kv_cache_bytes": cen["families"].get("kv_cache", 0),
        "kv_cache_buffers": len(kv_bufs),
        **({"error": audit["error"]} if audit.get("error") else {}),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mem_probe", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--models", default="",
                    help="comma list of zoo models (default: the bench "
                         "table)")
    ap.add_argument("--batch-size", type=int, default=DEFAULT_BATCH)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate mode: mnist + the serving decode "
                         "audit only, no artifact written")
    ap.add_argument("--out", default=None, metavar="MEM_rNN.json",
                    help="write the artifact here (default MEM_r01.json "
                         "at the repo root; --smoke writes nothing)")
    args = ap.parse_args(argv)

    names = ([m for m in args.models.split(",") if m] or
             (SMOKE_MODELS if args.smoke else ZOO_MODELS))

    failures = 0
    doc = {"metric": "compiled peak-HBM vs static estimator (zoo, "
                     "default configs)",
           "batch_size": args.batch_size, "models": {},
           "serving_paged": None}
    probed = {}
    for name in names:
        base = MODEL_ALIASES.get(name, name)
        try:
            if base not in probed:
                probed[base] = probe_model(base, args.batch_size)
            row = dict(probed[base])
            if base != name:
                row["alias_of"] = base
            doc["models"][name] = row
        except Exception as e:
            doc["models"][name] = {"error": str(e)[:200]}
            failures += 1
            print(f"[FAIL] {name}: {e}")
            continue
        peak = (row.get("compiled") or {}).get("peak_bytes")
        bad = row["donation_violations"]
        if bad or row.get("reconciled") is False:
            failures += 1
        print(f"[{'FAIL' if bad else 'ok'}] {name}: peak "
              f"{peak or '?'} B, est band "
              f"[{row['estimate']['total_low']}, "
              f"{row['estimate']['total_high']}] B, "
              f"{bad} donation violation(s) ({row['probe_s']}s)")

    try:
        doc["serving_paged"] = probe_serving_decode_paged()
        pbad = (doc["serving_paged"]["violations"]
                or doc["serving_paged"].get("error"))
        if pbad:
            failures += 1
        print(f"[{'FAIL' if pbad else 'ok'}] decoder_lm.decode_paged: "
              f"{doc['serving_paged']['aliased']}/"
              f"{doc['serving_paged']['expected']} state buffers aliased, "
              f"{len(doc['serving_paged']['violations'])} violation(s), "
              f"{doc['serving_paged']['kv_cache_buffers']} kv_cache "
              f"buffer(s) ({doc['serving_paged']['kv_cache_bytes']} B)")
    except Exception as e:
        doc["serving_paged"] = {"error": str(e)[:200]}
        failures += 1
        print(f"[FAIL] decoder_lm.decode_paged: {e}")

    if not args.smoke:
        out = args.out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "MEM_r01.json")
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, out)
        print(f"mem_probe: wrote {out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
