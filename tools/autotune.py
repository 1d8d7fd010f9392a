"""Offline autotune sweeps → the committed unified winner table.

The measurement half of the committed-table discipline
(`paddle_tpu/passes/autotune.py` is the lookup half): run a sweep on an
idle chip, print one JSON line per measurement, and with ``--commit``
rewrite ONLY the swept kind's entries in
``paddle_tpu/passes/autotune_table.json`` (other kinds' winners are
preserved), stamping ``device``/``tuned_at``. Build paths never measure
— they only look this table up.

Kinds:

- ``flash_attention``: fwd + full dq/dk/dv bwd of the attention region
  at each (T, d_head, causal) across the Pallas kernel's (bq, bk) grid
  vs the XLA fused-dot composition (the sweep tools/flash_autotune.py
  shipped, now writing the unified format), and of latent attention's
  expanded heads (192 / 128, causal, one 8 192-token sequence of 32).
  Where a full-model A/B exists, re-commit it with
  ``source="model-ab"`` — model rows override region sweeps
  (docs/performance.md).
- ``pass_pipeline``: full-model A/B of IR-pass candidate sets through
  ``bench.py --model M --passes ...`` subprocesses (fresh backend per
  candidate); the winning set is committed per (model, batch bucket)
  and ``paddle_tpu.passes.pipeline_for`` serves it at build time.

Run (idle TPU):

    python tools/autotune.py --kind flash_attention [--tokens 8192] --commit
    python tools/autotune.py --kind pass_pipeline --model resnet50 --commit
    python tools/autotune.py --print
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_name() -> str:
    """The table's ``device`` stamp, which a lookup trusts — so a
    backend that cannot say what it is raises. Call this only AFTER the
    last bench.py child has exited: it takes the chip for this process,
    and a child started afterwards could never get it."""
    import jax
    return jax.devices()[0].device_kind


# ------------------------------------------------------------------ flash

# (T, d, dv, heads): the attention regions of the fused block (value
# heads as wide as the query / key heads'), and latent attention
# expanded as the trained cell runs it — causal only, one sequence, and
# no XLA composition beside it (its [32, 8192, 8192] float32 scores do
# not fit the chip)
FLASH_REGIONS = [(T, d, d, 8) for T in (256, 512, 1024, 2048)
                 for d in (64, 128)] + [(8192, 192, 128, 32)]


def sweep_flash(table, tokens=8192):
    """(bq, bk) grid vs the XLA composition, committed per
    (T, d, causal) — and per dv where the value heads are of another
    size — in the unified format. Timing goes through
    autotune.measure_ms so the measurement counter records every sample
    (and CI's forbid guard would trip)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas as pk
    from paddle_tpu.passes import autotune as at

    def xla_attention(q, k, v, causal, scale):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            tq, tk = q.shape[2], k.shape[2]
            s = jnp.where(jnp.tril(jnp.ones((tq, tk), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    def grad_fn(fn):
        return jax.jit(lambda *a: sum(
            jnp.sum(g) for g in jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v)),
                argnums=(0, 1, 2))(*a)))

    rng = np.random.RandomState(0)
    for T, d, dv, h in FLASH_REGIONS:
        latent = dv != d
        b = max(1, tokens // T)
        q, k, v = (jnp.asarray(rng.randn(b, h, T, w), np.float32)
                   .astype(jnp.bfloat16) * 0.3 for w in (d, d, dv))
        scale = float(d) ** -0.5
        for causal in ((True,) if latent else (False, True)):
            xla_ms = None if latent else at.measure_ms(
                grad_fn(lambda q, k, v, c=causal:
                        xla_attention(q, k, v, c, scale)), q, k, v)
            best = None
            for bq in (128, 256, 512, 1024):
                if T % bq or (bq == 1024 and not latent):
                    continue
                for bk in (128, 256, 512, 1024):
                    if T % bk:
                        continue
                    row = {"T": T, "d": d, "causal": causal,
                           "bq": bq, "bk": bk, **({"dv": dv} if latent
                                                  else {})}
                    try:
                        ms = at.measure_ms(
                            grad_fn(lambda q, k, v, c=causal,
                                    bq=bq, bk=bk:
                                    pk.flash_attention(
                                        q, k, v, c, scale, bq, bk)),
                            q, k, v)
                    except Exception as e:   # over-VMEM config etc.
                        print(json.dumps({**row, "error": str(e)[:80]}),
                              flush=True)
                        continue
                    print(json.dumps(
                        {**row, "flash_ms": round(ms, 3),
                         "xla_ms": xla_ms and round(xla_ms, 3)}),
                        flush=True)
                    if best is None or ms < best[0]:
                        best = (ms, bq, bk)
            if best is None:
                continue
            params = at.flash_params(T, d, causal)
            if latent:      # the key ``causal_blocks`` reads: T exact
                params = {"T": T, "d": d, "dv": dv, "causal": 1}
            existing = table.get("entries", {}).get(
                at.fingerprint("flash_attention", params))
            if existing and existing.get("source") == "model-ab":
                # model rows OVERRIDE region sweeps (the round-5
                # precedence rule: region-optimal blocks measured
                # slower at the model level) — a region re-sweep
                # must never clobber a model-verified winner
                print(json.dumps(
                    {"T": T, "d": d, "causal": causal,
                     "kept": "model-ab entry", **existing}),
                    flush=True)
                continue
            entry = {"source": "region-sweep",
                     "flash_ms": round(best[0], 3)}
            if xla_ms is not None:
                entry["xla_ms"] = round(xla_ms, 3)
            if xla_ms is None or best[0] < xla_ms:
                entry.update(impl="flash", bq=best[1], bk=best[2])
            else:
                entry["impl"] = "xla"
            at.record(table, "flash_attention", params, entry)
    return table


# --------------------------------------------------------------- pipeline

# the candidate lattice: pass sets bench can apply to a training row
PIPELINE_CANDIDATES = (
    (),
    ("layout_assignment_pass",),
    ("layout_assignment_pass", "conv_block_fuse_pass"),
    ("conv_block_fuse_pass",),
)


def sweep_pipeline(table, model, batch_size=None, steps=None,
                   timeout=1200):
    """Full-model A/B: each candidate pass set runs as one
    ``bench.py --model M --passes ...`` subprocess (fresh backend — a
    pathological compile cannot poison the next candidate); the winner
    by throughput is committed per (model, bs bucket)."""
    from paddle_tpu.passes import autotune as at
    from bench import DEFAULT_BATCH_SIZES
    bs = batch_size or DEFAULT_BATCH_SIZES.get(model, 32)
    results = []
    for cand in PIPELINE_CANDIDATES:
        cmd = [sys.executable, os.path.join(REPO, "bench.py"),
               "--model", model, "--batch-size", str(bs),
               "--passes", ",".join(cand) if cand else "none"]
        if steps:
            cmd += ["--steps", str(steps)]
        t0 = time.time()
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout)
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("{")]
            row = json.loads(lines[-1]) if (r.returncode == 0
                                            and lines) else {}
        except (subprocess.TimeoutExpired, ValueError):
            row = {}
        rec = {"model": model, "bs": bs, "passes": list(cand),
               "value": row.get("value"), "unit": row.get("unit"),
               "mfu_pct": row.get("mfu_pct"),
               "wall_s": round(time.time() - t0, 1)}
        print(json.dumps(rec), flush=True)
        if rec["value"] is not None:
            results.append(rec)
    if not results:
        print(json.dumps({"model": model, "error": "no candidate ran"}),
              flush=True)
        return table
    best = max(results, key=lambda r: r["value"])
    at.record(table, "pass_pipeline",
              {"model": model, "bs": at.bucket_pow2(bs)},
              {"passes": best["passes"], "source": "model-ab",
               "value": best["value"], "unit": best["unit"],
               "candidates": {",".join(r["passes"]) or "none":
                              r["value"] for r in results}})
    return table


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kind", choices=["flash_attention",
                                       "pass_pipeline"])
    ap.add_argument("--model", action="append", default=[],
                    help="pass_pipeline: model(s) to A/B (repeatable)")
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--tokens", type=int, default=8192,
                    help="flash: B*T per measurement")
    ap.add_argument("--table", default=None,
                    help="table path (default: the committed in-repo "
                         "table)")
    ap.add_argument("--commit", action="store_true",
                    help="write winners into the table (atomic)")
    ap.add_argument("--print", dest="print_", action="store_true",
                    help="dump the committed table and exit")
    args = ap.parse_args(argv)

    from paddle_tpu.passes import autotune as at
    path = args.table or at.DEFAULT_TABLE_PATH
    table = at.load_table(path)

    if args.print_:
        print(json.dumps(table, indent=1, sort_keys=True))
        return 0
    if not args.kind:
        ap.error("--kind required (or --print)")

    # work on a deep copy so a sweep interrupted mid-way can't corrupt
    # the reader cache's view of the committed table
    table = json.loads(json.dumps(table))
    if args.kind == "flash_attention":
        sweep_flash(table, tokens=args.tokens)
    else:
        models = args.model or ["resnet50"]
        for m in models:
            sweep_pipeline(table, m, batch_size=args.batch_size,
                           steps=args.steps)
    table["device"] = _device_name()
    table["tuned_at"] = time.strftime("%Y-%m-%d")
    if args.commit:
        out = at.save_table(table, path)
        print(f"committed {len(table.get('entries', {}))} entries "
              f"-> {out}")
    else:
        print("TABLE " + json.dumps(table, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
