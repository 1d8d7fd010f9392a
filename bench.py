"""Benchmark harness entry point.

Mirrors the reference's fluid_benchmark CLI capability
(reference: benchmark/fluid/fluid_benchmark.py:139 train_parallel — reports
images/sec or words/sec averaged over steps) on TPU.

DEFAULT (no --model): the FULL sweep — one JSON line per model row (14
train + 3 infer + 1 serving cold-start) as each finishes, then one
compact aggregate JSON line
{"metric": "full sweep ...", "value": <headline resnet50 img/s>,
 "unit": ..., "vs_baseline": N, "mfu_pct": N, "rows": [...]}
whose rows[] carry the whole table with mfu_pct filled per row.
`--model X` runs one row; `--headline` is the resnet50-only shortcut.

Headline config: ResNet-50 train bs=128 amp-bf16 nhwc — the BASELINE.md
north-star row (ResNet-50 MFU on v5e). vs_baseline is img/s over the
reference's published 2S-Xeon MKL number (81.69 img/s,
IntelOptimizedPaddle.md:39-46). mfu_pct uses analytic model FLOPs at
2 FLOPs/MAC with backward = 2x forward (paddle_tpu/utils/flops.py) over
the chip's peak bf16 FLOP/s; while/scan sub-blocks count body x trips.

Timing runs device-side: exe.run(..., iterations=chunk) scans the whole
training step in one dispatch (core/lowering.py run_steps), so the host's
per-dispatch cost — which scales with the number of parameter buffers — is
excluded by construction.

Every row names the device it ran on (platform, device_kind, device_count
from jax.devices()). A sweep or a --check in which any row errored exits
non-zero; an accelerator with no peak on record (paddle_tpu/utils/flops.py)
fails the row rather than printing it without an MFU.

Run: python bench.py [--model resnet50|alexnet|transformer|...]
                     [--batch-size N] [--steps CHUNK]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


METRICS_SNAPSHOT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "bench_metrics.json")


def _write_metrics_snapshot(model_name: str, kind: str, nsteps: int,
                            dt: float, examples_per_step, tokens_per_step,
                            mfu, flops_per_step=None, passes=None):
    """Observability satellite: publish the measured window into the
    runtime gauges (steps/s, examples/s, tokens/s, MFU) and merge the
    full registry dump into bench_metrics.json next to this script —
    every bench row leaves a telemetry snapshot alongside the
    BENCH_*.json result, so future rounds read counters (retries,
    checkpoint CRCs, queue stalls) without re-running anything."""
    try:
        from paddle_tpu.observability import metrics as obs_metrics
        from paddle_tpu.observability import runtime as obs_runtime
        # rates computed from the measured window directly (NOT through
        # StepStats.record: with observability flags on the executor
        # already counted these steps into paddle_steps_total, and the
        # process-default ring holds warmup/compile samples). The
        # throughput/MFU gauges are set to the window's values so the
        # registry dump below carries them.
        if mfu is None and flops_per_step:
            # off-TPU the spec-sheet lookup knows no peak, but the
            # FLAGS_peak_flops override (runtime.mfu_ratio honors it)
            # still yields a real MFU — same contract as steps.jsonl
            mfu = obs_runtime.mfu_ratio(flops_per_step,
                                        dt / max(nsteps, 1))
        steps_per_s = nsteps / dt if dt > 0 else 0.0
        obs_runtime.STEP_TIME.set(dt / max(nsteps, 1))
        obs_runtime.STEPS_PER_S.set(steps_per_s)
        obs_runtime.EXAMPLES_PER_S.set(
            (examples_per_step or 0) * steps_per_s)
        obs_runtime.TOKENS_PER_S.set((tokens_per_step or 0) * steps_per_s)
        if mfu is not None:
            obs_runtime.MFU.set(mfu)
        try:
            with open(METRICS_SNAPSHOT_PATH) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
        # which IR passes fired for this row, and whether the autotune
        # cache served the build deterministically (hit/miss counters;
        # zero measurements is the CI contract — passes/autotune.py)
        from paddle_tpu.passes import autotune as _autotune
        merged[f"{model_name}-{kind}"] = {
            "steps_per_s": round(steps_per_s, 4),
            "examples_per_s": round(
                (examples_per_step or 0) * steps_per_s, 2),
            "tokens_per_s": round(
                (tokens_per_step or 0) * steps_per_s, 2),
            "mfu": mfu,
            "passes": list(passes or []),
            "autotune_lookups": _autotune.lookup_counts(),
            "autotune_measurements": _autotune.measurement_count(),
            "registry": obs_metrics.default_registry().snapshot(),
        }
        # HBM picture at snapshot time (compiled gauges + census live in
        # the registry dump above; this block adds the structured
        # top-buffers/watermark view the memdump and /memory route share)
        try:
            from paddle_tpu.observability import memory as obs_mem
            merged[f"{model_name}-{kind}"]["memory"] = obs_mem.dump_section()
        except Exception:
            pass
        tmp = METRICS_SNAPSHOT_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, METRICS_SNAPSHOT_PATH)
    except Exception:
        pass    # telemetry must never fail a bench row


ALEXNET_K40M_IMG_S = 425.0      # benchmark/README.md:33-38, bs256
VGG19_XEON_IMG_S = 28.46        # IntelOptimizedPaddle.md:29-36, bs64
                                # (our model is VGG16 — ~18% fewer FLOPs;
                                # treat vs_baseline as indicative only)

DEFAULT_BATCH_SIZES = {"alexnet": 256, "resnet50": 128,
                       "transformer": 32, "transformer_long": 2,
                       "transformer_big": 16,
                       "mnist": 2048, "stacked_dynamic_lstm": 64,
                       "vgg": 64, "se_resnext": 64,
                       "machine_translation": 64,
                       "deepfm": 2048, "googlenet": 128, "smallnet": 512,
                       "roofline_probe": 8192}
RESNET50_XEON_IMG_S = 81.69     # IntelOptimizedPaddle.md:39-46, bs64
GOOGLENET_K40M_IMG_S = 128 / 1.149   # benchmark/README.md:44-49, bs128
                                     # 1149 ms/batch → ~111.4 img/s
SMALLNET_K40M_IMG_S = 512 / 0.063039  # benchmark/README.md:52-57, bs512
                                      # 63.039 ms/batch → ~8122 img/s


# device-side steps per dispatch (exe.run iterations=N): sized so one
# chunk runs ~1-2s on a v5e chip (2026-08-01 record) — the host's
# per-dispatch cost disappears into the chunk
DEFAULT_CHUNKS = {"alexnet": 128, "resnet50": 32, "transformer": 32,
                  "transformer_big": 16,
                  "transformer_long": 32, "mnist": 512,
                  "stacked_dynamic_lstm": 128, "vgg": 16, "se_resnext": 32,
                  "machine_translation": 128, "deepfm": 512,
                  # googlenet: XLA's compile of LONG scans over the
                  # inception graph is pathological (>18 min at 64);
                  # 8 compiles in ~30 s and the window still spans 64+
                  # device steps
                  "googlenet": 8, "smallnet": 512, "roofline_probe": 16}


def _time_chunks(run_chunk, fence, min_seconds=3.0, min_chunks=2,
                 max_chunks=8, warmup=2):
    """Time repeated multi-step chunks. `run_chunk()` dispatches one chunk
    of device-side steps and returns a handle; `fence(handle)` waits for
    it by fetching the (small) result to the host, which the caller
    checks for finiteness anyway. Chunks repeat until
    the window exceeds `min_seconds` or `max_chunks` — dispatch is async,
    so the wall clock alone would let a cheap-dispatch model enqueue an
    unbounded backlog that the single closing fence must drain; the chunk
    cap bounds that. The fence is paid once per WINDOW, so no fence-cost
    subtraction/clamp is needed (round-1 advisor finding on the old
    hardcoded 0.105 s clamp). Returns (n_chunks, seconds, fenced value)."""
    # ≥2 fenced warmup chunks: the first compiles against the startup
    # arrays' layouts; its outputs can carry different XLA layouts, so the
    # second call may specialize (recompile) once more — both must finish
    # before the window opens or a ~20s compile lands inside the timing
    for _ in range(max(2, warmup)):
        fence(run_chunk())
    t0 = time.time()
    n = 0
    last = None
    while (n < min_chunks
           or (time.time() - t0 < min_seconds and n < max_chunks)):
        last = run_chunk()
        n += 1
    val = fence(last)
    return n, time.time() - t0, val


def _device_batch(exe, feed_specs, batch_size, seed=0, int_ranges=None,
                  stack_int=0):
    """Synthetic device-resident batch. stack_int > 0 gives every INT
    feed a leading [stack_int] axis with DISTINCT values per step (fed
    via exe.run(stacked_feed=[names])): a resident batch with fixed
    labels gets memorized within ~60 steps and the loss hits exact 0 →
    log(0) blowups in bf16; fresh labels/ids per scan step keep the
    measurement honest at negligible cost (int feeds are small)."""
    import jax
    rng = np.random.RandomState(seed)
    feeds = {}
    for name, (shape, dtype) in feed_specs.items():
        shape = [batch_size if d == -1 else d for d in shape]
        if dtype.startswith("int"):
            lo, hi = (int_ranges or {}).get(name, (0, 10))
            if stack_int:
                shape = [stack_int] + shape
            arr = rng.randint(lo, hi, size=shape).astype(dtype)
        else:
            arr = rng.rand(*shape).astype(dtype)
        feeds[name] = jax.device_put(arr, exe.device)
    return feeds


def _apply_tpu_passes(program, model_name, batch_size, passes_spec,
                      is_test, feed_names, fetch_names, scope=None):
    """Apply the IR-pass pipeline to a bench program BEFORE the amp/nhwc
    attr rewrites (so they tag the fused ops). `passes_spec` is None
    (committed per-model winner from the autotune table, or the
    defaults), "none" (control arm — zero passes), or a comma list of
    explicit pass names. Returns the applied names; the rewritten
    program was re-verified by paddle_tpu.analysis."""
    if passes_spec == "none":
        return []
    from paddle_tpu import passes as tpu_passes
    names = [p for p in passes_spec.split(",") if p] if passes_spec \
        else None
    return tpu_passes.apply_pipeline(
        program, scope=scope, names=names,
        model=None if names else model_name,
        batch_size=batch_size, is_test=is_test,
        feed_names=feed_names, fetch_names=fetch_names)


# one train row = (paddle_tpu.models module, build kwargs, unit,
# published baseline in that unit or None)
TRAIN_ROWS = {
    "alexnet": ("alexnet", {}, "images/sec", ALEXNET_K40M_IMG_S),
    "resnet50": ("resnet", {}, "images/sec", RESNET50_XEON_IMG_S),
    "mnist": ("mnist", {}, "images/sec", None),
    # T=256: the realistic Transformer-base WMT sequence length
    # (round-3 verdict: T=64 was a toy config that inflated tok/s and
    # understated attention cost); bs32 keeps tokens/step at 8192
    "transformer": ("transformer",
                    {"max_len": 256, "src_vocab": 32000,
                     "tgt_vocab": 32000, "fused_attention": True},
                    "tokens/sec", None),
    # the MFU-ceiling demonstrator (round-3 verdict item 3): an
    # arithmetic intensity that clears the v5e ridge (~240 FLOP/byte)
    # — d_model 1024 / d_inner 4096 / T 512, fused attention block +
    # fused-CE head, h=8 so d_head=128 fills the MXU lanes
    "transformer_big": ("transformer",
                        {"max_len": 512, "src_vocab": 32000,
                         "tgt_vocab": 32000, "d_model": 1024,
                         "d_inner": 4096, "n_head": 8, "n_layer": 6,
                         "fused_attention": True, "fused_head": True},
                        "tokens/sec", None),
    # long-context config: d_head 128 routes attention through the
    # Pallas flash kernels (fwd + blockwise bwd)
    "transformer_long": ("transformer",
                         {"max_len": 2048, "src_vocab": 8000,
                          "tgt_vocab": 8000, "d_model": 1024,
                          "d_inner": 2048, "n_head": 8, "n_layer": 2,
                          "fused_attention": True},
                         "tokens/sec", None),
    "stacked_dynamic_lstm": ("stacked_dynamic_lstm", {"max_len": 100},
                             "words/sec", None),
    "vgg": ("vgg", {}, "images/sec", VGG19_XEON_IMG_S),
    "se_resnext": ("se_resnext", {}, "images/sec", None),
    "machine_translation": ("machine_translation",
                            {"src_vocab": 10000, "tgt_vocab": 10000,
                             "max_len": 32}, "words/sec", None),
    "deepfm": ("deepfm", {}, "examples/sec", None),
    "googlenet": ("googlenet", {}, "images/sec", GOOGLENET_K40M_IMG_S),
    "smallnet": ("smallnet", {}, "images/sec", SMALLNET_K40M_IMG_S),
    # synthetic high-AI fc stack: the measured MFU-ceiling anchor
    # (models/roofline_probe.py docstring; round-3 verdict weak #1)
    "roofline_probe": ("roofline_probe", {}, "examples/sec", None),
}


def build_train_program(model_name: str, batch_size: int, amp: bool = True,
                        nhwc: bool = True, passes_spec: str = None,
                        **build_overrides):
    """The train row's program exactly as the bench times it: the row's
    model at its TRAIN_ROWS widths (``build_overrides`` replaces build
    kwargs — chip_smoke.py's CPU rehearsal shrinks the widths with it),
    the IR-pass pipeline, then the amp-bf16 and NHWC rewrites. Returns
    (main, startup, loss, feed_specs, applied_passes)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    module, kw, _, _ = TRAIN_ROWS[model_name]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        loss, _, feed_specs = getattr(models, module).build(
            is_train=True, **{**kw, **build_overrides})
        applied_passes = _apply_tpu_passes(
            main, model_name, batch_size, passes_spec, is_test=False,
            feed_names=sorted(feed_specs), fetch_names=[loss.name])
        if amp:
            from paddle_tpu.contrib.mixed_precision import rewrite_program_amp
            rewrite_program_amp(main)
        if nhwc:
            from paddle_tpu.contrib.layout import rewrite_program_nhwc
            rewrite_program_nhwc(main)
    return main, startup, loss, feed_specs, applied_passes


def run_bench(model_name: str, batch_size: int, steps: int, warmup: int = 5,
              amp: bool = False, mesh=None, nhwc: bool = True,
              batch_merge: int = 0, passes_spec: str = None):
    import paddle_tpu.fluid as fluid

    # valid ranges for integer feeds (labels in-class, seq_lens >= 1)
    int_ranges = {
        "stacked_dynamic_lstm": {"words": (0, 5000), "label": (0, 2),
                                 "seq_lens": (1, 101)},
    }.get(model_name)
    _, kw, unit, baseline = TRAIN_ROWS[model_name]
    main, startup, loss, feed_specs, applied_passes = build_train_program(
        model_name, batch_size, amp=amp, nhwc=nhwc,
        passes_spec=passes_spec)
    if batch_merge and batch_merge > 1:
        # k-step gradient accumulation (multi_batch_merge_pass capability:
        # fluid/batch_merge.py) — optimizer applies every k-th step on the
        # k-step mean gradient
        fluid.apply_batch_merge(main, startup, batch_merge)

    run_target = main
    n_chips = 1
    if mesh is not None:
        # dp mesh over the requested chips — XLA emits the collectives the
        # reference's nccl2/pserver update methods provided
        from paddle_tpu.parallel import DistributeConfig
        run_target = fluid.CompiledProgram(main).with_sharding(
            DistributeConfig(mesh=mesh, data_axis="dp"))
        n_chips = mesh.size

    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    chunk = max(2, steps if steps else DEFAULT_CHUNKS.get(model_name, 32))
    feeds = _device_batch(exe, feed_specs, batch_size,
                          int_ranges=int_ranges, stack_int=chunk)
    int_names = sorted(n for n, (sh, dt) in feed_specs.items()
                       if dt.startswith("int"))

    # one dispatch per CHUNK of device-side steps (exe.run iterations=N —
    # the lax.scan hot loop); float feeds are resident, int feeds (labels
    # /ids) are fresh per step (see _device_batch); the loss comes back
    # stacked [chunk], and a single D2H fetch per window is the fence
    def run_chunk():
        return exe.run(run_target, feed=feeds, fetch_list=[loss],
                       iterations=chunk, stacked_feed=int_names,
                       return_numpy=False)[0]

    def fence(handle):
        return np.asarray(handle)

    nchunks, dt, losses = _time_chunks(run_chunk, fence, warmup=warmup)
    nsteps = nchunks * chunk

    per_step = batch_size
    if unit in ("tokens/sec", "words/sec"):
        if "seq_lens" in feeds:
            # count actual words, not padded positions (the reference's
            # LoD word count, fluid_benchmark.py train_parallel); the
            # stacked int feed carries [chunk] batches — average per step
            sl = np.asarray(feeds["seq_lens"])
            per_step = int(sl.sum() // (chunk if "seq_lens" in int_names
                                        else 1))
        else:
            per_step = batch_size * kw.get("max_len", 64)
    value = per_step * nsteps / dt

    assert np.all(np.isfinite(losses)), "loss went non-finite"

    # DP scaling: the mesh arm's per-chip throughput over a single-chip
    # reference arm at the per-chip batch (the v5e-64 ≥90% headline,
    # ROADMAP item 1; tools/spmd_bench.py sweeps the full curve).
    # Single-chip rows report None — the column only means something
    # when a mesh actually ran.
    dp_scaling_pct = None
    if mesh is not None and n_chips > 1:
        from paddle_tpu.core.scope import Scope as _Scope
        ref_bs = max(batch_size // n_chips, 1)
        exe1 = fluid.Executor(fluid.TPUPlace())
        scope1 = _Scope()
        exe1.run(startup, scope=scope1)
        feeds1 = _device_batch(exe1, feed_specs, ref_bs,
                               int_ranges=int_ranges, stack_int=chunk)

        def run_ref():
            return exe1.run(main, feed=feeds1, fetch_list=[loss],
                            iterations=chunk, stacked_feed=int_names,
                            return_numpy=False, scope=scope1)[0]

        n1, dt1, _ = _time_chunks(run_ref, fence, min_seconds=1.5,
                                  warmup=2)
        ref_rate = ref_bs * n1 * chunk / dt1     # examples/s, 1 chip
        mesh_rate = batch_size * nsteps / dt     # examples/s, n chips
        dp_scaling_pct = mesh_rate / (n_chips * ref_rate) * 100

    # MFU: analytic model FLOPs (2 FLOPs/MAC, backward = 2x forward —
    # paddle_tpu.utils.flops docstring spells out the convention; XLA's own
    # compiled-executable cost analysis agrees within ~3% on ResNet-50)
    # over the attached chip's peak bf16 FLOP/s. None on the CPU platform;
    # an accelerator with no peak on record raises.
    from paddle_tpu.utils import flops as flops_mod
    mfu = flops_mod.mfu(main, batch_size, dt / nsteps * n_chips,
                        device=exe.device)

    # roofline twin for embedding-bound programs: gather-scatter HBM
    # bytes per step over the chip's peak bandwidth (None when the
    # program has no lookup/pool ops, e.g. the conv models)
    gather_bytes = flops_mod.program_gather_bytes(main, batch_size)
    gather_bps = (gather_bytes / (dt / nsteps * n_chips)
                  if gather_bytes else None)
    peak_hbm = flops_mod.device_peak_hbm(exe.device)
    bw_pct = (gather_bps / peak_hbm * 100
              if gather_bps and peak_hbm else None)

    # compiled peak-HBM twin to mfu_pct: XLA memory_analysis() on the
    # exact executable the timing loop dispatched (same compile-cache
    # key), as a fraction of the chip's HBM CAPACITY — None off-TPU
    # unless FLAGS_hbm_bytes pins a capacity
    peak_hbm_bytes = hbm_pct = None
    try:
        main.desc._obs_name = model_name
        cb = exe._compiled(run_target, sorted(feeds), [loss.name], False)
        mem = cb.analyzed_memory(
            fluid.global_scope(), feeds, iterations=chunk,
            stacked=sorted(set(int_names)) if int_names else False)
        if mem:
            peak_hbm_bytes = int(mem["peak_bytes"])
            cap = flops_mod.device_hbm_bytes(exe.device)
            if cap:
                hbm_pct = peak_hbm_bytes / cap * 100
    except Exception:
        pass

    _write_metrics_snapshot(
        model_name, "train", nsteps, dt, batch_size,
        per_step if unit in ("tokens/sec", "words/sec") else None, mfu,
        flops_per_step=flops_mod.program_flops(main, batch_size),
        passes=applied_passes)

    return {
        "metric": f"{model_name} train throughput (bs{batch_size}"
                  f"{', amp-bf16' if amp else ''}, {n_chips} chip"
                  f"{'s' if n_chips > 1 else ''})",
        "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": round(float(value / baseline), 2) if baseline else None,
        "mfu_pct": round(mfu * 100, 1) if mfu is not None else None,
        "dp_scaling_pct": (round(dp_scaling_pct, 1)
                           if dp_scaling_pct is not None else None),
        "peak_hbm_bytes": peak_hbm_bytes,
        "hbm_pct": round(hbm_pct, 1) if hbm_pct is not None else None,
        "gather_bytes_per_s": (round(gather_bps, 0)
                               if gather_bps is not None else None),
        "bw_pct": round(bw_pct, 1) if bw_pct is not None else None,
        "gflop_per_step": round(
            flops_mod.program_flops(main, batch_size) / 1e9, 1),
        "passes": applied_passes,
    }


RESNET50_XEON_INFER_IMG_S = 217.69  # IntelOptimizedPaddle.md:81-88, bs16
VGG19_XEON_INFER_IMG_S = 75.07      # IntelOptimizedPaddle.md:71-78, bs1
GOOGLENET_XEON_INFER_IMG_S = 600.94  # IntelOptimizedPaddle.md:91-98, bs16


def run_infer_bench(model_name: str, batch_size: int, steps: int,
                    warmup: int = 5, amp: bool = True, nhwc: bool = True,
                    passes_spec: str = None):
    """Inference throughput through the deployment path: build is_test
    graph -> save_inference_model -> AnalysisPredictor load (+BN-fold IR
    rewrite) -> timed forward passes (reference capability:
    inference/api/analysis_predictor.cc; baseline rows
    IntelOptimizedPaddle.md infer tables)."""
    import tempfile
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    nets = {
        "resnet50": (lambda im: models.resnet.resnet(im, 1000, depth=50,
                                                     is_train=False),
                     RESNET50_XEON_INFER_IMG_S),
        "vgg": (lambda im: models.vgg.vgg16(im, 1000, is_train=False),
                VGG19_XEON_INFER_IMG_S),
        "googlenet": (lambda im: models.googlenet.googlenet(
            im, 1000, is_train=False)[0], GOOGLENET_XEON_INFER_IMG_S),
    }
    if model_name not in nets:
        raise ValueError(f"--infer supports {sorted(nets)}, "
                         f"not {model_name!r}")
    net_fn, baseline = nets[model_name]
    image_size = 224

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 1
    with fluid.program_guard(main_p, startup):
        img = fluid.layers.data(name="data",
                                shape=[3, image_size, image_size],
                                dtype="float32")
        prob = fluid.layers.softmax(net_fn(img))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    with tempfile.TemporaryDirectory() as tmp:
        fluid.io.save_inference_model(tmp, ["data"], [prob], exe,
                                      main_program=main_p)
        config = AnalysisConfig()
        config.model_dir = tmp
        predictor = create_paddle_predictor(config)

    program = predictor._program
    pexe, scope = predictor._exe, predictor._scope
    fetch = predictor._fetch_names
    applied_passes = _apply_tpu_passes(
        program, model_name, batch_size, passes_spec, is_test=True,
        feed_names=["data"], fetch_names=list(fetch), scope=scope)
    if amp:
        from paddle_tpu.contrib.mixed_precision import rewrite_program_amp
        rewrite_program_amp(program)
    if nhwc:
        from paddle_tpu.contrib.layout import rewrite_program_nhwc
        rewrite_program_nhwc(program)

    # DIFFERENT image batch per scan step, generated on device: a
    # stateless forward over a resident batch is loop-invariant — XLA
    # computes it once and the "throughput" reads 8x past the roofline.
    # Each step also fetches its probs (stacked) so no step is DCE'd;
    # only the fence pays the D2H copy.
    chunk = max(2, steps if steps else 64)
    x = jax.random.uniform(
        jax.random.key(0),
        (chunk, batch_size, 3, image_size, image_size), jnp.float32)
    feeds = {"data": x}

    def run_chunk():
        return pexe.run(program, feed=feeds, fetch_list=fetch, scope=scope,
                        return_numpy=False, iterations=chunk,
                        stacked_feed=True)[0]

    def fence(handle):
        return np.asarray(handle)

    nchunks, dt, out = _time_chunks(run_chunk, fence, warmup=warmup)
    nsteps = nchunks * chunk
    assert np.all(np.isfinite(out)) and out.shape == (chunk, batch_size, 1000)
    value = batch_size * nsteps / dt
    from paddle_tpu.utils import flops as flops_mod
    mfu = flops_mod.mfu(program, batch_size, dt / nsteps, device=pexe.device)
    _write_metrics_snapshot(model_name, "infer", nsteps, dt, batch_size,
                            None, mfu,
                            flops_per_step=flops_mod.program_flops(
                                program, batch_size),
                            passes=applied_passes)
    return {
        "metric": f"{model_name} infer throughput (bs{batch_size}"
                  f"{', amp-bf16' if amp else ''}, 1 chip)",
        "value": round(float(value), 2),
        "unit": "images/sec",
        "vs_baseline": round(float(value / baseline), 2) if baseline else None,
        "mfu_pct": round(mfu * 100, 1) if mfu is not None else None,
        "passes": applied_passes,
    }


def run_coldstart_bench(model_name: str = "resnet50",
                        batch_size: int = 16):
    """Serving cold-start: load->first-inference latency with the
    persisted AOT executable vs recompile-from-source (reference:
    analysis_predictor.cc model-load path starts serving from a
    deserialized program; Predictor.save_compiled/load_compiled give the
    TPU analogue by serializing the compiled XLA executable next to the
    StableHLO export)."""
    import tempfile
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    if model_name != "resnet50":
        raise ValueError("--coldstart benchmarks the resnet50 serving "
                         f"path; {model_name!r} has no cold-start row")
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = 1
    with fluid.program_guard(main_p, startup):
        img = fluid.layers.data(name="data", shape=[3, 224, 224],
                                dtype="float32")
        prob = fluid.layers.softmax(models.resnet.resnet(
            img, 1000, depth=50, is_train=False))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)

    rng = np.random.RandomState(0)
    batch = {"data": rng.rand(batch_size, 3, 224, 224).astype(np.float32)}

    with tempfile.TemporaryDirectory() as tmp:
        fluid.io.save_inference_model(tmp, ["data"], [prob], exe,
                                      main_program=main_p)
        config = AnalysisConfig()
        config.model_dir = tmp

        def make_pred():
            # same amp-bf16 + NHWC serving config as the infer rows (the
            # fp32-NCHW resnet compile is pathologically slow on this
            # stack and is not a config anyone serves)
            pred = create_paddle_predictor(config)
            from paddle_tpu.contrib.mixed_precision import \
                rewrite_program_amp
            from paddle_tpu.contrib.layout import rewrite_program_nhwc
            rewrite_program_amp(pred._program)
            rewrite_program_nhwc(pred._program)
            return pred

        # path A: compile from source at first inference
        pred_a = make_pred()
        t0 = time.time()
        out_a = pred_a.run(batch)
        t_compile = time.time() - t0
        pred_a.save_compiled(tmp, batch)

        # path B: deserialize the persisted executable, no compiler
        pred_b = make_pred()
        t0 = time.time()
        assert pred_b.load_compiled(tmp)
        out_b = pred_b.run(batch)
        t_aot = time.time() - t0
        np.testing.assert_allclose(out_a[0], out_b[0], rtol=2e-3,
                                   atol=2e-3)   # bf16 serving config

    return {
        "metric": f"{model_name} serving cold-start, AOT-load -> first "
                  f"inference (bs{batch_size}, 1 chip)",
        "value": round(t_aot, 3), "unit": "seconds",
        "vs_baseline": None,
        "compile_from_source_s": round(t_compile, 3),
        "speedup": round(t_compile / t_aot, 1) if t_aot else None,
    }


def aggregate_line(rows, head, n_ok):
    """The sweep aggregate is the FINAL stdout line and must survive the
    driver's tail-window capture (round-3 verdict item 6: BENCH_r03
    physically lost its head rows to truncation) — so rows[] is COMPACT:
    short name, value, unit, mfu. The verbose per-row lines with
    vs_baseline/gflop_per_step were already printed as each model
    finished."""
    compact = []
    for r in rows:
        if "cold-start" in r["metric"]:
            c = {"m": r["metric"].split()[0] + "-coldstart",
                 "v": r.get("value"), "u": r.get("unit")}
            if r.get("value") is None:
                c["err"] = (r.get("error") or "?")[:40]
            compact.append(c)
            continue
        name = r["metric"].split(" train ")[0].split(" infer")[0]
        kind = "infer" if (" infer" in r["metric"]
                           or "deploy" in r["metric"]) else "train"
        c = {"m": name if kind == "train" else f"{name}-infer",
             "v": (round(r["value"], 1)
                   if r.get("value") is not None else None),
             "u": r.get("unit")}
        if r.get("mfu_pct") is not None:
            c["mfu"] = r["mfu_pct"]
        if r.get("dp_scaling_pct") is not None:
            c["dp"] = r["dp_scaling_pct"]
        if r.get("bw_pct") is not None:
            c["bw"] = r["bw_pct"]
        if r.get("hbm_pct") is not None:
            c["hbm"] = r["hbm_pct"]
        if r.get("value") is None:
            c["err"] = (r.get("error") or "?")[:40]
        compact.append(c)
    return {
        "metric": f"full sweep ({n_ok}/{len(rows)} rows; headline: "
                  f"{head['metric']})",
        "value": head.get("value"), "unit": head.get("unit"),
        "vs_baseline": head.get("vs_baseline"),
        "mfu_pct": head.get("mfu_pct"),
        # the device as the headline row's own process saw it (the
        # sweep parent never touches JAX)
        **{k: head.get(k) for k in ("platform", "device_kind",
                                    "device_count")},
        "rows": compact}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None,
                    choices=["alexnet", "resnet50", "roofline_probe",
                             "transformer",
                             "transformer_big", "transformer_long", "mnist",
                             "stacked_dynamic_lstm", "vgg", "se_resnext",
                             "machine_translation", "deepfm", "googlenet",
                             "smallnet"])
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None,
                    help="device-side steps per dispatch chunk "
                         "(default: per-model table)")
    ap.add_argument("--batch-merge", type=int, default=0,
                    help="k-step gradient accumulation (the reference's "
                         "multi_batch_merge_pass capability)")
    ap.add_argument("--passes", default=None, metavar="P1,P2|none",
                    help="IR-pass pipeline for the row: default is the "
                         "committed autotune winner for the model (or "
                         "the static pipeline); 'none' disables (the "
                         "A/B control arm tools/autotune.py uses); a "
                         "comma list applies exactly those passes")
    ap.add_argument("--no-passes", dest="passes", action="store_const",
                    const="none", help="alias for --passes none")
    ap.add_argument("--all", nargs="?", const="", default=None,
                    metavar="M1,M2",
                    help="sweep every model (or a comma list) printing one "
                         "JSON line each; failures print an error line "
                         "and the sweep continues")
    ap.add_argument("--headline", action="store_true",
                    help="run only the headline resnet50 row (the pre-r3 "
                         "default; the default is now the full sweep)")
    ap.add_argument("--coldstart", action="store_true",
                    help="serving cold-start row: AOT executable load vs "
                         "recompile-from-source (resnet50)")
    ap.add_argument("--infer", action="store_true",
                    help="benchmark the deployment/inference path "
                         "(save_inference_model -> AnalysisPredictor)")
    ap.add_argument("--amp", dest="amp", action="store_true", default=True,
                    help="bf16 MXU compute (fp32 master weights) — default")
    ap.add_argument("--no-amp", dest="amp", action="store_false")
    ap.add_argument("--no-nhwc", dest="nhwc", action="store_false",
                    default=True, help="disable the channels-last layout "
                    "rewrite (contrib.layout)")
    ap.add_argument("--check", nargs="?", const="", default=None,
                    metavar="BASELINE_JSON",
                    help="perf-regression gate: re-run a row subset and "
                         "fail (exit 1) if any row regresses more than "
                         "--check-tolerance below the committed aggregate "
                         "(default baseline: the newest BENCH_r*.json; "
                         "accepts the driver artifact or a raw aggregate "
                         "line)")
    ap.add_argument("--check-models", default="mnist,transformer",
                    metavar="M1,M2",
                    help="rows to re-measure for --check (compact "
                         "aggregate names; suffix -infer for deployment "
                         "rows). Default: two fast always-runnable rows")
    ap.add_argument("--check-tolerance", type=float, default=0.08,
                    help="allowed fractional shortfall per row before "
                         "--check fails (default 0.08)")
    ap.add_argument("--chips", type=int, default=0,
                    help="train over a dp mesh of this many chips (one "
                         "SPMD dispatch, docs/performance.md 'SPMD "
                         "execution'); the row gains dp_scaling_pct vs "
                         "an inline single-chip reference arm. 0 "
                         "(default) keeps the single-chip row")
    args = ap.parse_args()

    # ONE PROCESS PER CHIP: the sweep / --check parent below never
    # imports jax — a parent that has touched JAX holds the chip and
    # every per-model child would then fail or hang. Only the single-row
    # branch at the bottom of main() runs on the device.
    def run_one_subprocess(m, infer=False, coldstart=False):
        # one subprocess per model: a fresh backend per run keeps a
        # pathological compile (googlenet-style) or OOM from taking
        # the whole sweep down. Every non-sweep flag forwards.
        cmd = [sys.executable, __file__, "--model", m]
        if not args.amp:
            cmd.append("--no-amp")
        if not args.nhwc:
            cmd.append("--no-nhwc")
        if args.passes:
            cmd += ["--passes", args.passes]
        if infer:
            cmd.append("--infer")
        if coldstart:
            cmd.append("--coldstart")
        if args.batch_size:
            cmd += ["--batch-size", str(args.batch_size)]
        if args.steps:
            cmd += ["--steps", str(args.steps)]
        if args.batch_merge:
            cmd += ["--batch-merge", str(args.batch_merge)]
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1200)
            lines = [l for l in r.stdout.splitlines()
                     if l.startswith("{")]
            ok = r.returncode == 0 and lines
            err = r.stderr[-300:]
        except subprocess.TimeoutExpired:
            ok, err = False, "timeout after 1200s"
        if ok:
            row = json.loads(lines[-1])
        else:
            kind = ("serving cold-start" if coldstart
                    else "infer" if infer else "train")
            row = {"metric": f"{m} {kind} throughput", "value": None,
                   "unit": None, "vs_baseline": None, "error": err}
        print(json.dumps(row), flush=True)
        return row

    import subprocess
    if args.check is not None:
        # Perf-regression gate (round-4 VERDICT #8): round-5 edits must
        # not trade one row for another unnoticed. Re-measures each
        # requested row fresh (subprocess = fresh backend) and compares
        # against the committed aggregate's same-named compact row.
        if not args.check:            # default: newest COMMITTED round —
            # a fresh uncommitted sweep artifact must never become its
            # own baseline (the gate would compare the run to itself)
            import os
            repo = os.path.dirname(os.path.abspath(__file__))
            try:
                tracked = subprocess.run(
                    ["git", "ls-files", "BENCH_r*.json"], cwd=repo,
                    capture_output=True, text=True, check=True
                ).stdout.split()
            except (OSError, subprocess.CalledProcessError):
                import glob                   # non-git checkout fallback
                tracked = sorted(os.path.basename(p) for p in
                                 glob.glob(os.path.join(repo,
                                                        "BENCH_r*.json")))
            if not tracked:
                ap.error("--check: no committed BENCH_r*.json baseline")
            args.check = os.path.join(repo, sorted(tracked)[-1])
        with open(args.check) as f:
            base = json.load(f)
        base_rows = (base.get("parsed") or base).get("rows") or []
        by_name = {r["m"]: r for r in base_rows}
        regressions, checked = [], 0
        for name in [m for m in args.check_models.split(",") if m]:
            ref = by_name.get(name)
            if ref is None or ref.get("v") is None:
                print(json.dumps({"check": name, "status": "no-baseline"}),
                      flush=True)
                continue
            if name.endswith("-coldstart"):
                m, kw = name[:-len("-coldstart")], {"coldstart": True}
            elif name.endswith("-infer"):
                m, kw = name[:-len("-infer")], {"infer": True}
            else:
                m, kw = name, {}
            row = run_one_subprocess(m, **kw)
            v = row.get("value")
            checked += 1
            if v is None:
                regressions.append(name)
                status = "ERROR"
                ratio = None
            else:
                ratio = round(v / ref["v"], 3)
                # latency-unit rows (cold-start seconds) regress UP
                lower_better = (ref.get("u") or "").startswith("second")
                ok_row = (v <= ref["v"] * (1.0 + args.check_tolerance)
                          if lower_better else
                          v >= ref["v"] * (1.0 - args.check_tolerance))
                status = "ok" if ok_row else "REGRESSION"
                if not ok_row:
                    regressions.append(name)
            print(json.dumps({"check": name, "value": v,
                              "baseline": ref["v"], "ratio": ratio,
                              "status": status}), flush=True)
        print(json.dumps({
            "metric": f"perf-check vs {args.check} "
                      f"(tol {args.check_tolerance:.0%})",
            "value": checked - len(regressions), "unit": f"of {checked} "
            f"rows ok", "vs_baseline": None,
            "regressions": regressions}))
        # a gate that measured nothing (all names missed the baseline)
        # must fail loudly, not report success
        sys.exit(1 if (regressions or checked == 0) else 0)
    if args.all is not None:
        models_ = ([m for m in args.all.split(",") if m] if args.all
                   else sorted(DEFAULT_BATCH_SIZES))
        rows = [run_one_subprocess(m, infer=args.infer) for m in models_]
        sys.exit(1 if any(r.get("value") is None for r in rows) else 0)
    if args.model is None and not args.headline and not args.infer \
            and not args.coldstart:
        # DEFAULT: the FULL sweep — every train model plus the three
        # deployment-path rows, one JSON line each as they finish, then
        # one aggregate line (driver schema + rows[]) so the driver
        # artifact substantiates the whole table (round-2 verdict item 2;
        # reference: fluid_benchmark.py:139 reports every model).
        # headline first: if the harness ever truncates the sweep, the
        # most important rows are already on stdout
        order = ["resnet50", "transformer"] + [
            m for m in sorted(DEFAULT_BATCH_SIZES)
            if m not in ("resnet50", "transformer")]
        rows = [run_one_subprocess(m) for m in order]
        rows += [run_one_subprocess(m, infer=True)
                 for m in ("resnet50", "vgg", "googlenet")]
        rows.append(run_one_subprocess("resnet50", coldstart=True))
        head = next((r for r in rows if r.get("value") is not None
                     and r["metric"].startswith("resnet50 train")),
                    next((r for r in rows if r.get("value") is not None),
                         rows[0]))
        n_ok = sum(1 for r in rows if r.get("value") is not None)
        print(json.dumps(aggregate_line(rows, head, n_ok),
                         separators=(",", ":")))
        sys.exit(0 if n_ok == len(rows) else 1)
    if args.model is None:
        args.model = "resnet50"
    # the single-row branch: the only part of this file that runs JAX
    from paddle_tpu.utils import chip
    chip.compile_cache_dir()
    if args.coldstart:
        result = run_coldstart_bench(args.model, args.batch_size or 16)
    elif args.infer:
        infer_bs = {"resnet50": 16, "vgg": 1, "googlenet": 16}
        if args.model not in infer_bs:
            ap.error(f"--infer supports {sorted(infer_bs)}; "
                     f"{args.model!r} has no deployment-path benchmark")
        bs = args.batch_size or infer_bs[args.model]
        result = run_infer_bench(args.model, bs, args.steps, amp=args.amp,
                                 nhwc=args.nhwc, passes_spec=args.passes)
    else:
        bs = args.batch_size or DEFAULT_BATCH_SIZES[args.model]
        mesh = None
        if args.chips and args.chips > 1:
            import jax
            from paddle_tpu.parallel import make_mesh
            mesh = make_mesh({"dp": args.chips},
                             devices=jax.devices()[:args.chips])
        result = run_bench(args.model, bs, args.steps, amp=args.amp,
                           nhwc=args.nhwc, batch_merge=args.batch_merge,
                           passes_spec=args.passes, mesh=mesh)
    print(json.dumps({**result, **chip.device_record()}))


if __name__ == "__main__":
    main()
