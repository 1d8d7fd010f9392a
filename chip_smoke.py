"""Chip smoke: the trainer and the slot-scheduler server, end to end on
one TPU chip, through the entry points a user calls.

    python chip_smoke.py               # one chip (what the driver runs)
    python chip_smoke.py --four-chips  # only the SPMD phase, on 4 chips
    python chip_smoke.py --rehearse    # CPU rehearsal at tiny widths

One process, the only one that touches JAX; it starts no child that
needs the chip. Phases (each prints one ``[phase] {json}`` line per
fact; any failure stops the run, exit code 1, last line ``"ok": false``):

0. device   — platform must be ``tpu`` (``--rehearse`` relaxes ONLY
              this), device_kind must be in utils/flops.py's peak table;
              the native library is rebuilt from csrc/.
1. parity   — the verify skill's MNIST MLP, 3 Adam steps, fp32 at
              matmul precision "highest": Executor(TPUPlace()) vs
              Executor(CPUPlace()) in this process; the repaired Pallas
              row kernels vs jnp.take; the save/load host-callback ops.
2. trainer  — bench.py's transformer_big row at its widths (d_model
              1024, d_inner 4096, 8 heads, 6 layers, vocab 32000, T 512,
              batch 16, amp-bf16, default pass pipeline) for three
              multi-step dispatches.
3. server   — decoder_lm at those widths (prompt ladder 128/256/512,
              128 new tokens, 16 slots) on ModelServer: paged KV fp32
              and int8; eight overlapping requests each, zero compiles
              after warm-up, zero AOT fallbacks, greedy tokens checked
              against GenerativeModel.full_forward_generate.
4. spmd     — (``--four-chips`` only) the phase-1 model and
              transformer_big over dp=4 and dp=2 x tp=2 meshes against
              the single-device curve.

Everything printed is a set-up fact, not a benchmark metric. The last
stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
with the device as JAX reports it — a rehearsal truthfully says
``"platform": "cpu"``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150          # the contract's limit is 1200 s

# full width = bench.py's transformer_big row; the decoder server has
# no published width of its own and takes the same
FULL = {
    "trainer": {"batch": 16, "steps": 4, "amp": True, "build": {}},
    "server": {"d_model": 1024, "d_inner": 4096, "n_head": 8,
               "n_layer": 6, "vocab": 32000, "prompt_len": 512,
               "prompt_buckets": (128, 256, 512), "max_new": 128,
               "n_slots": 16},
    "kernel": {"rows": 4096, "width": 1024, "heads": 8, "picks": 2048},
    "oracle_tokens": 12,
}
TINY = {
    # amp off: XLA:CPU has no bf16 x bf16 -> f32 dot
    "trainer": {"batch": 4, "steps": 2, "amp": False,
                "build": {"max_len": 32, "src_vocab": 128,
                          "tgt_vocab": 128, "d_model": 64, "d_inner": 128,
                          "n_head": 2, "n_layer": 1}},
    "server": {"d_model": 32, "d_inner": 64, "n_head": 2, "n_layer": 2,
               "vocab": 64, "prompt_len": 16, "prompt_buckets": (4, 8, 16),
               "max_new": 8, "n_slots": 4},
    "kernel": {"rows": 96, "width": 128, "heads": 2, "picks": 40},
    "oracle_tokens": 6,
}

# jit-cache misses (fires whether or not the persistent cache then
# serves the program) and persistent-cache hits among them
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_backend_compiles = [0]
_cache_hits = [0]


def _on_duration(event, _seconds, **_):
    _backend_compiles[0] += event == _COMPILE_EVENT


def _on_event(event, **_):
    _cache_hits[0] += event == _CACHE_HIT_EVENT


def say(phase: str, **facts):
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


def check(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------- phase 0

def attached_device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_setup(device: dict) -> str:
    import jax
    from paddle_tpu.utils import chip, flops
    cache = chip.compile_cache_dir()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    # raises for an accelerator with no peak on record; None on the CPU
    peak = flops.device_peak_flops(jax.devices()[0])
    say("device", **device, jax=jax.__version__, peak_bf16_flops=peak,
        compile_cache=cache, cache_entries_before=cache_entries(cache))
    # the chip tool copies the tree as it stands on disk, ignored build
    # outputs included: rebuild the native library from what git commits
    from paddle_tpu.core import native
    t0 = time.time()
    shutil.rmtree(os.path.join(REPO, "paddle_tpu", "_native"),
                  ignore_errors=True)
    native.lib()
    say("device", native_rebuilt_s=round(time.time() - t0, 2))
    return cache


# --------------------------------------------------------------- phase 1

def build_mlp():
    """The verify skill's canonical MNIST MLP (softmax classifier on
    the synthetic argmax-projection task, Adam)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup):
        img = layers.data(name="img", shape=[784], dtype="float32")
        label = layers.data(name="label", shape=[1], dtype="int64")
        h = layers.fc(input=img, size=128, act="relu")
        h = layers.fc(input=h, size=64, act="relu")
        logits = layers.fc(input=h, size=10)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def mlp_feeds(step: int, batch: int = 64):
    rng = np.random.RandomState(100 + step)
    proj = np.random.RandomState(0).rand(784, 10).astype(np.float32)
    xv = rng.rand(batch, 784).astype(np.float32)
    return {"img": xv,
            "label": np.argmax(xv @ proj, axis=1).astype(np.int64)[:, None]}


def loss_curve(prog, startup, loss, place, feed_fn, steps: int = 3,
               init=None):
    """`steps` optimizer steps in a fresh scope -> (curve, scope, exe).
    The scope starts from the startup program, or from ``init`` (name ->
    host array) placed on the executor's device."""
    import jax
    import paddle_tpu.fluid as fluid
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    if init is None:
        exe.run(startup, scope=scope)
    for name, value in (init or {}).items():
        scope.set_var(name, jax.device_put(value, exe.device))
    curve = [float(np.asarray(exe.run(prog, feed=feed_fn(s),
                                      fetch_list=[loss],
                                      scope=scope)[0]).reshape(()))
             for s in range(steps)]
    check(np.all(np.isfinite(curve)), f"non-finite loss curve {curve}")
    return curve, scope, exe


def check_row_kernels(cfg: dict):
    """The repaired Pallas row kernels, compiled for the attached device
    (interpreted on the CPU), against jnp.take on one random table."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas as plk
    from paddle_tpu.ops.pallas import embed_cache, paged_attention
    interp = plk.interpret_mode()
    r, d, h, k = cfg["rows"], cfg["width"], cfg["heads"], cfg["picks"]
    rng = np.random.RandomState(0)
    picks = rng.randint(0, r + 8, size=k)           # sentinels past R
    want_idx = np.minimum(picks, r - 1)
    f32 = rng.randn(r, d).astype(np.float32)
    for dtype in (jnp.float32, jnp.bfloat16, jnp.int8):
        table = jnp.asarray(f32 * 40).astype(dtype)
        got = jax.jit(lambda t, i: paged_attention.gather_rows(
            t, i, interpret=interp))(table, jnp.asarray(picks))
        check(np.array_equal(np.asarray(got.astype(jnp.float32)),
                             np.asarray(table.astype(jnp.float32))
                             [want_idx]),
              f"gather_rows != take for {jnp.dtype(dtype).name}")
    ps = 16                     # a whole number of fp32 and bf16 tiles
    pages = rng.randint(0, r // ps + 2, size=max(k // ps, 3))
    for dtype in (jnp.float32, jnp.bfloat16):
        table = jnp.asarray(f32 * 40).astype(dtype)
        got = jax.jit(lambda t, p: paged_attention.gather_pages(
            t, p, ps, interpret=interp))(table, jnp.asarray(pages))
        want = table.reshape(r // ps, ps, d)[
            np.minimum(pages, r // ps - 1)].reshape(-1, d)
        check(np.array_equal(np.asarray(got.astype(jnp.float32)),
                             np.asarray(want.astype(jnp.float32))),
              f"gather_pages != take for {jnp.dtype(dtype).name}")
    codes = jnp.asarray(rng.randint(-127, 128, (r, d)), jnp.int8)
    scales = jnp.asarray(np.abs(rng.randn(r, h)), jnp.float32)
    got = jax.jit(lambda c, s, i: paged_attention.gather_rows_dequant(
        c, s, i, h, interpret=interp))(codes, scales, jnp.asarray(picks))
    want = (np.asarray(codes)[want_idx].astype(np.float32)
            .reshape(k, h, d // h)
            * np.asarray(scales)[want_idx][:, :, None]).reshape(k, d)
    check(np.array_equal(np.asarray(got), want),
          "gather_rows_dequant != take * scale")
    slots = rng.permutation(r + 4)[:k]              # some >= R: dropped
    rows = rng.randn(k, d).astype(np.float32)
    got = jax.jit(lambda t, s, v: embed_cache.scatter_rows(
        t, s, v, interpret=interp))(jnp.asarray(f32), jnp.asarray(slots),
                                    jnp.asarray(rows))
    want = f32.copy()
    want[slots[slots < r]] = rows[slots < r]
    check(np.array_equal(np.asarray(got), want),
          "scatter_rows != table.at[slots].set(rows)")
    say("parity", row_kernels="gather rows f32/bf16/int8, gather pages "
        "f32/bf16, dequant, scatter == jnp reference (exact)",
        shape=[r, d], interpreted=interp)


def check_host_callback_ops():
    """The in-graph save/load ops (io_callback) on the attached device."""
    import paddle_tpu.fluid as fluid
    x = np.random.RandomState(2).rand(3, 4).astype(np.float32)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.npy")
        outs = {}
        for op, inputs, feed in (("save", {"X": ["x"]}, {"x": x}),
                                 ("load", {}, {})):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                block = main.global_block()
                if inputs:
                    block.create_var(name="x", shape=list(x.shape),
                                     dtype="float32")
                block.create_var(name="out", dtype="float32")
                block.append_op(op, inputs=inputs, outputs={"Out": ["out"]},
                                attrs={"file_path": path})
            exe = fluid.Executor(fluid.TPUPlace())
            outs[op] = exe.run(main, feed=feed, fetch_list=["out"],
                               scope=fluid.Scope())[0]
        check(np.array_equal(np.load(path), x), "save op wrote other data")
        check(np.array_equal(np.asarray(outs["load"]), x),
              "load op read other data")
    say("parity", host_callback_ops="save + load round-trip exact")


def phase_parity(cfg: dict):
    import jax
    import paddle_tpu.fluid as fluid
    main, startup, loss = build_mlp()
    # fp32 end to end: the MXU's default precision rounds fp32 operands
    # to bf16 (~3e-3 relative), which is exactly the class of silent
    # drop RTOL must catch
    rtol = 1e-4
    # one set of initial values for both: the package's default PRNG
    # ('rbg', FLAGS_tpu_prng) draws other bits on the TPU than on the
    # CPU for the same seed, so each backend's own startup differs
    scope0 = fluid.Scope()
    fluid.Executor(fluid.TPUPlace()).run(startup, scope=scope0)
    init = {n: np.asarray(scope0.find_var(n))
            for n in scope0.local_var_names()}
    with jax.default_matmul_precision("highest"):
        dev = loss_curve(main, startup, loss, fluid.TPUPlace(),
                         mlp_feeds, init=init)[0]
        cpu = loss_curve(main, startup, loss, fluid.CPUPlace(),
                         mlp_feeds, init=init)[0]
    rel = float(np.max(np.abs(np.subtract(dev, cpu)) / np.abs(cpu)))
    say("parity", model="mnist_mlp 784-128-64-10, 3 Adam steps, fp32 "
        "highest", device_curve=dev, cpu_curve=cpu, max_rel_diff=rel,
        rtol=rtol)
    check(rel <= rtol, f"device vs CPU loss curves differ by {rel}")
    check_row_kernels(cfg["kernel"])
    check_host_callback_ops()


# --------------------------------------------------------------- phase 2

def phase_trainer(cfg: dict):
    import jax
    import paddle_tpu.fluid as fluid
    from bench import TRAIN_ROWS, _device_batch, build_train_program
    from paddle_tpu.ops import pallas as plk
    from paddle_tpu.ops.pallas import fused_ce

    t = cfg["trainer"]
    batch, k = t["batch"], t["steps"]
    kw = {**TRAIN_ROWS["transformer_big"][1], **t["build"]}
    main, startup, loss, feed_specs, passes = build_train_program(
        "transformer_big", batch, amp=t["amp"], **t["build"])
    tlen, d = kw["max_len"], kw["d_model"]
    say("trainer", row="transformer_big", batch=batch, widths=kw,
        amp="bf16" if t["amp"] else None, passes=passes, kernels_on=plk.on_tpu()
        and not plk.kernels_disabled(),
        flash_engage=plk.flash_engage(tlen, tlen, d // kw["n_head"], True),
        fused_ce_supported=fused_ce.supported(batch * tlen, d,
                                              kw["tgt_vocab"]))

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    feeds = _device_batch(exe, feed_specs, batch, stack_int=k)
    names = sorted(feeds)

    def dispatch():
        t0 = time.time()
        out = exe.run(main, feed=feeds, fetch_list=[loss], iterations=k,
                      stacked_feed=names, return_numpy=False,
                      scope=scope)[0]
        jax.block_until_ready(out)
        return np.asarray(out).reshape(-1), time.time() - t0

    # 1st: compile. 2nd: the known layout re-specialisation (outputs of
    # the first call carry other layouts than the startup arrays).
    # 3rd: steady.
    curves, secs, compiles = [], [], []
    for _ in range(3):
        c0 = _backend_compiles[0]
        curve, dt = dispatch()
        curves.append(curve)
        secs.append(dt)
        compiles.append(_backend_compiles[0] - c0)
    losses = np.concatenate(curves)
    check(np.all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(losses[-1] != losses[0], "loss did not move")
    check(compiles[2] == 0, f"steady dispatch recompiled: {compiles}")

    cb = exe._compiled(main, names, [loss.name], False)
    state, consts = cb._resident_state(scope)
    text = cb._multi_fn(k, names).lower(
        state, consts, feeds, np.uint32(0)).compile().as_text()
    n_custom = text.count("tpu_custom_call")
    if plk.on_tpu():
        check(n_custom > 0, "no Pallas kernel in the compiled step")
    say("trainer", first_dispatch_s=round(secs[0], 2),
        second_dispatch_s=round(secs[1], 2),
        backend_compiles_per_dispatch=compiles,
        steady_step_s=round(secs[2] / k, 4), steps_per_dispatch=k,
        losses=[round(float(v), 4) for v in losses],
        tpu_custom_calls_in_step=n_custom,
        peak_bytes_in_use=_peak_bytes())


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# --------------------------------------------------------------- phase 3

def _counter_total(family) -> float:
    return sum(child.value for child in family.children().values())


def serve_variant(cfg: dict, label: str, codec, oracle):
    """One KV codec on a ModelServer: warm up (the only compiles),
    eight overlapping requests, checks."""
    import jax
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving import metrics as smetrics

    s = cfg["server"]
    name = "lm_" + label
    t0 = time.time()
    programs = T.build_decoder_lm_programs(
        **s, modes=T.slot_modes(), kv_codec=codec)
    engine = serving.make_slot_model(name, programs)
    server = serving.ModelServer()
    try:
        server.add_model(engine)
        warm_s = time.time() - t0

        rng = np.random.RandomState(11)
        lens = [int(v) for v in rng.randint(1, s["prompt_len"] + 1, 8)]
        lens[0], lens[1] = s["prompt_buckets"][0], s["prompt_len"]
        budgets = [int(v) for v in
                   rng.randint(max(2, s["max_new"] // 4),
                               s["max_new"] + 1, 8)]
        budgets[0] = budgets[1] = s["max_new"]
        prompts = [rng.randint(1, s["vocab"], n) for n in lens]

        served0 = _counter_total(smetrics.COMPILATIONS)
        compiles0 = _backend_compiles[0]
        fallback0 = _counter_total(smetrics.AOT_FALLBACK)
        with serving.forbid_compiles():
            futs = [server.submit_generate(name, [prompts[i]],
                                           max_new=budgets[i])
                    for i in range(4)]
            # the second wave joins while the first holds decode slots
            t_wait = time.time()
            while not (in_flight := server.stats()[name]["active_slots"]):
                check(time.time() - t_wait < 300, "no admission in 300 s")
                time.sleep(0.001)
            futs += [server.submit_generate(name, [prompts[i]],
                                            max_new=budgets[i])
                     for i in range(4, 8)]
            outs = [f.result(timeout=900)[0] for f in futs]
        for i, out in enumerate(outs):
            check(len(out) == budgets[i],
                  f"request {i}: {len(out)} tokens of {budgets[i]}")
            check(np.all((out >= 0) & (out < s["vocab"])),
                  f"request {i}: token out of vocab")
        check(_counter_total(smetrics.COMPILATIONS) == served0
              and _backend_compiles[0] == compiles0,
              f"compiled after warm-up: serving counter "
              f"+{_counter_total(smetrics.COMPILATIONS) - served0}, "
              f"backend +{_backend_compiles[0] - compiles0}")
        check(_counter_total(smetrics.AOT_FALLBACK) == fallback0,
              "an AOT executable fell back to the jit path")

        text = engine._cb_decode.fn.lower(*engine._args(
            engine._cb_decode, engine._decode_feeds())
        ).compile().as_text()
        n_custom = text.count("tpu_custom_call")
        # the pool variable: [n_pages, page_size, H*D], which the
        # chip keeps row-major at rest (minor_to_major 2,1,0)
        shape = next(tuple(a.shape) for n, a in engine.scope.iter_vars()
                     if n.endswith("_page_k_0"))
        check(shape == (engine.n_pages, engine.page_size, s["d_model"]),
              f"pool variable is {shape}")
        dims = ",".join(str(d) for d in shape)
        at_rest = set(re.findall(r"\w+\[%s\]\{([\d,]+)" % dims,
                                 text.splitlines()[0]))
        if jax.default_backend() == "tpu":
            check(n_custom > 0, "paged decode step holds no Pallas "
                  "gather (tpu_custom_call)")
            check(at_rest == {"2,1,0"}, f"pool at rest is {at_rest}, "
                  f"not row-major: every step would transpose it")
        how = _check_against_oracle(cfg, oracle, prompts[:2], outs[:2])
        say("server", variant=label, views=T.slot_modes(),
            warm_up_s=round(warm_s, 2),
            requests=8, prompt_lens=lens, budgets=budgets,
            tokens_returned=int(sum(len(o) for o in outs)),
            decode_steps=server.stats()[name]["sched_steps"],
            in_flight_at_second_wave=in_flight,
            compiles_after_warm_up=0, aot_fallbacks=0,
            tpu_custom_calls_in_decode_step=n_custom, oracle_check=how,
            peak_bytes_in_use=_peak_bytes(), pool_shape=list(shape),
            pool_minor_to_major=sorted(at_rest))
    finally:
        server.stop()


def _check_against_oracle(cfg, oracle, prompts, outs) -> str:
    """Greedy tokens vs the full-forward oracle over the same seed and
    weights. Random weights leave near-ties between the top logits, and
    the engine and the oracle round differently (cached single-token
    matmuls vs one full-sequence matmul, bf16-class on the MXU): where
    an argmax flips, teacher-force the oracle on the engine's own stream
    and require the engine's token to be within LOGIT_TOL of the
    oracle's best logit at every step."""
    n = cfg["oracle_tokens"]
    want = oracle.full_forward_generate(prompts, max_new=n)
    if all(np.array_equal(w, o[:n]) for w, o in zip(want, outs)):
        return f"exact: {n} greedy tokens x {len(prompts)} requests"
    logit_tol = 0.05
    t_total = oracle.prompt_len + oracle.max_new
    seq = np.zeros((oracle.policy.bucket_for(len(prompts)), t_total),
                   np.int64)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq[i, :len(p)] = p
        seq[i, len(p):len(p) + n - 1] = o[:n - 1]
    fetches, _ = oracle._full.fn(*oracle._args(
        oracle._full, {"ids": seq[:, :, None]}))
    logits = np.asarray(fetches[0])
    worst = 0.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + n]          # [n, V]
        gap = rows.max(-1) - rows[np.arange(n), o[:n]]
        spread = float(rows.std())
        worst = max(worst, float(gap.max()) / spread)
    check(worst <= logit_tol,
          f"engine token is {worst:.4f} logit-std below the oracle's "
          f"best (tolerance {logit_tol})")
    return (f"logit tolerance: engine tokens within {worst:.4f} "
            f"logit-std of the oracle's best (<= {logit_tol}), "
            f"teacher-forced, {n} steps x {len(prompts)} requests")


def phase_server(cfg: dict):
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    s = cfg["server"]
    oracle = serving.GenerativeModel(
        "lm_oracle", T.build_decoder_lm_programs(**s),
        serving.BucketPolicy((2,)))
    serve_variant(cfg, "paged_fp32", "none", oracle)
    serve_variant(cfg, "paged_int8", "int8", oracle)


# --------------------------------------------------------------- phase 4

def _spread_over(scope, names, n_devices: int) -> dict:
    """How the named scope arrays sit on the mesh: every one must have
    a shard on each of ``n_devices`` devices; returns how many are
    partitioned (a shard smaller than the array) vs replicated."""
    import jax
    split = 0
    for n in names:
        arr = scope.find_var(n)
        check(isinstance(arr, jax.Array), f"{n} is not a device array")
        on = {s.device for s in arr.addressable_shards}
        check(len(on) == n_devices,
              f"{n} sits on {len(on)} device(s), not {n_devices}")
        split += arr.addressable_shards[0].data.shape != arr.shape
    return {"arrays": len(names), "partitioned": split,
            "replicated": len(names) - split}


def spmd_case(label, prog_builder, feed_fn, rtol):
    """Single-device curve vs dp=4 and dp=2 x tp=2, 3 steps each."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel import DistributeConfig, make_mesh
    main, startup, loss = prog_builder()
    ref = loss_curve(main, startup, loss, fluid.TPUPlace(), feed_fn)[0]
    for axes, model_axis, want in (
            ({"dp": 4}, None, ("all-reduce",)),
            ({"dp": 2, "tp": 2}, "tp",
             ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all"))):
        dist = DistributeConfig(mesh=make_mesh(axes), data_axis="dp",
                                model_axis=model_axis)
        prog = fluid.CompiledProgram(main).with_sharding(dist)
        got, scope, exe = loss_curve(prog, startup, loss,
                                     fluid.TPUPlace(), feed_fn)
        rel = float(np.max(np.abs(np.subtract(got, ref)) / np.abs(ref)))
        feeds = feed_fn(0)
        cb = exe._compiled(prog, sorted(feeds), [loss.name], False)
        # the state the step updates: parameters + optimizer accumulators
        spread = _spread_over(scope, cb.sig.state_names, 4)
        if model_axis:
            check(spread["partitioned"] > 0,
                  "tp mesh left every parameter replicated")
        feeds = {n: np.asarray(v, cb.feed_dtype(n)) for n, v in
                 feeds.items()}
        state, consts = cb._resident_state(scope)
        text = cb.fn.lower(state, consts, feeds,
                           np.uint32(0)).compile().as_text()
        found = [c for c in want if c in text]
        check(found, f"none of {want} in the compiled step")
        say("spmd", model=label, mesh=axes, curve=got, single=ref,
            max_rel_diff=rel, rtol=rtol, state=spread, collectives=found,
            tpu_custom_calls_in_step=text.count("tpu_custom_call"))
        check(rel <= rtol, f"{label} {axes}: curves differ by {rel}")


def phase_spmd(cfg: dict):
    import jax
    from bench import TRAIN_ROWS, build_train_program
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, have {len(jax.devices())}")
    # fp32 "highest": the dp / tp split changes only the order of fp32
    # sums (tests/test_spmd_exec.py holds 1e-6 on the CPU; the MXU's
    # 6-pass fp32 is allowed one more digit)
    with jax.default_matmul_precision("highest"):
        spmd_case("mnist_mlp", build_mlp, mlp_feeds, rtol=1e-5)
    t = cfg["trainer"]
    kw = {**TRAIN_ROWS["transformer_big"][1], **t["build"]}

    def big():
        # dropout off, as every sharded-vs-single parity test has it:
        # the curve must not depend on how a mask is partitioned
        main, startup, loss, _, _ = build_train_program(
            "transformer_big", t["batch"], amp=t["amp"],
            **{**t["build"], "dropout": 0.0})
        startup.random_seed = 1
        return main, startup, loss

    def big_feeds(step):
        rng = np.random.RandomState(300 + step)
        return {n: rng.randint(0, kw["tgt_vocab"],
                               (t["batch"], kw["max_len"], 1))
                .astype(np.int64)
                for n in ("src_ids", "tgt_ids", "lbl_ids")}

    # amp-bf16, and the lone chip runs the flash kernel where the mesh
    # runs the composed attention: measured 4e-6 (dp=4) and 7e-6
    # (dp=2 x tp=2) on four v5e chips; a lost collective moves the curve
    # by percents
    spmd_case("transformer_big", big, big_feeds,
              rtol=2e-4 if t["amp"] else 1e-5)


# ------------------------------------------------------------------ main

def _watchdog():
    print(f"chip_smoke: no result after {DEADLINE_S} s — giving up",
          file=sys.stderr, flush=True)
    print(json.dumps({"ok": False, "device": None}), flush=True)
    os._exit(3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD phase (4 devices)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny widths, and the platform "
                         "assertion (only that) is relaxed")
    args = ap.parse_args(argv)
    cfg = TINY if args.rehearse else FULL
    timer = threading.Timer(DEADLINE_S, _watchdog)
    timer.daemon = True
    timer.start()
    sys.path.insert(0, REPO)
    t0 = time.time()
    device, ok = None, False
    try:
        device = attached_device()
        # before anything else runs: --rehearse relaxes this, only this
        check(args.rehearse or device["platform"] == "tpu",
              f"no accelerator: jax.devices()[0].platform is "
              f"{device['platform']!r} (CPU rehearsal: --rehearse)")
        cache = phase_setup(device)
        if args.four_chips:
            phase_spmd(cfg)
        else:
            phase_parity(cfg)
            phase_trainer(cfg)
            phase_server(cfg)
        say("done", seconds=round(time.time() - t0, 1),
            compile_requests=_backend_compiles[0],
            served_by_persistent_cache=_cache_hits[0],
            compiled_afresh=_backend_compiles[0] - _cache_hits[0],
            cache_entries_after=cache_entries(cache))
        ok = True
    except Exception:
        traceback.print_exc()
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
