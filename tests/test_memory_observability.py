"""HBM memory observability (paddle_tpu.observability.memory, ISSUE 15):
compiled memory_analysis breakdown + per-signature cache, the donation
audit against the compiled input_output_alias header (green on an
optimizer-apply step, red on a donate=False control), the live-buffer
census and family classification, the exact KV-pool gauge on the slot
serving engine, the OOM-forensics memdump from a fault-injected
dispatch, the estimator reconciliation against XLA's compiled numbers,
and the one-flag-lookup zero-overhead contract when FLAGS_memory_stats
is off."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu import flags
from paddle_tpu.observability import memory as obs_memory
from paddle_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_memory_state():
    """Memory telemetry holds process-global state (caches, noted
    scopes, watermark, force-enable) and tests flip flags — both reset
    around every test here."""
    saved = dict(flags._OVERRIDES)
    obs_memory._reset_for_tests()
    yield
    flags._OVERRIDES.clear()
    flags._OVERRIDES.update(saved)
    obs_memory._reset_for_tests()


def _train_program(hidden=16):
    """fc stack + Adam step: the optimizer-apply program the donation
    audit must hold green (every param/accumulator donates and aliases).
    The first fc's weight [64, hidden] is deliberately the largest
    buffer — the OOM test asserts the memdump names it."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[64], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(x, size=hidden, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _largest_param_name(main):
    """The [64, hidden] fc weight — fc names carry the process-global
    unique_name counter, so tests resolve it from the program instead
    of hard-coding fc_0."""
    blk = main.desc.blocks[0]
    best = max((v for v in blk.vars.values()
                if getattr(v, "is_parameter", False)),
               key=lambda v: int(np.prod(v.shape)))
    return best.name


def _feeds(batch=8):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 64).astype(np.float32),
            "y": rng.rand(batch, 1).astype(np.float32)}


def _run_once(main, startup, loss, scope=None, **kw):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feeds(), fetch_list=[loss], scope=scope, **kw)
    return exe


# -- compiled breakdown ---------------------------------------------------

def test_compiled_breakdown_and_cache():
    """memory_analysis() fields come back per signature; the second
    query is a cache hit (same object, no re-lower)."""
    obs_memory.enable()
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    exe = _run_once(main, startup, loss, scope=scope)
    cb = exe._compiled(main, sorted(_feeds()), [loss.name], False)
    mem = cb.analyzed_memory(scope, _feeds())
    assert mem is not None
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "alias_bytes", "generated_code_bytes", "peak_bytes"):
        assert k in mem and mem[k] >= 0
    # params + accumulators are donated arguments: argument bytes must
    # cover at least the resident parameter bytes (64*16 + 16 floats)
    assert mem["argument_bytes"] >= (64 * 16 + 16) * 4
    assert mem["peak_bytes"] > 0
    assert cb.analyzed_memory(scope, _feeds()) is mem   # cache hit


def test_compiled_gauges_exported():
    """The executor telemetry path publishes the breakdown under
    paddle_hbm_compiled_bytes{program,kind} when memory stats are on."""
    obs_memory.enable()
    main, startup, loss = _train_program()
    main.desc._obs_name = "t_mem_prog"
    _run_once(main, startup, loss)
    kinds = {kind: child.value for (prog, kind), child
             in obs_memory.HBM_COMPILED._children.items()
             if prog == "t_mem_prog"}
    assert "peak" in kinds and kinds["peak"] > 0
    assert "argument" in kinds and "temp" in kinds


# -- donation audit -------------------------------------------------------

def test_donation_audit_green_on_optimizer_apply():
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    exe = _run_once(main, startup, loss, scope=scope)
    cb = exe._compiled(main, sorted(_feeds()), [loss.name], False)
    audit = cb.donation_audit(scope, _feeds())
    assert audit["violations"] == []
    assert not audit.get("error")
    # params + Adam moments + beta pow accs all alias in place
    assert len(audit["aliased"]) >= 4
    assert audit["program"]


def test_donation_audit_flags_nondonated_state():
    """Negative control: a donate=False executable re-materializes its
    state outputs — the audit must say so, and count the metric."""
    from paddle_tpu.core.lowering import CompiledBlock
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    _run_once(main, startup, loss, scope=scope)
    cb = CompiledBlock(main.desc, 0, sorted(_feeds()), [loss.name],
                       donate=False)
    before = obs_memory.DONATION_VIOLATIONS.labels(
        program=cb.obs_label).value
    audit = cb.donation_audit(scope, _feeds())
    assert audit["violations"], "donate=False must fail the alias audit"
    assert obs_memory.DONATION_VIOLATIONS.labels(
        program=cb.obs_label).value == before + len(audit["violations"])
    # cached: asking again must not double-count
    cb.donation_audit(scope, _feeds())
    assert obs_memory.DONATION_VIOLATIONS.labels(
        program=cb.obs_label).value == before + len(audit["violations"])


# -- census ---------------------------------------------------------------

def test_census_families_and_watermark():
    obs_memory.enable()
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    _run_once(main, startup, loss, scope=scope)
    cen = obs_memory.census([scope])
    fams = cen["families"]
    # 64x16 + 16x1 weights, two biases
    assert fams["param"] == (64 * 16 + 16 + 16 + 1) * 4
    # Adam: moment1 + moment2 per param, plus per-param scalar
    # beta1/beta2 pow accumulators (4 params x 2 scalars x 4 B)
    assert fams["optimizer_moment"] == 2 * fams["param"] + 4 * 2 * 4
    assert cen["total_bytes"] == sum(fams.values())
    assert cen["buffers"][0]["name"] == _largest_param_name(main)
    assert cen["buffers"][0]["family"] == "param"
    # the executor's telemetry pass recorded a watermark >= this census
    assert obs_memory.watermark() >= cen["total_bytes"]


def test_classify_known_names():
    obs_memory.note_params(["emb_table"])
    obs_memory.register_buffer_family("emb_table_rows", "embed_cache")
    assert obs_memory.classify("lm_page_k_0") == "kv_cache"
    assert obs_memory.classify("lm_page_vs_1") == "kv_cache"
    assert obs_memory.classify("lm_page_c_1") == "kv_cache"
    assert obs_memory.classify("lm_cache_v_1") == "other"
    assert obs_memory.classify("fc_0.w_0_moment1_0") == "optimizer_moment"
    assert obs_memory.classify("fc_0.w_0_velocity_0") == "optimizer_moment"
    assert obs_memory.classify("fc_0.w_0@GRAD") == "activation"
    assert obs_memory.classify("fc_0.w_0") == "param"
    assert obs_memory.classify("emb_table") == "param"
    assert obs_memory.classify("emb_table_rows") == "embed_cache"
    assert obs_memory.classify("tmp_3") == "other"


# -- serving KV pool ------------------------------------------------------

def test_kv_pool_gauge_exact_bytes():
    """The slot engine's page pool is [n_pages, page_size, d_model] fp32
    per layer per k/v, by default every slot at full length — the
    gauge must match that product EXACTLY."""
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    n_slots, prompt_len, max_new = 2, 4, 4
    d_model, n_head, n_layer = 16, 2, 2
    sgm = serving.make_slot_model(
        "lm_membytes",
        T.build_decoder_lm_programs(
            prompt_len=prompt_len, max_new=max_new, vocab=32,
            d_model=d_model, d_inner=32, n_head=n_head, n_layer=n_layer,
            modes=T.slot_modes(), n_slots=n_slots))
    cache_len = prompt_len + max_new
    assert sgm.n_pages * sgm.page_size == n_slots * cache_len
    expect = n_slots * cache_len * d_model * 4 * n_layer * 2
    got = obs_memory.kv_pool_bytes(sgm.scope, "lm_membytes")
    assert got == expect
    assert obs_memory.HBM_KV_POOL.labels(
        model="lm_membytes").value == expect


# -- OOM forensics --------------------------------------------------------

def test_oom_chaos_memdump(tmp_path):
    """Fault-injected OOM at the dispatch site → the executor writes an
    atomic memdump JSON into the flight-recorder dir naming the largest
    live buffer (fc_0.w_0, family param), then re-raises."""
    d = str(tmp_path / "fr")
    flags.set("flight_recorder_dir", d)
    obs_memory.enable()
    main, startup, loss = _train_program()
    main.desc._obs_name = "t_oom_prog"
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    with faults.active("executor.dispatch:raise@1:exc=MemoryError"):
        with pytest.raises(MemoryError):
            exe.run(main, feed=_feeds(), fetch_list=[loss], scope=scope)
    dumps = [f for f in os.listdir(d) if f.endswith(".memdump.json")]
    assert len(dumps) == 1
    with open(os.path.join(d, dumps[0])) as f:
        doc = json.load(f)
    assert doc["reason"] == "oom"
    assert doc["exc_type"] == "MemoryError"
    assert doc["program"] == "t_oom_prog"
    assert doc["top_buffers"][0]["name"] == _largest_param_name(main)
    assert doc["top_buffers"][0]["family"] == "param"
    assert doc["total_bytes"] > 0
    assert (obs_memory.OOM_EVENTS.labels(program="t_oom_prog").value
            == 1)


def test_flight_recorder_dump_has_memory_section(tmp_path):
    from paddle_tpu.observability import flight_recorder
    flags.set("flight_recorder_dir", str(tmp_path))
    rec = flight_recorder.ensure_started()
    try:
        main, startup, loss = _train_program()
        scope = fluid.Scope()
        obs_memory.enable()
        _run_once(main, startup, loss, scope=scope)
        path = rec.dump("test")
        with open(path) as f:
            doc = json.load(f)
        assert "memory" in doc
        mem = doc["memory"]
        assert mem["total_bytes"] > 0
        assert mem["families"].get("param", 0) > 0
        assert mem["top_buffers"]
    finally:
        flight_recorder.shutdown()


# -- estimator reconciliation --------------------------------------------

@pytest.mark.parametrize("model_name", ["mnist", "smallnet"])
def test_estimator_reconciled_with_compiled(model_name):
    """contrib.memory_usage's band against XLA's compiled peak on zoo
    models: resident parameters can never exceed the compiled peak, and
    the peak stays within the straight per-var sum plus slack (XLA
    liveness reuse only shrinks the activation term)."""
    from paddle_tpu import models
    from paddle_tpu.contrib.memory_usage import memory_usage
    batch = 4
    mod = getattr(models, model_name)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        loss, _, feed_specs = mod.build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feeds = {}
    for name, (shape, dtype) in sorted(feed_specs.items()):
        sh = [batch if d == -1 else d for d in shape]
        feeds[name] = np.zeros(
            sh, np.int32 if dtype.startswith("int") else np.float32)
    cb = exe._compiled(main, sorted(feeds), [loss.name], False)
    mem = cb.analyzed_memory(scope, feeds)
    est = memory_usage(main, batch)
    assert mem and mem["peak_bytes"] > 0
    assert est["parameters"] <= mem["peak_bytes"]
    assert mem["peak_bytes"] <= 2 * est["total_high"] + (1 << 20)


def test_optimizer_slots_no_double_count():
    """A minimized program already holds its accumulators as
    persistables — optimizer_slots must NOT add on top (the double-count
    the compiled reconciliation caught); a forward-only program still
    gets the slots estimate."""
    from paddle_tpu.contrib.memory_usage import memory_usage
    main, startup, loss = _train_program()
    with_slots = memory_usage(main, 8, optimizer_slots=2)
    without = memory_usage(main, 8)
    assert with_slots == without

    infer_main, infer_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(infer_main, infer_startup):
        x = layers.data(name="x", shape=[64], dtype="float32")
        layers.fc(x, size=16)
    base = memory_usage(infer_main, 8)
    slots = memory_usage(infer_main, 8, optimizer_slots=2)
    assert slots["persistent"] == base["persistent"] + 2 * base["parameters"]


# -- snapshot + zero-overhead contract ------------------------------------

def test_memory_snapshot_shape():
    obs_memory.enable()
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    _run_once(main, startup, loss, scope=scope)
    snap = obs_memory.snapshot()
    assert set(snap) == {"families", "total_bytes", "top_buffers",
                         "watermark_bytes", "watermark_history"}
    assert snap["total_bytes"] > 0
    json.dumps(snap)    # the /memory route serves exactly this


def test_zero_overhead_when_off(monkeypatch):
    """With FLAGS_memory_stats off, one dispatch costs exactly ONE
    'memory_stats' flag lookup and nothing else from the memory
    subsystem (the step-sampler contract)."""
    main, startup, loss = _train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    # warm the compile cache so the counted run is a steady-state dispatch
    exe.run(main, feed=_feeds(), fetch_list=[loss], scope=scope)

    lookups = []
    real_get = flags.get

    def counting_get(name):
        if name == "memory_stats":
            lookups.append(name)
        return real_get(name)

    monkeypatch.setattr(flags, "get", counting_get)
    census_calls = []
    monkeypatch.setattr(obs_memory, "census",
                        lambda *a, **k: census_calls.append(1) or
                        {"families": {}, "total_bytes": 0, "buffers": []})
    exe.run(main, feed=_feeds(), fetch_list=[loss], scope=scope)
    assert len(lookups) == 1
    assert census_calls == []
