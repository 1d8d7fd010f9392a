"""Device scopes (paddle_tpu.observability.device_scopes, ISSUE 35): the
compiled text of a decode view and of a train step names every Fluid op
type of the block (``grad/<type>`` for the backward ops), the fused
serving ops' phases and a module ``jit_<label>``; the parser finds
fusions, kernels and ``while`` bodies; the map is built only when asked,
from blocks that may be gone, and a dispatch does nothing for it."""

import gc
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.lowering import CompiledBlock, _Executables
from paddle_tpu.core.registry import OPS
from paddle_tpu.fluid import layers
from paddle_tpu.observability import device_scopes as ds
from paddle_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.runners import serve_glm5, serve_hybrid  # noqa: E402


# ------------------------------------------------------- tiny programs

GPT2 = dict(n_layer=2, d_model=32, d_inner=64, n_head=2, vocab=64,
            prompt_len=16, max_new=16, prompt_buckets=(8, 16), n_slots=4,
            page_size=4)
HYBRID = dict(
    n_layer=4, d_model=64, n_head=4, vocab=96, prompt_len=16, max_new=16,
    prompt_buckets=[8, 16], n_slots=4, page_size=4,
    layer_kinds=["gqa", "kda", "kda", "kda"], n_kv_head=2, head_dim=16,
    kda_heads=4, kda_head_dim=16, kda_conv_taps=4, kda_gate_rank=8,
    n_routed_experts=16, n_experts_held=4, n_experts_per_tok=4,
    d_expert=24, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_eps=1e-5, dtype="float32")
LATENT = dict(
    n_layer=2, d_model=64, d_inner=96, n_head=4, vocab=96, prompt_len=32,
    max_new=8, prompt_buckets=[16, 32], n_slots=4, page_size=4,
    layer_kinds=["mla"], first_k_dense=1, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
    index_n_heads=2, index_head_dim=16, index_topk=8, n_routed_experts=16,
    n_experts_held=4, n_experts_per_tok=4, d_expert=24,
    n_shared_experts=1, norm_topk_prob=True, router_bias=True,
    routed_scaling_factor=2.5, rms_eps=1e-5, dtype="float32")


def gpt2_engine(name="lm"):
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    programs = T.build_decoder_lm_programs(
        name=name, modes=T.slot_modes("paged"), kv_codec="none", **GPT2)
    return serving.make_slot_model(name, programs)


def family_engine(family):
    if family == "gpt2":
        return gpt2_engine()
    cfg = dict(kv_layout="paged", kv_codec="none")
    if family == "hybrid":
        return families.Family(serve_hybrid, {
            **cfg, "build": HYBRID,
            "reference": "solar_open2_250b_ep8_d4"}).shared()
    return families.Family(serve_glm5, {
        **cfg, "build": LATENT, "reference": "glm5_744b_ep16_d5"}).shared()


def train_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[64], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def feeds(n=8):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(n, 64).astype(np.float32),
            "y": rng.randn(n, 1).astype(np.float32)}


def scopes_of(block: CompiledBlock) -> dict:
    """{module: {instruction: scope}} of what ``block`` ran."""
    out = {}
    for exe in block._exes.device_executables():
        module, scopes = ds.instruction_scopes(exe.as_text())
        out[module] = scopes
    return out


def lowered_scopes(block: CompiledBlock) -> set:
    """Every program scope in the LOWERED module of the block's single
    step (before XLA removes or merges anything)."""
    (_key, specs), = block._exes.ran.items()
    text = block.fn.lower(*specs).as_text(debug_info=True)
    import re
    return {ds.program_scope(name)
            for name in re.findall(r'loc\("([^"]+)"', text)} - {""}


# ----------------------------------------------- the names in a program

PHASED = {"gpt2": ["kv_attention_decode_paged"],
          "hybrid": ["kv_attention_decode_paged", "kda_decode",
                     "expert_ffn_held"],
          "latent": ["mla_decode_paged", "expert_ffn_held"]}


@pytest.mark.parametrize("family", sorted(PHASED))
def test_decode_view_names_its_ops_its_phases_and_its_module(family):
    engine = family_engine(family)
    engine.warmup()
    block = engine._cb_decode
    op_types = {op.type for op in block.block.ops
                if op.type not in ("feed", "fetch")}
    # lowered: every op type that emits anything is a scope
    lowered = lowered_scopes(block)
    tops = {s.split("/")[0] for s in lowered}
    assert op_types - tops <= {"fill_constant", "assign", "shape",
                               "reshape", "reshape2", "cast"}, \
        op_types - tops
    assert tops <= op_types
    # compiled: the module is the block's, the phases are all there
    (module, scopes), = scopes_of(block).items()
    assert module == "jit_" + ds.module_name("lm.decode_paged", op_types)
    assert module.startswith("jit_lm_decode_paged_s")
    compiled = set(scopes.values())
    for op in PHASED[family]:
        for phase in ds.PHASES[op]:
            assert f"{op}/{phase}" in compiled, (op, phase)
    assert "token_sample" in compiled
    # a prefill view carries no phased op but the expert layer's
    prefill = engine._cb_prefill[engine.prompt_buckets[0]]
    (pmodule, pscopes), = scopes_of(prefill).items()
    assert pmodule.startswith("jit_lm_prefill_paged_")
    assert not [s for s in set(pscopes.values())
                if "decode_paged" in s or "kda_decode" in s]


def test_verify_view_names_its_phases():
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    programs = T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes("paged", spec=True), kv_codec="none",
        **GPT2)
    engine = serving.make_slot_model("lm", programs)
    engine.warmup()
    (module, scopes), = scopes_of(engine._cb_verify).items()
    assert module.startswith("jit_lm_decode_verify_paged_s")
    for phase in ds.PHASES["kv_attention_verify_paged"]:
        assert f"kv_attention_verify_paged/{phase}" in set(scopes.values())


def test_train_step_names_forward_backward_and_optimizer():
    main, startup, loss = train_program()
    main.desc._obs_name = "mlp.train"
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds(), fetch_list=[loss], scope=scope)
    block = exe._compiled(main, sorted(feeds()), [loss.name], False)
    ops = [op for op in block.block.ops if op.type not in ("feed", "fetch")]
    want = {ds.op_scope(op) for op in ops}
    assert {s for s in want if s.startswith("grad/")} == {
        "grad/" + t for t in ("mul", "elementwise_add", "relu", "mean",
                              "square_error_cost")} & want
    assert "adam" in want and "grad/mul" in want
    lowered = lowered_scopes(block)
    assert want - lowered <= {"fill_constant"}, want - lowered
    (module, scopes), = scopes_of(block).items()
    assert module == "jit_mlp_train"
    compiled = set(scopes.values())
    assert {"mul", "grad/mul", "adam"} <= compiled
    # the scan of several steps is another module, and its body's
    # instructions are found through JAX's own ``while/body``
    stacked = {n: np.stack([v, v]) for n, v in feeds().items()}
    exe.run(main, feed=stacked, fetch_list=[loss], scope=scope,
            iterations=2, stacked_feed=sorted(stacked))
    both = scopes_of(block)
    assert set(both) == {"jit_mlp_train", "jit_mlp_train_x2"}
    assert {"mul", "grad/mul", "adam"} <= set(
        both["jit_mlp_train_x2"].values())


# ------------------------------------------------------------ the parser

HLO = '''HloModule jit_lm_decode_paged_s1225, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %exp.2 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(lm)/kda_decode/state/exp" stack_frame_id=3}
  ROOT %add.4 = f32[8]{0} add(%exp.2, %param_0), metadata={op_name="jit(lm)/kda_decode/state/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(lm)/expert_ffn_held/up/mul"}
}

%body.3 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(lm)/while/body/kda_decode/state/add"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %fusion.7)
}

ENTRY %main.10 (state__w__.1: f32[8]) -> f32[8] {
  %state__w__.1 = f32[8]{0} parameter(0), metadata={op_name="state[\\'w\\']"}
  %slice-start.5 = ((f32[8]{0}), f32[8]{0:S(1)}, s32[]) slice-start(%state__w__.1), slice={[0:8]}
  %slice-done.5 = f32[8]{0:S(1)} slice-done(%slice-start.5)
  %copy.3 = f32[8]{0} copy(%state__w__.1), metadata={op_name="state[\\'w\\']"}
  %fusion.8 = f32[8]{0} fusion(%slice-done.5), kind=kLoop, calls=%fused_computation.2
  %gather_pages.22 = f32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(lm)/kv_attention_decode_paged/gather/jit(gather_pages)/pallas_call"}
  %merged.4 = f32[8]{0} add(%fusion.8, %gather_pages.22), metadata={op_name="jit(lm)/jit(_where)/select_n;jit(lm)/token_sample/add"}
  %bare.6 = f32[8]{0} negate(%merged.4), metadata={op_name="jit(lm)/jit(_where)/select_n"}
  ROOT %while.9 = f32[8]{0} while(%bare.6), condition=%cond.4, body=%body.3, metadata={op_name="jit(lm)/while"}
}
'''


def test_parser_finds_fusions_kernels_and_while_bodies():
    module, scopes = ds.instruction_scopes(HLO)
    assert module == "jit_lm_decode_paged_s1225"
    # a fusion with a name of its own, inside a while body
    assert scopes["fusion.7"] == "kda_decode/state"
    # a fusion without one takes its root's
    assert scopes["fusion.8"] == "expert_ffn_held/up"
    # a kernel, under the jitted wrapper's own jit(..)
    assert scopes["gather_pages.22"] == "kv_attention_decode_paged/gather"
    # XLA merged two: the first name that is the program's counts
    assert scopes["merged.4"] == "token_sample"
    # what the compiler added works for its first user
    assert scopes["slice-done.5"] == scopes["slice-start.5"] \
        == "expert_ffn_held/up"
    assert scopes["copy.3"] == "kv_attention_decode_paged/gather"
    # code outside every scope stays outside; containers have no scope
    assert scopes["bare.6"] == "" and scopes["while.9"] == ""
    # the instructions inside a fused computation are there too
    assert scopes["exp.2"] == "kda_decode/state"


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/jit(main)/mla_decode_paged/index/jit(_take)/gather",
     "mla_decode_paged/index"),
    ("jit(f)/mla_decode_paged/dot_general", "mla_decode_paged"),
    # a phase counts only under the op that declares it
    ("jit(f)/mul/attend/dot_general", "mul"),
    ("jit(f)/grad/mul/transpose(jvp(mul))/dot_general", "grad/mul"),
    # JAX's own scan: not the Fluid while op
    ("jit(f)/while/body/closed_call/adam/mul", "adam"),
    ("jit(f)/while/cond/lt", ""),
    # the Fluid while op around JAX's loop around a lowered op
    ("jit(f)/while/while/body/mul/dot_general", "while/mul"),
    ("jit(f)/cond/branch_1_fun/token_sample/sort", "token_sample"),
    # the last component is the primitive, whatever it is called
    ("jit(f)/mul", ""), ("mul", ""), ("", ""),
])
def test_program_scope(op_name, want):
    assert "mul" in OPS and "while" in OPS and "adam" in OPS
    assert ds.program_scope(op_name) == want


def test_phases_are_declared_and_folded_into_the_module_name(monkeypatch):
    with pytest.raises(ValueError, match="not a declared phase"):
        ds.phase("kda_decode", "gather")
    plain = ds.module_name("lm.prefill@128", ["mul", "layer_norm"])
    assert plain == "lm_prefill_128"
    before = ds.module_name("lm.decode_paged", ["mul", "kda_decode"])
    assert before.startswith("lm_decode_paged_s") and len(before) == 21
    # another block's phases do not move this one's name ...
    monkeypatch.setitem(ds.PHASES, "mla_decode_paged",
                        ("project", "index", "score", "select", "attend"))
    assert ds.module_name("lm.decode_paged", ["mul", "kda_decode"]) == before
    # ... its own do, and with the name every compile-cache key
    monkeypatch.setitem(ds.PHASES, "kda_decode", ("conv", "state", "out"))
    assert ds.module_name("lm.decode_paged", ["mul", "kda_decode"]) != before


# ------------------------------------- built on demand, and only then

class Counted:
    """Counts every ``as_text`` of a compiled executable in the
    process."""

    def __init__(self, monkeypatch):
        self.texts = 0
        as_text = jax.stages.Compiled.as_text

        def counted_text(exe, *a, **k):
            self.texts += 1
            return as_text(exe, *a, **k)
        monkeypatch.setattr(jax.stages.Compiled, "as_text", counted_text)


def test_tracing_off_a_request_and_a_run_do_nothing_for_the_map(
        monkeypatch):
    from paddle_tpu import serving
    notes = []
    note = _Executables.note
    monkeypatch.setattr(_Executables, "note",
                        lambda self, *a: (notes.append(a[0]),
                                          note(self, *a))[1])
    engine = gpt2_engine("lm_off")
    server = serving.ModelServer()
    main, startup, loss = train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    try:
        server.add_model(engine)              # warms every program
        for _ in range(2):      # the 2nd compiles for committed state
            exe.run(main, feed=feeds(), fetch_list=[loss], scope=scope)
        compiled_for = len(notes)
        assert compiled_for >= 4              # per COMPILE, as it says
        counted = Counted(monkeypatch)
        ran = {id(v) for b in (engine._cb_decode,
                               exe._compiled(main, sorted(feeds()),
                                             [loss.name], False))
               for v in b._exes.ran.values()}
        assert not tracing.default_tracer().enabled
        out = server.generate("lm_off", [np.arange(1, 7)], max_new=6)
        assert len(out[0]) == 6
        for _ in range(3):
            exe.run(main, feed=feeds(), fetch_list=[loss], scope=scope)
        # no text, no executable kept, no map, nothing noted or rebuilt
        assert counted.texts == 0
        assert len(notes) == compiled_for
        for block in (engine._cb_decode,
                      exe._compiled(main, sorted(feeds()), [loss.name],
                                    False)):
            assert not block._exes.compiled
            assert {id(v) for v in block._exes.ran.values()} <= ran
    finally:
        server.stop()


def test_map_is_read_after_the_window_from_blocks_that_are_gone():
    """The harness's order: build, start the trace, run, let the runner
    return (the engine is freed), THEN ask."""
    def window():
        engine = gpt2_engine("lm_gone")
        engine.warmup()
        tracing.default_tracer().start()
        engine.step()
        return engine._cb_decode.obs_label
    try:
        label = window()
        gc.collect()
        table = ds.scopes()
    finally:
        tracing.default_tracer().stop()
        ds.hold()                       # let this test's blocks go
    module = "jit_" + ds.module_name(label, ["kv_attention_decode_paged"])
    assert module in table
    assert "kv_attention_decode_paged/gather" in set(table[module].values())
    cost = ds.last_build()
    # (other tests' blocks of the same names may be alive beside it:
    # conflicts are theirs to count, not this test's)
    assert cost["executables_parsed"] >= 1 and cost["seconds"] > 0
    assert cost["instructions"] >= len(table[module])
    # asked again, nothing is parsed again
    assert ds.scopes()[module] == table[module]
    assert ds.last_build() is cost


def test_the_second_lowering_finds_the_dispatchs_own_executable():
    """The map's round trip costs no trace, no lowering and no compile:
    the arguments' placement was noted when the dispatch compiled."""
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_k: seen.append(event))
    engine = gpt2_engine("lm_hit")
    engine.warmup()
    engine.step()
    del seen[:]
    exes = engine._cb_decode._exes.device_executables() \
        + engine._cb_prefill[8]._exes.device_executables()
    assert len(exes) == 2
    assert not [e for e in seen if e.endswith((
        "jaxpr_to_mlir_module_duration", "backend_compile_duration"))]


def test_one_executable_serves_every_analysis():
    main, startup, loss = train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds(), fetch_list=[loss], scope=scope)
    block = exe._compiled(main, sorted(feeds()), [loss.name], False)
    assert block.analyzed_flops(scope, feeds()) > 0
    (one,) = block._exes.compiled.values()
    assert block.analyzed_memory(scope, feeds())["peak_bytes"] > 0
    assert block.donation_audit(scope, feeds())["violations"] == []
    assert list(block._exes.compiled.values()) == [one]
    assert block._exes.device_executables() == [one]


def test_engine_gives_the_executables_it_loaded_ahead_of_time(tmp_path):
    engine = gpt2_engine("lm_aot")
    engine._aot[("decode_paged",)] = marker = object()
    assert engine._aot_names.device_executables() == [marker]
    assert engine._aot_names in ds._sources
