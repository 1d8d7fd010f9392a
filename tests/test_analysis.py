"""Build-time program verifier (paddle_tpu.analysis): known-bad corpus
asserting rule id, severity, and op provenance per diagnostic; the
all-green pass over the model zoo and book programs; executor
integration via FLAGS_verify_program; the proglint CLI; and the
shape-inference failure taxonomy (reference capability: C++ InferShape +
op-registry validation on append_op, framework/operator.cc:963)."""

import importlib.util
import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis, flags, models
from paddle_tpu.analysis import Severity
from paddle_tpu.core import ir
from paddle_tpu.core.shape_inference import abstract_eval_op
from paddle_tpu.fluid import layers


def find(diags, rule):
    return [d for d in diags if d.rule == rule]


def one(diags, rule):
    hits = find(diags, rule)
    assert len(hits) == 1, (rule, [d.format() for d in diags])
    return hits[0]


# -- known-bad corpus --------------------------------------------------------

def test_corpus_dangling_input():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="y", shape=[4, 4], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["missing"]},
                          outputs={"Out": ["y"]}))
    d = one(analysis.analyze_program(desc), "dangling-input")
    assert d.severity == Severity.ERROR
    assert (d.block_idx, d.op_index, d.op_type) == (0, 0, "relu")
    assert d.var == "missing"


def test_corpus_unknown_op():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2], dtype="float32"))
    b.append_op(ir.OpDesc(type="frobnicate", inputs={"X": ["x"]},
                          outputs={"Out": ["x"]}))
    d = one(analysis.analyze_program(desc), "unknown-op")
    assert d.severity == Severity.ERROR
    assert d.op_type == "frobnicate" and d.op_index == 0


def test_corpus_dtype_drift():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 3], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 3], dtype="float64"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    d = one(analysis.analyze_program(desc), "dtype-mismatch")
    assert d.severity == Severity.ERROR
    assert (d.op_index, d.op_type, d.var) == (0, "relu", "y")
    assert d.details["declared"] == "float64"
    assert d.details["inferred"] == "float32"


def test_corpus_shape_drift():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 3], dtype="float32"))
    b.add_var(ir.VarDesc(name="w", shape=[3, 5], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 4], dtype="float32"))  # != [2,5]
    b.append_op(ir.OpDesc(type="mul", inputs={"X": ["x"], "Y": ["w"]},
                          outputs={"Out": ["y"]}))
    d = one(analysis.analyze_program(desc), "shape-mismatch")
    assert d.severity == Severity.ERROR
    assert (d.op_index, d.op_type, d.var) == (0, "mul", "y")
    assert d.details["inferred"] == [2, 5]
    assert d.details["declared"] == [2, 4]


def test_corpus_dead_op():
    desc = ir.ProgramDesc()
    b = desc.global_block
    for n in ("x", "y", "z"):
        b.add_var(ir.VarDesc(name=n, shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    b.append_op(ir.OpDesc(type="tanh", inputs={"X": ["x"]},
                          outputs={"Out": ["z"]}))
    diags = analysis.analyze_program(desc, feed_names=["x"],
                                     fetch_names=["y"])
    d = one(diags, "dead-op")
    assert d.severity == Severity.WARNING
    assert (d.op_index, d.op_type) == (1, "tanh")
    # without a fetch set the rule stays quiet
    assert not find(analysis.analyze_program(desc), "dead-op")


def test_corpus_waw_param_hazard():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="w", shape=[2, 2], dtype="float32",
                         persistable=True, is_parameter=True))
    mk = dict(type="fill_constant", outputs={"Out": ["w"]},
              attrs={"shape": [2, 2], "value": 0.0, "dtype": "float32"})
    b.append_op(ir.OpDesc(**mk))
    b.append_op(ir.OpDesc(**mk))
    d = one(analysis.analyze_program(desc), "waw-param")
    assert d.severity == Severity.ERROR          # no intervening read
    assert d.var == "w" and d.op_index == 1
    assert d.details == {"first_write": 0, "second_write": 1,
                         "intervening_read": False}


def test_corpus_dropout_in_inference():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[8], dtype="float32")
        h = layers.dropout(x, dropout_prob=0.3)
        layers.mean(h)
    infer = main.clone(for_test=True)
    d = one(analysis.analyze_program(infer), "rng-in-inference")
    assert d.severity == Severity.WARNING
    assert d.op_type == "dropout"
    assert d.details["self_gating"] is True
    # train-mode program: quiet
    assert not find(analysis.analyze_program(main), "rng-in-inference")


def test_corpus_sampling_in_inference_not_gated():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="p", shape=[4, 10], dtype="float32"))
    b.add_var(ir.VarDesc(name="ids", shape=[4], dtype="int64"))
    b.append_op(ir.OpDesc(type="sampling_id", inputs={"X": ["p"]},
                          outputs={"Out": ["ids"]}))
    d = one(analysis.analyze_program(desc, is_test=True),
            "rng-in-inference")
    assert d.details["self_gating"] is False


def test_corpus_def_before_use():
    desc = ir.ProgramDesc()
    b = desc.global_block
    for n in ("x", "y", "z"):
        b.add_var(ir.VarDesc(name=n, shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["y"]},   # y not yet
                          outputs={"Out": ["z"]}))
    b.append_op(ir.OpDesc(type="tanh", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    d = one(analysis.analyze_program(desc), "def-before-use")
    assert d.severity == Severity.ERROR
    assert (d.op_index, d.var) == (0, "y")
    assert d.details["first_write_index"] == 1


def test_corpus_unfed_input():
    desc = ir.ProgramDesc()
    b = desc.global_block
    for n in ("x", "lbl", "y"):
        b.add_var(ir.VarDesc(name=n, shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="elementwise_add",
                          inputs={"X": ["x"], "Y": ["lbl"]},
                          outputs={"Out": ["y"]}))
    diags = analysis.analyze_program(desc, feed_names=["x"],
                                     fetch_names=["y"])
    d = one(diags, "unfed-input")
    assert d.severity == Severity.ERROR and d.var == "lbl"
    # feeding it silences the rule
    assert not find(analysis.analyze_program(desc, feed_names=["x", "lbl"],
                                             fetch_names=["y"]),
                    "unfed-input")


def _while_program(bind_p: bool):
    """block 1 = while body reading parent var 'p'; bound via x_vars
    only when bind_p."""
    desc = ir.ProgramDesc()
    b0 = desc.global_block
    b0.add_var(ir.VarDesc(name="c", shape=[1], dtype="bool"))
    b0.add_var(ir.VarDesc(name="p", shape=[2, 2], dtype="float32"))
    b0.add_var(ir.VarDesc(name="out_c", shape=[1], dtype="bool"))
    b1 = desc.append_block(parent_idx=0)
    b1.add_var(ir.VarDesc(name="tmp", shape=[2, 2], dtype="float32"))
    b1.append_op(ir.OpDesc(type="relu", inputs={"X": ["p"]},
                           outputs={"Out": ["tmp"]}))
    b1.append_op(ir.OpDesc(type="logical_not", inputs={"X": ["c"]},
                           outputs={"Out": ["c"]}))
    b0.append_op(ir.OpDesc(
        type="while",
        inputs={"Carry": ["c"], "X": (["p"] if bind_p else [])},
        outputs={"Out": ["out_c"]},
        attrs={"sub_block": 1, "cond_var": "c", "carry_vars": ["c"],
               "x_vars": (["p"] if bind_p else [])}))
    return desc


def test_corpus_subblock_unbound_read():
    diags = analysis.analyze_program(_while_program(bind_p=False))
    d = one(diags, "subblock-unbound-read")
    assert d.severity == Severity.ERROR
    assert (d.block_idx, d.var) == (1, "p")
    assert d.details["owner_type"] == "while"
    assert not find(analysis.analyze_program(_while_program(bind_p=True)),
                    "subblock-unbound-read")


def test_corpus_attr_schema():
    desc = ir.ProgramDesc()
    b0 = desc.global_block
    b0.add_var(ir.VarDesc(name="c", shape=[1], dtype="bool"))
    b0.append_op(ir.OpDesc(            # missing cond_var/carry_vars,
        type="while",                  # sub_block out of range
        inputs={"Carry": ["c"]}, outputs={"Out": ["c"]},
        attrs={"sub_block": 7}))
    diags = find(analysis.analyze_program(desc), "attr-schema")
    assert diags and all(d.severity == Severity.ERROR for d in diags)
    msgs = " | ".join(d.message for d in diags)
    assert "cond_var" in msgs and "block 7" in msgs


def test_corpus_grad_pairing():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="w@GRAD", shape=[2], dtype="float32"))
    d = one(analysis.analyze_program(desc), "grad-pairing")
    assert d.severity == Severity.WARNING
    assert d.details["forward_var"] == "w"


# -- suppressions ------------------------------------------------------------

def test_suppression_per_op_and_per_run():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="y", shape=[2], dtype="float32"))
    op = b.append_op(ir.OpDesc(type="relu", inputs={"X": ["missing"]},
                               outputs={"Out": ["y"]}))
    assert find(analysis.analyze_program(desc), "dangling-input")
    # per-run
    assert not find(analysis.analyze_program(
        desc, suppress=("dangling-input",)), "dangling-input")
    # per-op attr
    analysis.suppress_op(op, "dangling-input")
    assert not find(analysis.analyze_program(desc), "dangling-input")
    # "*" suppresses everything anchored to the op
    op.attrs["__lint_suppress__"] = ["*"]
    assert not [d for d in analysis.analyze_program(desc)
                if d.op_index == 0]


# -- executor integration (FLAGS_verify_program) -----------------------------

def _corpus_bad_programs():
    """(label, desc, expected rule) — every ERROR-severity corpus
    program, for the build-time rejection sweep."""
    out = []

    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["missing"]},
                          outputs={"Out": ["y"]}))
    out.append(("dangling_input", desc, "dangling-input"))

    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 2], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="frobnicate", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    out.append(("unknown_op", desc, "unknown-op"))

    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 3], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 3], dtype="float64"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    out.append(("dtype_drift", desc, "dtype-mismatch"))

    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 3], dtype="float32"))
    b.add_var(ir.VarDesc(name="w", shape=[3, 5], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 4], dtype="float32"))
    b.append_op(ir.OpDesc(type="mul", inputs={"X": ["x"], "Y": ["w"]},
                          outputs={"Out": ["y"]}))
    out.append(("shape_drift", desc, "shape-mismatch"))

    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="w", shape=[2, 2], dtype="float32",
                         persistable=True, is_parameter=True))
    mk = dict(type="fill_constant", outputs={"Out": ["w"]},
              attrs={"shape": [2, 2], "value": 0.0, "dtype": "float32"})
    b.append_op(ir.OpDesc(**mk))
    b.append_op(ir.OpDesc(**mk))
    out.append(("waw_param", desc, "waw-param"))

    return out


@pytest.mark.parametrize(
    "label,desc,rule",
    _corpus_bad_programs(),
    ids=[label for label, _, _ in _corpus_bad_programs()])
def test_verify_flag_rejects_corpus_at_build(label, desc, rule):
    """Acceptance: with FLAGS_verify_program=1 every known-bad corpus
    program is rejected at CompiledBlock build with a diagnostic naming
    the offending op and rule."""
    from paddle_tpu.core.lowering import CompiledBlock
    fetch = [next(iter(desc.global_block.vars))]
    flags.set("verify_program", True)
    try:
        with pytest.raises(analysis.ProgramVerificationError) as ei:
            CompiledBlock(desc, 0, [], fetch)
        assert rule in str(ei.value)
    finally:
        flags.reset("verify_program")


def test_verify_program_flag_rejects_at_build():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["nope"]},
                          outputs={"Out": ["y"]}))
    from paddle_tpu.core.lowering import CompiledBlock
    flags.set("verify_program", True)
    try:
        with pytest.raises(analysis.ProgramVerificationError) as ei:
            CompiledBlock(desc, 0, [], ["y"])
        msg = str(ei.value)
        assert "dangling-input" in msg and "relu" in msg
    finally:
        flags.reset("verify_program")


def test_verify_program_flag_clean_program_runs():
    flags.set("verify_program", True)
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[4], dtype="float32")
            loss = layers.mean(layers.fc(x, size=3))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (out,) = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                         fetch_list=[loss])
        assert np.isfinite(float(out))
    finally:
        flags.reset("verify_program")


def test_build_strategy_verify_knob():
    from paddle_tpu.fluid.compiler import BuildStrategy, CompiledProgram
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=3))
    # corrupt the program after build: point an op at a missing var
    main.desc.global_block.ops[0].inputs["X"] = ["gone"]
    bs = BuildStrategy()
    bs.verify_program = True
    cp = CompiledProgram(main).with_build_strategy(bs)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(analysis.ProgramVerificationError):
        exe.run(cp, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])


def test_analysis_metrics_published():
    from paddle_tpu.observability import metrics as obs_metrics
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="y", shape=[2], dtype="float32"))
    b.append_op(ir.OpDesc(type="frobnicate", outputs={"Out": ["y"]}))
    fam = obs_metrics.counter("paddle_analysis_diagnostics_total",
                              "", ("rule", "severity"))
    before = fam.labels(rule="unknown-op", severity="error").value
    analysis.analyze_program(desc)
    assert fam.labels(rule="unknown-op",
                      severity="error").value == before + 1
    hist = obs_metrics.histogram("paddle_analysis_duration_seconds", "")
    assert hist.labels().count >= 1


# -- shape-inference failure taxonomy (satellite fix) ------------------------

def test_abstract_eval_taxonomy():
    from paddle_tpu.core.registry import OPS, register_op

    @register_op("___test_buggy_op", no_grad=True)
    def _buggy(ctx, ins, attrs):          # noqa: ARG001
        raise TypeError("deliberate emitter bug")

    try:
        b = ir.BlockDesc()
        b.add_var(ir.VarDesc(name="x", shape=[2, 2], dtype="float32"))
        b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))

        res = abstract_eval_op(b, ir.OpDesc(type="no_such_op"))
        assert not res.ok and res.skipped == "unregistered-op"

        res = abstract_eval_op(b, ir.OpDesc(
            type="relu", inputs={"X": ["undeclared"]},
            outputs={"Out": ["y"]}))
        assert not res.ok and res.skipped == "missing-input-shape"

        res = abstract_eval_op(b, ir.OpDesc(
            type="___test_buggy_op", inputs={"X": ["x"]},
            outputs={"Out": ["y"]}))
        assert not res.ok and res.error_type == "TypeError"
        assert "deliberate emitter bug" in res.error

        res = abstract_eval_op(b, ir.OpDesc(
            type="relu", inputs={"X": ["x"]}, outputs={"Out": ["y"]}))
        assert res.ok and res.outputs["y"] == ((2, 2), "float32")
    finally:
        # the registry is process-global and test_op_smoke_sweep asserts
        # exact coverage of it — never leak the fixture op
        OPS.pop("___test_buggy_op", None)


def test_shape_infer_error_surfaces_as_diagnostic():
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 2], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="___test_buggy_op2",
                          inputs={"X": ["x"]}, outputs={"Out": ["y"]}))
    from paddle_tpu.core.registry import OPS, register_op

    @register_op("___test_buggy_op2", no_grad=True)
    def _buggy2(ctx, ins, attrs):         # noqa: ARG001
        raise ValueError("bad broadcast")

    try:
        d = one(analysis.analyze_program(desc), "shape-infer-error")
        assert d.severity == Severity.WARNING
        assert d.op_type == "___test_buggy_op2"
        assert d.details["error_type"] == "ValueError"
    finally:
        OPS.pop("___test_buggy_op2", None)


def test_sparse_embedding_vjp_abstract_eval_regression():
    """Regression (analyzer corpus, satellite fix): the lookup_table
    __vjp__ returns a RowSparseGrad pytree; abstract eval must report
    its dense shape, not crash on the missing .shape attribute — and
    the whole embedding-train program must analyze error-free."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        ids = layers.data(name="ids", shape=[1], dtype="int64")
        emb = layers.embedding(ids, size=[20, 8])
        loss = layers.mean(layers.fc(emb, size=2))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    diags = analysis.analyze_program(main, feed_names=["ids"],
                                     fetch_names=[loss.name])
    bad = [d for d in diags if d.severity >= Severity.WARNING]
    assert not bad, [d.format() for d in bad]


def test_dynamic_batch_grad_reshape_regression():
    """Regression (satellite fix): a reshape([-1, V]) between forward
    and loss makes the grad var's -1 mean B*T, not B. The sentinel-space
    fixpoint keeps them distinct, so no false shape-infer-error from the
    __vjp__ cotangent reshape."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4, 6], dtype="float32")
        h = layers.fc(x, size=5, num_flatten_dims=2)      # [-1, 4, 5]
        h2 = layers.reshape(h, shape=[-1, 5])             # [B*4, 5]
        loss = layers.mean(layers.fc(h2, size=1))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    diags = analysis.analyze_program(main, feed_names=["x"],
                                     fetch_names=[loss.name])
    bad = [d for d in diags if d.severity >= Severity.WARNING]
    assert not bad, [d.format() for d in bad]


# -- all-green pass over the model zoo + book programs -----------------------

_MODEL_CFGS = {
    "mnist": {},
    "smallnet": {},
    "deepfm": dict(num_fields=4, vocab_size=100),
    "roofline_probe": dict(d=16, depth=2),
    "machine_translation": {},
    "alexnet": dict(class_dim=10, image_size=64),
    "vgg": dict(class_dim=10, image_size=32),
    "resnet": dict(class_dim=10, image_size=32),
    "se_resnext": dict(class_dim=10, image_size=32),
    "googlenet": dict(class_dim=10, image_size=128),
    "stacked_dynamic_lstm": {},
    "transformer": dict(src_vocab=50, tgt_vocab=50, max_len=8,
                        d_model=16, d_inner=32, n_head=2, n_layer=1,
                        dropout=0.1),
}
_HEAVY = {"alexnet", "vgg", "resnet", "se_resnext", "googlenet",
          "stacked_dynamic_lstm", "transformer"}


def _assert_model_green(name):
    kw = _MODEL_CFGS[name]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        out = getattr(models, name).build(**kw)
    loss, fetches, specs = out[0], out[1] or [], out[2]
    fetch_names = [loss.name] + [getattr(f, "name", str(f))
                                 for f in fetches]
    for program, feeds, fns in ((main, sorted(specs), fetch_names),
                                (startup, [], None)):
        diags = analysis.analyze_program(program, feed_names=feeds,
                                         fetch_names=fns)
        errs = [d for d in diags if d.severity == Severity.ERROR]
        assert not errs, (name, [d.format() for d in errs])


@pytest.mark.parametrize("name", sorted(n for n in _MODEL_CFGS
                                        if n not in _HEAVY))
def test_model_zoo_green(name):
    _assert_model_green(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(_HEAVY))
def test_model_zoo_green_heavy(name):
    _assert_model_green(name)


def _assert_model_green_post_pass(name):
    """The pass-pipeline extension of the zoo sweep: apply the TPU
    rewrite passes, then the full rule catalog over the REWRITTEN
    program must stay error-free (proglint green on every post-pass
    program — the 'every rewritten program re-verified' contract)."""
    from paddle_tpu import passes as tpu_passes
    kw = _MODEL_CFGS[name]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 1
    with fluid.program_guard(main, startup):
        out = getattr(models, name).build(**kw)
    loss, fetches, specs = out[0], out[1] or [], out[2]
    fetch_names = [loss.name] + [getattr(f, "name", str(f))
                                 for f in fetches]
    tpu_passes.apply_pipeline(main, feed_names=sorted(specs),
                              fetch_names=fetch_names, verify=False)
    diags = analysis.analyze_program(main, feed_names=sorted(specs),
                                     fetch_names=fetch_names)
    errs = [d for d in diags if d.severity == Severity.ERROR]
    assert not errs, (name, [d.format() for d in errs])


@pytest.mark.parametrize("name", sorted(n for n in _MODEL_CFGS
                                        if n not in _HEAVY))
def test_model_zoo_green_post_pass(name):
    _assert_model_green_post_pass(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(_HEAVY))
def test_model_zoo_green_post_pass_heavy(name):
    _assert_model_green_post_pass(name)


def test_book_program_green_word2vec():
    VOCAB, EMB = 20, 8
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with fluid.program_guard(main, startup):
        words = [layers.data(name=f"w{i}", shape=[1], dtype="int64")
                 for i in range(4)]
        target = layers.data(name="tgt", shape=[1], dtype="int64")
        embs = [layers.embedding(
            w, size=[VOCAB, EMB],
            param_attr=fluid.ParamAttr(name="shared_emb"))
            for w in words]
        hidden = layers.fc(layers.concat(embs, axis=1), size=16,
                           act="relu")
        pred = layers.fc(hidden, size=VOCAB, act="softmax")
        avg = layers.mean(layers.cross_entropy(input=pred, label=target))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(avg)
    feeds = [f"w{i}" for i in range(4)] + ["tgt"]
    diags = analysis.analyze_program(main, feed_names=feeds,
                                     fetch_names=[avg.name])
    errs = [d for d in diags if d.severity == Severity.ERROR]
    assert not errs, [d.format() for d in errs]


# -- proglint CLI ------------------------------------------------------------

def _proglint():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "proglint.py")
    spec = importlib.util.spec_from_file_location("proglint", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_proglint_saved_model_exit_codes(tmp_path, capsys):
    proglint = _proglint()
    # clean program -> 0
    desc = ir.ProgramDesc()
    b = desc.global_block
    b.add_var(ir.VarDesc(name="x", shape=[2, 2], dtype="float32"))
    b.add_var(ir.VarDesc(name="y", shape=[2, 2], dtype="float32"))
    b.append_op(ir.OpDesc(type="relu", inputs={"X": ["x"]},
                          outputs={"Out": ["y"]}))
    good = tmp_path / "good"
    good.mkdir()
    (good / "__model__.json").write_text(json.dumps(
        {"program": desc.to_dict(), "feed_names": ["x"],
         "fetch_names": ["y"]}))
    assert proglint.main([str(good)]) == 0

    # dangling input -> 1, diagnostic names rule + op
    desc.global_block.ops[0].inputs["X"] = ["missing"]
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "__model__.json").write_text(json.dumps(
        {"program": desc.to_dict(), "feed_names": ["x"],
         "fetch_names": ["y"]}))
    capsys.readouterr()
    assert proglint.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "dangling-input" in out and "relu" in out

    # JSON output is machine-readable
    assert proglint.main([str(bad), "--json"]) == 1
    rec = json.loads(capsys.readouterr().out.splitlines()[0])
    assert rec["rule"] == "dangling-input"
    assert rec["severity"] == "error"


def test_proglint_list_rules(capsys):
    proglint = _proglint()
    assert proglint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("dangling-input", "shape-mismatch", "dead-op",
                "waw-param", "rng-in-inference", "unknown-op"):
        assert rid in out


# -- cross-view program contracts (analysis/contracts.py) --------------------

def _decoder_family(modes):
    from paddle_tpu.models import transformer
    return transformer.build_decoder_lm_programs(
        prompt_len=8, max_new=8, vocab=32, d_model=16, d_inner=32,
        n_head=2, n_layer=2, prompt_buckets=(4, 8), n_slots=4, spec_k=3,
        modes=modes)


def test_contracts_full_family_green():
    """The contract the CI gate (proglint --contracts) enforces: the
    whole decoder_lm family — full, paged and verify views over
    every prompt bucket — passes every cross-view rule."""
    from paddle_tpu.analysis.contracts import DECODER_LM_MODES
    from paddle_tpu.models import transformer
    fam = transformer.contracts_lint_family()
    # four modes; the prefill fans out to a view per bucket + alias
    assert len(DECODER_LM_MODES) == 4 and len(fam) == 6
    diags = analysis.verify_family(fam)
    assert diags == [], [d.format() for d in diags]


def test_contract_view_var_drift():
    fam = _decoder_family(("prefill_paged", "decode_paged"))
    fam["decode_paged"][0].desc.global_block.vars["lm_emb"].shape = [33, 16]
    diags = analysis.verify_family(fam)
    assert [(d.rule, d.var) for d in diags] == \
        [("ctr-view-var-drift", "lm_emb")]
    assert diags[0].severity == Severity.ERROR
    assert "drifts across views" in diags[0].message


def test_contract_salt_misalignment():
    fam = _decoder_family(("prefill_paged", "decode_paged"))
    # shift every rng initializer of ONE view by one startup op index —
    # per-index salting means the views would initialize different
    # weights for the "shared" parameters
    ops = fam["decode_paged"][1].desc.global_block.ops
    ops.insert(0, ops.pop())
    diags = analysis.verify_family(fam)
    assert diags and {d.rule for d in diags} == {"ctr-salt-misalignment"}
    assert any(d.var == "lm_emb" for d in diags)


def test_contract_stale_donation_read():
    fam = _decoder_family(("prefill_paged", "decode_paged"))
    # prefill demotes a page pool that the decode view mutates in place:
    # prefill would then read a local temp, never the donated buffer
    fam["prefill_paged"][0].desc.global_block.vars[
        "lm_page_k_0"].persistable = False
    diags = analysis.verify_family(fam)
    assert [d.rule for d in diags] == ["ctr-stale-donation-read"]
    d = diags[0]
    assert d.var == "lm_page_k_0"
    assert d.details["as"] == "a non-persistable temp"
    assert d.details["offending_view"].startswith("prefill_paged")


def test_contract_geometry_drift():
    import dataclasses
    fam = _decoder_family(("prefill_paged", "decode_paged"))
    m = fam["decode_paged"][0]
    m._geometry = dataclasses.replace(m._geometry, cache_len=32)
    diags = analysis.verify_family(fam)
    # the views disagree, and the drifted view's own page table no
    # longer spans its cache
    assert [(d.rule, d.var) for d in diags] == \
        [("ctr-geometry-drift", "cache_len"),
         ("ctr-geometry-drift", "page_table")]


def test_validate_geometry_record():
    from paddle_tpu.analysis.contracts import validate_geometry
    g = validate_geometry("decode_verify_paged", 8, 8, n_slots=4,
                          spec_k=3)
    assert (g.cache_len, g.window, g.page_size) == (16, 4, 4)
    assert g.max_pages == 4 and g.n_pages == 4 * g.max_pages
    assert g.store_dtype == "float32"          # FLAGS default codec
    with pytest.raises(ValueError, match="needs n_slots"):
        validate_geometry("decode_paged", 8, 8)
    with pytest.raises(ValueError, match="must divide"):
        validate_geometry("prefill_paged", 8, 8, n_slots=4, page_size=3)
    with pytest.raises(ValueError, match="verify window"):
        validate_geometry("decode_verify_paged", 8, 8, n_slots=4,
                          spec_k=16)
    with pytest.raises(ValueError, match="not in"):
        validate_geometry("nope", 8, 8)
