"""The hybrid block's TRAINING half on the CPU at a tiny size (ISSUE 47):
``build_lm``'s loss and every parameter's gradient against the plain
reference, the expert shares' gradients adding up, the MTP module's
shift, the routers' bias update, latent attention without an indexer,
the flash kernels at head sizes 192 / 128 (interpreted), the scopes
the trace readers go by, and the ``full`` view as the family's oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models import transformer as T
from chipbench.reference import joyai_llm_flash_ep16_d6 as ref

BUILD = dict(
    vocab=96, d_model=64, d_inner=128, n_head=4, n_layer=3,
    layer_kinds=["mla"], first_k_dense=1, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=3.2e7,
    n_routed_experts=16, n_experts_held=4, held_start=0, n_experts_per_tok=4,
    d_expert=32, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, router_bias=True, rms_eps=1e-6,
    dtype="float32", mtp_layers=1, mtp_weight=0.3, bias_update_gamma=1e-3)


def programs(seq_len, **over):
    build = {**BUILD, "seq_len": seq_len, **over}
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, totals, _ = T.build_lm(**build)
    return build, main, startup, loss, totals


def started(exe, startup):
    """A scope with the weights as start-up draws them (its seed is
    fixed)."""
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    return scope


def trainer(seq_len, **over):
    build, main, startup, loss, totals = programs(seq_len, **over)
    exe = fluid.Executor(fluid.TPUPlace())
    return build, main, loss, totals, started(exe, startup), exe


def sample(build, batch=2, seed=0):
    t = build["seq_len"]
    seq = np.random.RandomState(seed).randint(0, build["vocab"],
                                              (batch, t + 2))
    return {"ids": seq[:, :t, None], "lbl_ids": seq[:, 1:t + 1, None],
            "lbl2_ids": seq[:, 2:, None]}


def flat(feed):
    return [feed[n][..., 0] for n in ("ids", "lbl_ids", "lbl2_ids")]


# 64 tokens a sequence: the expert layer's dense way; 384: its grouped
# way (768 tokens > DENSE_MAX_TOKENS), three turns of 1 024 rows
@pytest.mark.parametrize("seq_len", [64, 384])
def test_loss_and_every_gradient_agree_with_the_reference(seq_len):
    """rtol 2e-5 on the loss, 1e-4 on every gradient's norm: float32 on
    both sides, only the order of sums differs (7e-8 and 9e-7 read)."""
    build, main, loss, _totals, scope, exe = trainer(seq_len)
    roles = ref.param_shapes(build)
    params = main.global_block().all_parameters()
    assert [p.name for p in params] == ["lm_" + r for r, _ in roles]
    assert [tuple(p.shape) for p in params] == [s for _, s in roles]
    values = {r: np.asarray(scope.find_var("lm_" + r)) for r, _ in roles}
    which = [r for r, _ in roles if "router_bias" not in r]
    feed = sample(build)
    want_loss, want_norms, want_bias, _ = ref.loss_and_grad_norms(
        values, *flat(feed), build, which)
    out = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[loss.name] + [f"lm_{r}@GRAD" for r in which])
    assert float(np.asarray(out[0]).reshape(())) == pytest.approx(
        want_loss, rel=2e-5)
    for role, grad, want in zip(which, out[1:], want_norms):
        got = float(np.sqrt(np.sum(np.square(np.asarray(grad, np.float64)))))
        assert got == pytest.approx(want, rel=1e-4), role
    # the step's bias update, from the step's own load: exact
    for tag, want in want_bias.items():
        np.testing.assert_array_equal(
            np.asarray(scope.find_var(f"lm_{tag}_moe.router_bias")), want)


@pytest.mark.parametrize("n_tokens", [96, 768])
def test_the_shares_gradients_add_up_to_the_uncut_layers(n_tokens):
    """Four members holding four of sixteen experts each: their routed
    parts' gradients with respect to the layer's input, with the shared
    expert counted once, add up to the uncut reference's (every expert
    held, float32 "highest"). Both ways of the op: dense at 96 tokens,
    grouped at 768."""
    from paddle_tpu.ops import expert_ffn as ops
    rng = np.random.RandomState(3)
    m, f, e, k = 32, 16, 16, 4
    p = {"router": rng.randn(m, e) * 0.5, "router_bias": rng.randn(1, e)
         * 0.01, "w_gate": rng.randn(e, m, f) * 0.2,
         "w_up": rng.randn(e, m, f) * 0.2, "w_down": rng.randn(e, f, m) * 0.2,
         "s_gate": rng.randn(m, f) * 0.2, "s_up": rng.randn(m, f) * 0.2,
         "s_down": rng.randn(f, m) * 0.2}
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    x = jnp.asarray(rng.randn(n_tokens, m), jnp.float32)
    g = jnp.asarray(rng.randn(n_tokens, m), jnp.float32)
    build = dict(n_routed_experts=e, n_experts_held=e, n_experts_per_tok=k,
                 norm_topk_prob=True, routed_scaling_factor=2.5)

    def share(x, start):
        combine, idx = ops.route(x, p["router"], k, True, 2.5,
                                 p["router_bias"])
        y, _ = ops.held_experts_part(
            x, combine, idx, p["w_gate"][start:start + 4],
            p["w_up"][start:start + 4], p["w_down"][start:start + 4],
            start, None, e)
        return jnp.sum(y * g)

    def shared(x):
        return jnp.sum(ops.swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
                       * g)

    def uncut(x):
        y, _ = ref.expert_layer(p, x, build, ref.Prec(), held=(0, e))
        return jnp.sum(y * g)

    with jax.default_matmul_precision("highest"):
        parts = sum(jax.grad(share)(x, s) for s in (0, 4, 8, 12)) \
            + jax.grad(shared)(x)
        whole = jax.grad(uncut)(x)
    np.testing.assert_allclose(parts, whole, rtol=2e-4, atol=2e-5)


def test_the_mtp_module_shifts_by_two_and_shares_head_and_table():
    build, main, startup, loss, _totals = programs(32)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = started(exe, startup)
    ops = main.global_block().ops
    head = [op for op in ops if op.type == "dense"
            and op.desc.input("W") == ["lm_head_w"]]
    table = [op for op in ops if op.type == "lookup_table"
             and op.desc.input("W") == ["lm_emb"]]
    assert len(head) == 2 and len(table) == 2      # main and MTP
    feed = sample(build)

    def run(feed):
        # the trained program: a step moves the weights, so a fresh
        # scope a run — of the same programs, compiled once
        out = exe.run(main, feed=feed, scope=started(exe, startup),
                      fetch_list=[loss.name, "lm_head_w@GRAD",
                                  "lm_emb@GRAD"])
        return [np.asarray(o) for o in out]
    base = run(feed)
    # position T's MTP target is beyond the T - 1 positions of the mean
    moved = {**feed, "lbl2_ids": feed["lbl2_ids"].copy()}
    moved["lbl2_ids"][:, -1] = (moved["lbl2_ids"][:, -1] + 1) % 96
    assert run(moved)[0] == base[0]
    # every other position's target t_{i+2} is in it
    moved["lbl2_ids"][:, 0] = (moved["lbl2_ids"][:, 0] + 1) % 96
    assert run(moved)[0] != base[0]
    # and the reference with the target one further on reads another loss
    values = {r: np.asarray(scope.find_var("lm_" + r))
              for r, _ in ref.param_shapes(build)}
    right = ref.loss_and_grad_norms(values, *flat(feed), build, [])[0]
    wrong = ref.loss_and_grad_norms(values, *flat(feed), build, [],
                                    mtp_shift=1)[0]
    assert float(base[0]) == pytest.approx(right, rel=2e-5)
    # (random targets: any of them costs about ln V, so the loss moves
    # by its fourth digit; the gradients' entries move whole)
    assert abs(wrong - right) / right > 2e-4
    # both losses' gradients reach the shared head and table: without
    # the MTP loss they are other gradients
    _b, m0, l0, _t, s0, e0 = trainer(32, mtp_weight=0.0)
    alone = e0.run(m0, feed=feed, scope=s0,
                   fetch_list=[l0.name, "lm_head_w@GRAD", "lm_emb@GRAD"])
    for with_mtp, without in zip(base[1:], alone[1:]):
        assert not np.allclose(with_mtp, np.asarray(without), rtol=1e-3)


def test_the_bias_update_follows_the_sign_rule_and_no_gradient_reaches_b():
    build, main, loss, totals, scope, exe = trainer(32)
    block = main.global_block()
    biases = [p for p in block.all_parameters()
              if p.name.endswith(".router_bias")]
    assert len(biases) == 3 and not any(p.trainable for p in biases)
    assert not [n for n in block.vars
                if "router_bias" in n and "@GRAD" in n]
    updates = [op for op in block.ops if op.type == "router_bias_update"]
    adams = [i for i, op in enumerate(block.ops) if op.type == "adam"]
    assert len(updates) == 3
    assert min(i for i, op in enumerate(block.ops)
               if op.type == "router_bias_update") > max(adams)
    before = {p.name: np.asarray(scope.find_var(p.name)) for p in biases}
    loads = [op.desc.input("Load")[0] for op in updates]
    out = exe.run(main, feed=sample(build), scope=scope, fetch_list=loads)
    for op, load in zip(updates, out):
        load = np.asarray(load)
        assert load.sum() == 2 * 32 * 4            # B x T x K picks
        name = op.desc.input("Bias")[0]
        want = before[name] + 1e-3 * np.sign(load.mean() - load).astype(
            np.float32).reshape(1, -1)
        np.testing.assert_array_equal(np.asarray(scope.find_var(name)), want)
    for name, load in zip((t.name for t in totals), out):
        np.testing.assert_array_equal(np.asarray(scope.find_var(name)),
                                      np.asarray(load))


def test_router_bias_update_op_alone():
    from paddle_tpu.core.registry import get_op
    emit = get_op("router_bias_update").emit
    bias = jnp.zeros((1, 4), jnp.float32)
    load = jnp.asarray([5, 1, 3, 3], jnp.int32)
    out = emit(None, {"Bias": [bias], "Load": [load],
                      "LoadTotal": [jnp.ones(4, jnp.int32)]},
               {"gamma": 0.5})
    np.testing.assert_array_equal(out["BiasOut"][0],
                                  [[-0.5, 0.5, 0.0, 0.0]])
    np.testing.assert_array_equal(out["LoadTotalOut"][0], [6, 2, 4, 4])
    assert get_op("router_bias_update").no_grad


def _mla_weights(rng, m, a, indexer):
    h, ql, dc = a["n_head"], 24, a["kv_lora_rank"]
    dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                  a["v_head_dim"])
    shapes = {"Wdq": (m, ql), "QNorm": (ql,), "Wuq": (ql, h * (dn + dr)),
              "Wdkv": (m, dc + dr), "KvNorm": (dc,), "Wuk": (dc, h * dn),
              "Wuv": (dc, h * dv), "Wo": (h * dv, m)}
    if indexer:
        j, di = a["index_n_heads"], a["index_head_dim"]
        shapes.update({"Wiq": (ql, j * di), "Wik": (m, di),
                       "IkScale": (di,), "IkBias": (di,), "Wiw": (m, j)})
    return {n: jnp.asarray(np.ones(s) if len(s) == 1 else
                           rng.randn(*s) * 0.2, jnp.float32)
            for n, s in shapes.items()}


def test_mla_without_an_indexer_is_expanded_attentions_dense_limit():
    """``mla_full`` (index_topk None: no indexer's weights, no
    selection) against ``expanded_attention`` with an indexer whose
    index_topk covers the sequence — the selection keeps everything —
    and against the reference's latent attention."""
    from paddle_tpu.core.registry import EmitContext, get_op
    from paddle_tpu.ops import mla
    rng = np.random.RandomState(5)
    t, m = 48, 32
    a = dict(n_head=2, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16)
    with_index = dict(a, index_n_heads=2, index_head_dim=16, index_topk=t)
    w = _mla_weights(rng, m, with_index, True)
    x = jnp.asarray(rng.randn(1, t, m), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, row, ki, qi, wi = mla.token_terms(x[0], w, jnp.arange(t),
                                             with_index, 1e4, 1e-6)
        dense = mla.dense(mla.expanded_attention(
            q, row[:, :16], row[:, 16:24], qi, wi, ki, w, with_index),
            w["Wo"])
        none = {n: v for n, v in w.items() if n in (
            "Wdq", "QNorm", "Wuq", "Wdkv", "KvNorm", "Wuk", "Wuv", "Wo")}
        ctx = EmitContext(base_key=None, step_base_key=None, op_index=0,
                          is_test=False, program=None, dist=None)
        out = get_op("mla_full").emit(
            ctx, {n: [v] for n, v in {"X": x, **none}.items()},
            {**a, "rope_theta": 1e4, "epsilon": 1e-6})["Out"][0]
        plain = ref.mla(
            {n: none[s] for n, s in zip(ref._MLA, (
                "Wdq", "QNorm", "Wuq", "Wdkv", "KvNorm", "Wuk", "Wuv",
                "Wo"))}, x[0],
            dict(a, rms_eps=1e-6, rope_theta=1e4), ref.Prec())
    np.testing.assert_allclose(out[0], dense, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[0], plain, rtol=1e-4, atol=1e-5)


def _plain_attention(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_flash_kernels_at_heads_of_192_and_128_forward_and_backward():
    """Query / key heads of 192 and value heads of 128, two key blocks:
    the forward and both backward kernels (interpreted) against plain
    jax.numpy."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(7)
    q, k = (jnp.asarray(rng.randn(1, 2, 256, 192), jnp.float32)
            for _ in range(2))
    v, g = (jnp.asarray(rng.randn(1, 2, 256, 128), jnp.float32)
            for _ in range(2))
    scale = 192 ** -0.5
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, True, scale, 128, 128, True), q, k, v)
        want, want_pull = jax.vjp(
            lambda q, k, v: _plain_attention(q, k, v, scale), q, k, v)
        assert out.shape == (1, 2, 256, 128)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        for got, exp in zip(pull(g), want_pull(g)):
            assert got.shape == exp.shape
            np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-4)


# 128 tokens: one tile a head (``pick_blocks`` takes the whole sequence);
# 384: three query blocks by three key blocks of 128 — the causal
# schedule's six visible tiles of nine
@pytest.mark.parametrize("seq_len", [128, 384])
def test_the_trainer_through_the_interpreted_kernels(monkeypatch, seq_len):
    """The whole op with its kernels forced (interpreted): the same loss
    and gradients as the composed attention's, and the kernels' grids
    run over the visible tiles alone
    (``paddle_flash_causal_blocks_total``)."""
    import sys
    from paddle_tpu.ops import pallas as pk
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    build, main, loss, _t, scope, exe = trainer(seq_len, n_layer=2)
    feed = sample(build, batch=1)
    fetch = [loss.name, "lm_l1_mla.wuq@GRAD", "lm_l1_mla.wdkv@GRAD"]
    want = [np.asarray(o) for o in
            exe.run(main, feed=feed, scope=scope, fetch_list=fetch)]
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    read = lambda: {(kern, kind): fa.CAUSAL_BLOCKS.labels(  # noqa: E731
        kernel=kern, kind=kind).value for kern in ("fwd", "dq", "dkv")
        for kind in ("visited", "computed")}
    before = read()
    build, main, loss, _t, scope, exe = trainer(seq_len, n_layer=2)
    got = exe.run(main, feed=feed, scope=scope, fetch_list=fetch)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-4, atol=1e-6)
    grew = {k: n - before[k] for k, n in read().items()}
    bq, bk = pk.pick_blocks(seq_len, seq_len)
    assert (seq_len // bq, seq_len // bk) == {128: (1, 1),
                                              384: (3, 3)}[seq_len]
    tiles = {128: 1, 384: 6}[seq_len]
    for kern in ("fwd", "dq", "dkv"):
        assert grew[kern, "visited"] == grew[kern, "computed"] > 0
        assert grew[kern, "visited"] % (build["n_head"] * tiles) == 0


def test_the_full_view_is_refused_by_name_where_it_does_not_exist():
    arch = {k: v for k, v in BUILD.items() if k not in (
        "vocab", "d_model", "d_inner", "n_head", "n_layer", "mtp_layers",
        "mtp_weight", "bias_update_gamma")}
    T.hybrid_arch(arch, "full", 3)                          # accepted
    with pytest.raises(ValueError, match="only 'mla' has a full view"):
        T.hybrid_arch({**arch, "layer_kinds": ["gqa", "mla"], "n_kv_head": 2,
                       "head_dim": 16}, "full", 3)
    with pytest.raises(ValueError, match="index_topk"):
        T.hybrid_arch({**arch, "index_topk": 8, "index_n_heads": 2,
                       "index_head_dim": 16}, "full", 3)
    with pytest.raises(ValueError, match="no index_topk"):
        T.hybrid_arch(arch, "decode_paged", 3)
    with pytest.raises(ValueError, match="index_n_heads"):
        T.hybrid_arch({**arch, "index_topk": 8}, "decode_paged", 3)


def test_device_scopes_tell_the_mtp_module_apart():
    from paddle_tpu.observability import device_scopes as ds
    build, main, _loss, _t, _s, _e = trainer(32)
    ops = main.desc.global_block.ops
    scopes = [ds.op_scope(op) for op in ops]
    assert "mla_full" in scopes and "mtp/mla_full" in scopes
    assert "grad/mla_full" in scopes and "grad/mtp/mla_full" in scopes
    assert "mtp/expert_ffn_held" in scopes and "grad/mtp/dense" in scopes
    # the main model's head and losses lie outside the module's scope
    assert scopes.count("mtp/softmax_with_cross_entropy") == 1
    assert scopes.count("softmax_with_cross_entropy") == 1
    for path, want in (
            ("jit(lm)/jit(main)/mtp/mla_full/attend/dot_general",
             "mtp/mla_full/attend"),
            ("jit(lm)/grad/mtp/expert_ffn_held/transpose(jvp(x))/mul",
             "grad/mtp/expert_ffn_held"),
            ("jit(lm)/grad/mla_full/jvp(attend)/custom_vjp_call/exp",
             "grad/mla_full")):
        assert ds.program_scope(path) == want


def test_the_full_view_is_the_familys_oracle():
    """``GenerativeModel.full_forward_generate`` over the hybrid block's
    ``full`` view: greedy tokens equal to the plain reference's forward
    on the same weights."""
    from paddle_tpu.serving.engine import GenerativeModel
    arch = {k: v for k, v in BUILD.items() if k not in (
        "mtp_layers", "mtp_weight", "bias_update_gamma")}
    programs = T.build_decoder_lm_programs(prompt_len=8, max_new=8,
                                           modes=("full",), **arch)
    model = GenerativeModel("lm", programs)
    prompt = np.asarray([5, 17, 3, 60, 22], np.int64)
    got = model.full_forward_generate([prompt], max_new=4)[0]
    build = {**arch, "mtp_layers": 0}
    p = {r: np.asarray(model.scope.find_var("lm_" + r))
         for r, _ in ref.param_shapes(build)}
    seq = list(prompt)
    for _ in range(4):
        ids = jnp.asarray(seq, jnp.int32)[None]
        x = p["emb"][ids]
        for tag, dense in ref.layer_tags(build):
            layer = {k[len(tag) + 1:]: v for k, v in p.items()
                     if k.startswith(tag + "_")}
            x, _ = ref._layer(layer, x, ref._items(build), dense, False,
                              True)
        logits = ref.rms_norm(x[0, -1], p["lnf_scale"], 1e-6,
                              ref.Prec()) @ p["head_w"]
        seq.append(int(jnp.argmax(logits)))
    assert list(got) == seq[len(prompt):]


# ------------------------------------------------------------------ ISSUE 50
# a recomputed op keeps what its kernel named: the flash forward's
# output and log-sum-exp (contrib/recompute.py:KEPT), so the backward
# holds no forward kernel; forward op and `__vjp__` come from one trace

def _step_jaxpr(main, loss, feed):
    """The whole step's jaxpr, as the executor lowers it."""
    from paddle_tpu.core.lowering import CompiledBlock
    cb = CompiledBlock(main.desc, 0, list(feed), [loss.name])
    gvars = main.desc.global_block.vars

    def struct(n):
        return jax.ShapeDtypeStruct(tuple(gvars[n].shape),
                                    jnp.dtype(gvars[n].dtype))
    return jax.make_jaxpr(cb.fn)(
        {n: struct(n) for n in cb.sig.state_names},
        {n: struct(n) for n in cb.sig.const_names},
        {n: jax.ShapeDtypeStruct(v.shape, v.dtype) for n, v in feed.items()},
        jax.ShapeDtypeStruct((), jnp.uint32)).jaxpr


def _forward_kernels(jaxpr):
    """The flash FORWARD calls of a jaxpr, nested ones too: the
    ``pallas_call`` equations whose results are (out, lse)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and len(eqn.outvars) == 2 \
                and eqn.outvars[1].aval.shape[-1] == 1:
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _forward_kernels(sub)
    return n


def _kept(op_type):
    from paddle_tpu.ops import grad_ops
    return (grad_ops.KEPT_VALUES.labels(op=op_type).value,
            grad_ops.KEPT_BYTES.labels(op=op_type).value)


def test_a_recomputed_latent_layers_gradients_are_the_untagged_programs(
        monkeypatch):
    """``mla_full`` tagged for recomputation, its kernels forced
    (interpreted): the loss and EVERY parameter's gradient equal the
    untagged program's bit for bit — what the backward keeps are the
    forward kernel's own ``out`` and ``lse``."""
    from paddle_tpu.contrib.recompute import rewrite_program_recompute
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")

    def run(tags):
        build, main, loss, _t, scope, exe = trainer(128, n_layer=2)
        if tags:
            # two main layers and the MTP module's, forward op + snapshot
            assert rewrite_program_recompute(main, tags) == 6
        fetch = [loss.name] + sorted(
            n for n in main.desc.global_block.vars
            if n.startswith("lm_") and n.endswith("@GRAD"))
        return fetch, [np.asarray(o) for o in exe.run(
            main, feed=sample(build, batch=1), scope=scope,
            fetch_list=fetch)]

    names, want = run(())
    _, got = run(("mla_full",))
    assert len(names) > 40
    for n, a, b in zip(names, got, want):
        np.testing.assert_array_equal(a, b, err_msg=n)


@pytest.mark.parametrize("kept,per_layer", [(True, 1), (False, 2)])
def test_a_recomputed_latent_layer_runs_its_forward_kernel_once(
        monkeypatch, kept, per_layer):
    """The step's jaxpr holds ONE forward ``pallas_call`` a recomputed
    latent layer — the forward op's, whose ``out`` and ``lse`` the
    checkpoint keeps — where the bare checkpoint's (nothing named:
    ``KEPT`` emptied) holds two, the second inside the backward's
    recomputation. Counted in the jaxpr: the forward op and its
    ``__vjp__`` are ONE trace, so no merge is left to the compiler."""
    from paddle_tpu.contrib import recompute
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    if not kept:
        monkeypatch.setattr(recompute, "KEPT", ())
    build, main, loss, _t, _scope, _exe = trainer(128, n_layer=2)
    recompute.rewrite_program_recompute(main, ("mla_full",))
    before = _kept("mla_full")
    jaxpr = _step_jaxpr(main, loss, sample(build, batch=1))
    assert _forward_kernels(jaxpr) == 3 * per_layer
    values, nbytes = (a - b for a, b in zip(_kept("mla_full"), before))
    # four heads of 16 over 128 tokens in float32, and their row sums
    assert (values, nbytes) == (
        (6, 3 * (4 * 128 * 16 * 4 + 4 * 128 * 4)) if kept else (0, 0))


def test_an_untagged_latent_layers_backward_re_traces_its_forward(
        monkeypatch):
    """Without the tag nothing changes: the `__vjp__` op re-traces the
    forward (two forward calls a layer in the jaxpr, the second for the
    compiler to merge or drop) and the counter of kept values stands."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    build, main, loss, _t, _scope, _exe = trainer(128, n_layer=2)
    before = _kept("mla_full")
    jaxpr = _step_jaxpr(main, loss, sample(build, batch=1))
    assert _forward_kernels(jaxpr) == 6
    assert _kept("mla_full") == before


@pytest.mark.parametrize("op_type", ["swiglu_ffn", "expert_ffn_held"])
def test_an_op_in_which_nothing_is_named_keeps_its_inputs_alone(op_type):
    """``paddle_recompute_kept_values_total`` / ``_bytes_total`` read 0
    for a recomputed op without a named value, each time it is lowered,
    and are in the exporters' catalog."""
    from paddle_tpu.contrib.recompute import rewrite_program_recompute
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    build, main, loss, _t, scope, exe = trainer(64, n_layer=2)
    assert rewrite_program_recompute(main, (op_type,)) >= 2
    before = _kept(op_type)
    exe.run(main, feed=sample(build, batch=1), scope=scope,
            fetch_list=[loss.name])
    assert _kept(op_type) == before
    # the family exists for the op all the same: a scrape shows the zero
    exporters._preregister_catalog()
    snap = obs_metrics.default_registry().snapshot()
    assert "paddle_recompute_kept_values_total" in snap
    assert "paddle_recompute_kept_bytes_total" in snap
