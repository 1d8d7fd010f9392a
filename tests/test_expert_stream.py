"""The expert layer's dense way over the HIT experts alone (ISSUE 54):
``ops/pallas/expert_stream.py:hit_experts`` interpreted against the
``jnp`` dense way it stands for, the rule that engages it by shape
(``ops/expert_ffn.py:dense_tier``), and one whole tiny served program
through it. Shapes stay tiny: the interpreter is slow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import expert_ffn
from paddle_tpu.ops import pallas as pk

# tokens, d_model, d_expert, held experts (the first 6 of a router 12
# wide), picks a token
N, M, F, HELD, ROUTER, K = 8, 128, 256, 6, 12, 2


def _case(draw, dtype, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(N, M), dtype)
    wg, wu = (jnp.asarray(rng.randn(HELD, M, F) * 0.1, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(HELD, F, M) * 0.1, dtype)
    idx = {
        # four of the six held experts, and picks held elsewhere
        "some": np.stack([np.array([0, 2, 4, 5, 7, 9, 11, 2]),
                          np.array([8, 4, 10, 6, 2, 0, 5, 9])], axis=1),
        "none": rng.randint(HELD, ROUTER, (N, K)),
        "all": np.stack([np.arange(N) % HELD, (np.arange(N) + 3) % HELD],
                        axis=1)}[draw]
    combine = jnp.asarray(rng.rand(N, K), jnp.float32)
    # tokens 1 and 6 are an inactive slot's: routed nowhere
    valid = jnp.asarray([True, False, True, True, True, True, False, True])
    return x, combine, jnp.asarray(idx, jnp.int32), wg, wu, wd, valid


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("draw", ["some", "none", "all"])
def test_the_kernel_gives_the_dense_ways_sum(draw, dtype, monkeypatch):
    """``held_experts_part`` through the interpreted kernel against the
    two products over every held expert: the same ``y`` to the
    tolerances of ``tests/test_pallas_kernels.py``, the same ``sizes``;
    an expert no valid token picked is never multiplied."""
    x, combine, idx, wg, wu, wd, valid = _case(draw, dtype)

    def part(forced):
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)
        before = expert_ffn.EXPERT_DENSE_LOWERED.labels(path="skip").value
        y, sizes = jax.jit(
            lambda *a: expert_ffn.held_experts_part(*a, 0, valid, ROUTER))(
            x, combine, idx, wg, wu, wd)
        took = expert_ffn.EXPERT_DENSE_LOWERED.labels(path="skip").value \
            - before
        return np.asarray(y), np.asarray(sizes), took
    want, want_sizes, took = part("0")
    assert not took
    got, sizes, took = part("1")
    assert took == 1
    np.testing.assert_array_equal(sizes, want_sizes)
    assert (sizes > 0).sum() == {"some": 4, "none": 0, "all": 6}[draw]
    assert np.isfinite(got).all()
    if draw == "none":
        assert not got.any() and not want.any()
        return
    assert np.abs(want).max() > 1.0
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got, want, atol=tol * np.abs(want).max())
    # the masked tokens add nothing
    assert not got[[1, 6]].any()


@pytest.mark.parametrize("tile", [128, 256])
def test_unhit_experts_weights_are_never_read(tile):
    """NaN in the weights of every expert without a token: the kernel's
    sum — over two tiles of ``d_expert`` an expert, or one — stays
    finite and equal, where a product with zero does not."""
    from paddle_tpu.ops.pallas import expert_stream as es
    x, combine, idx, wg, wu, wd, valid = _case("some", jnp.float32)
    want, sizes = expert_ffn.held_experts_part(x, combine, idx, wg, wu, wd,
                                               0, valid, ROUTER)
    unhit = np.asarray(sizes) == 0
    order, n_hit = es.hit_order(sizes)
    assert int(n_hit[0]) == 4 and unhit.sum() == 2
    assert list(np.asarray(order)) == [0, 2, 4, 5, 5, 5]
    wg, wu, wd = (jnp.where(unhit[:, None, None], jnp.nan, w)
                  for w in (wg, wu, wd))
    # a token's combine weight for each held expert, written out
    w = np.zeros((N, HELD), np.float32)
    for t, e, c in zip(np.repeat(np.arange(N), K), np.ravel(idx),
                       np.ravel(combine)):
        if valid[t] and e < HELD:
            w[t, e] += c
    got = es.hit_experts(x, jnp.asarray(w), sizes, wg, wu, wd, tile=tile,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * np.abs(want).max())
    refer, _ = expert_ffn.held_experts_part(x, combine, idx, wg, wu, wd, 0,
                                            valid, ROUTER)
    assert np.isnan(np.asarray(refer)).any()


# (tokens, picks, router) of every expert cell's decode step, Solar's
# largest dense prefill, and what must keep the two products whatever
# the draw promises; d_model and d_expert as served
@pytest.mark.parametrize("case,tokens,top_k,router,m,f,devices,want", [
    ("glm5_step", 32, 8, 256, 6144, 2048, 1, "skip"),        # 36 % unpicked
    ("trinity_step", 32, 8, 128, 2048, 1024, 1, "skip"),     # 12.7 %
    ("solar_step", 128, 8, 320, 4096, 1280, 1, "all"),       # 3.9 %
    ("granite_step", 128, 10, 72, 4096, 768, 1, "all"),      # ~0
    ("lfm2_step", 64, 4, 32, 2048, 1792, 1, "all"),          # 0.02 %
    ("solar_prefill_512", 512, 8, 320, 4096, 1280, 1, "all"),
    ("over_the_ridge", 256, 8, 2048, 6144, 2048, 1, "all"),  # 37 %, 256 rows
    ("under_a_mesh", 32, 8, 256, 6144, 2048, 4, "all"),
    ("no_whole_lane_tiles", 32, 8, 256, 6144, 2000, 1, "all")])
def test_the_rule_reads_shapes_and_the_mesh(case, tokens, top_k, router, m,
                                            f, devices, want, monkeypatch):
    """On a TPU (steered: the rule asks ``on_tpu``) the kernel engages
    where a uniform router leaves a tenth of the held experts unpicked
    in a decode-sized call, off a mesh, at widths of whole lane tiles —
    from (n_tokens, top_k, n_experts) and the mesh alone; off the chip
    the two products stay."""
    from jax.sharding import Mesh
    share = expert_ffn.unpicked_share(tokens, top_k, router)
    assert (share >= expert_ffn.SKIP_MIN_UNPICKED) == (
        case in ("glm5_step", "trinity_step", "over_the_ridge",
                 "under_a_mesh", "no_whole_lane_tiles"))
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("dp",))
    assert expert_ffn.dense_tier(tokens, top_k, router, m, f, mesh) == "all"
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    assert expert_ffn.dense_tier(tokens, top_k, router, m, f, mesh) == want


def test_the_counter_is_in_the_exporters_catalog():
    from paddle_tpu.observability import exporters, metrics as obs_metrics
    exporters._preregister_catalog()
    assert "paddle_expert_dense_lowered_total" in \
        obs_metrics.default_registry().snapshot()


# one whole served program: two layers of grouped-KV attention and 16
# experts (all held, 2 picks a token, a shared one) at widths of one
# lane tile; 4 slots x 2 picks over 16 experts leave 59 % unpicked
BUILD = dict(
    n_layer=2, d_model=128, d_inner=128, n_head=2, vocab=64, prompt_len=32,
    max_new=8, prompt_buckets=[32], n_slots=4, page_size=4,
    layer_kinds=["gqa"], first_k_dense=0, n_kv_head=1, head_dim=64,
    qk_norm=True, gqa_gate=False, gqa_rope_theta=1e4,
    n_routed_experts=16, n_experts_held=16, n_experts_per_tok=2,
    d_expert=128, n_shared_experts=1, router_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1.0, tie_embeddings=True,
    rms_eps=1e-5, dtype="float32")


def _served(forced, monkeypatch):
    """Three requests decoded together by a fresh engine whose programs
    are traced with the kernel forced on (interpreted) or off: (tokens,
    logits, expert layers lowered under ``skip``, under ``all``)."""
    import families
    from chipbench.runners import serve_hybrid
    cfg = dict(build=BUILD, kv_layout="paged", kv_codec="none")
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", forced)
    count = lambda: {p: expert_ffn.EXPERT_DENSE_LOWERED.labels(  # noqa: E731
        path=p).value for p in ("skip", "all")}
    before = count()
    engine = families.Family(serve_hybrid, cfg).fresh(seed=7)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, BUILD["vocab"], n) for n in (5, 11, 2)]
    served = serve_hybrid.serve_together(
        engine, serve_hybrid.LogitProbe(engine), prompts, [6, 4, 5])
    grew = {p: n - before[p] for p, n in count().items()}
    return served, grew


def test_a_served_program_through_the_kernel_gives_the_refer_tiers_tokens(
        monkeypatch):
    """``PADDLE_TPU_FORCE_PALLAS=1``: the decode view's two expert
    layers lower under ``skip`` (4 tokens: 59 % unpicked) and the
    32-token prefill's under ``all`` (1.4 %), every lowering counted;
    tokens equal the refer tier's, logits to float32 rounding."""
    kernel, grew = _served("1", monkeypatch)
    assert grew["skip"] and grew["skip"] % 2 == 0
    assert grew["all"] and grew["all"] % 2 == 0
    refer, grew = _served("0", monkeypatch)
    assert not grew["skip"] and grew["all"] % 2 == 0
    for (toks, logits, *_), (want_toks, want_logits, *_) in zip(kernel,
                                                                refer):
        np.testing.assert_array_equal(toks, want_toks)
        np.testing.assert_allclose(logits, want_logits, atol=2e-5)
