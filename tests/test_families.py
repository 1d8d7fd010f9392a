"""``tests/families.py``: the builder the family files share."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import families  # noqa: E402
from chipbench.reference import solar_open2_250b_ep8_d4 as ref  # noqa: E402
from chipbench.runners import serve_hybrid  # noqa: E402

# the hybrid block of tests/test_hybrid_lm.py at its smallest: one layer
# that leases pages and one that keeps a state per slot
BUILD = dict(
    n_layer=2, d_model=64, n_head=4, vocab=96, prompt_len=16, max_new=16,
    prompt_buckets=[16], n_slots=4, page_size=4,
    layer_kinds=["gqa", "kda"], n_kv_head=2, head_dim=16,
    kda_heads=4, kda_head_dim=16, kda_conv_taps=4, kda_gate_rank=8,
    n_routed_experts=16, n_experts_held=4, n_experts_per_tok=4,
    d_expert=24, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_eps=1e-5, dtype="float32")
CFG = dict(build=BUILD, kv_layout="paged", kv_codec="none",
           reference="solar_open2_250b_ep8_d4")


def test_two_askers_share_a_family_and_a_fresh_one_is_startups():
    """Two descriptions of one (runner, configuration, seed) get the
    SAME engine, with one probe; another seed, other sizes or ``fresh``
    get another — and a fresh engine's slots hold what start-up left,
    whatever the shared one has served."""
    one = families.Family(serve_hybrid, CFG, ref, serve_hybrid.LogitProbe)
    other = families.Family(serve_hybrid, dict(CFG, build=dict(BUILD)), ref,
                            serve_hybrid.LogitProbe)
    shared = one.shared()
    assert other.shared() is shared
    assert other.shared(n_layer=2) is shared        # no change is no change
    assert one.probe(shared) is other.probe(shared)
    shared.reset()
    prompt, toks, _logits, states = one.request(shared, 11, max_new=3)
    assert len(prompt) == 11 and len(toks) == 3
    assert shared.free_count() == shared.n_slots      # admitted AND released
    assert any(np.abs(s).max() > 0 for s in states)
    fresh = one.fresh(warm=False)
    assert fresh is not shared
    assert fresh.free_count() == fresh.n_slots
    for n in fresh.state_vars:
        assert not np.asarray(fresh.scope.find_var(n)).any(), n
    assert any(np.asarray(shared.scope.find_var(n)).any()
               for n in shared.state_vars)
    # the same weights: the same seed through the same builder
    for n, w in one.params_of(shared).items():
        np.testing.assert_array_equal(np.asarray(w),
                                      np.asarray(fresh.scope.find_var(n)))


def test_a_patch_holds_while_a_family_is_built_and_not_after():
    from paddle_tpu.ops import expert_ffn
    was = expert_ffn.DENSE_MAX_TOKENS
    seen = []
    family = families.Family(
        serve_hybrid, CFG, ref, prepare=lambda engine, build, seed:
        seen.append((expert_ffn.DENSE_MAX_TOKENS, build["n_layer"], seed)))
    grouped = family.shared(warm=False, patches=families.GROUPED)
    assert seen == [(0, 2, 5)] and expert_ffn.DENSE_MAX_TOKENS == was
    # the patch and the seed are part of what a shared family is
    assert family.shared(warm=False) is not grouped
    assert seen[-1] == (was, 2, 5)
    assert family.shared(warm=False, seed=6) is not family.shared(warm=False)
    assert seen[-1] == (was, 2, 6) and len(seen) == 3
