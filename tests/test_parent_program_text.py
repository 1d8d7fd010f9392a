"""Every configuration the benchmark served before this PR's lowers the
program text of the parent commit (PR 56: the grouped kinds' new keys —
per-kind KV heads, a value head of its own size, a partial rotation, a
value scale, a sink — are parameters whose defaults change nothing; PR
59: the delta rule's rectangular state and decay a head, the block
without a norm before a sub-layer, the norm over a whole projection and
the stack without an expert layer are too, and MiMo-V2-Flash's and the
multi-head family's views join the table).

One case a configuration and backend: the tiny preset of the
configuration's own cell test is built through
``build_decoder_lm_programs`` / ``make_slot_model`` and every prefill
view and the decode view are LOWERED (StableHLO text, nothing compiled
or run), once as the CPU traces them and once as the chip does
(``ops.pallas.on_tpu`` true, for a described v5e: the kernels' tiers).
A digest of each text is compared with the one taken on the parent
commit, committed as ``tests/data/parent_program_text.json`` (written by
this module itself on the parent's tree: ``PADDLE_PARENT_TEXT_WRITE=
<file> python -m pytest tests/test_parent_program_text.py``). A PR that
changes an old program ON PURPOSE writes the file anew on its own tree
and says so.

Three more kinds of case since PR 62 (a full grouped-KV layer's decode
attends its live pages in place where ``ops/kv_attention.py:
attends_in_place`` holds; the tiny presets' tables lie under its floor,
so their views stay the parent's and say nothing of the new path):

- every view of the nine served configurations AT THE COMMITTED SIZE
  (``chipbench/configs/<name>.json``, ``init=False``: structs in, text
  out) lowered for the described v5e against the parent's digest. Since
  PR 66 the digests are written anew on each PR's PARENT (PR 62's
  in-place decode views and PR 64's grouped prefill views are in them),
  so all are the same but the views ``DIFFERS`` lists — what this PR
  changed on purpose (PR 67: Olmo-Hybrid's two prefill views, whose
  twelve Gated DeltaNet layers solve a chunk's triangular system in
  blocks of 16 rows; PR 66's GLM-5 decode view, whose five latent
  layers score their index planes in place, is the parent's text now).
  The paths' counters (``paddle_dsa_index_lowered_total``,
  ``paddle_kv_decode_attend_lowered_total``,
  ``paddle_expert_grouped_lowered_total``) are read beside every
  configuration: they hold what the parent's text holds;
- the ONE op ``kv_attention_decode_paged`` lowered alone at each of the
  seven served configurations' PUBLISHED full-layer geometries for the
  described v5e (structs in, text out: nothing allocated, compiled or
  run), with the path counter's label and the kernels in the text held
  to ISSUE 62's table — in place for four, the parent's gathers for
  three;
- the jaxpr of ``attend_pages`` as GLM-5's decode calls it (one plane, a
  value width): the digest taken on the parent. The views' digests cut
  every kernel's serialized body out, so they cannot see a kernel
  change; the jaxpr is what the body is lowered from, and it carries no
  source location. ``score_pages`` (PR 66), which walks the same pages
  through the same helper, has its own recorded beside it.
"""

import functools
import hashlib
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "parent_program_text.json")
WRITE = os.environ.get("PADDLE_PARENT_TEXT_WRITE")
# configuration -> the cell test that holds its tiny preset
PRESETS = {
    "solar_open2_250b_ep8_d4": "test_chipbench_serve_hybrid",
    "glm5_744b_ep16_d5": "test_chipbench_serve_glm5",
    "trinity_mini_26b_d5": "test_chipbench_serve_trinity",
    "granite4_h_small_ep4_d10": "test_chipbench_serve_granite",
    "lfm2_8b_a1b_d12": "test_chipbench_serve_lfm2",
    "mimo_v2_flash_ep16_d7": "test_chipbench_serve_mimo",
    # the multi-head family: the preset is chipbench_tiny's own
    "gpt2_medium_d12": "chipbench_tiny",
    # PR 65's own (no parent lowers it: the digests are this tree's, so
    # the case holds the configuration's views still from here on)
    "jamba2_3b": "test_chipbench_serve_jamba2",
}


def tiny_config(name):
    sys.path.insert(0, os.path.join(HERE, "chipbench"))
    try:
        module = importlib.import_module(PRESETS[name])
        return getattr(module, "tiny_config", None)() \
            if hasattr(module, "tiny_config") else module.serve_config()
    finally:
        sys.path.remove(os.path.join(HERE, "chipbench"))


def view_digests(cfg, sharding) -> dict:
    """{view: sha256 of its lowered text} of the configuration's
    prefill views and decode view, lowered for ``sharding``'s device."""
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T
    build = cfg["build"]
    programs = T.build_decoder_lm_programs(
        name="lm", modes=T.slot_modes(cfg["kv_layout"]),
        kv_codec=cfg["kv_codec"],
        **{**build, "prompt_buckets": tuple(build["prompt_buckets"]),
           **({"layer_kinds": tuple(build["layer_kinds"])}
              if "layer_kinds" in build else {})})
    eng = serving.make_slot_model("lm", programs, init=False)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def text(key, cb, feeds):
        gvars = programs[key][0].desc.global_block.vars
        state = {n: struct(gvars[n].shape, gvars[n].dtype)
                 for n in cb.sig.state_names}
        consts = {n: struct(gvars[n].shape, gvars[n].dtype)
                  for n in cb.sig.const_names}
        feeds = {k: struct(np.shape(v), jnp.int32
                           if np.asarray(v).dtype == np.int64
                           else np.asarray(v).dtype)
                 for k, v in feeds.items()}
        return cb.fn.lower(state, consts, feeds,
                           struct((), jnp.uint32)).as_text()

    views = {"decode_paged": text("decode_paged", eng._cb_decode,
                                  eng._decode_feeds())}
    for p, cb in sorted(eng._cb_prefill.items()):
        views[f"prefill_paged@{p}"] = text(f"prefill_paged@{p}", cb,
                                           eng._prefill_feeds(p))
    return {k: hashlib.sha256(scrubbed(v).encode()).hexdigest()
            for k, v in views.items()}


def scrubbed(text: str) -> str:
    """A program's text without what names the checkout or a source
    line: a kernel's serialized body (``backend_config`` carries source
    paths and line numbers, which move with every edit of the calling
    module) and source locations
    (``tests/test_aot_tpu_compile.py:_scrubbed_sha``'s rule)."""
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', "", text)
    text = re.sub(r" at [^\s]+:\d+", "", text)
    return re.sub(r"/[\w/.\-]+\.py(:\d+)?", "", text)


def record(name, kind, got):
    """``got`` under [name][kind] of the table in the file ``WRITE``
    names (the parent's tree writes what ``DATA`` holds)."""
    table = {}
    if os.path.isfile(WRITE):
        with open(WRITE) as f:
            table = json.load(f)
    table.setdefault(name, {})[kind] = got
    with open(WRITE, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)


@pytest.fixture
def sharding(request, monkeypatch):
    from jax.sharding import SingleDeviceSharding
    if request.param == "cpu":
        return SingleDeviceSharding(jax.devices()[0])
    from jax.experimental import topologies
    from paddle_tpu.ops import pallas as pk
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology here: "
                    f"{type(e).__name__}: {e}")
    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("sharding", ["cpu", "chip"], indirect=True)
@pytest.mark.parametrize("name", sorted(PRESETS))
def test_the_configuration_lowers_the_parents_text(name, sharding, request):
    backend = request.node.callspec.params["sharding"]
    got = view_digests(tiny_config(name), sharding)
    if WRITE:
        return record(name, backend, got)
    with open(DATA) as f:
        want = json.load(f)["digests"][name][backend]
    assert got == want, sorted(k for k in want if got.get(k) != want[k])


# configuration -> its full softmax layers, all of which attend IN PLACE
# in the decode view at the committed size ...
IN_PLACE_LAYERS = {"mimo_v2_flash_ep16_d7": 2, "trinity_mini_26b_d5": 1,
                   "granite4_h_small_ep4_d10": 1,
                   "olmo_hybrid_7b_pp2_d16": 4, "jamba2_3b": 2}
# ... or all by copy (GLM-5 has no such layer: its planes are latent)
COPY_LAYERS = {"gpt2_medium_d12": 12, "solar_open2_250b_ep8_d4": 1,
               "lfm2_8b_a1b_d12": 3, "glm5_744b_ep16_d5": 0}
# configuration -> its prompt buckets over ``DENSE_MAX_TOKENS``: the
# prefill views whose expert layers take the grouped way, their products
# through ``ops/pallas/grouped_matmul.py`` on a chip (PR 64). Solar's
# buckets end at 512 (the dense way); gpt2's, Olmo-Hybrid's and Jamba2's
# stacks hold no expert
GROUPED_KERNEL_PREFILLS = {
    "glm5_744b_ep16_d5": (6144, 8192),
    "granite4_h_small_ep4_d10": (1024, 2048),
    "lfm2_8b_a1b_d12": (2048, 4096),
    "mimo_v2_flash_ep16_d7": (4096, 8192, 16384, 32768),
    "trinity_mini_26b_d5": (2048, 4096, 8192, 16384)}
# configuration -> the views that do NOT lower the parent's text, and
# must not: what THIS PR changed on purpose. The committed digests are
# the parent's own (written anew on it: PR 62's in-place decode views
# and PR 64's grouped prefill views are in them), so the list is one
# PR's — PR 67: Olmo-Hybrid's prefills solve a chunk's triangular system
# in diagonal blocks of 16 rows (``ops/gdn.py:_unit_lower_solve``; no
# counter: there is no second path)
DIFFERS = {"olmo_hybrid_7b_pp2_d16": ["prefill_paged@4096",
                                      "prefill_paged@8192"]}
# configuration -> its latent layers, whose indexer scores in place
INDEX_IN_PLACE_LAYERS = {"glm5_744b_ep16_d5": 5}


def _grew(counter, paths):
    """A reader of how far ``counter`` grew, by path, since this call."""
    read = lambda: {p: counter.labels(path=p).value            # noqa: E731
                    for p in paths}
    before = read()
    return lambda: {p: n - before[p] for p, n in read().items()}


@pytest.mark.parametrize("sharding", ["chip"], indirect=True)
@pytest.mark.parametrize("name", sorted({**IN_PLACE_LAYERS, **COPY_LAYERS}))
def test_the_committed_size_lowers_the_parents_text_but_in_place(
        name, sharding):
    """The cell's own configuration file, every view: the parent's text
    letter for letter, but the views ``DIFFERS`` lists (PR 67's have
    no path counter: every ``gdn_prefill`` takes the one solve). The
    paths' counters hold what the parent's text holds: the indexer's
    reads ``pages`` five times a trace of GLM-5's decode view and moves
    nowhere else, a full layer's decode attends ``in_place`` or
    by ``copy`` for every one of a configuration, and the long prefill
    views' grouped products are the ``kernel``'s (PR 64), never
    ``ragged_dot``'s."""
    from paddle_tpu.ops import expert_ffn, mla
    from paddle_tpu.ops import kv_attention as kv
    with open(os.path.join(os.path.dirname(HERE), "chipbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    attend = _grew(kv.KV_DECODE_ATTEND_LOWERED, ("in_place", "copy"))
    grouped = _grew(expert_ffn.EXPERT_GROUPED_LOWERED,
                    ("kernel", "ragged_dot"))
    index = _grew(mla.DSA_INDEX_LOWERED, ("pages", "rows"))
    got = view_digests(cfg, sharding)
    attend, grouped, index = attend(), grouped(), index()
    if WRITE:
        return record(name, "committed", got)
    with open(DATA) as f:
        want = json.load(f)["digests"][name]["committed"]
    assert sorted(got) == sorted(want)
    assert [k for k in sorted(want) if got[k] != want[k]] \
        == DIFFERS.get(name, [])
    # one increment a latent layer and trace of the decode view
    layers = INDEX_IN_PLACE_LAYERS.get(name, 0)
    assert not index["rows"] and index["pages"] % max(layers, 1) == 0
    assert bool(index["pages"]) == bool(layers)
    kernel_views = GROUPED_KERNEL_PREFILLS.get(name, ())
    assert all(p > expert_ffn.DENSE_MAX_TOKENS for p in kernel_views)
    assert not grouped["ragged_dot"]
    assert bool(grouped["kernel"]) == bool(kernel_views)
    # one increment a full layer and trace of the decode view
    path, layers = ("in_place", IN_PLACE_LAYERS[name]) \
        if name in IN_PLACE_LAYERS else ("copy", COPY_LAYERS[name])
    other = "copy" if path == "in_place" else "in_place"
    assert not attend[other] and attend[path] % max(layers, 1) == 0
    assert bool(attend[path]) == bool(layers)


def _served_full_layers():
    sys.path.insert(0, HERE)
    try:
        return importlib.import_module("test_pallas_kernels")
    finally:
        sys.path.remove(HERE)


@pytest.mark.parametrize("sharding", ["chip"], indirect=True)
@pytest.mark.parametrize("name", [
    "mimo_v2_flash_ep16_d7", "trinity_mini_26b_d5",
    "granite4_h_small_ep4_d10", "solar_open2_250b_ep8_d4",
    "lfm2_8b_a1b_d12", "gpt2_medium_d12", "olmo_hybrid_7b_pp2_d16"])
def test_the_decode_op_alone_lowers_the_tables_path(name, sharding):
    """``kv_attention_decode_paged`` alone, at the configuration's
    published full-layer geometry, lowered for the described v5e: the
    path counter grows by one under the label ISSUE 62's table gives,
    an in-place text holds ``attend_pages`` and no gather, a copy text
    the parent's two ``gather_pages`` and no ``attend_pages``."""
    import types
    from paddle_tpu.core.registry import get_op
    geoms = _served_full_layers()
    m, h, n_kv, dk, dv, rows, slots, dtype, want = \
        geoms._SERVED_FULL_LAYERS[name]
    attrs, keys, vals, ps, mp, _ = geoms._served_full_layer(name)

    def struct(shape, dt=jnp.dtype(dtype)):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sharding)
    col = struct((slots, 1), jnp.int32)
    ins = {"X": struct((slots, 1, m)), "Wq": struct((m, h * dk)),
           "Wk": struct((m, keys.shape[1])), "Wv": struct((m, vals.shape[1])),
           "Wo": struct((h * dv, m)),
           "PageK": struct((slots * mp, ps, keys.shape[1])),
           "PageV": struct((slots * mp, ps, vals.shape[1])),
           "PageTable": struct((slots, mp), jnp.int32),
           "Pos": col, "SeqLen": col, "GenStart": col, "Active": col}
    emit = get_op("kv_attention_decode_paged").emit

    def op(ins):
        out = emit(types.SimpleNamespace(mesh=None),
                   {k: [v] for k, v in ins.items()}, attrs)
        return {k: v[0] for k, v in out.items()}
    before = geoms._decode_lowered()
    text = jax.jit(op).lower(ins).as_text()
    assert {p: n - before[p] for p, n in geoms._decode_lowered().items()} \
        == {p: float(p == want) for p in before}
    calls = lambda kernel: len(re.findall(                     # noqa: E731
        rf'kernel_name = "{kernel}"', text))
    assert (calls("attend_pages"), calls("gather_pages")) \
        == ((1, 0) if want == "in_place" else (0, 2)), text[:2000]


def _latent_attend_jaxprs() -> dict:
    """{case: sha256 of the jaxpr} of ``attend_pages`` as GLM-5's cell
    calls it (32 slots of 12 288 rows of 640, 64 heads, the first 512
    lanes attended: ``ops/mla.py:_mla_decode_paged``) and as its float32
    twin would."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    S = jax.ShapeDtypeStruct
    b, s_len, w, h = 32, 12288, 640, 64
    out = {}
    for case, dtype, ps, vw in (("bf16-ps16-vw512", jnp.bfloat16, 16, 512),
                                ("f32-ps8-whole", jnp.float32, 8, 0)):
        args = [S((b, h, w), dtype), S((b * s_len, w), dtype),
                S((b, s_len // ps), jnp.int32)] \
            + [S((b,), jnp.int32)] * 3 + [S((b, s_len), jnp.bool_)]
        jaxpr = jax.make_jaxpr(functools.partial(
            pa.attend_pages.__wrapped__, page_size=ps, scale=0.0722,
            value_width=vw))(*args)
        out[case] = hashlib.sha256(str(jaxpr).encode()).hexdigest()
    return out


def _index_score_jaxprs() -> dict:
    """{case: sha256 of the jaxpr} of ``score_pages`` as GLM-5's cell
    calls it (32 slots of 12 288 rows of 128, 32 indexer heads:
    ``ops/mla.py:_mla_decode_paged``) and as its float32 twin would."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    S = jax.ShapeDtypeStruct
    b, s_len, w, j = 32, 12288, 128, 32
    out = {}
    for case, dtype, ps in (("bf16-ps16", jnp.bfloat16, 16),
                            ("f32-ps8", jnp.float32, 8)):
        args = [S((b, j, w), dtype), S((b, j), jnp.float32),
                S((b * s_len, w), dtype), S((b, s_len // ps), jnp.int32)] \
            + [S((b,), jnp.int32)] * 3
        jaxpr = jax.make_jaxpr(functools.partial(
            pa.score_pages.__wrapped__, page_size=ps))(*args)
        out[case] = hashlib.sha256(str(jaxpr).encode()).hexdigest()
    return out


def test_the_index_score_kernel_traces_its_recorded_jaxpr():
    """``score_pages`` as GLM-5's decode calls it (PR 66) traces the
    jaxpr recorded when it was measured — kernel body, grid, scratch and
    plan: the views' digests cut every kernel's body out, and the walk
    it shares with ``attend_pages`` is the other kernel's too."""
    got = _index_score_jaxprs()
    if WRITE:
        return record("score_pages", "jaxpr", got)
    with open(DATA) as f:
        want = json.load(f)["digests"]["score_pages"]["jaxpr"]
    assert got == want


def test_the_latent_attend_kernel_traces_the_parents_jaxpr():
    """GLM-5's one-plane call of ``attend_pages``, which PR 62 gave a
    second plane for other callers, still traces the parent's jaxpr —
    kernel body, grid, scratch and plan — letter for letter."""
    got = _latent_attend_jaxprs()
    if WRITE:
        return record("attend_pages", "jaxpr", got)
    with open(DATA) as f:
        want = json.load(f)["digests"]["attend_pages"]["jaxpr"]
    assert got == want
