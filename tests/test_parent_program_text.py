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
"""

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
        table = {}
        if os.path.isfile(WRITE):
            with open(WRITE) as f:
                table = json.load(f)
        table.setdefault(name, {})[backend] = got
        with open(WRITE, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        return
    with open(DATA) as f:
        want = json.load(f)["digests"][name][backend]
    assert got == want, sorted(k for k in want if got.get(k) != want[k])
