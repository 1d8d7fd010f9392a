"""The DSA indexer and the sparse latent attention of a decode step:
milliseconds a step of the device's ops, and the share of the roofline
they reach (``flops_latent``'s bytes over the peak bandwidth or its
operations over the peak rate, whichever is longer, over that time), the
bytes and operations from the program's row counters
(``obs["dsa_rows"]``: the rows the indexer scored and the rows attended
over the window's decode steps, all layers).

The ops are those of the executions of the decode module wholly inside
the window (``scope_ms.table``: the engine's snapshots of the expert
counters are other modules and no steps), a STEP is one such execution,
and the counters' rows — the whole window's, ``obs["units"]
["decode_steps"]`` steps — are scaled to the executions the trace holds
(685 of 687 in the recorded run). ``group`` names the mechanism:

- ``index`` — by SCOPE, ``mla_decode_paged/index`` of the fused serving
  op (an op is found by (module, instruction) -> scope path, whatever
  kernel or fusion implements it; until PR 60 the ops were found by
  kernel name and result shape, and a program that scored the index
  plane in place, without the ``gather_pages`` kernel, would have left
  the share silent): the indexer's products over the cached keys, today
  a ``gather_pages`` kernel over every row of every slot's table and
  the scoring ``f32[n_slots, cache_len / 128, 128]``
  (ops/mla.py:_slot_scores). The bytes counted are the LIVE rows' keys
  read once: the share reads low, and says how far a kernel that stops
  at a slot's length and reads the plane in place could go. (Picking the
  index_topk best of the scores lies under ``mla_decode_paged/select``
  and is not in it: it reads no cache row.)
- ``sparse`` — by RESULT SHAPE, as since PR 33 (the series is unbroken):
  the gather of the selected latent rows ``[n_slots * index_topk, W]``
  and the two absorbed contractions over them, scores and probabilities
  ``[n_slots, n_head, index_topk]`` and the attended latent ``[n_slots,
  n_head, W]`` — shapes nothing else in the step has. The scope
  ``mla_decode_paged/attend`` also holds the page table's copy and three
  small products (2.598 against 2.47 ms on one traced run, PERF.md
  PR 60), so the group goes by scope only once the program gives the
  kernel a sub-scope of its own.
- ``selected_pct`` (no trace): 100 x rows attended / rows scored.

A program without latent-attention layers, without the counters (a
parent of PR 33) or, for ``index``, without device scopes gives nothing
to read, and so does a group of which no op ran: None, never 0.
"""

import re

from chipbench import flops, flops_latent
from chipbench.layer_metrics import scope_ms

MODULE = r"jit_\w+_decode_paged(_s[0-9a-f]{4})?"
INDEX_SCOPES = ["mla_decode_paged/index"]
LANES = 128
_ITEMSIZE = {"float32": 4, "bfloat16": 2}
_NEEDS = ("index_topk", "index_head_dim", "kv_lora_rank")


def latent_width(build: dict) -> int:
    wide = build["kv_lora_rank"] + build["qk_rope_head_dim"]
    return -(-wide // LANES) * LANES


def sparse_keep(build: dict):
    dt = "f32" if build["dtype"] == "float32" else "bf16"
    b, h, k = build["n_slots"], build["n_head"], build["index_topk"]
    wide = latent_width(build)
    shapes = {f"{dt}[{b * k},{wide}]", f"{dt}[{b},{k},{wide}]",
              f"f32[{b},{h},{k}]", f"{dt}[{b},{h},{k}]",
              f"f32[{b},{h},{wide}]", f"{dt}[{b},{h},{wide}]"}

    def keep(scope, ev):
        return any(w in shapes for w in ev[0].split())
    return keep


def index_keep(scope, ev):
    return scope_ms.in_scope(scope, INDEX_SCOPES)


def seconds_and_steps(obs, group):
    """(seconds of the group's ops, executions of the decode module)
    wholly inside the window, averaged over the devices; None where no
    such execution lies there or, for the group that goes by scope, the
    program gave no map for the module."""
    tab = scope_ms.table(obs)
    wanted = re.compile(MODULE)
    keep = index_keep if group == "index" \
        else sparse_keep(obs["config"]["build"])
    seconds, steps = [], []
    for device, runs in tab["runs"].items():
        n = sum(1 for name, _s, _e in runs if wanted.fullmatch(name))
        ops = [(scope, ev) for name, scope, ev in tab["ops"][device]
               if wanted.fullmatch(name)]
        if not n or (group == "index"
                     and any(scope is None for scope, _ev in ops)):
            return None
        steps.append(n)
        seconds.append(sum(ev[2] for scope, ev in ops
                           if keep(scope, ev)) / 1e9)
    if not steps:
        return None
    return sum(seconds) / len(seconds), sum(steps) / len(steps)


def read(obs, group, what=None):
    build = obs["config"]["build"]
    rows = obs.get("dsa_rows") or {}
    if any(k not in build for k in _NEEDS) or not rows.get("scored"):
        return None
    if group == "selected_pct":
        return 100.0 * rows["selected"] / rows["scored"]
    counted = (obs.get("units") or {}).get("decode_steps")
    found = seconds_and_steps(obs, group) if counted else None
    if not found or not found[0]:
        return None
    seconds, steps = found
    if what == "ms":
        return 1e3 * seconds / steps
    size = _ITEMSIZE[build["dtype"]]
    # the counters cover the window's steps; the trace's ops are those
    # of the ``steps`` whole executions inside it
    share = steps / counted
    if group == "index":
        n = rows["scored"] * share
        bytes_ = flops_latent.index_bytes(n, build["index_head_dim"], size)
        ops = flops_latent.index_flops(n, build["index_n_heads"],
                                       build["index_head_dim"])
    else:
        n = rows["selected"] * share
        bytes_ = flops_latent.sparse_bytes(
            n, build["kv_lora_rank"], build["qk_rope_head_dim"], size)
        ops = flops_latent.sparse_flops(
            n, build["n_head"], build["kv_lora_rank"],
            build["qk_rope_head_dim"])
    return flops.roofline_pct(ops, bytes_, seconds, obs["peaks"])
