"""Three mechanisms of a hybrid model's decode step, read from the
device trace: milliseconds a step, or the share of the roofline
(``flops_hybrid``'s bytes over the peak bandwidth or its operations
over the peak rate, whichever is longer, over the ops' time; all are
HBM-bound at the cell's sizes).

``group`` selects the ops, among those of the program executions that
the ``serving.decode_step`` spans caused, by RESULT SHAPE — shapes that
nothing else in the step has — never "all custom calls":

- ``kda_state``: the ops that write the recurrent state
  ``f32[n_slots, H, D, D]``, the reduction over it
  ``f32[n_slots, H, 2, D]`` (``ops/kda.py:_delta_step``) and the conv
  window ``[n_slots, taps - 1, 3 * H * D]``.
- ``expert_up``: the held experts' gate and up projections with their
  activation, ``[n_slots, n_held, d_expert]`` in float32 (the gate
  product) and in the storage dtype (the weighted hidden rows): two of
  the expert layer's three matrices. The down projection's result is
  ``[n_slots, d_model]`` like a dozen other ops', so it is counted
  neither in the time nor in the bytes; the shared expert is not
  counted either: the metrics are named ``moe_up_*`` for what they
  read.
- ``page_gather``: the ``gather_pages`` kernels of the softmax layers
  (``ops/pallas/paged_attention.py``, by the kernel's name AND its
  result ``[n_slots * cache_len, n_kv_head * head_dim]`` in the pool's
  dtype): every row of every slot, K and V, read once and written once.
  (``kv_gather_*`` read the same kernel on the multi-head family, whose
  rows are ``d_model`` wide; their reader takes every custom call of
  the step.)

A decode STEP is an execution of the decode view's module (by its
name) that the spans caused, wholly inside the window. Until PR 60 every
execution under the spans counted as one, and the engine's snapshots of
the expert counters (``jit_copy``, four tiny executions every 32 steps
on a model with expert layers) made 12.5 % more steps than there were:
the time a step read that much low and the shares that much high —
``moe_up_roofline`` 101.3 on ``serve_solar_decode_closed`` (ledger, PRs
56 and 59), over what the chip can do.

A configuration without KDA layers or held experts gives nothing to
read: None.
"""

import re

from chipbench import flops, flops_hybrid
from chipbench import trace_reduce as tr

SPAN = "serving.decode_step"
MODULE = re.compile(r"jit_\w+_decode_paged(_s[0-9a-f]{4})?(\(\d+\))?")
_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def kda_shapes(build: dict) -> tuple:
    s, h, d = build["n_slots"], build["kda_heads"], build["kda_head_dim"]
    conv = "f32" if build["dtype"] == "float32" else "bf16"
    return (f"f32[{s},{h},{d},{d}]", f"f32[{s},{h},2,{d}]",
            f"{conv}[{s},{build['kda_conv_taps'] - 1},{3 * h * d}]")


def expert_shapes(build: dict) -> tuple:
    tail = f"[{build['n_slots']},{build['n_experts_held']}," \
           f"{build['d_expert']}]"
    return ("f32" + tail,
            ("f32" if build["dtype"] == "float32" else "bf16") + tail)


def gather_shape(build: dict, kv_codec: str) -> str:
    rows = build["n_slots"] * (build["prompt_len"] + build["max_new"])
    dtype = {"none": build["dtype"], "bf16": "bfloat16"}[kv_codec]
    return f"{'f32' if dtype == 'float32' else 'bf16'}" \
           f"[{rows},{build['n_kv_head'] * build['head_dim']}]"


_NEEDS = {"kda_state": "kda_heads", "expert_up": "n_experts_held",
          "page_gather": "n_kv_head"}


def decode_steps(red: dict, spans) -> float:
    """Executions of the decode view's module among those that ``spans``
    caused (``trace_reduce.ops_of_spans``'s rule: wholly inside the
    window, more than half under the spans), a device."""
    spans = tr.union(spans)
    n = [sum(1 for name, s, d in events
             if MODULE.fullmatch(name) and d > 0 and s >= red["t0_ns"]
             and s + d <= red["t1_ns"]
             and d - tr.total(tr.subtract([(s, s + d)], spans)) > 0.5 * d)
         for events in red["modules"].values()]
    return sum(n) / len(n) if n else 0


def read(obs, group, what):
    build = obs["config"]["build"]
    red = obs["reduced"]
    spans = tr.spans_named(red, SPAN)
    steps = decode_steps(red, spans) if spans else 0
    if not steps or _NEEDS[group] not in build:
        return None
    if group == "page_gather":
        codec = obs["config"].get("kv_codec", "none")
        if codec not in ("none", "bf16"):
            return None
        shape = gather_shape(build, codec)

        def keep(ev):
            words = ev[0].split()
            return words[0].startswith("gather_pages") and shape in words
    else:
        shapes = kda_shapes(build) if group == "kda_state" \
            else expert_shapes(build)

        def keep(ev):
            return any(s in ev[0].split() for s in shapes)
    seconds = tr.mean_seconds(red, keep, spans)
    if seconds == 0.0:
        return None
    if what == "ms":
        return 1e3 * seconds / steps
    size = _ITEMSIZE[build["dtype"]]
    if group == "page_gather":
        bytes_ = flops_hybrid.page_gather_bytes_per_step(
            build["n_slots"], build["prompt_len"] + build["max_new"],
            _kinds(build).count("gqa"),
            build["n_kv_head"] * build["head_dim"],
            4 if shape.startswith("f32") else 2)
        ops = 0.0
    elif group == "kda_state":
        sizes = (build["n_slots"], _kinds(build).count("kda"),
                 build["kda_heads"], build["kda_head_dim"])
        bytes_ = flops_hybrid.kda_state_bytes_per_step(
            *sizes, build["kda_conv_taps"], size)
        ops = flops_hybrid.kda_state_flops_per_step(*sizes)
    else:
        sizes = (build["n_slots"], build["n_layer"],
                 build["n_experts_held"], build["d_model"],
                 build["d_expert"])
        bytes_ = flops_hybrid.expert_up_bytes_per_step(*sizes, size)
        ops = flops_hybrid.expert_up_flops_per_step(*sizes)
    return flops.roofline_pct(ops * steps, bytes_ * steps, seconds,
                              obs["peaks"])


def _kinds(build: dict) -> list:
    period = build["layer_kinds"]
    return [period[i % len(period)] for i in range(build["n_layer"])]
