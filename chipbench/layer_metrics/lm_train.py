"""The language-model trainer's step by scope, from a traced run of
``runners/train_lm.py``:

- ``what="scope_ms"``: the device time, a step, of the ops that lie
  under one of ``scopes`` in the step module (``scope_ms``'s ``ms``,
  forward and ``grad/`` alike: ``mla_full`` is the attention of every
  layer, ``expert_ffn_held`` the experts, ``mtp`` the
  multi-token-prediction module whole — its attention and experts count
  under the other two as well);
- ``what="kernel_ms"``: the device time, a step, of the KERNELS
  (``custom-call`` events) that lie under the scope ``mla_full`` — the
  causal attention of every layer, the module's too, forward and
  backward, and the forward run again where the backward recomputes
  it;
- ``what="roofline"``: the share of the roofline that time is of — the
  operations of causal attention at the configuration's head sizes,
  forward and backward, nothing recomputed counted
  (``flops_mla_train.mla_attention_train_flops``), over the chip's
  bfloat16 peak, or q, k, v and the context moved once over the peak
  bandwidth, whichever is longer. The count reads the sequence, the
  heads and their sizes and not the implementation.

None where the program has no such scope or no kernel under it (a
parent of PR 47, a composed attention)."""

import re

from chipbench import flops, flops_mla_train
from chipbench import trace_reduce as tr
from chipbench.layer_metrics import scope_ms

STEP = r"jit_\w+_x\d+"
MODULE = re.compile(STEP)
SCOPE = "mla_full"


def kernel_ms_per_step(obs):
    steps = (obs.get("units") or {}).get("steps")
    if not steps:
        return None
    tab = scope_ms.table(obs)
    values = []
    for ops in tab["ops"].values():
        mine = [ev for name, scope, ev in ops
                if MODULE.fullmatch(name) and scope
                and scope_ms.in_scope(scope, [SCOPE])
                and tr.is_custom_call(ev)]
        if not mine:
            return None
        values.append(sum(ev[2] for ev in mine) / 1e6 / steps)
    return sum(values) / len(values) if values else None


def read(obs, what, scopes=()):
    if what == "scope_ms":
        return scope_ms.read(obs, "ms", STEP, list(scopes), "steps")
    ms = kernel_ms_per_step(obs)
    if ms is None or what == "kernel_ms":
        return ms
    if what != "roofline":
        raise ValueError(f"lm_train cannot read {what!r}")
    build, traffic = obs["config"]["build"], obs["traffic"]
    shape = (build, traffic["seq_len"],
             traffic["sequences_per_step"] * obs["chips"])
    return flops.roofline_pct(
        flops_mla_train.mla_attention_train_flops(*shape),
        flops_mla_train.mla_attention_bytes(*shape), ms / 1e3, obs["peaks"])
