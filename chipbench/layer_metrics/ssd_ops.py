"""The state-space (``ssd``) layers of a hybrid model, from what
``runners/serve_granite.py`` observes and from the device's time under
the program's own scopes (``scope_ms``: an op is selected by module and
scope, whatever implements it):

- ``what="decode_ms"`` (a traced run): the device time of the ops under
  the scope ``ssd_decode`` — projections, conv, state update, gated
  norm of every SSD layer — a decode step;
- ``what="state_roofline"``: the share of the roofline of the decode
  steps' state updates — ``flops_ssd.state_bytes`` of the LIVE slots
  (``obs["slot_steps"]``, the scheduler's count) over the peak
  bandwidth, or its operations over the peak rate, whichever is longer,
  over the device time of the ops under ``ssd_decode/state`` in the
  same steps. It reads the same work whatever implements the scope: a
  kernel that reads the state once, or a pair of fusions that read it
  twice;
- ``what="scan_ms"``: the device time of the ops under the scope
  ``ssd_prefill`` (whole: projections, conv, scan, gated norm), a
  prefill of the window;
- ``what="scan_roofline"``: ``flops_ssd.scan_flops`` of the rows the
  program counted (``paddle_ssd_chunk_rows_total``: whole chunks) at the
  chunk the build states, over the peak rate, over the device time of
  the ops under ``ssd_prefill/scan``. The scan multiplies in float32
  (six passes of the MXU a product): the share says how far a scan in
  the storage dtype could go;
- ``what="scan_padding_pct"`` (any run): 100 x (1 - true prompt tokens
  scanned / rows the scan computed), from the program's two counters;
- ``what="prefill_window_share_pct"`` (a traced run): the device's busy
  time in the executions of the prefill views (modules named
  ``jit_<model>_prefill_paged_<bucket>``) as a share of the traced
  window.

A program without SSD layers, scopes or counters (a parent of PR 42)
gives nothing to read: None."""

import re

from chipbench import flops, flops_ssd
from chipbench import trace_reduce as tr
from chipbench.layer_metrics import scope_ms

DECODE = r"jit_\w+_decode_paged(_s[0-9a-f]{4})?"
PREFILL = r"jit_\w+_prefill_paged_\d+(_s[0-9a-f]{4})?"


def _sizes(build: dict):
    kinds = build.get("layer_kinds") or []
    n = sum(kinds[i % len(kinds)] == "ssd"
            for i in range(build["n_layer"])) if kinds else 0
    if not n:
        return None
    return (n, build["ssd_heads"], build["ssd_head_dim"],
            build["ssd_d_state"], build.get("ssd_groups", 1))


def read(obs, what):
    sizes = _sizes(obs["config"]["build"])
    if sizes is None:
        return None
    layers, heads, head_dim, d_state, groups = sizes
    units = obs.get("units") or {}
    if what == "scan_padding_pct":
        tokens, rows = obs.get("ssd_tokens"), obs.get("ssd_rows")
        return None if not rows or tokens is None \
            else 100.0 * (1.0 - tokens / rows)
    if "reduced" not in obs:
        return None
    if what == "decode_ms":
        return scope_ms.read(obs, "ms", DECODE, ["ssd_decode"],
                             "decode_steps")
    if what == "scan_ms":
        return scope_ms.read(obs, "ms", PREFILL, ["ssd_prefill"],
                             "prefills")
    if what == "state_roofline":
        ms = scope_ms.read(obs, "ms", DECODE, ["ssd_decode/state"],
                           "decode_steps")
        steps, live = units.get("decode_steps"), obs.get("slot_steps")
        if not ms or not steps or not live:
            return None
        args = (live, layers, heads, head_dim, d_state)
        return flops.roofline_pct(
            flops_ssd.state_flops(*args), flops_ssd.state_bytes(*args),
            ms * steps / 1e3, obs["peaks"])
    if what == "scan_roofline":
        ms = scope_ms.read(obs, "ms", PREFILL, ["ssd_prefill/scan"],
                           "prefills")
        prefills, rows = units.get("prefills"), obs.get("ssd_rows")
        if not ms or not prefills or not rows:
            return None
        ops = flops_ssd.scan_flops(
            rows, obs["config"]["build"]["ssd_chunk"], heads, head_dim,
            d_state, groups)
        return flops.roofline_pct(ops, 0.0, ms * prefills / 1e3,
                                  obs["peaks"])
    if what == "prefill_window_share_pct":
        tab = scope_ms.table(obs)
        wanted = re.compile(PREFILL)
        busy = [tr.total((ev[1], ev[1] + ev[2])
                         for name, _scope, ev in ops
                         if wanted.fullmatch(name))
                for ops in tab["ops"].values()]
        window = obs["reduced"]["window_s"]
        if not busy or not window:
            return None
        return 100.0 * sum(busy) / len(busy) / 1e9 / window
    raise ValueError(f"ssd_ops cannot read {what!r}")
