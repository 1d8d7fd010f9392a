"""The Mamba-1 selective-scan (``s6``) layers of a hybrid model, from
what ``runners/serve_jamba2.py`` observes and from the device's time
under the program's own scopes (``scope_ms``: an op is selected by module
and scope, whatever implements it):

- ``what="scan_ms"`` (a traced run): the device time of the ops under the
  scope ``s6_prefill/scan`` — the selective scan of every Mamba layer — a
  prefill of the window;
- ``what="scan_roofline"``: ``flops_s6.scan_flops`` and ``scan_bytes`` of
  the TRUE tokens the program counted (``paddle_s6_tokens_scanned_total``)
  over the peak rate or the peak bandwidth, whichever is longer, over the
  device time of the ops under ``s6_prefill/scan`` in the same window.
  The same work whatever implements the scope: padded rows, a loop that
  waits on the vector units and arrays written only to be read again
  show as lost share;
- ``what="scan_padding_pct"`` (any run): 100 x (1 - true prompt tokens
  scanned / rows the scan walked), from the program's two counters;
- ``what="state_ms"`` (a traced run): the device time of the ops under
  ``s6_decode/state`` a decode step;
- ``what="state_roofline"``: ``flops_s6.state_bytes`` of the LIVE slots
  (``obs["slot_steps"]``, the scheduler's count) over the peak bandwidth,
  or its operations over the peak rate, whichever is longer, over the
  device time of the ops under ``s6_decode/state`` in the same steps: one
  read and one write of the state, whatever runs the update.

A program without S6 layers, scopes or counters (a parent of PR 65)
gives nothing to read: None."""

from chipbench import flops, flops_s6
from chipbench.layer_metrics import scope_ms, ssd_ops

DECODE, PREFILL = ssd_ops.DECODE, ssd_ops.PREFILL


def _sizes(build: dict):
    kinds = build.get("layer_kinds") or []
    n = sum(kinds[i % len(kinds)] == "s6"
            for i in range(build["n_layer"])) if kinds else 0
    if not n:
        return None
    return n, build["s6_d_inner"], build["s6_d_state"]


def read(obs, what):
    sizes = _sizes(obs["config"]["build"])
    if sizes is None:
        return None
    layers, d_inner, d_state = sizes
    units = obs.get("units") or {}
    if what == "scan_padding_pct":
        tokens, rows = obs.get("s6_tokens"), obs.get("s6_rows")
        return None if not rows or tokens is None \
            else 100.0 * (1.0 - tokens / rows)
    if "reduced" not in obs:
        return None
    if what == "scan_ms":
        return scope_ms.read(obs, "ms", PREFILL, ["s6_prefill/scan"],
                             "prefills")
    if what == "state_ms":
        return scope_ms.read(obs, "ms", DECODE, ["s6_decode/state"],
                             "decode_steps")
    if what == "scan_roofline":
        ms = scope_ms.read(obs, "ms", PREFILL, ["s6_prefill/scan"],
                           "prefills")
        prefills, tokens = units.get("prefills"), obs.get("s6_tokens")
        if not ms or not prefills or not tokens:
            return None
        return flops.roofline_pct(
            flops_s6.scan_flops(tokens, d_inner, d_state),
            flops_s6.scan_bytes(tokens, d_inner, d_state),
            ms * prefills / 1e3, obs["peaks"])
    if what == "state_roofline":
        ms = scope_ms.read(obs, "ms", DECODE, ["s6_decode/state"],
                           "decode_steps")
        steps, live = units.get("decode_steps"), obs.get("slot_steps")
        if not ms or not steps or not live:
            return None
        args = (live, layers, d_inner, d_state)
        return flops.roofline_pct(
            flops_s6.state_flops(*args), flops_s6.state_bytes(*args),
            ms * steps / 1e3, obs["peaks"])
    raise ValueError(f"s6_ops cannot read {what!r}")
