"""The Gated DeltaNet (``gdn``) layers of a hybrid model, from what
``runners/serve_olmo_hybrid.py`` observes and from the device's time
under the program's own scopes (``scope_ms``: an op is selected by module
and scope, whatever implements it):

- ``what="scan_ms"`` (a traced run): the device time of the ops under the
  scope ``gdn_prefill/scan`` — the chunked scan of every linear layer — a
  prefill of the window;
- ``what="scan_roofline"``: ``flops_gdn.scan_flops`` and ``scan_bytes``
  of the TRUE tokens the program counted
  (``paddle_gdn_tokens_scanned_total``) at the chunk the build states,
  over the peak rate or the peak bandwidth, whichever is longer, over the
  device time of the ops under ``gdn_prefill/scan`` in the same window.
  The same work whatever implements the scope: padded rows, float32
  products (six passes of the MXU each) and an inverse formed in full
  show as lost share, and a later kernel is read by the same yardstick;
- ``what="scan_padding_pct"`` (any run): 100 x (1 - true prompt tokens
  scanned / rows the scan computed), from the program's two counters;
- ``what="state_ms"`` (a traced run): the device time of the ops under
  ``gdn_decode/state`` a decode step;
- ``what="state_roofline"``: ``flops_gdn.state_bytes`` of the LIVE slots
  (``obs["slot_steps"]``, the scheduler's count) over the peak bandwidth,
  or its operations over the peak rate, whichever is longer, over the
  device time of the ops under ``gdn_decode/state`` in the same steps:
  one read and one write of the state as the algorithm has it, whatever
  tier runs and however the device pads the tile.

A program without GDN layers, scopes or counters (a parent of PR 59)
gives nothing to read: None."""

from chipbench import flops, flops_gdn
from chipbench.layer_metrics import scope_ms, ssd_ops

DECODE, PREFILL = ssd_ops.DECODE, ssd_ops.PREFILL


def _sizes(build: dict):
    kinds = build.get("layer_kinds") or []
    n = sum(kinds[i % len(kinds)] == "gdn"
            for i in range(build["n_layer"])) if kinds else 0
    if not n:
        return None
    return (n, build["gdn_heads"], build["gdn_key_dim"],
            build["gdn_value_dim"])


def read(obs, what):
    sizes = _sizes(obs["config"]["build"])
    if sizes is None:
        return None
    layers, heads, key_dim, value_dim = sizes
    units = obs.get("units") or {}
    if what == "scan_padding_pct":
        tokens, rows = obs.get("gdn_tokens"), obs.get("gdn_rows")
        return None if not rows or tokens is None \
            else 100.0 * (1.0 - tokens / rows)
    if "reduced" not in obs:
        return None
    if what == "scan_ms":
        return scope_ms.read(obs, "ms", PREFILL, ["gdn_prefill/scan"],
                             "prefills")
    if what == "state_ms":
        return scope_ms.read(obs, "ms", DECODE, ["gdn_decode/state"],
                             "decode_steps")
    if what == "scan_roofline":
        ms = scope_ms.read(obs, "ms", PREFILL, ["gdn_prefill/scan"],
                           "prefills")
        prefills, tokens = units.get("prefills"), obs.get("gdn_tokens")
        if not ms or not prefills or not tokens:
            return None
        chunk = obs["config"]["build"].get("gdn_chunk", 64)
        return flops.roofline_pct(
            flops_gdn.scan_flops(tokens, chunk, heads, key_dim, value_dim),
            flops_gdn.scan_bytes(tokens, heads, key_dim, value_dim),
            ms * prefills / 1e3, obs["peaks"])
    if what == "state_roofline":
        ms = scope_ms.read(obs, "ms", DECODE, ["gdn_decode/state"],
                           "decode_steps")
        steps, live = units.get("decode_steps"), obs.get("slot_steps")
        if not ms or not steps or not live:
            return None
        args = (live, layers, heads, key_dim, value_dim)
        return flops.roofline_pct(
            flops_gdn.state_flops(*args), flops_gdn.state_bytes(*args),
            ms * steps / 1e3, obs["peaks"])
    raise ValueError(f"gdn_ops cannot read {what!r}")
