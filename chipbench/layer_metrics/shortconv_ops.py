"""The gated short convolutions (``conv`` layers) of a hybrid model and
the prefill of its attention layers, from what ``runners/serve_lfm2.py``
observes and from the device's time under the program's own scopes
(``scope_ms``: an op is selected by module and scope, whatever
implements it):

- ``what="prefill_ms"`` (a traced run): the device time of the ops under
  the scope ``shortconv_prefill`` — projections, conv, gate and output
  of every conv layer — a prefill of the window;
- ``what="decode_ms"``: the same under ``shortconv_decode``, a decode
  step;
- ``what="prefill_roofline"``: ``flops_shortconv.mixer_flops`` of the
  TRUE tokens the program counted in the window's prefills
  (``paddle_shortconv_tokens_total{view="prefill"}``: a prompt's length,
  times the conv layers), over the peak rate, over the device time of
  the whole scope ``shortconv_prefill`` in the same window. The same
  work whatever implements it: a bucket's padded rows and the
  elementwise passes show as lost share;
- ``what="attn_prefill_ms"``: the device time of the ops under the scope
  ``kv_attention_prefill_paged`` (whole: projections, norm, rotation,
  scores, page write), a prefill of the window.

A program without conv layers, scopes or the counter (a parent of PR 51)
gives nothing to read: None."""

from chipbench import flops, flops_shortconv
from chipbench.layer_metrics import scope_ms, ssd_ops

DECODE, PREFILL = ssd_ops.DECODE, ssd_ops.PREFILL


def read(obs, what):
    build = obs["config"]["build"]
    if "conv" not in (build.get("layer_kinds") or []) \
            or "reduced" not in obs:
        return None
    if what == "prefill_ms":
        return scope_ms.read(obs, "ms", PREFILL, ["shortconv_prefill"],
                             "prefills")
    if what == "decode_ms":
        return scope_ms.read(obs, "ms", DECODE, ["shortconv_decode"],
                             "decode_steps")
    if what == "attn_prefill_ms":
        return scope_ms.read(obs, "ms", PREFILL,
                             ["kv_attention_prefill_paged"], "prefills")
    if what == "prefill_roofline":
        ms = scope_ms.read(obs, "ms", PREFILL, ["shortconv_prefill"],
                           "prefills")
        prefills = (obs.get("units") or {}).get("prefills")
        tokens = (obs.get("shortconv_tokens") or {}).get("prefill")
        if not ms or not prefills or not tokens:
            return None
        return flops.roofline_pct(
            flops_shortconv.mixer_flops(tokens, build["d_model"]), 0.0,
            ms * prefills / 1e3, obs["peaks"])
    raise ValueError(f"shortconv_ops cannot read {what!r}")
