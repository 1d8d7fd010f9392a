"""The decode attention of a model whose grouped-KV layers have a
geometry of their own per kind (``runners/serve_mimo.py``'s
observations): the share of its roofline that each of the two
contractions reaches, and how much of what the full layers gather is
live.

- ``what="full_ms"`` / ``"window_ms"`` (a traced run): the device time a
  decode step of the ops under the full layers' three phases
  (``kv_attention_decode_paged/{write,gather,attend}``) and under the
  window variant (``kv_attention_decode_paged/window``), through
  ``scope_ms`` (``tests/chipbench/test_chipbench_scope_ms.py`` pins the
  metrics whose reader IS ``scope_ms`` to PR 35's ten, so these two
  come through here, as PR 47's did through ``lm_train.py``);
- ``what="full_roofline"`` (a traced run): ``flops_grouped_kv``'s bytes
  over the peak bandwidth or its operations over the peak rate,
  whichever is longer, of the LIVE rows the full layers attended
  (``paddle_kv_full_rows_attended_total`` over the window: per step,
  slot and full layer the slot's positions) at the full layers' KV heads
  and head sizes, over the device time of the ops under the scopes
  ``kv_attention_decode_paged/{write,gather,attend}`` (``scope_ms``; a
  window layer's lie under ``.../window/...`` and are not in these) in
  the same steps. The gather copies every row of every slot's table,
  live or not, and the contraction reads the copies again: the share
  says how far an attend of the live pages in place could go;
- ``what="window_roofline"``: the same of the rows the window layers
  attended (``paddle_kv_window_rows_attended_total``) at the WINDOW
  layers' KV heads, over the ops under
  ``kv_attention_decode_paged/window``;
- ``what="live_rows_pct"``: rows the full layers attended over rows
  their gathers copied (``paddle_kv_full_rows_gathered_total``), in %.

A program without these counters (a parent of PR 56) leaves the
observations out: None."""

from chipbench import flops, flops_grouped_kv
from chipbench.layer_metrics import scope_ms

MODULE = r"jit_\w+_decode_paged(_s[0-9a-f]{4})?"
FULL = ["kv_attention_decode_paged/write", "kv_attention_decode_paged/gather",
        "kv_attention_decode_paged/attend"]
WINDOW = ["kv_attention_decode_paged/window"]
_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(obs, what):
    if what == "live_rows_pct":
        rows, gathered = obs.get("full_rows"), obs.get("full_rows_gathered")
        return 100.0 * rows / gathered if rows and gathered else None
    if what not in ("full_ms", "window_ms", "full_roofline",
                    "window_roofline"):
        raise ValueError(f"grouped_kv_attn cannot read {what!r}")
    build = obs["config"]["build"]
    steps = (obs.get("units") or {}).get("decode_steps")
    window = what.startswith("window")
    if not steps or "head_dim" not in build:
        return None
    ms = scope_ms.read(obs, "ms", MODULE, WINDOW if window else FULL,
                       "decode_steps")
    if what.endswith("_ms"):
        return ms
    rows = obs.get("window_rows" if window else "full_rows")
    if not rows or not ms:
        return None
    n_kv = (build.get("swa_n_kv_head") if window else None) \
        or build["n_kv_head"]
    dk = build["head_dim"]
    dv = build.get("gqa_v_head_dim") or dk
    bytes_ = flops_grouped_kv.kv_bytes(rows, n_kv, dk, dv,
                                       _ITEMSIZE[build["dtype"]])
    ops = flops_grouped_kv.attn_flops(rows, build["n_head"], dk, dv)
    return flops.roofline_pct(ops, bytes_, ms * steps / 1e3, obs["peaks"])
