"""The page pool's window group and the window layers' decode step, from
what ``runners/serve_trinity.py`` observes:

- ``what="held_pct"``: mean share of the window group's pages not free
  while the window was open (``paddle_kv_group_pages_free`` over
  ``paddle_kv_group_pages_total`` of group ``window``, sampled every
  0.1 s as ``kv_pages_held_pct`` samples the pool's), in %;
- ``what="recycled_per_step"``: window-group pages that LIVE requests
  returned because they lay behind the window
  (``paddle_kv_window_pages_released_total``), a decode step of the
  window;
- ``what="ms"`` (a traced run): the device time of the ops under the
  scope ``kv_attention_decode_paged/window`` (``scope_ms``: write,
  gather and attend of every window layer; their projections, norms
  and rotation lie in the op's own scope), a decode step. The full
  layers are ``attn_ms_per_step.decode`` less this;
- ``what="roofline"`` (a traced run): the share of the roofline of the
  window layers' decode attention — ``flops_window``'s bytes over the
  peak bandwidth or its operations over the peak rate, whichever is
  longer, from the rows the program counted
  (``paddle_kv_window_rows_attended_total``), over the device time of
  the ops under the scope ``kv_attention_decode_paged/window``
  (``scope_ms``: write, gather and attend of every window layer) in the
  same steps. The gather copies every row of a slot's ring, live or
  not, and the contraction reads the copies again: the share says how
  far a kernel that attends the live rows in place could go.

A program without a window group (a parent of PR 37) gives nothing to
read: None."""

from chipbench import flops, flops_window
from chipbench.layer_metrics import scope_ms

MODULE = r"jit_\w+_decode_paged(_s[0-9a-f]{4})?"
SCOPE = "kv_attention_decode_paged/window"
_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(obs, what):
    steps = (obs.get("units") or {}).get("decode_steps")
    if what == "held_pct":
        share = obs.get("kv_window_pages_held")
        return None if share is None else 100.0 * share
    if what == "recycled_per_step":
        released = obs.get("window_pages_released")
        return None if released is None or not steps \
            else released / steps
    if what not in ("ms", "roofline"):
        raise ValueError(f"window_attn cannot read {what!r}")
    build = obs["config"]["build"]
    if not steps or "window" not in build:
        return None
    ms = scope_ms.read(obs, "ms", MODULE, [SCOPE], "decode_steps")
    if what == "ms":
        return ms
    rows = obs.get("window_rows")
    if not rows or not ms:
        return None
    bytes_ = flops_window.window_bytes(
        rows, build["n_kv_head"], build["head_dim"],
        _ITEMSIZE[build["dtype"]])
    ops = flops_window.window_flops(rows, build["n_head"],
                                    build["head_dim"])
    return flops.roofline_pct(ops, bytes_, ms * steps / 1e3, obs["peaks"])
