"""The expert layers' grouped way — what a prefill of more than
``ops/expert_ffn.py:DENSE_MAX_TOKENS`` tokens takes through the held
experts — from the device's time under the program's own scope and from
the program's own counter:

- ``what="experts_ms"`` (a traced run): the device time of the ops under
  the scope ``expert_ffn_held`` (route, up, down and the shared expert
  of every expert layer) in the executions of the prefill views
  (``jit_<model>_prefill_paged_<bucket>``), a prefill of the window —
  ``experts_ms_per_step.decode``'s twin for the views that step metric
  leaves out, read as ``ssd_scan_ms_per_prefill`` reads ``ssd_prefill``.
  It reads whatever implements the scope, so a parent of PR 44 gives a
  number too;
- ``what="held_rows_pct"`` (any run): 100 x the assignment rows the
  grouped way held (what this program's experts were routed and
  computed) over the rows it was given (bucket tokens x top_k, the worst
  case), from ``paddle_moe_grouped_rows_total`` of the program's default
  registry as the run left it: the runner brings the family up to the
  window's close (``engine.expert_token_counts()``), and no runner hands
  on its reading at the window's opening, so the share is over every
  prefill of the process — the check's and the priming's beside the
  window's, the same prompts' lengths and the same router. A load
  reading: how much of the worst case the routing draw and the prompts'
  padding used.

None where there is nothing to read: no trace, no such scope in a
prefill view, or a program without the family (a parent of PR 44)."""

from chipbench.layer_metrics import scope_ms, ssd_ops

FAMILY = "paddle_moe_grouped_rows_total"


def _rows(model: str):
    """{"given": .., "held": ..} of ``model``, or None."""
    from paddle_tpu.observability import metrics
    fam = metrics.default_registry().get(FAMILY)
    if fam is None:
        return None
    i_model = fam.labelnames.index("model")
    i_rows = fam.labelnames.index("rows")
    return {labels[i_rows]: child.value
            for labels, child in fam.children().items()
            if labels[i_model] == model}


def read(obs, what):
    if what == "held_rows_pct":
        from chipbench.runners import serve
        rows = _rows(serve.MODEL)
        if not rows or not rows.get("given"):
            return None
        return 100.0 * rows.get("held", 0.0) / rows["given"]
    if what == "experts_ms":
        if "reduced" not in obs:
            return None
        return scope_ms.read(obs, "ms", ssd_ops.PREFILL,
                             ["expert_ffn_held"], "prefills")
    raise ValueError(f"moe_grouped cannot read {what!r}")
