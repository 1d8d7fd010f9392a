"""Device milliseconds per unit of work (a train step, a decode step, a
prefill): the ops selected from the device trace — all of them (their
union, so overlap counts once) or the Pallas kernels
(``tpu_custom_call``). With ``span_prefix`` the ops are those of the
whole program executions that the program's host spans of that name
caused, and the unit is one such execution; without, all ops of the
window over the runner's count of ``unit``."""

from chipbench import trace_reduce as tr


def read(obs, unit=None, select="all", span_prefix=None):
    red = obs["reduced"]
    spans = None
    if span_prefix:
        spans = tr.spans_named(red, span_prefix)
        n = tr.ops_of_spans(red, spans)[1] if spans else 0
    else:
        n = obs["units"].get(unit)
    if not n:
        return None
    if select == "custom_call":
        seconds = tr.mean_seconds(red, tr.is_custom_call, spans)
        if seconds == 0.0:
            return None          # no kernel in the step: nothing to read
    else:
        seconds = tr.busy_seconds(red, spans)
    return 1e3 * seconds / n
