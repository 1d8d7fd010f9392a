"""The two sides of an idle gap that the program names itself
(``obs["reduced"]["host_spans"]``: the tracer's spans of the window, on
the trace's clock), cut to the window as ``span_seconds`` cuts:

- ``what="pause_pct"``  100 x the seconds under the spans ``host.pause``
  (the pause watch's: the interval by which a thread that only sleeps
  woke late — the process stood still, or could not run it) over the
  window. Beside ``device_idle_pct.*``: idle less pause is what the
  program itself left idle.
- ``what="starved_pct"``  100 x the zero-length markers
  ``serving.starved.decode`` inside the window (a decode dispatch that
  found the previous dispatch's output ready: the device had run dry)
  over ``obs["units"]["decode_steps"]``.

Most windows hold no such span, and a line that left the metric out
there would say nothing: where the program HAS the counter family that
is kept beside the span, an empty window reads 0.0; None only where the
family is missing (a parent without it), as ``program_counter_total``
does, or there is no step to divide by."""

PAUSE = "host.pause"
STARVED = "serving.starved.decode"
FAMILY = {"pause_pct": "paddle_host_pauses_total",
          "starved_pct": "paddle_serving_dispatch_starved_total"}


def read(obs, what):
    from paddle_tpu.observability import metrics
    if metrics.default_registry().get(FAMILY[what]) is None:
        return None
    red = obs["reduced"]
    t0, t1 = red["t0_ns"], red["t1_ns"]
    if what == "pause_pct":
        cut = [(max(a, t0), min(b, t1)) for name, a, b in red["host_spans"]
               if name == PAUSE]
        seconds = sum(b - a for a, b in cut if b > a) / 1e9
        return 100.0 * seconds / red["window_s"]
    steps = obs["units"].get("decode_steps")
    if not steps:
        return None
    marks = sum(1 for name, a, _b in red["host_spans"]
                if name == STARVED and t0 <= a < t1)
    return 100.0 * marks / steps
