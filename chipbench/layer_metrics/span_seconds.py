"""Host time under the program's own spans (``obs["reduced"]
["host_spans"]``: the tracer's spans of the window, on the trace's
clock): the seconds, cut to the window, of the spans whose name starts
with one of ``prefixes``. ``per`` says over what:

- ``"unit:<key>"``  milliseconds per unit of ``obs["units"][<key>]``
  (a decode step, a prefill: the runner's count over the same window);
- ``"spans:<prefix>"``  milliseconds per span whose name starts with
  ``<prefix>`` (one ``executor.run`` is one dispatch);
- ``"window"``  percent of the window.

The spans of one list are consecutive pieces of one thread's work, so
their seconds add. Host clock alone: nothing here depends on the offset
between the host's and the device's clock. None when the program
records no such span (a parent without them) or there is no unit."""


def read(obs, prefixes, per):
    red = obs["reduced"]
    t0, t1 = red["t0_ns"], red["t1_ns"]
    prefixes = tuple(prefixes)
    spans = [(max(a, t0), min(b, t1)) for name, a, b in red["host_spans"]
             if name.startswith(prefixes)]
    spans = [(a, b) for a, b in spans if b > a]
    if not spans:
        return None
    seconds = sum(b - a for a, b in spans) / 1e9
    kind, _, key = per.partition(":")
    if kind == "window":
        return 100.0 * seconds / red["window_s"]
    if kind == "unit":
        n = obs["units"].get(key)
    elif kind == "spans":
        n = sum(1 for name, a, b in red["host_spans"]
                if name.startswith(key) and b > t0 and a < t1)
    else:
        raise ValueError(f"per={per!r}: want unit:<key>, spans:<prefix> "
                         f"or window")
    return 1e3 * seconds / n if n else None
