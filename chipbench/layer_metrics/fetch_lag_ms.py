"""The host's clock against the device's, inside one trace: the median,
over the window's fetches, of (end of the program's fetch span, put on
the trace's clock by the window marks) minus (end of the program
execution the fetch waited for, ``red["modules"]``, the device's own
clock), in milliseconds.

``np.asarray(fetches[0])`` returns when the device's program has ended
and its first output is on the host, so the true lag is the copy and the
wake-up, about a tenth of a millisecond. A reading far from that, of
either sign, is the offset between the two clocks in this trace: an idle
gap shorter than it cannot be trusted to the span that seems to cover
it. The execution a fetch waited for is the one it overlaps longest.
None without such spans (a parent without them) or executions."""

import statistics


def read(obs, fetch_prefix):
    red = obs["reduced"]
    t0, t1 = red["t0_ns"], red["t1_ns"]
    fetches = [(a, b) for name, a, b in red["host_spans"]
               if name.startswith(fetch_prefix) and a >= t0 and b <= t1]
    lags = []
    for events in red["modules"].values():
        runs = [(s, s + d) for _name, s, d in events if d > 0]
        for a, b in fetches:
            best, best_overlap = None, 0.0
            for s, e in runs:
                overlap = min(b, e) - max(a, s)
                if overlap > best_overlap:
                    best, best_overlap = e, overlap
            if best is not None:
                lags.append((b - best) / 1e6)
    return statistics.median(lags) if lags else None
