"""Collective time in the 4-chip trace: milliseconds a step in which a
collective was in flight on a device (``what="ms_per_step"``), or the
share of that time in which no other op ran on that device
(``what="exposed_pct"``); averaged over the devices."""

from chipbench import trace_reduce as tr


def read(obs, what):
    red = obs["reduced"]
    steps = obs["units"].get("steps")
    per_device = tr.collective_intervals(obs["trace"], red["t0_ns"],
                                         red["t1_ns"])
    in_flight, exposed = [], []
    for name, intervals in per_device.items():
        compute = tr.clip([e for e in red["devices"][name]
                           if not tr.is_collective(e)],
                          red["t0_ns"], red["t1_ns"])
        in_flight.append(tr.total(intervals))
        exposed.append(tr.total(tr.subtract(intervals, compute)))
    if not steps or not sum(in_flight):
        return None
    if what == "ms_per_step":
        return sum(in_flight) / len(in_flight) / 1e6 / steps
    return 100.0 * sum(exposed) / sum(in_flight)
