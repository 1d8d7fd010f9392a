"""Mean of a program histogram over the window from the deltas of its
sum and count (never its percentiles: those are bucket edges)."""


def read(obs, family):
    count = obs["counters"][family + "_count"]
    return 1e3 * obs["counters"][family + "_sum"] / count if count else None
