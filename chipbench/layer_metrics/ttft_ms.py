"""A percentile of the client-side time to first token (due -> the
future's resolution) over the traced window's requests: the tail beside
the end-to-end median, which swings too widely for a bound."""

import numpy as np


def read(obs, percentile):
    ttft = obs.get("ttft_s")
    if ttft is None or not len(ttft):
        return None
    return 1e3 * float(np.percentile(ttft, percentile))
