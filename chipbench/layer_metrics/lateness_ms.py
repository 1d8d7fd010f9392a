"""How late the generator sent: a percentile of sent - due."""

import numpy as np


def read(obs, percentile):
    late = obs.get("lateness_s")
    if late is None or not len(late):
        return None
    return 1e3 * float(np.percentile(late, percentile))
