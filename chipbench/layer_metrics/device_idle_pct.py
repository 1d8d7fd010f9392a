"""1 - the union of the device's op intervals over the traced window."""


def read(obs):
    red = obs["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
