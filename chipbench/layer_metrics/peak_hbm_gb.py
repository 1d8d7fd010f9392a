"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after
the window, in GB."""


def read(obs):
    return obs["peak_bytes"] / 1e9 if obs["peak_bytes"] else None
