"""Occupied slot-steps over slot-steps of the window's decode steps."""


def read(obs):
    occ = obs.get("slot_occupancy")
    return None if occ is None else 100.0 * occ
