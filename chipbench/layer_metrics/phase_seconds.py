"""Seconds of the named set-up phases (the runner's phase clock)."""


def read(obs, phases):
    return sum(obs["phases"][p] for p in phases)
