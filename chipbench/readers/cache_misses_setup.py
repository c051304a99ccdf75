"""Persistent-cache misses during set-up: 0 once a checkout is warm."""


def read(obs):
    return obs["watch_setup"]["misses"] if obs.get("watch_setup") else None
