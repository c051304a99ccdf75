"""A program counter's growth over the window as a share (%) of a quantity
the runner counted (prefix-cache tokens over prompt tokens due)."""


def read(obs, counter, of):
    base = obs["window"].get(of)
    if not base or counter not in obs["counters"]:
        return None
    return 100.0 * obs["counters"][counter] / base
