"""A whole-window rate: all the work of the window over all its wall time."""


def read(obs, work, scale=1.0):
    w = obs["window"]
    if not w.get(work) or not w["wall_s"]:
        return None
    return scale * w[work] / w["wall_s"]
