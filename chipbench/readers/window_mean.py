"""The mean of a list the runner kept over the whole window."""


def read(obs, values):
    v = obs["window"].get(values) or []
    return sum(v) / len(v) if v else None
