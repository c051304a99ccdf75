"""1 - union of the XLA Ops intervals over the traced window, in %."""


def read(obs):
    return None if obs["trace"] is None else 100.0 * obs["trace"].idle_share()
