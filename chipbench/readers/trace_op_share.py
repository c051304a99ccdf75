"""Device time of the ops whose name matches, as a share (%) of the time
the device was busy."""


def read(obs, ops):
    t = obs["trace"]
    if t is None:
        return None
    s = t.op_seconds(ops)
    return 100.0 * s / t.busy_s() if s else None
