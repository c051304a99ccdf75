"""1 - (device time of the step programs) / traced window, in %: the part of
the wall in which no step program ran, whatever the host was doing."""


def read(obs, module):
    t = obs["trace"]
    if t is None:
        return None
    busy = t.module_seconds(module)
    return 100.0 * (1.0 - busy / t.window_s) if busy else None
