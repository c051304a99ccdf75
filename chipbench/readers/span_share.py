"""The share (%) of the window's wall time spent inside one host span."""


def read(obs, span):
    w = obs["window"]
    v = obs["spans"].durations_ms(span, w["t_open"], w["t_close"])
    return 100.0 * sum(v) / 1e3 / w["wall_s"] if v else None
