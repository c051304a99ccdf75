"""A percentile of the durations (ms) of one of the benchmark's host spans,
over the window."""
from chipbench.lib.trace import percentile


def read(obs, span, q):
    w = obs["window"]
    v = obs["spans"].durations_ms(span, w["t_open"], w["t_close"])
    return percentile(v, q) if v else None
