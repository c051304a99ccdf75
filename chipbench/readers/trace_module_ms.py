"""A percentile of the device time (ms) of the compiled programs whose name
matches, read from the XLA Modules lane alone."""
from chipbench.lib.trace import percentile


def read(obs, module, q):
    if obs["trace"] is None:
        return None
    v = obs["trace"].module_ms(module)
    return percentile(v, q) if v else None
