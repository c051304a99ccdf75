"""A percentile of a list the runner kept over the whole window (gaps
between tokens, times to first token, the generator's lateness)."""
from chipbench.lib.trace import percentile


def read(obs, values, q, min_count=1):
    v = obs["window"].get(values) or []
    return percentile(v, q) if len(v) >= min_count else None
