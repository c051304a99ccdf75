"""Mean number of active slots per decode launch in the traced window."""


def read(obs):
    if obs["trace"] is None or not obs["calls"]["decode"]:
        return None
    lo, hi = obs["trace_clock"]
    n = [len(ctx) for at, ctx in obs["calls"]["decode"] if lo <= at < hi]
    return sum(n) / len(n) if n else None
