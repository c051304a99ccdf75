"""The decode program's share (%) of its roofline: the least time the chip
could take for the steps the traced window ran (the larger of operations over
peak and bytes over bandwidth, from flops/<family>.py: weights once a step
and the KV of live tokens), over the device time those programs took."""


def read(obs, module):
    t = obs["trace"]
    if t is None or obs["peak"] is None:
        return None
    lo, hi = obs["trace_clock"]
    steps = [ctx for at, ctx in obs["calls"]["decode"] if lo <= at < hi]
    dev_ms = t.module_ms(module)
    if not steps or not dev_ms:
        return None
    cfg, fl, peak = obs["spec"].config, obs["flops"], obs["peak"]
    item = cfg["served_itemsize"]
    least = [max(f / peak["flops_bf16"], b / peak["hbm_bytes_s"])
             for f, b in (fl.decode_step(cfg, ctx, item) for ctx in steps)]
    return 100.0 * (sum(least) / len(least)) / (sum(dev_ms) / len(dev_ms) / 1e3)
