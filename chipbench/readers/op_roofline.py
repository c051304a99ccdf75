"""A device op's share (%) of its roofline over the traced window: the least
time the chip could take for the work the window's decode steps gave it (the
larger of operations over peak and bytes over bandwidth, from the function
`work` of flops/<family>.py, one call a decode step the benchmark saw start),
over the summed device time of the ops whose name matches. Nothing to read
(no trace, no such op, no such function): None."""


def read(obs, ops, work):
    t, peak = obs["trace"], obs["peak"]
    fn = getattr(obs["flops"], work, None)
    if t is None or peak is None or fn is None:
        return None
    seconds = t.op_seconds(ops)
    lo, hi = obs["trace_clock"]
    steps = [ctx for at, ctx in obs["calls"]["decode"] if lo <= at < hi]
    if not seconds or not steps:
        return None
    cfg = obs["spec"].config
    item = cfg["served_itemsize"]
    least = sum(max(f / peak["flops_bf16"], b / peak["hbm_bytes_s"])
                for f, b in (fn(cfg, ctx, item) for ctx in steps))
    return 100.0 * least / seconds
