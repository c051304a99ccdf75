"""Model operations of every token the traced window processed (prefill
chunks and decode steps the benchmark saw start in it; flops/<family>.py),
over window x peak, in %."""


def read(obs):
    t = obs["trace"]
    if t is None or obs["peak"] is None or not obs["calls"]["decode"]:
        return None
    lo, hi = obs["trace_clock"]
    cfg, fl = obs["spec"].config, obs["flops"]
    ops = sum(fl.decode_step(cfg, ctx, 4)[0]
              for at, ctx in obs["calls"]["decode"] if lo <= at < hi)
    # the head of a prompt's last chunk is left out (a chunk does not say
    # whether it is the last): under 0.1 % of a chunk's operations
    ops += sum(fl.prompt_flops(cfg, start, start + n, with_head=False)
               for at, start, n in obs["calls"]["prefill"] if lo <= at < hi)
    return 100.0 * ops / (hi - lo) / obs["peak"]["flops_bf16"]
