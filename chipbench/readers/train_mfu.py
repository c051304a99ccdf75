"""The whole step's share (%) of the chips' bf16 peak: forward + backward
operations per token (flops/<family>.py) x tokens a second over chips x peak.
Tokens a second come from the step programs the traced window holds."""


def read(obs, module):
    t = obs["trace"]
    if t is None or obs["peak"] is None:
        return None
    whole = t.module_ms(module)
    if not whole:
        return None
    # steps in the window, a step that straddles an edge for its part
    steps = t.module_seconds(module) / (sum(whole) / len(whole) / 1e3)
    w = obs["window"]
    tokens_s = steps * w["rows"] * w["seq"] / t.window_s
    per_token = obs["flops"].train_flops_per_token(obs["spec"].config, w["seq"])
    return 100.0 * per_token * tokens_s / (obs["chips"] * obs["peak"]["flops_bf16"])
