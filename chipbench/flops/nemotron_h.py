"""Operations and bytes the Nemotron-H family needs, from its shapes alone and
from what the routing hit.

They count the work of the algorithm, whatever implements it. A decode step
reads every weight it uses once: the Mamba blocks' projections, the attention
block, the expert layers' router, latent projections and shared expert, the
head — and of the routed experts **those the step's routing hit**, an entry an
expert layer (``ctx.experts_hit``, which the runner reads from the program's
own counts; a step whose counts are not known counts no routed expert: the
least time is then understated, never overstated). It reads and writes every
active slot's **recurrent state** once a Mamba block: ``H P N`` float32 values
(``state_itemsize`` of the configuration's file, whatever the weights'
itemsize) and the convolution's tail of ``K - 1`` rows of ``[x ; B ; C]``
(`state_step`: what the update must move, not what a kernel happens to move).
A cached token is keys and values of the stored heads in the attention blocks
alone. A multiply-add is two operations. Sizes under the keys of the release's
``config.json``; ``experts_held`` is the configuration file's.
"""
from __future__ import annotations


def _s(cfg):
    c = cfg["hidden_size"]
    h, p, g, n, k = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                     cfg["n_groups"], cfg["ssm_state_size"],
                     cfg["conv_kernel"])
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    di = h * p
    cc = di + 2 * g * n
    pattern = cfg["hybrid_override_pattern"]
    lat, f, fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                  cfg["moe_shared_expert_intermediate_size"])
    held = (cfg.get("experts_held") or (0, cfg["n_routed_experts"]))[1]
    return dict(
        c=c, h=h, p=p, n=n, k=k, hq=hq, hk=hk, d=d, di=di, cc=cc,
        m_layers=pattern.count("M"), a_layers=pattern.count("*"),
        e_layers=pattern.count("E"), held=held,
        mamba=c * (di + cc + h) + di * c,
        attn=c * (hq + 2 * hk) * d + hq * d * c,
        latent=2 * c * lat, shared=2 * c * fs, expert=2 * lat * f,
        router=c * cfg["n_routed_experts"], head=c * cfg["vocab_size"],
        top_k=cfg["num_experts_per_tok"], experts=cfg["n_routed_experts"],
        # float32 leaves a block: norms, convolution, dt_bias, A_log, D, the
        # gated norm's gain; the router's bias
        m_small=c + (k + 1) * cc + 3 * h + di, a_small=c,
        e_small=c + cfg["n_routed_experts"])


def pairs_expected(cfg):
    """Held (token, expert) pairs a token an expert layer, under uniform
    routing."""
    s = _s(cfg)
    return s["top_k"] * s["held"] / s["experts"]


def scan_flops(cfg):
    """One token's state update and read-out, one Mamba block: decay, the
    outer product added (3 a state value), the product with ``C`` (2), and
    the convolution."""
    s = _s(cfg)
    return 5 * s["h"] * s["p"] * s["n"] + 2 * s["k"] * s["cc"]


def attended_row_flops(cfg):
    """One query token's scores and weighted sum over one cached row."""
    s = _s(cfg)
    return 4 * s["hq"] * s["d"]


def _matmul_params(cfg, pairs=None):
    """Parameters a token's matmuls touch, all blocks, without the head;
    `pairs`: held pairs a token an expert layer (expected if None)."""
    s = _s(cfg)
    if pairs is None:
        pairs = pairs_expected(cfg)
    return s["m_layers"] * s["mamba"] + s["a_layers"] * s["attn"] \
        + s["e_layers"] * (s["router"] + s["latent"] + s["shared"]
                           + pairs * s["expert"])


def token_flops(cfg, context, with_head, pairs=None):
    """One token through the stack at position ``context - 1`` (a decode
    step)."""
    s = _s(cfg)
    flops = 2 * _matmul_params(cfg, pairs) + s["m_layers"] * scan_flops(cfg) \
        + s["a_layers"] * attended_row_flops(cfg) * context
    return flops + (2 * s["head"] if with_head else 0)


def prompt_flops(cfg, start, end, with_head=True):
    """Prefilling prompt positions [start, end) of one request; the head
    runs once, on the prompt's last position."""
    s = _s(cfg)
    n = end - start
    rows = (start + end + 1) * n // 2          # sum of (t + 1)
    return n * (2 * _matmul_params(cfg) + s["m_layers"] * scan_flops(cfg)) \
        + s["a_layers"] * attended_row_flops(cfg) * rows \
        + (2 * s["head"] if with_head else 0)


def weight_bytes(cfg, itemsize, experts_hit=()):
    """All that a decode step must read of the weights: the Mamba blocks',
    the attention blocks', the expert layers' latent projections and shared
    expert, the head (`itemsize` each), the routers and the small leaves
    (float32), and the routed experts hit (`experts_hit`: an entry an expert
    layer). The embedding is read a row a slot, which is left out."""
    s = _s(cfg)
    return itemsize * (s["m_layers"] * s["mamba"] + s["a_layers"] * s["attn"]
                       + s["e_layers"] * (s["latent"] + s["shared"])
                       + s["head"] + sum(experts_hit) * s["expert"]) \
        + 4 * (s["e_layers"] * (s["router"] + s["e_small"])
               + s["m_layers"] * s["m_small"] + s["a_layers"] * s["a_small"]
               + s["c"])


def kv_bytes_per_row(cfg, itemsize):
    s = _s(cfg)
    return s["a_layers"] * 2 * s["hk"] * s["d"] * itemsize


def state_bytes_per_slot(cfg, itemsize):
    """What one slot keeps, all Mamba blocks: the state in the
    configuration's ``state_itemsize`` (float32), the convolution's tail in
    `itemsize`."""
    s = _s(cfg)
    return s["m_layers"] * (
        s["h"] * s["p"] * s["n"] * cfg.get("state_itemsize", 4)
        + (s["k"] - 1) * s["cc"] * itemsize)


def _hit(contexts):
    return getattr(contexts, "experts_hit", None) or ()


def state_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's recurrence alone: every active
    slot's state and tail read once and written once a Mamba block, and the
    operations of the update."""
    s = _s(cfg)
    n = len(contexts)
    return n * s["m_layers"] * scan_flops(cfg), \
        2 * n * state_bytes_per_slot(cfg, itemsize)


def attention_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's attention over the cache alone:
    the rows attended, read once, and the products a row."""
    s = _s(cfg)
    rows = sum(contexts)
    return (s["a_layers"] * attended_row_flops(cfg) * rows,
            kv_bytes_per_row(cfg, itemsize) * rows)


def experts_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's routed experts alone: the
    weights of the experts hit, read once; the operations of the expected
    held pairs (far under the ridge at a few rows an expert)."""
    s = _s(cfg)
    pairs = len(contexts) * pairs_expected(cfg) * s["e_layers"]
    return 2 * pairs * s["expert"], \
        itemsize * sum(_hit(contexts)) * s["expert"]


def decode_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of one decode step over slots whose contexts (the
    positions before and at the new token) are `contexts`."""
    flops = len(contexts) * token_flops(cfg, 0, True) \
        + attention_step(cfg, contexts, itemsize)[0]
    return flops, weight_bytes(cfg, itemsize, _hit(contexts)) \
        + kv_bytes_per_row(cfg, itemsize) * sum(contexts) \
        + state_step(cfg, contexts, itemsize)[1]
