"""Operations and bytes the openPangu-Ultra-MoE family needs, from its shapes
alone and from what the routing hit.

They count the work of the algorithm, whatever implements it. A decode step
reads every weight it uses once: attention, the dense FFN, the shared
experts, the router, the head — and of the routed experts **those the step's
routing hit**, an entry an expert layer (``ctx.experts_hit``, which the runner
reads from the program's own counts; a step whose counts are not known counts
no routed expert: the least time is then understated, never overstated). A
cached token is one latent row of ``kv_lora_rank + qk_rope_head_dim`` values a
layer (the zeros a stored row is padded with are no work). Attention over a
row, absorbed (a decode step): ``2 H ((rank + rope) + rank)`` operations, the
scores and the weighted sum in the latent space. A prompt position attends
up-projected: ``2 H (nope + rope + v)`` a row, and its own row is expanded
once. A multiply-add is two operations. Sizes under the keys of the release's
``config.json``; ``experts_held`` is the configuration file's.
"""
from __future__ import annotations


def _s(cfg):
    c = cfg["hidden_size"]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    held = (cfg.get("experts_held") or (0, cfg["n_routed_experts"]))[1]
    return dict(
        c=c, h=h, dn=dn, dr=dr, dv=dv, rq=rq, r=r, layers=layers,
        dense=dense, expert_layers=layers - dense, held=held,
        attn=c * rq + rq * h * (dn + dr) + c * (r + dr)
        + r * h * (dn + dv) + h * dv * c,
        ffn=3 * c * cfg["intermediate_size"],
        expert=3 * c * cfg["moe_intermediate_size"],
        shared=3 * c * cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        router=c * cfg["n_routed_experts"], head=c * cfg["vocab_size"],
        top_k=cfg["num_experts_per_tok"], experts=cfg["n_routed_experts"])


def pairs_expected(cfg):
    """Held (token, expert) pairs a token an expert layer, under uniform
    routing."""
    s = _s(cfg)
    return s["top_k"] * s["held"] / s["experts"]


def row_values(cfg):
    """Values a token leaves in the cache a layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def absorbed_row_flops(cfg):
    """One head-set's scores and weighted sum over one latent row."""
    s = _s(cfg)
    return 2 * s["h"] * (s["r"] + s["dr"] + s["r"])


def _matmul_params(cfg, pairs=None):
    """Parameters a token's matmuls touch, all layers, without the head;
    `pairs`: held pairs a token an expert layer (expected if None)."""
    s = _s(cfg)
    if pairs is None:
        pairs = pairs_expected(cfg)
    return s["layers"] * s["attn"] + s["dense"] * s["ffn"] \
        + s["expert_layers"] * (s["shared"] + s["router"]
                                + pairs * s["expert"])


def token_flops(cfg, context, with_head, pairs=None):
    """One token through the stack at position ``context - 1``, attending
    absorbed (a decode step)."""
    s = _s(cfg)
    flops = 2 * _matmul_params(cfg, pairs) \
        + s["layers"] * absorbed_row_flops(cfg) * context
    return flops + (2 * s["head"] if with_head else 0)


def prompt_flops(cfg, start, end, with_head=True):
    """Prefilling prompt positions [start, end) of one request, attending
    up-projected; the head runs once, on the prompt's last position."""
    s = _s(cfg)
    n = end - start
    rows = (start + end + 1) * n // 2          # sum of (t + 1)
    return n * 2 * _matmul_params(cfg) \
        + s["layers"] * 2 * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * rows \
        + (2 * s["head"] if with_head else 0)


def weight_bytes(cfg, itemsize, experts_hit=()):
    """All that a decode step must read of the weights: every layer's
    attention, the dense FFNs, the shared experts and the head (`itemsize`
    each), the routers and the norms (float32), and the routed experts hit
    (`experts_hit`: an entry an expert layer). The embedding is read a row
    a slot, which is left out."""
    s = _s(cfg)
    return itemsize * (s["layers"] * s["attn"] + s["dense"] * s["ffn"]
                       + s["expert_layers"] * s["shared"] + s["head"]
                       + sum(experts_hit) * s["expert"]) \
        + 4 * (s["expert_layers"] * s["router"]
               + s["layers"] * (4 * s["c"] + s["rq"] + s["r"]) + s["c"])


def kv_bytes_per_row(cfg, itemsize):
    return cfg["num_hidden_layers"] * row_values(cfg) * itemsize


def _hit(contexts):
    return getattr(contexts, "experts_hit", None) or ()


def decode_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of one decode step over slots whose contexts (the
    positions before and at the new token) are `contexts`."""
    flops = len(contexts) * token_flops(cfg, 0, True) \
        + cfg["num_hidden_layers"] * absorbed_row_flops(cfg) * sum(contexts)
    return flops, weight_bytes(cfg, itemsize, _hit(contexts)) \
        + kv_bytes_per_row(cfg, itemsize) * sum(contexts)


def attention_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's attention over the cache alone,
    all layers: the latent rows attended, read once, and the absorbed
    products a row. The two are 242 operations a byte apart: the larger of
    operations over peak and bytes over bandwidth is the least time."""
    rows = sum(contexts)
    return (cfg["num_hidden_layers"] * absorbed_row_flops(cfg) * rows,
            kv_bytes_per_row(cfg, itemsize) * rows)


def experts_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's routed experts alone: the
    weights of the experts hit, read once; the operations of the expected
    held pairs (far under the ridge at a few rows an expert)."""
    s = _s(cfg)
    pairs = len(contexts) * pairs_expected(cfg) * s["expert_layers"]
    return 2 * pairs * s["expert"], \
        itemsize * sum(_hit(contexts)) * s["expert"]
