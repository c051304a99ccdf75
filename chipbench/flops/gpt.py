"""Operations and bytes a GPT-2 style decoder needs, from its shapes alone.

They count the work of the algorithm, whatever implements it: every weight
is read once a step, attention touches the keys and values of *live* tokens
only (not of a whole `max_len` view), padding does no work. A multiply-add is
two operations.
"""
from __future__ import annotations


def _sizes(cfg):
    c = cfg["n_embd"]
    return cfg["n_layer"], c, cfg.get("n_inner") or 4 * c, cfg["vocab_size"]


def layer_matmul_params(cfg):
    n_layer, c, f, _ = _sizes(cfg)
    return n_layer * (3 * c * c + c * c + 2 * c * f)


def token_flops(cfg, context, with_head):
    """One token through the stack attending to `context` positions (itself
    included); `with_head` adds the vocabulary projection."""
    n_layer, c, _, v = _sizes(cfg)
    flops = 2 * layer_matmul_params(cfg) + n_layer * 4 * context * c
    return flops + (2 * c * v if with_head else 0)


def prompt_flops(cfg, start, end, with_head=True):
    """Prefilling prompt positions [start, end) of one request; the head
    runs once, on the prompt's last position (`with_head`)."""
    n_layer, c, _, v = _sizes(cfg)
    n = end - start
    # sum over positions p of 4 * (p + 1) * c per layer
    attn = n_layer * 4 * c * (n * (start + end + 1) // 2)
    return n * 2 * layer_matmul_params(cfg) + attn \
        + (2 * c * v if with_head else 0)


def weight_bytes(cfg, itemsize):
    """All that a decode step must read of the weights: every layer, the
    final norm and the tied head (the embedding table, once)."""
    n_layer, c, f, v = _sizes(cfg)
    per_layer = 3 * c * c + c * c + 2 * c * f + 3 * c + c + f + c + 4 * c
    return itemsize * (n_layer * per_layer + v * c + 2 * c)


def kv_bytes_per_token(cfg, itemsize):
    n_layer, c, _, _ = _sizes(cfg)
    return 2 * n_layer * c * itemsize


def decode_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of one decode step over slots whose live contexts
    (tokens attended, the new one included) are `contexts`."""
    flops = sum(token_flops(cfg, n, True) for n in contexts)
    moved = weight_bytes(cfg, itemsize) \
        + kv_bytes_per_token(cfg, itemsize) * sum(contexts)
    return flops, moved
