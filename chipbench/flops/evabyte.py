"""Operations and bytes the EvaByte family needs, from its shapes alone.

They count the work of the algorithm, whatever implements it: every weight is
read once a step; a position ``t`` attends the rows of its own window up to
itself and one summary row for every chunk of every window before
(`rows_attended`), not its whole context; padding does no work. A multiply-add
is two operations. Sizes under the keys of EvaByte's ``config.json``.
"""
from __future__ import annotations


def _sizes(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["num_pred_heads"],
            cfg["num_attention_heads"])


def layer_matmul_params(cfg):
    n_layer, c, f, _, _, _ = _sizes(cfg)
    return n_layer * (4 * c * c + 3 * c * f)


def head_params(cfg):
    _, c, _, v, p, _ = _sizes(cfg)
    return c * v * p              # every prediction head is computed


def rows_attended(cfg, t):
    """K/V rows position `t` attends: a summary row for every chunk of the
    windows before its own, and its window's rows up to itself."""
    w = cfg["window_size"]
    return (w // cfg["chunk_size"]) * (t // w) + t % w + 1


def token_flops(cfg, context, with_head):
    """One token through the stack at position ``context - 1`` (`context`
    positions stand before and at it); `with_head` adds the output heads."""
    n_layer, c, _, _, _, _ = _sizes(cfg)
    flops = 2 * layer_matmul_params(cfg) \
        + n_layer * 4 * rows_attended(cfg, context - 1) * c
    return flops + (2 * head_params(cfg) if with_head else 0)


def prompt_flops(cfg, start, end, with_head=True):
    """Prefilling prompt positions [start, end) of one request; the heads
    run once, on the prompt's last position (`with_head`)."""
    n_layer, c, _, _, _, _ = _sizes(cfg)
    rows = sum(rows_attended(cfg, t) for t in range(start, end))
    return (end - start) * 2 * layer_matmul_params(cfg) \
        + n_layer * 4 * c * rows + (2 * head_params(cfg) if with_head else 0)


def weight_bytes(cfg, itemsize):
    """All that a decode step must read of the weights: every layer's
    matrices (`itemsize` each), its two norms and two per-head feature
    vectors (float32), the final norm and the heads. The embedding is read a
    row a slot, which is left out."""
    n_layer, c, _, _, _, _ = _sizes(cfg)
    return itemsize * (layer_matmul_params(cfg) + head_params(cfg)) \
        + 4 * (n_layer * 4 * c + c)


def kv_bytes_per_row(cfg, itemsize):
    n_layer, c, _, _, _, _ = _sizes(cfg)
    return 2 * n_layer * c * itemsize


def decode_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of one decode step over slots whose contexts (the
    positions before and at the new token) are `contexts`."""
    flops = sum(token_flops(cfg, n, True) for n in contexts)
    rows = sum(rows_attended(cfg, n - 1) for n in contexts)
    return flops, weight_bytes(cfg, itemsize) \
        + kv_bytes_per_row(cfg, itemsize) * rows


def attention_step(cfg, contexts, itemsize):
    """``(flops, bytes)`` of a decode step's attention alone, all layers:
    the rows attended, read once, and two products a row."""
    n_layer, c, _, _, _, _ = _sizes(cfg)
    rows = sum(rows_attended(cfg, n - 1) for n in contexts)
    return n_layer * 4 * rows * c, kv_bytes_per_row(cfg, itemsize) * rows


def roll_bytes(cfg, itemsize):
    """One roll: a window's rows read, its chunks' summary rows written."""
    w = cfg["window_size"]
    return kv_bytes_per_row(cfg, itemsize) * (w + w // cfg["chunk_size"])
