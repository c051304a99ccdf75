"""Operations a BERT-style encoder with an MLM head needs for one training
step, from its shapes alone: forward plus backward (twice the forward), no
recomputation counted, a multiply-add as two operations."""
from __future__ import annotations


def forward_flops_per_token(cfg, seq):
    c = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    per_layer = 2 * (3 * c * c + c * c + 2 * c * f) + 4 * seq * c
    head = 2 * c * c + 2 * c * cfg["vocab_size"]
    return layers * per_layer + head


def train_flops_per_token(cfg, seq):
    return 3 * forward_flops_per_token(cfg, seq)
