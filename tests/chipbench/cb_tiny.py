"""A toy cell of every kind, written into a temporary directory: what a later
PR does to add a cell (new files and nothing else), at a size the CPU holds."""
import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.lib import harness  # noqa: E402

# Set as PERF.md section 2 sets the cells' own, from `control.py` at this size
# on the CPU (seeds 21-26 and 31-36): the program reads 0 on both gaps (its
# float32 tokens are the reference's; the CPU multiplies in float32) and the
# int8 control far above; the bfloat16 step reads 0.0058-0.0065 on
# grad_diff_all_leaves, the int8 control 0.0130-0.0141, half a batch 0.99.
# The toy's other numbers do not part the control from the program (width
# 64: too few terms to average over) and keep wide limits.
SERVE_LIMITS = {"logit_gap_max": 2e-4, "logit_gap_mean": 2e-6,
                "min_tokens_compared": 10}
TRAIN_LIMITS = {"grad_norm_gap_worst_leaf": 0.1,
                "delta_norm_gap_worst_leaf": 0.1,
                "grad_diff_all_leaves": 0.0095, "grad_diff_worst_leaf": 0.3}
FILES = {
    "configs/gpt-tiny.json": {
        "family": "gpt", "runner": "serve", "n_layer": 2, "n_embd": 64,
        "n_head": 4, "n_inner": 128, "n_positions": 128, "vocab_size": 4000,
        "layer_norm_epsilon": 1e-5, "served_itemsize": 4,
        "engine": {"max_slots": 4, "max_len": 128, "page_tokens": 8,
                   "prefill_chunk": 16, "kv_dtype": "fp", "prefix_reuse": True,
                   "policy": "fifo", "max_queue": 64}},
    "configs/bert-tiny.json": {
        "family": "bert", "runner": "train", "num_hidden_layers": 2,
        "hidden_size": 64, "num_attention_heads": 4, "intermediate_size": 128,
        "vocab_size": 500, "max_position_embeddings": 32, "type_vocab_size": 2,
        "dropout_prob": 0.0, "layer_norm_eps": 1e-5,
        "trainer": {"amp": "bfloat16", "adam": {
            "learning_rate": 1e-4, "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8}}},
    "traffic/chat-tiny.json": {
        "kind": "open_loop", "rate_rps": 20, "sizes": 12,
        "prompt": {"median": 30, "sigma": 0.4, "lo": 10, "hi": 60},
        "output": {"median": 8, "sigma": 0.3, "lo": 4, "hi": 16},
        "shared_prefix": {"tokens": 16, "share": 0.5}, "page_tokens": 8,
        "ramp_s": 0.5, "ramp_sizes": 6, "trace_s": 1,
        "check_requests": 3, "check_pad": 80},
    "traffic/mlm-tiny.json": {
        "kind": "train", "rows_per_chip": 4, "seq": 32, "read_every": 5,
        "check_steps": 3, "check_block_rows": 2, "warm_chunks_min": 2,
        "warm_chunks_max": 3, "warm_tolerance": 0.5, "trace_s": 1},
    "workloads/tiny.chat.json": {
        "config": "gpt-tiny", "traffic": "chat-tiny", "chips": 1,
        "end_to_end": ["itl_p50_ms", "setup_s", "serve_tokens_s",
                       "toy_requests_s"],
        "per_layer": ["prefix_hit_share.itl", "ttft_p50_ms.chat", "itl_p95_ms.chat",
                      "submit_wait_p50_ms.itl"], "limits": SERVE_LIMITS},
    "workloads/tiny.train.json": {
        "config": "bert-tiny", "traffic": "mlm-tiny", "chips": 1,
        "end_to_end": ["train_tokens_s", "setup_s"],
        "per_layer": ["input_wait_share"], "limits": TRAIN_LIMITS},
    "workloads/tiny.dp4.json": {
        "config": "bert-tiny", "traffic": "mlm-tiny", "chips": 4,
        "end_to_end": ["train_tokens_s", "setup_s"],
        "per_layer": ["input_wait_share"], "limits": TRAIN_LIMITS},
    # an end-to-end metric the benchmark does not hold yet, on a reader it has
    "metrics/serve_tokens_s.json": {
        "unit": "tokens/s", "better": "higher", "source": "host_clock",
        "reader": "rate", "params": {"work": "tokens"}},
    # a metric of a new kind: its own file and its own reader
    "metrics/toy_requests_s.json": {
        "unit": "requests/s", "better": "higher", "source": "host_clock",
        "reader": "toy_rate", "params": {"of": "requests_finished"}},
}
TOY_READER = '''"""A reader a later PR adds: requests finished a second."""


def read(obs, of):
    w = obs["window"]
    return w[of] / w["wall_s"] if w.get(of) else None
'''


def make_root(tmp):
    """chipbench's metric files and peaks, plus the toy cells."""
    root = os.path.join(str(tmp), "cells")
    shutil.copytree(os.path.join(harness.CHIPBENCH, "metrics"),
                    os.path.join(root, "metrics"))
    shutil.copy(os.path.join(harness.CHIPBENCH, "peaks.json"), root)
    for rel, obj in FILES.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
    os.makedirs(os.path.join(root, "readers"))
    with open(os.path.join(root, "readers", "toy_rate.py"), "w") as f:
        f.write(TOY_READER)
    return root


def run(root, workload, seed=3, seconds=1.0, capsys=None):
    """One run with the look for a chip skipped; returns the result."""
    from chipbench import run as entry

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    return entry.run_cell(args, root=root, require_tpu=False)
