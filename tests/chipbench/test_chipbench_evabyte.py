"""The EvaByte cell's files, reference, operation counts, readers and runner
on the CPU: the reference's attention against the sets `L` and `R` built one
position at a time, `flops/evabyte.py` against a hand count, a toy cell of the
new runner end to end (and with a token altered where it is produced), and
the cell's new metrics on synthetic lanes and on the toy run's own records."""
import json
import os

import numpy as onp
import pytest

import cb_tiny
from chipbench.flops import evabyte as flops
from chipbench.lib import harness
from chipbench.lib.trace import Trace
from chipbench.readers import counter_share, op_roofline, program_steps
from chipbench.readers import trace_op_share
from chipbench.reference import evabyte as ref

PUBLISHED = json.load(open(os.path.join(
    harness.CHIPBENCH, "configs", "evabyte.json")))
TINY = {
    "family": "evabyte", "runner": "serve_eva", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "intermediate_size": 96,
    "vocab_size": 50, "num_pred_heads": 2, "window_size": 32, "chunk_size": 4,
    "rope_theta": 100000, "rms_norm_eps": 1e-5, "init_std": 0.2,
    "max_position_embeddings": 192, "served_itemsize": 4,
    "served_dtype": "float32",
    "engine": {"max_slots": 4, "max_len": 192, "page_tokens": 4,
               "prefill_chunk": 8, "kv_dtype": "fp", "prefix_reuse": False,
               "policy": "fifo", "max_queue": 64}}
FILES = {
    "configs/eva-tiny.json": TINY,
    "traffic/docs-tiny.json": {
        "kind": "open_loop", "rate_rps": 20, "sizes": 12,
        "prompt": {"median": 70, "sigma": 0.3, "lo": 40, "hi": 120},
        "output": {"median": 14, "sigma": 0.3, "lo": 8, "hi": 40},
        "page_tokens": 4, "ramp_s": 0.5, "ramp_sizes": 4, "trace_s": 1,
        "check_requests": 3, "check_pad": 192},
    "workloads/tiny.docs.json": {
        "config": "eva-tiny", "traffic": "docs-tiny", "chips": 1,
        "end_to_end": ["itl_p50_ms", "setup_s"],
        "per_layer": ["eva_rows_share.docs", "eva_roll_ms_p50.docs"],
        "limits": cb_tiny.SERVE_LIMITS}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = cb_tiny.make_root(tmp_path_factory.mktemp("cb_eva"))
    for rel, obj in FILES.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root


# -- the reference ------------------------------------------------------------

def test_reference_attention_is_the_sets_position_by_position():
    """`window_attention` and `summaries`, window by window as `logits_at`
    uses them, against a loop that builds L and R as Python sets."""
    import jax.numpy as jnp

    rng = onp.random.default_rng(3)
    t_all, h, d, window, chunk = 80, 2, 8, 32, 4
    q, k, v = (rng.normal(size=(t_all, h, d)).astype(onp.float32)
               for _ in range(3))
    phi, mu = (rng.normal(size=(h, d)).astype(onp.float32) for _ in range(2))
    want = ref.attention_by_sets(q, k, v, phi, mu, window, chunk)
    k_hat = jnp.zeros((96 // chunk, h, d))
    v_hat = jnp.zeros_like(k_hat)
    got = []
    for t0 in range(0, t_all, window):
        sl = slice(t0, min(t0 + window, t_all))
        got.append(ref.window_attention(q[sl], k[sl], v[sl], k_hat, v_hat, t0,
                                        window, chunk))
        if sl.stop - t0 == window:
            kh, vh = ref.summaries(jnp.asarray(k[sl]), jnp.asarray(v[sl]),
                                   phi, mu, chunk)
            k_hat = k_hat.at[t0 // chunk:sl.stop // chunk].set(kh)
            v_hat = v_hat.at[t0 // chunk:sl.stop // chunk].set(vh)
    onp.testing.assert_allclose(onp.concatenate(got), want, rtol=2e-5,
                                atol=2e-6)
    # position 70 stands in window 2: 16 chunks behind it, 7 rows beside it
    assert flops.rows_attended({"window_size": 32, "chunk_size": 4}, 70) \
        == 16 + 7


def test_leaves_cover_the_published_parameter_count():
    """16 layers of 202.4 M, the embedding and all eight heads."""
    assert ref.n_params(PUBLISHED) == 16 * (
        4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128) \
        + 320 * 4096 + 4096 + 8 * 320 * 4096
    names = [name for name, *_ in ref.leaves(PUBLISHED)]
    assert len(names) == len(set(names)) == 3 + 16 * 11


# -- operations and bytes -----------------------------------------------------

def test_flops_and_bytes_against_a_hand_count():
    cfg = PUBLISHED
    mm = 16 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
    head = 8 * 320 * 4096
    assert flops.layer_matmul_params(cfg) == mm
    # position 17,000: 8 finished windows of 128 summaries, 616 + 1 rows
    assert flops.rows_attended(cfg, 17000) == 8 * 128 + 617
    assert flops.rows_attended(cfg, 2047) == 2048
    assert flops.rows_attended(cfg, 2048) == 128 + 1
    f, b = flops.decode_step(cfg, [17001, 2049], 2)
    rows = 8 * 128 + 617 + 129
    assert f == 2 * (2 * mm + 2 * head) + 16 * 4 * rows * 4096
    assert b == 2 * (mm + head) + 4 * (16 * 4 * 4096 + 4096) \
        + rows * 2 * 16 * 4096 * 2
    assert flops.attention_step(cfg, [17001, 2049], 2) == \
        (16 * 4 * rows * 4096, rows * 2 * 16 * 4096 * 2)
    # a chunk of 512 that starts a window attends 128 w + 1 .. 128 w + 512
    assert flops.prompt_flops(cfg, 4096, 4608, with_head=False) == \
        512 * 2 * mm + 16 * 4 * 4096 * (512 * 256 + 512 * 513 // 2)
    assert flops.roll_bytes(cfg, 2) == (2048 + 128) * 2 * 16 * 4096 * 2


# -- the new metrics' readers -------------------------------------------------

def synthetic_obs():
    """Six seconds with two decode steps: 32 kernel calls of 50 us and one
    roll program of three fusions."""
    ops = [["mx_paged_decode.%d tpu_custom_call" % i, 1_000_000 * i, 50_000]
           for i in range(32)]
    ops += [["fusion.%d" % i, 40_000_000 + 1_000_000 * i, 450_000]
            for i in range(16)]
    lanes = {"devices": {"/device:TPU:0": {
        "XLA Ops": ops, "XLA Modules": [["jit_decode(1)", 0, 50_000_000]]}},
        "host": [["cb.window", 0, 6_000_000_000]]}
    spec = harness.Spec("evabyte.docs")
    return {"trace": Trace(lanes), "trace_clock": (10.0, 16.0), "spec": spec,
            "peak": spec.peak("TPU v5 lite"), "flops": flops,
            "calls": {"decode": [(11.0, [17001] * 8), (12.0, [17002] * 8),
                                 (17.0, [1] * 8)]},
            "counters": {"mx_serve_decode_rows_total": 1500,
                         "mx_serve_decode_rows_total.summary": 1000},
            "window": {"decode_positions": 12000}}


def test_attention_roofline_and_time_share_on_a_synthetic_lane():
    obs = synthetic_obs()
    rows = 8 * (8 * 128 + 617) + 8 * (8 * 128 + 618)
    least = rows * 2 * 16 * 4096 * 2 / 819e9          # bytes bind
    got = op_roofline.read(obs, ops="mx_paged_decode", work="attention_step")
    assert got == pytest.approx(100 * least / (32 * 50e-6))
    share = trace_op_share.read(obs, ops="mx_paged_decode|mx_eva_roll")
    assert share == pytest.approx(100 * 32 * 50e-6 / (32 * 50e-6 + 16 * 450e-6))
    # nothing to read: no such op, no such function, no trace
    assert op_roofline.read(obs, ops="mx_nothing", work="attention_step") is None
    assert op_roofline.read(obs, ops="mx_paged_decode", work="none") is None
    assert op_roofline.read(dict(obs, trace=None), ops="x", work="y") is None


def test_rows_shares_read_the_counters_over_the_positions():
    obs = synthetic_obs()
    m = harness.Spec("evabyte.docs").metric
    both = m("eva_rows_share.docs")["params"]
    summary = m("eva_summary_rows_share.docs")["params"]
    assert counter_share.read(obs, **both) == pytest.approx(12.5)
    assert counter_share.read(obs, **summary) == pytest.approx(100 / 12)
    # a program without the counters (the parent commit): left out
    assert counter_share.read(dict(obs, counters={}), **both) is None


# -- a toy cell of the new runner ---------------------------------------------

def test_toy_cell_runs_is_correct_and_feeds_the_new_metrics(root):
    res = cb_tiny.run(root, "tiny.docs", seed=5, seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["notes"]["in_window"]["compiles"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    # every sampled request crossed a roll, in prefill or in decode
    from incubator_mxnet_tpu.telemetry import registry, tracing

    assert registry.counter("mx_serve_eva_rolls_total").value >= 12
    recs = tracing.step_records()
    assert any(r["eva_roll"] > 0 for r in recs)
    obs = {"window": {"t_open": recs[0]["t_start"],
                      "t_close": recs[-1]["t_start"] + 1}}
    roll_ms = program_steps.read(obs, field="eva_roll", q=50)
    assert roll_ms is not None and roll_ms > 0
    # the phases still account for the steps' wall
    accounted = program_steps.read(obs, per="wall", field=[
        "admit", "prefill_launch", "prefill_readback", "decode_launch",
        "decode_readback", "emit", "eva_roll"])
    assert accounted > 90.0


def test_altered_token_is_not_correct(root, monkeypatch):
    """A token altered where it is produced: the decode program's output."""
    from incubator_mxnet_tpu.serve.eva import EvaSlotDecoder

    inner = EvaSlotDecoder.decode_step

    def altered(self, *a, **kw):
        return (inner(self, *a, **kw) + 1) % 50

    monkeypatch.setattr(EvaSlotDecoder, "decode_step", altered)
    res = cb_tiny.run(root, "tiny.docs", seed=6, seconds=1.5)
    assert res["correct"] is False


def test_runner_window_counts_rows_and_positions(root):
    """The runner's own readings: rows attended over the positions the
    steps stood at, and the share of decode steps that carried a chunk."""
    import argparse

    from chipbench import run as entry
    from chipbench.runners import serve_eva

    spec = harness.Spec("tiny.docs", root)
    args = argparse.Namespace(seed=8, seconds=1.5, trace=0)
    env = entry.Env(spec, args, harness.find_devices(1, False), None)
    got = serve_eva.run(env)
    w, c = got["window"], got["counters"]
    assert w["decode_positions"] > 0
    rows = c["mx_serve_decode_rows_total"]
    assert rows == c["mx_serve_decode_rows_total.window"] \
        + c["mx_serve_decode_rows_total.summary"] > 0
    # window 32, chunk 4: at most 32 rows of the window + 8 a window behind
    assert rows < w["decode_positions"]
    assert 0.0 <= w["decode_steps_with_chunk_share"] <= 1.0
    assert 0.9 < w["step_accounted_share"] <= 1.0
    assert all(harness.passed(ch) for ch in got["checks"])


# -- the control ---------------------------------------------------------------

def control_lines(capsys, *argv):
    from chipbench import control_eva

    capsys.readouterr()
    control_eva.main(list(argv))
    out = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("CONTROL ")]
    by_side = {}
    for line in out:
        by_side.setdefault(line["side"], []).append(line)
    return by_side


def test_int8_control_comes_out_not_correct_through_this_runner(root, capsys):
    got = control_lines(capsys, "--workload", "tiny.docs", "--seeds", "41,42",
                        "--control-seeds", "2", "--seconds", "1.5", "--root",
                        root, "--any-device")
    assert [line["correct"] for line in got["program"]] == [True] * 2
    assert [line["correct"] for line in got["control_int8"]] == [False] * 2


def test_a_layer_with_a_part_left_out_comes_out_not_correct(root, capsys):
    """The summaries skipped, `mu` dropped, `alpha` uniform: what the window
    served fails the cell's limits against each such reference."""
    got = control_lines(capsys, "--workload", "tiny.docs", "--seeds", "43",
                        "--seconds", "1.5", "--root", root, "--any-device",
                        "--variants")
    assert got["program"][0]["correct"] is True
    for side in ("variant_skip_summaries", "variant_drop_mu",
                 "variant_uniform_alpha"):
        assert got[side][0]["correct"] is False, side
        assert got[side][0]["failed"]
