"""The Nemotron-H cell's files, reference, operation counts, readers and runner
on the CPU: the reference's independence and its leaves against the cut's
arithmetic, the configuration's file against the catalog's keys,
`flops/nemotron_h.py` against the reference's parameter counts and a hand
count, the cell's new metrics on synthetic lanes, a toy cell of the new runner
end to end (and with a token altered where it is produced), and each planted
fault and the int8 control through the cell's own comparison."""
import ast
import json
import os

import numpy as onp
import pytest

import cb_tiny
from chipbench.flops import nemotron_h as flops
from chipbench.lib import harness
from chipbench.lib.trace import Trace
from chipbench.readers import op_roofline, trace_op_share, window_mean
from chipbench.reference import nemotron_h as ref

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = json.load(open(os.path.join(
    harness.CHIPBENCH, "configs", "nemotron-3-super-120b-a12b.json")))
TINY = {
    "family": "nemotron_h", "runner": "serve_nemotron_h",
    "num_hidden_layers": 6, "hybrid_override_pattern": "MEM*EM",
    "hidden_size": 64, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "moe_latent_size": 24,
    "moe_shared_expert_intermediate_size": 48, "routed_scaling_factor": 5.0,
    "vocab_size": 50, "layer_norm_epsilon": 1e-5,
    "max_position_embeddings": 192, "experts_held": [4, 6], "init_std": 0.2,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "served_itemsize": 4, "served_dtype": "float32", "state_itemsize": 4,
    "engine": {"max_slots": 4, "max_len": 192, "page_tokens": 4,
               "prefill_chunk": 32, "kv_dtype": "fp", "prefix_reuse": False,
               "policy": "fifo", "max_queue": 64}}
FILES = {
    "configs/nemotron-tiny.json": TINY,
    "traffic/turns-tiny.json": {
        "kind": "open_loop", "rate_rps": 20, "sizes": 12,
        "prompt": {"median": 50, "sigma": 0.4, "lo": 20, "hi": 100},
        "output": {"median": 20, "sigma": 0.3, "lo": 12, "hi": 40},
        "page_tokens": 4, "ramp_s": 0.5, "ramp_sizes": 4, "trace_s": 1,
        "check_requests": 3, "check_pad": 192},
    "workloads/tiny.turns.json": {
        "config": "nemotron-tiny", "traffic": "turns-tiny", "chips": 1,
        "end_to_end": ["itl_p50_ms", "setup_s"],
        "per_layer": ["moe_experts_hit_mean.think",
                      "moe_held_pair_share.think", "state_resets_mean.turns"],
        # few tokens at the toy widths: every margin above 0 is decisive
        "limits": dict(cb_tiny.SERVE_LIMITS, decisive_margin=1e-5,
                       logit_gap_mean_decisive=2e-6,
                       min_decisive_tokens=10, state_gap_first=1e-4,
                       state_gap_max=1e-4)}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = cb_tiny.make_root(tmp_path_factory.mktemp("cb_nemotron"))
    for rel, obj in FILES.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root


# -- the reference and the configuration's file --------------------------------

def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.CHIPBENCH, "reference", "nemotron_h.py")
    tree = ast.parse(open(path).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
    assert not any("incubator_mxnet_tpu" in n for n in names)
    assert {n.split(".")[0] for n in names} <= {
        "__future__", "dataclasses", "functools", "math", "chipbench", "jax",
        "numpy"}


def test_leaves_cover_the_cuts_parameter_count():
    """The issue's table: a Mamba block 109.64 M, the attention block 35.66
    M, an expert layer 54.53 M beside 128 experts of 5.505 M; 2 x 134.2 M of
    vocabulary: 4,648 M held."""
    c = 4096
    mamba = c * 18560 + 8192 * c + c + 10240 * 4 + 10240 + 3 * 128 + 8192
    attn = c * (4096 + 256 + 256) + 4096 * c + c
    shared = c + 512 * c + 512 + 2 * c * 1024 + 2 * c * 5376
    expert = 2 * 1024 * 2688
    total = 5 * mamba + attn + 5 * (shared + 128 * expert) \
        + 2 * 32768 * c + c
    assert ref.n_params(PUBLISHED) == total
    assert [round(n / 1e6, 2) for n in (mamba, attn, shared)] == [
        109.64, 35.66, 54.53]
    assert round(expert / 1e6, 3) == 5.505 and round(total / 1e6) == 4648
    names = [name for name, *_ in ref.leaves(PUBLISHED)]
    assert len(names) == len(set(names)) \
        == 3 + 5 * 9 + 5 + 5 * (7 + 2 * 128)
    assert "layers.1.mixer.experts.127.down_proj.weight" in names
    assert "layers.1.mixer.experts.128.down_proj.weight" not in names
    assert "layers.7.mixer.q_proj.weight" in names


def test_the_configurations_file_against_the_catalogs_keys():
    """Every number of the catalog's `config` under the same key, but the
    keys `reduced` names, whose published values stand under `published`;
    no width among them; what is the builder's own under `assumed`."""
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row, = [r for r in map(json.loads, open(CATALOG))
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert PUBLISHED["source"] == row["source_url"]
    reduced = PUBLISHED["reduced"]
    assert reduced == ["num_hidden_layers", "hybrid_override_pattern",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"]
    for key, value in row["config"].items():
        if key in reduced:
            assert PUBLISHED["published"][key] == value, key
        else:
            assert PUBLISHED[key] == value, key
    # the cut is a prefix of the published pattern: one whole period
    cut = PUBLISHED["hybrid_override_pattern"]
    assert row["config"]["hybrid_override_pattern"].startswith(cut)
    assert (cut.count("M"), cut.count("E"), cut.count("*")) == (5, 5, 1)
    assert len(cut) == PUBLISHED["num_hidden_layers"] == 11
    assert PUBLISHED["experts_held"] == [0, 128]
    assert PUBLISHED["n_routed_experts"] == 512         # the router's width
    assert PUBLISHED["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert {"no_rotary", "latent_moe", "mamba", "state", "weights",
            "mtp"} <= set(PUBLISHED["assumed"])
    assert "4 chips share each layer" in PUBLISHED["deployment"]
    assert "8 pipeline stages" in PUBLISHED["deployment"]
    bench = json.load(open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")))
    entry, = [c for c in bench["configs"]
              if c["name"] == "nemotron-3-super-120b-a12b"]
    assert entry["reduced"] == reduced and entry["source"] == row["source_url"]
    cell, = [w for w in bench["workloads"]
             if w["name"] == "nemotron3super.turns"]
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    engine = PUBLISHED["engine"]
    assert engine["n_pages"] == 64 * (engine["max_len"] // 16) + 1
    assert engine["prefix_reuse"] is False


def test_reference_blocks_of_queries_are_one_causal_attention():
    """The attention of `Q_BLOCK` queries at a time is plain grouped-head
    causal attention over the request."""
    import jax
    import jax.numpy as jnp

    s = ref.sizes(TINY)
    rng = onp.random.default_rng(0)
    t = 2 * ref.Q_BLOCK
    u = jnp.asarray(rng.normal(size=(t, s.c)), jnp.float32)
    p = {f"mixer.{n}_proj.weight": jnp.asarray(
        rng.normal(size=shape) * 0.2, jnp.float32)
        for n, shape in (("q", (s.hq * s.d, s.c)), ("k", (s.hk * s.d, s.c)),
                         ("v", (s.hk * s.d, s.c)), ("o", (s.c, s.hq * s.d)))}
    with jax.default_matmul_precision("highest"):
        got = onp.asarray(ref.attention(p, u, s))
    un = onp.asarray(u, onp.float64)
    q = (un @ onp.asarray(p["mixer.q_proj.weight"]).T).reshape(t, s.hq, s.d)
    k = (un @ onp.asarray(p["mixer.k_proj.weight"]).T).reshape(t, s.hk, s.d)
    v = (un @ onp.asarray(p["mixer.v_proj.weight"]).T).reshape(t, s.hk, s.d)
    out = onp.zeros((t, s.hq, s.d))
    for h in range(s.hq):
        sc = q[:, h] @ k[:, h // 2].T / onp.sqrt(s.d)
        sc = onp.where(onp.tril(onp.ones((t, t), bool)), sc, -onp.inf)
        w = onp.exp(sc - sc.max(-1, keepdims=True))
        out[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, h // 2]
    want = out.reshape(t, -1) @ onp.asarray(p["mixer.o_proj.weight"]).T
    onp.testing.assert_allclose(got, want, atol=2e-4)


def test_seeded_kinds_are_the_initialisation_the_file_states():
    import jax

    from chipbench.lib import seeded

    s = ref.sizes(PUBLISHED)
    key = seeded.key_of(3)
    draw = lambda kind: onp.asarray(ref.leaf(  # noqa: E731
        key, "t", 0, (4096,), kind, s))
    a = onp.exp(draw("a_log"))
    assert 1.0 <= a.min() < 1.2 and 15.5 < a.max() <= 16.0
    dt = onp.asarray(jax.nn.softplus(draw("dt_bias")))
    assert 0.00099 <= dt.min() and dt.max() <= 0.1001
    conv = draw("conv")
    assert -0.5 <= conv.min() < -0.49 and 0.49 < conv.max() <= 0.5
    assert abs(draw("weight").std() - 0.02) < 0.002
    assert abs(draw("table").std() - 1.0) < 0.05
    assert abs(draw("gain").mean() - 1.0) < 0.005


# -- operations and bytes -------------------------------------------------------

def test_flops_and_bytes_against_the_references_leaves_and_a_hand_count():
    cfg = PUBLISHED
    by_kind = {"weight": 0, "small": 0}
    experts = 0
    for name, _, _, shape, kind in ref.leaves(cfg):
        n = int(onp.prod(shape))
        if ".experts." in name:
            experts += n
        elif name == "embeddings.weight":
            continue
        elif kind == "weight" and "gate.weight" not in name:
            by_kind["weight"] += n
        else:
            by_kind["small"] += n
    # every matrix but the embedding once (bfloat16), the router and the
    # small leaves float32, and the experts hit
    assert flops.weight_bytes(cfg, 2, ()) == \
        2 * by_kind["weight"] + 4 * by_kind["small"]
    assert flops.weight_bytes(cfg, 2, (128,) * 5) - flops.weight_bytes(
        cfg, 2, ()) == 2 * experts
    one = 2 * 1024 * 2688
    assert flops.pairs_expected(cfg) == 22 * 128 / 512 == 5.5
    mm = 5 * (4096 * 18560 + 8192 * 4096) + (4096 * 4608 + 4096 * 4096) \
        + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 5.5 * one)
    scan = 5 * 128 * 64 * 128 + 2 * 4 * 10240
    assert flops.token_flops(cfg, 600, True) == \
        2 * mm + 5 * scan + 4 * 32 * 128 * 600 + 2 * 4096 * 32768
    assert flops.prompt_flops(cfg, 512, 1024, with_head=False) == \
        512 * (2 * mm + 5 * scan) \
        + 4 * 32 * 128 * (512 * 512 + 512 * 513 // 2)

    class Ctx(list):
        experts_hit = None

    ctx = Ctx([600] * 60)
    ctx.experts_hit = [120, 119, 121, 118, 122]
    slot = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert slot == 5 * 4_255_744
    assert flops.state_step(cfg, ctx, 2) == (60 * 5 * scan, 2 * 60 * slot)
    assert flops.state_step(cfg, ctx, 4)[1] == \
        2 * 60 * 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 4)
    assert flops.experts_step(cfg, ctx, 2) == (
        2 * 60 * 5.5 * 5 * one, 2 * 600 * one)
    assert flops.attention_step(cfg, ctx, 2) == (
        4 * 32 * 128 * 36000, 1024 * 36000)
    f, b = flops.decode_step(cfg, ctx, 2)
    assert b == flops.weight_bytes(cfg, 2, ctx.experts_hit) \
        + 1024 * 36000 + 2 * 60 * slot
    assert f == 60 * flops.token_flops(cfg, 0, True) + 4 * 32 * 128 * 36000
    # the issue's reckoning of a step: ~11 GB, the state 2.7 GB of it
    full = Ctx([600] * 64)
    full.experts_hit = [120] * 5
    assert 10.5e9 < flops.decode_step(cfg, full, 2)[1] < 11.5e9
    assert round(flops.state_step(cfg, full, 2)[1] / 1e9, 2) == 2.72


# -- the new metrics' readers ---------------------------------------------------

def synthetic_obs():
    """Six seconds with two decode steps: 10 state kernel calls of 700 us,
    fusions; the steps' contexts carry the experts hit."""
    ops = [["mx_ssm_decode.%d tpu_custom_call" % i, 1_000_000 * i, 700_000]
           for i in range(10)]
    ops += [["fusion.%d" % i, 60_000_000 + 1_000_000 * i, 450_000]
            for i in range(16)]
    lanes = {"devices": {"/device:TPU:0": {
        "XLA Ops": ops, "XLA Modules": [["jit_decode(1)", 0, 90_000_000]]}},
        "host": [["cb.window", 0, 6_000_000_000]]}
    spec = harness.Spec("nemotron3super.turns")

    class Ctx(list):
        experts_hit = None

    a, b, late = Ctx([500] * 64), Ctx([501] * 62), Ctx([1] * 64)
    return {"trace": Trace(lanes), "trace_clock": (10.0, 16.0), "spec": spec,
            "peak": spec.peak("TPU v5 lite"), "flops": flops,
            "calls": {"decode": [(11.0, a), (12.0, b), (17.0, late)]},
            "counters": {}, "window": {"state_resets": [0, 1, 0, 0, 2, 0]}}


def test_the_cells_new_metrics_on_a_synthetic_lane():
    obs = synthetic_obs()
    m = harness.Spec("nemotron3super.turns").metric
    read = lambda name, reader: reader.read(  # noqa: E731
        obs, **m(name)["params"])
    least = (64 + 62) * 2 * 5 * 4_255_744 / 819e9      # the bytes bind
    assert read("ssm_decode_roofline.turns", op_roofline) == pytest.approx(
        100 * least / (10 * 700e-6))
    assert read("ssm_decode_roofline.turns", op_roofline) < 100
    busy = 10 * 700e-6 + 16 * 450e-6
    assert read("ssm_time_share.turns", trace_op_share) == pytest.approx(
        100 * 10 * 700e-6 / busy)
    assert read("state_resets_mean.turns", window_mean) == 0.5
    # a program without the kernel or the records (the parent commit): each
    # metric is left out, none raises
    bare = dict(obs, window={})
    bare["trace"] = Trace({"devices": {"/device:TPU:0": {
        "XLA Ops": [["fusion.1", 0, 1000]], "XLA Modules": []}},
        "host": [["cb.window", 0, 6_000_000_000]]})
    for name, reader in (("ssm_decode_roofline.turns", op_roofline),
                         ("ssm_time_share.turns", trace_op_share),
                         ("state_resets_mean.turns", window_mean)):
        assert reader.read(bare, **m(name)["params"]) is None, name
    cell = harness.Spec("nemotron3super.turns").cell
    bench = json.load(open(os.path.join(harness.CHECKOUT, "BENCHMARK.json")))
    for entry in bench["end_to_end"] + bench["per_layer"]:
        listed = "nemotron3super.turns" in entry.get("workloads", [])
        reported = entry["name"] in cell["per_layer"] + cell["end_to_end"]
        assert listed == (reported and "workloads" in entry), entry["name"]


# -- a toy cell of the new runner -------------------------------------------------

def test_toy_cell_runs_is_correct_and_feeds_the_new_metrics(root):
    res = cb_tiny.run(root, "tiny.turns", seed=5, seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["notes"]["in_window"]["compiles"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    from incubator_mxnet_tpu.telemetry import tracing

    recs = tracing.step_records()
    assert sum(r.get("state_resets", 0) for r in recs) >= 4
    assert any("moe_experts_hit" in r for r in recs)


def test_runner_window_reads_the_states_and_the_expert_layers_counts(root):
    import argparse

    from chipbench import run as entry
    from chipbench.runners import serve_nemotron_h

    spec = harness.Spec("tiny.turns", root)
    args = argparse.Namespace(seed=8, seconds=1.5, trace=0)
    env = entry.Env(spec, args, harness.find_devices(1, False), None)
    got = serve_nemotron_h.run(env)
    w, c = got["window"], got["counters"]
    assert 0 < c["mx_serve_moe_pairs_total.held"] \
        < c["mx_serve_moe_pairs_total.routed"] == w["moe_pairs_routed"]
    assert w["decode_steps"] == len(w["moe_experts_hit"]) > 0
    assert all(0 <= h <= 6 for h in w["moe_experts_hit"])
    assert sum(w["state_resets"]) >= 1 and 0 < w["chunk_step_share"] < 1
    assert all(harness.passed(ch) for ch in got["checks"])
    # the state itself was compared: the slots' own against the reference's
    # after the tokens they had consumed (float32 here: to rounding)
    names = [ch["name"] for ch in got["checks"]]
    assert "state_slots_compared" in names
    if got["states"]:
        assert "state_gap_max" in names and "state_gap_first" in names
        assert got["state_gaps"].shape == (len(got["states"]), 3)
        assert got["state_gaps"].max() < 1e-5
    measured = entry.measure(["state_resets_mean.turns"], spec,
                             dict(got, spec=spec))
    assert measured["state_resets_mean.turns"]["value"] > 0


def test_altered_token_is_not_correct(root, monkeypatch):
    """A token altered where it is produced: the decode program's output."""
    from incubator_mxnet_tpu.serve.ssm import HybridSlotDecoder

    inner = HybridSlotDecoder.fetch_tokens

    def altered(self, out):
        return (inner(self, out) + 1) % 50

    monkeypatch.setattr(HybridSlotDecoder, "fetch_tokens", altered)
    res = cb_tiny.run(root, "tiny.turns", seed=6, seconds=1.5)
    assert res["correct"] is False


# -- the control ------------------------------------------------------------------

def control_lines(capsys, *argv):
    from chipbench import control_nemotron_h

    capsys.readouterr()
    control_nemotron_h.main(list(argv))
    out = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("CONTROL ")]
    by_side = {}
    for line in out:
        by_side.setdefault(line["side"], []).append(line)
    return by_side


def test_each_planted_fault_and_int8_come_out_not_correct(root, capsys):
    """A state not reset when a slot changes hands, a state carried in
    bfloat16, the score bias dropped, the scaling factor 1, the shared
    expert dropped, int8 matmul inputs: what the window served, or the state
    it left in its slots, fails the cell's limits against each such
    reference. (A state carried in bfloat16 moves no argmax over the toy's
    66 tokens of a 50-word vocabulary: the state's own comparison parts
    it.)"""
    got = control_lines(capsys, "--workload", "tiny.turns", "--seeds", "43",
                        "--seconds", "1.5", "--root", root, "--any-device",
                        "--variants")
    assert got["program"][0]["correct"] is True
    if "program_states" in got:         # slots were decoding at the close
        assert max(got["program_states"][0]["gaps"]) < 1e-5
        for side in ("variant_state_not_reset", "variant_state_bf16",
                     "variant_int8"):
            assert "state_gap_max" in got[side][0]["failed"], side
    for side in ("variant_state_not_reset", "variant_no_score_bias", "variant_scaling_factor_one",
                 "variant_drop_shared_expert", "variant_int8"):
        assert got[side][0]["correct"] is False, side
        assert got[side][0]["failed"]


def test_a_state_carried_in_bfloat16_moves_the_references_logits():
    """The control's `state_bf16` switch, read on the logits themselves."""
    from chipbench import control_nemotron_h

    rng = onp.random.default_rng(9)
    tokens = rng.integers(0, 50, (1, 192)).astype(onp.int32)
    rows = [(0, t) for t in range(150, 190)]
    exact = ref.logits_at(TINY, 3, tokens, rows)
    patch = control_nemotron_h.variants(ref)["state_bf16"]
    kept = ref.carry
    try:
        ref.carry = patch["carry"]
        ref._programs.cache_clear()
        rounded = ref.logits_at(TINY, 3, tokens, rows)
    finally:
        ref.carry = kept
        ref._programs.cache_clear()
    again = ref.logits_at(TINY, 3, tokens, rows)
    assert onp.array_equal(exact, again)
    assert 1e-4 < onp.abs(rounded - exact).max() < 1.0


def test_control_readings_run_with_this_runner(root, capsys):
    got = control_lines(capsys, "--workload", "tiny.turns", "--seeds", "44",
                        "--control-seeds", "1", "--seconds", "1.5", "--root",
                        root, "--any-device")
    assert got["program"][0]["correct"] is True
    assert got["control_int8"][0]["correct"] is False
