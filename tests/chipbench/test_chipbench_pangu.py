"""The openPangu-Ultra-MoE cell's files, reference, operation counts, readers
and runner on the CPU: the reference's independence and its leaves against
the cut's arithmetic, `flops/pangu.py` against a hand count, the cell's new
metrics on synthetic lanes, a toy cell of the new runner end to end (and with
a token altered where it is produced), and each planted fault and the int8
control through the cell's own comparison."""
import ast
import json
import os

import numpy as onp
import pytest

import cb_tiny
from chipbench.flops import pangu as flops
from chipbench.lib import harness
from chipbench.lib.trace import Trace
from chipbench.readers import (counter_share, op_roofline, trace_op_share,
                               window_mean)
from chipbench.reference import pangu as ref

PUBLISHED = json.load(open(os.path.join(
    harness.CHIPBENCH, "configs", "openpangu-ultra-moe-718b.json")))
TINY = {
    "family": "pangu", "runner": "serve_pangu", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5, "vocab_size": 50,
    "rope_theta": 25600000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 192, "experts_held": [4, 6], "init_std": 0.2,
    "served_itemsize": 4, "served_dtype": "float32",
    "engine": {"max_slots": 4, "max_len": 192, "page_tokens": 4,
               "prefill_chunk": 16, "kv_dtype": "fp", "prefix_reuse": False,
               "policy": "fifo", "max_queue": 64}}
FILES = {
    "configs/pangu-tiny.json": TINY,
    "traffic/think-tiny.json": {
        "kind": "open_loop", "rate_rps": 20, "sizes": 12,
        "prompt": {"median": 50, "sigma": 0.4, "lo": 20, "hi": 100},
        "output": {"median": 20, "sigma": 0.3, "lo": 12, "hi": 40},
        "page_tokens": 4, "ramp_s": 0.5, "ramp_sizes": 4, "trace_s": 1,
        "check_requests": 3, "check_pad": 192},
    "workloads/tiny.think.json": {
        "config": "pangu-tiny", "traffic": "think-tiny", "chips": 1,
        "end_to_end": ["itl_p50_ms", "setup_s"],
        "per_layer": ["moe_experts_hit_mean.think",
                      "moe_held_pair_share.think"],
        # few tokens at the toy widths: every margin above 0 is decisive
        "limits": dict(cb_tiny.SERVE_LIMITS, decisive_margin=1e-4,
                       logit_gap_mean_decisive=2e-6,
                       min_decisive_tokens=10)}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = cb_tiny.make_root(tmp_path_factory.mktemp("cb_pangu"))
    for rel, obj in FILES.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root


# -- the reference ------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.CHIPBENCH, "reference", "pangu.py")
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
    """Tentpole 2's table: attention 196.6 M a layer; the dense layer 621.2
    M; an expert layer 1,000.7 M; 2 x 147.5 M of vocabulary: 4.92 G."""
    c = 7680
    attn = c * 1536 + 1536 * 128 * 192 + c * 576 + 512 * 128 * 256 \
        + 128 * 128 * c
    norms = 4 * c + 1536 + 512
    dense = attn + norms + 3 * c * 18432
    expert = attn + norms + 256 * c + 17 * 3 * c * 2048
    total = dense + 4 * expert + 2 * 19200 * c + c
    assert ref.n_params(PUBLISHED) == total
    assert round(attn / 1e6, 1) == 196.6 and round(total / 1e9, 2) == 4.92
    assert round((expert - norms) / 1e6, 1) == 1000.7
    names = [name for name, *_ in ref.leaves(PUBLISHED)]
    assert len(names) == len(set(names)) == 3 + 5 * 11 + 3 + 4 * (4 + 48)
    assert "layers.3.mlp.experts.15.down_proj.weight" in names
    assert "layers.3.mlp.experts.16.down_proj.weight" not in names


def test_the_configuration_keeps_every_published_width():
    cat = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else []
    row = [r for r in cat if r["name"] == "openPangu-Ultra-MoE-718B"]
    if not row:
        pytest.skip("the catalog is not on this machine")
    for key, value in row[0]["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value
        else:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["source"] == row[0]["source_url"]


def test_reference_blocks_of_queries_are_one_causal_attention():
    """`attention` a block of `Q_BLOCK` queries at a time against a direct
    per-head softmax over the whole sequence (numpy, float64), the rotary
    term by its definition (pairs ``i, i + d/2``)."""
    import jax
    import jax.numpy as jnp

    s = ref.sizes(dict(TINY))
    key = ref.seeded.key_of(3)
    p = {name: ref.leaf(key, name, 1, shape(s), kind, s.init_std)
         for name, shape, kind in ref.ATTN_LEAVES}
    t = 2 * ref.Q_BLOCK
    x = jnp.asarray(onp.random.default_rng(0).normal(size=(t, s.c)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = onp.asarray(ref.attention(p, x, s))
    f = {k: onp.asarray(v, onp.float64) for k, v in p.items()}
    xn = onp.asarray(x, onp.float64)

    def norm(a, g):
        return a / onp.sqrt((a * a).mean(-1, keepdims=True) + s.eps) * g

    def rot(a, pos):                       # (..., d) at scalar positions
        half = a.shape[-1] // 2
        ang = pos * s.theta ** (-onp.arange(half) / half)
        a1, a2 = a[..., :half], a[..., half:]
        return onp.concatenate([a1 * onp.cos(ang) - a2 * onp.sin(ang),
                                a2 * onp.cos(ang) + a1 * onp.sin(ang)], -1)

    u = norm(xn, f["input_layernorm.weight"])
    cq = norm(u @ f["self_attn.q_a_proj.weight"].T,
              f["self_attn.q_a_layernorm.weight"])
    q = (cq @ f["self_attn.q_b_proj.weight"].T).reshape(t, s.heads, -1)
    kv = u @ f["self_attn.kv_a_proj_with_mqa.weight"].T
    ckv = norm(kv[:, :s.r], f["self_attn.kv_a_layernorm.weight"])
    kvb = (ckv @ f["self_attn.kv_b_proj.weight"].T).reshape(t, s.heads, -1)
    kr = onp.stack([rot(kv[i, s.r:], i) for i in range(t)])
    out = onp.zeros((t, s.heads * s.dv))
    for i in (0, 7, ref.Q_BLOCK - 1, ref.Q_BLOCK, t - 1):
        for h in range(s.heads):
            qr = rot(q[i, h, s.dn:], i)
            sc = (kvb[:i + 1, h, :s.dn] @ q[i, h, :s.dn] + kr[:i + 1] @ qr) \
                / onp.sqrt(s.dn + s.dr)
            w = onp.exp(sc - sc.max())
            out[i, h * s.dv:(h + 1) * s.dv] = \
                (w / w.sum()) @ kvb[:i + 1, h, s.dn:]
        want = out[i] @ f["self_attn.o_proj.weight"].T
        onp.testing.assert_allclose(got[i], want, atol=2e-4)


# -- operations and bytes -----------------------------------------------------

def test_flops_and_bytes_against_a_hand_count():
    cfg = PUBLISHED
    c, attn, expert = 7680, 196575232, 3 * 7680 * 2048
    mm = 5 * attn + 3 * c * 18432 + 4 * (expert + 256 * c + 0.5 * expert)
    head = 19200 * c
    row = 2 * 128 * (576 + 512)
    assert row == 278528 and flops.absorbed_row_flops(cfg) == row
    assert flops.pairs_expected(cfg) == 0.5
    assert flops.token_flops(cfg, 3000, True) == \
        2 * mm + 5 * row * 3000 + 2 * head

    class Ctx(list):
        experts_hit = None

    ctx = Ctx([3000, 500])
    f, b = flops.decode_step(cfg, ctx, 2)
    small = 4 * (4 * 256 * c + 5 * (4 * c + 1536 + 512) + c)
    held_none = 2 * (5 * attn + 3 * c * 18432 + 4 * expert + head) + small
    assert f == 2 * (2 * mm + 2 * head) + 5 * row * 3500
    assert b == held_none + 3500 * 5 * 576 * 2     # no count known: no expert
    ctx.experts_hit = [14, 12, 16, 9]
    assert flops.decode_step(cfg, ctx, 2)[1] == b + 2 * 51 * expert
    assert flops.weight_bytes(cfg, 2, [16] * 4) == held_none + 2 * 64 * expert
    assert flops.attention_step(cfg, ctx, 2) == (5 * row * 3500,
                                                 3500 * 5 * 576 * 2)
    # 242 operations a byte: on the v5e's ridge of 240.5
    ops, byts = flops.attention_step(cfg, ctx, 2)
    assert round(ops / byts) == 242
    assert flops.experts_step(cfg, ctx, 2) == (2 * 2 * 0.5 * 4 * expert,
                                               2 * 51 * expert)
    # a chunk of 512 at 2,048 attends 2,049 .. 2,560 rows, up-projected
    assert flops.prompt_flops(cfg, 2048, 2560, with_head=False) == \
        512 * 2 * mm + 5 * 2 * 128 * 320 * (512 * 2048 + 512 * 513 // 2)


# -- the new metrics' readers -------------------------------------------------

def synthetic_obs():
    """Six seconds with two decode steps: 10 attention kernel calls of 300
    us, 16 expert kernel calls of 400 us, a chunk's 8 calls, fusions."""
    ops = [["mx_mla_decode.%d tpu_custom_call" % i, 1_000_000 * i, 300_000]
           for i in range(10)]
    ops += [["mx_moe_experts.%d tpu_custom_call" % i,
             20_000_000 + 1_000_000 * i, 400_000] for i in range(16)]
    ops += [["mx_moe_chunk_experts.%d tpu_custom_call" % i,
             40_000_000 + 1_000_000 * i, 500_000] for i in range(8)]
    ops += [["fusion.%d" % i, 60_000_000 + 1_000_000 * i, 450_000]
            for i in range(16)]
    lanes = {"devices": {"/device:TPU:0": {
        "XLA Ops": ops, "XLA Modules": [["jit_decode(1)", 0, 90_000_000]]}},
        "host": [["cb.window", 0, 6_000_000_000]]}
    spec = harness.Spec("pangu718b.think")

    class Ctx(list):
        experts_hit = None

    a, b, late = Ctx([3000] * 64), Ctx([3001] * 64), Ctx([1] * 64)
    a.experts_hit, b.experts_hit = [14, 13, 15, 14], [16, 12, 14, 13]
    return {"trace": Trace(lanes), "trace_clock": (10.0, 16.0), "spec": spec,
            "peak": spec.peak("TPU v5 lite"), "flops": flops,
            "calls": {"decode": [(11.0, a), (12.0, b), (17.0, late)]},
            "counters": {"mx_serve_moe_pairs_total.held": 130,
                         "mx_serve_moe_pairs_total.routed": 2048},
            "window": {"moe_pairs_routed": 2048,
                       "moe_experts_hit": [14.0, 13.75]}}


def test_the_cells_new_metrics_on_a_synthetic_lane():
    obs = synthetic_obs()
    m = harness.Spec("pangu718b.think").metric
    read = lambda name, reader: reader.read(  # noqa: E731
        obs, **m(name)["params"])
    rows = 64 * 3000 + 64 * 3001
    # the operations bind, by a hair: 278,528 a row over 197 T against 5,760
    # bytes over 819 G
    least = max(5 * 278528 * rows / 197e12, rows * 5 * 576 * 2 / 819e9)
    assert least == 5 * 278528 * rows / 197e12
    assert read("mla_decode_roofline.think", op_roofline) == pytest.approx(
        100 * least / (10 * 300e-6))
    experts = 2 * (56 + 55) * 3 * 7680 * 2048 / 819e9
    assert read("moe_roofline.think", op_roofline) == pytest.approx(
        100 * experts / (16 * 400e-6))       # the chunk's calls are not read
    busy = 10 * 300e-6 + 16 * 400e-6 + 8 * 500e-6 + 16 * 450e-6
    assert read("mla_time_share.think", trace_op_share) == pytest.approx(
        100 * 10 * 300e-6 / busy)
    assert read("moe_time_share.think", trace_op_share) == pytest.approx(
        100 * (16 * 400e-6 + 8 * 500e-6) / busy)
    assert read("moe_experts_hit_mean.think", window_mean) == 13.875
    assert read("moe_held_pair_share.think", counter_share) == pytest.approx(
        100 * 130 / 2048)
    # a program without the kernels, the counters or the records (the
    # parent commit): each metric is left out, none raises
    bare = dict(obs, counters={}, window={})
    bare["trace"] = Trace({"devices": {"/device:TPU:0": {
        "XLA Ops": [["fusion.1", 0, 1000]], "XLA Modules": []}},
        "host": [["cb.window", 0, 6_000_000_000]]})
    for name, reader in (("mla_decode_roofline.think", op_roofline),
                         ("moe_roofline.think", op_roofline),
                         ("mla_time_share.think", trace_op_share),
                         ("moe_time_share.think", trace_op_share),
                         ("moe_experts_hit_mean.think", window_mean),
                         ("moe_held_pair_share.think", counter_share)):
        assert reader.read(bare, **m(name)["params"]) is None, name


# -- a toy cell of the new runner ---------------------------------------------

def test_toy_cell_runs_is_correct_and_feeds_the_new_metrics(root):
    res = cb_tiny.run(root, "tiny.think", seed=5, seconds=1.5)
    assert res["correct"] is True and res["failed"] == 0
    assert res["notes"]["in_window"]["compiles"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s"}
    # the program's own records say what the expert layers did
    from incubator_mxnet_tpu.telemetry import tracing

    recs = [r for r in tracing.step_records() if "moe_experts_hit" in r]
    assert recs and all(r["moe_pairs_held"] <= r["moe_pairs_routed"]
                        for r in recs)


def test_runner_window_reads_the_expert_layers_counts(root):
    import argparse

    from chipbench import run as entry
    from chipbench.runners import serve_pangu

    spec = harness.Spec("tiny.think", root)
    args = argparse.Namespace(seed=8, seconds=1.5, trace=0)
    env = entry.Env(spec, args, harness.find_devices(1, False), None)
    got = serve_pangu.run(env)
    w, c = got["window"], got["counters"]
    assert c["mx_serve_decode_rows_total"] > 0
    assert 0 < c["mx_serve_moe_pairs_total.held"] \
        < c["mx_serve_moe_pairs_total.routed"] == w["moe_pairs_routed"]
    assert c["mx_serve_moe_experts_hit_total"] > 0
    assert w["decode_steps"] == len(w["moe_experts_hit"]) > 0
    assert all(0 <= h <= 6 for h in w["moe_experts_hit"])
    assert all(harness.passed(ch) for ch in got["checks"])


def test_altered_token_is_not_correct(root, monkeypatch):
    """A token altered where it is produced: the decode program's output."""
    from incubator_mxnet_tpu.serve.mla import MLASlotDecoder

    inner = MLASlotDecoder.fetch_tokens

    def altered(self, out):
        return (inner(self, out) + 1) % 50

    monkeypatch.setattr(MLASlotDecoder, "fetch_tokens", altered)
    res = cb_tiny.run(root, "tiny.think", seed=6, seconds=1.5)
    assert res["correct"] is False


# -- the control ---------------------------------------------------------------

def control_lines(capsys, *argv):
    from chipbench import control_pangu

    capsys.readouterr()
    control_pangu.main(list(argv))
    out = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("CONTROL ")]
    by_side = {}
    for line in out:
        by_side.setdefault(line["side"], []).append(line)
    return by_side


def test_each_planted_fault_and_int8_come_out_not_correct(root, capsys):
    """The rotary term dropped, the scaling factor 1, the shared expert
    dropped, the post-norms dropped, one held expert's pairs dropped, int8
    matmul inputs: what the window served fails the cell's limits against
    each such reference."""
    got = control_lines(capsys, "--workload", "tiny.think", "--seeds", "43",
                        "--seconds", "1.5", "--root", root, "--any-device",
                        "--variants")
    assert got["program"][0]["correct"] is True
    for side in ("variant_drop_rope_term", "variant_scaling_factor_one",
                 "variant_drop_shared_expert", "variant_drop_post_norms",
                 "variant_drop_one_experts_pairs", "variant_int8"):
        assert got[side][0]["correct"] is False, side
        assert got[side][0]["failed"]


def test_flips_reading_replays_the_float32_choice_of_experts(root, capsys):
    """`--flips`: the reference rounded to bfloat16 with its own choice of
    experts and with float32's replayed, through the cell's comparison; the
    program's gaps split by the rows at which the choice differed."""
    got = control_lines(capsys, "--workload", "tiny.think", "--seeds", "43",
                        "--seconds", "1.5", "--root", root, "--any-device",
                        "--variants", "scaling_factor_one", "--flips")
    assert got["program"][0]["correct"] is True
    assert got["variant_scaling_factor_one"][0]["correct"] is False
    assert "variant_int8" not in got
    free, forced = got["bf16_free"][0], got["bf16_forced"][0]
    for line in (free, forced):
        assert line["logit_gap_max"] >= 0 and "correct" in line
    # the same choice of experts: no expert's whole part moves
    assert forced["logit_gap_mean"] <= free["logit_gap_mean"] + 1e-6
    choice = got["bf16_free_choice"][0]
    assert 0 <= choice["pairs_of_row_and_layer_flipped"] \
        <= choice["rows_flipped"] <= 1
    at, other = got["program_at_flip_rows"][0], got["program_elsewhere"][0]
    assert at["rows"] + other["rows"] \
        == got["program"][0]["served_tokens_compared"]
    # (float32 at the toy widths: the program's own gap is nothing)
    assert at["logit_gap_max"] == other["logit_gap_max"] == 0


def test_routing_replay_forces_the_choice():
    """`control_pangu.Routing`: a replayed pass chooses what was recorded,
    whatever its own scores say; `held_sets` tells sets, not orders."""
    import jax.numpy as jnp

    from chipbench import control_pangu

    s = ref.sizes(dict(TINY))
    u = jnp.asarray(onp.random.default_rng(0).normal(size=(8, s.c)),
                    jnp.float32)
    key = ref.seeded.key_of(3)
    with control_pangu.Routing(ref) as first:
        pr = ref._programs(s, "float32")
        p = pr["shared"](key, jnp.int32(1))
        ids, w, _ = pr["route_step"](p, u)
    planted = (onp.asarray(ids) + 1) % s.experts
    with control_pangu.Routing(ref, replay=[planted]) as again:
        ids2, w2, _ = ref._programs(s, "float32")["route_step"](p, u)
    assert ref._programs is first.inner
    onp.testing.assert_array_equal(onp.asarray(ids2), planted)
    onp.testing.assert_array_equal(again.ids[0], planted)
    onp.testing.assert_allclose(onp.asarray(w2).sum(-1), s.route_scale,
                                rtol=1e-5)
    assert not onp.allclose(onp.asarray(w), onp.asarray(w2))
    a = onp.asarray([[4, 9, 1], [5, 6, 0]])
    onp.testing.assert_array_equal(
        control_pangu.held_sets(a, (4, 6)),
        control_pangu.held_sets(a[:, ::-1], (4, 6)))
    assert list(control_pangu.held_sets(a, (4, 6))) == [1 | 32, 2 | 4]


def test_route_margin_is_the_held_experts_distance_from_the_boundary():
    """Router logits planted: 16 experts, top-4, ids 4-9 held."""
    import jax.numpy as jnp

    s = ref.sizes(dict(TINY))
    assert (s.experts, s.top_k, s.held) == (16, 4, (4, 6))
    gate = onp.zeros((s.experts, s.c), onp.float32)
    gate[onp.arange(16), onp.arange(16)] = 1.0
    z = onp.tile(-onp.arange(16, dtype=onp.float32), (3, 1))   # 0, -1, -2 ..
    # row 0: experts 0-3 chosen, boundary (-3, -4); held 4 is the first out
    # row 1: held 4 raised to -2.75: chosen, 3 falls out; boundary (-2.75, -3)
    z[1, 4] = -2.75
    # row 2: every held expert far below, none near the boundary
    z[2, 4:10] -= 10
    u = onp.zeros((3, s.c), onp.float32)
    u[:, :16] = z
    m = onp.asarray(ref.route_margin({"mlp.gate.weight": jnp.asarray(gate)},
                                     jnp.asarray(u), s))
    onp.testing.assert_allclose(m, [1.0, 0.25, 11.0], rtol=1e-6)


def test_decisive_mean_reads_the_rows_above_the_margin_only():
    from chipbench.runners import serve_pangu

    limits = dict(logit_gap_max=1.0, logit_gap_mean=0.5, decisive_margin=0.1,
                  logit_gap_mean_decisive=0.01, min_decisive_tokens=2)
    g = onp.asarray([0.9, 0.0, 0.004, 0.0])
    margin = onp.asarray([0.01, 0.5, 0.2, 0.11])
    got = {c["name"]: c for c in serve_pangu.gap_checks(g, limits, margin)}
    assert got["logit_gap_mean"]["value"] == pytest.approx(0.226)
    assert got["logit_gap_mean_decisive"]["value"] == pytest.approx(0.004 / 3)
    assert got["decisive_tokens_compared"]["value"] == 3
    assert all(harness.passed(c) for c in got.values())
    # a fault that spreads over every row shows on the decisive ones
    got = serve_pangu.gap_checks(g + 0.02, limits, margin)
    assert [c["name"] for c in got if not harness.passed(c)] \
        == ["logit_gap_mean_decisive"]
    assert len(serve_pangu.gap_checks(g, limits)) == 2
