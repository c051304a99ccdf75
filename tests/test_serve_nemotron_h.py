"""`mx.serve` for the Nemotron-H family (`serve/ssm.py`,
`models/nemotron_h.py`, `ops/ssm.py`): the program against the plain reference
through `ServeEngine` (chunks of several sizes, then decode, through state and
pages), a slot's state across requests and across a decode step it takes no
part in, what the pools hold and what counts it, the expert layer's share
arithmetic, and the refusals."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from chipbench.reference import nemotron_h as ref
from chipbench.runners import serve_nemotron_h as runner
from incubator_mxnet_tpu.models import nemotron_h
from incubator_mxnet_tpu.serve import (HybridSlotDecoder, ShardedSlotDecoder,
                                       SlotDecoder)
from incubator_mxnet_tpu.serve.api import slots_class
from incubator_mxnet_tpu.telemetry import hbm, registry, tracing

# 3 Mamba blocks (8 heads of 8, 2 groups, state 128), 2 expert layers (16
# experts, top-4, ids 4-9 held, latent 24), 1 attention block (4 over 2 heads)
CFG = dict(num_hidden_layers=6, hybrid_override_pattern="MEM*EM",
           hidden_size=64, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
           ssm_state_size=128, conv_kernel=4, chunk_size=8,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           n_routed_experts=16, num_experts_per_tok=4,
           moe_intermediate_size=32, moe_latent_size=24,
           moe_shared_expert_intermediate_size=48, routed_scaling_factor=5.0,
           vocab_size=50, layer_norm_epsilon=1e-5,
           max_position_embeddings=160, experts_held=[4, 6], init_std=0.1,
           time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)
ENGINE = dict(max_slots=3, max_len=160, page_tokens=4, prefill_chunk=32)


@pytest.fixture(scope="module")
def dec():
    return runner.build_decoder(CFG, 7, ref, "float32")


def drive(eng, handles, limit=4000):
    for _ in range(limit):
        if all(h.done for h in handles):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def gaps_to_reference(prompts, outs, pad=160):
    tokens = onp.zeros((len(prompts), pad), onp.int32)
    rows = []
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = onp.concatenate([p, onp.asarray(o, onp.int32)])
        tokens[b, :seq.size - 1] = seq[:-1]
        rows += [(b, p.size - 1 + j) for j in range(len(o))]
    logits = ref.logits_at(CFG, 7, tokens, rows)
    served = onp.concatenate([onp.asarray(o) for o in outs])
    return logits.max(-1) - logits[onp.arange(served.size), served]


def prompts_of(seed, sizes):
    rng = onp.random.default_rng(seed)
    return [rng.integers(0, 50, n).astype(onp.int32) for n in sizes]


# -- (a) the program against the reference ------------------------------------

@pytest.mark.parametrize("chunk", [32, 16], ids=["chunks-32-8", "chunks-16-4"])
def test_served_logits_are_the_references_through_state_and_pages(dec, chunk):
    """Three requests of unlike lengths in the slots at once (a fourth
    queued behind them, which takes a slot another request left): prefill in
    chunks of two sizes, the state carried from chunk to chunk, then decode
    through state and pages; the served token is the reference's best at
    every position (float32: to 1e-4), and the counts came home."""
    registry.reset()
    tracing.reset()
    eng = mx.serve.ServeEngine(dec, **dict(ENGINE, prefill_chunk=chunk))
    slots = eng._sched.slots
    assert type(slots) is HybridSlotDecoder
    assert slots.chunk_buckets == (chunk // 4, chunk)
    prompts = prompts_of(0, (70, 33, 90, 12))
    new = [40, 35, 10, 60]
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    drive(eng, handles)
    outs = [h.result() for h in handles]
    gap = gaps_to_reference(prompts, outs)
    assert gap.size == sum(new) and gap.max() <= 1e-4
    rep = {k: v["value"] for k, v in registry.report().items()
           if "value" in v}
    held = rep['mx_serve_moe_pairs_total{kind="held"}']
    assert 0 < held < rep['mx_serve_moe_pairs_total{kind="routed"}']
    assert 0 < rep["mx_serve_moe_experts_hit_total"] <= held
    assert rep["mx_serve_state_slot_resets_total"] == 4
    assert rep["mx_serve_state_bytes"] == slots.state_bytes > 0
    for op in ("ssm_decode", "moe_experts", "paged_decode_attention"):
        assert rep[f'mx_kernel_dispatch_total{{impl="xla",op="{op}"}}'] >= 1
    recs = tracing.step_records()
    assert sum(r.get("state_resets", 0) for r in recs) == 4
    assert sum(r["moe_pairs_held"] for r in recs
               if "moe_pairs_held" in r) == held
    # two expert layers, four experts a token: a step's decoding slots and
    # a fetched chunk's real rows routed 8 pairs each
    for r in recs:
        if r.get("decoding") and not r.get("chunks"):
            assert r["moe_pairs_routed"] % 8 == 0
            assert r["moe_pairs_routed"] >= 8 * r["decoding"]
    n_programs = slots.xla_program_count()
    more = [eng.submit(p, 6) for p in prompts_of(1, (20, 41))]
    drive(eng, more)
    assert slots.xla_program_count() == n_programs      # nothing recompiles
    eng.shutdown(drain=False)


def test_a_slot_that_served_a_request_gives_the_next_a_fresh_engines_logits(
        dec):
    """One slot: the second request takes it with the first one's state
    still in the leaves, and its first chunk reads zeros in their place."""
    first, second = prompts_of(2, (37, 29))
    used = mx.serve.ServeEngine(dec, **dict(ENGINE, max_slots=1))
    a = used.submit(first, 12)
    drive(used, [a])
    left = used._sched.slots.slot_state(0)
    assert onp.abs(left["ssm"]).max() > 0 and onp.abs(left["conv"]).max() > 0
    b = used.submit(second, 20)
    drive(used, [b])
    fresh = mx.serve.ServeEngine(dec, **dict(ENGINE, max_slots=1))
    c = fresh.submit(second, 20)
    drive(fresh, [c])
    assert list(b.result()) == list(c.result())
    for kind, got in used._sched.slots.slot_state(0).items():
        assert onp.array_equal(got, fresh._sched.slots.slot_state(0)[kind])
    assert gaps_to_reference([second], [b.result()]).max() <= 1e-4
    used.shutdown(drain=False)
    fresh.shutdown(drain=False)


def prefilled(dec, sizes):
    """A slots object with a prompt of each of `sizes` prefilled, slot by
    slot."""
    slots = HybridSlotDecoder(dec, **ENGINE)
    key = jax.random.key(0)
    for slot, prompt in enumerate(prompts_of(3, sizes)):
        slots.set_slot_pages(
            slot, slots.allocator.alloc(slots.pages_needed(prompt.size + 8)))
        for t0 in range(0, prompt.size, 32):
            slots.prefill_chunk_step(slot, prompt[t0:t0 + 32], t0, key)
    return slots


def test_a_decode_step_leaves_inactive_slots_state_bit_identical(dec):
    slots = prefilled(dec, (21, 40, 9))
    before = [slots.slot_state(s) for s in range(3)]
    active = onp.array([True, False, True])
    out = slots.decode_step(onp.array([3, 4, 5]), onp.array([21, 40, 9]),
                            active, jax.random.key(1), onp.ones(3))
    assert slots.fetch_tokens(out).shape == (3,)
    for s in range(3):
        for kind, was in before[s].items():
            same = onp.array_equal(slots.slot_state(s)[kind], was)
            assert same == (not active[s]), (s, kind)


def test_a_chunks_state_is_carried_and_its_padding_changes_nothing(dec):
    """40 tokens as 32 + 8 (whole buckets) and 37 as 32 + 5 (the second
    chunk padded to its bucket of 8): what the slot holds after its prompt
    is the reference's state and tail after the prompt's last token."""
    slots = prefilled(dec, (40, 37))
    tokens = onp.zeros((2, 160), onp.int32)
    for b, p in enumerate(prompts_of(3, (40, 37))):
        tokens[b, :p.size] = p
    finals = {}
    ref.forward(CFG, 7, tokens, [40, 37], finals=finals)
    mamba = [li for li, k in enumerate(CFG["hybrid_override_pattern"])
             if k == "M"]
    for b in range(2):
        got = slots.slot_state(b)
        for nth, li in enumerate(mamba):
            state, tail = finals[li, b]
            onp.testing.assert_allclose(got["ssm"][nth], state, atol=2e-5)
            onp.testing.assert_allclose(got["conv"][nth], tail, atol=1e-5)


# -- (b) what the pools hold, and what counts it ------------------------------

def test_pools_hold_one_page_leaf_and_a_state_leaf_of_each_kind_a_mamba_block(
        dec):
    slots = HybridSlotDecoder(dec, **ENGINE)
    assert dec.layer_kinds() == ("state", None, "state", "pages", None,
                                 "state")
    assert dec.kv_geometry()[:3] == (1, 2, 16)
    # pages exist for the one attention block only
    assert slots.pages_needed(33) == 9 and slots.n_pages == 3 * 40 + 1
    assert slots.page_bytes == 2 * 2 * 4 * 16 * 4
    slots._ensure_pool()
    pools = slots._pools
    assert {k: len(v) for k, v in pools.items()} == {
        "k": 1, "v": 1, "ssm": 3, "conv": 3}
    # four heads of 8 side by side in a row's lanes, the state index before
    assert pools["ssm"][0].shape == (3, 2, 128, 32)
    assert pools["ssm"][0].dtype == jnp.float32
    assert pools["conv"][0].shape == (3, 3, 8 * 8 + 2 * 2 * 128)
    state = 3 * 3 * (8 * 8 * 128 + 3 * 576) * 4
    assert slots.state_bytes == state
    assert slots.cache_bytes == state + slots.n_pages * slots.page_bytes
    assert slots.kv_bytes_per_slot == slots.cache_bytes / 3
    census = hbm.census(top_k=0)
    assert census["detail"]["serve.kv_pool"]["state_bytes"] == state
    assert census["owners"]["serve.kv_pool"] >= slots.cache_bytes
    report = slots.shardcheck_report()
    assert report["decode"].per_device_bytes >= slots.cache_bytes
    slots.release()
    assert slots.state_bytes == 0 and slots.cache_bytes == 0


def test_every_family_says_which_layers_hold_pages():
    """The one place the programs ask how many layers a decoder has: the
    three older families answer as `kv_geometry()[0]` did."""
    from types import SimpleNamespace

    from incubator_mxnet_tpu.models import evabyte, gpt, pangu
    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    net = gpt.GPTModel(50, 32, 64, 3, 2, 64, dropout=0.0)
    net.initialize()
    gpt_dec = GPTDecoder(net)
    assert gpt_dec.layer_kinds() == ("pages",) * gpt_dec.kv_geometry()[0] \
        == ("pages",) * 3
    sized = SimpleNamespace(config=SimpleNamespace(num_hidden_layers=5))
    for cls in (evabyte.EvaByteDecoder, pangu.PanguDecoder):
        assert cls.layer_kinds(sized) == ("pages",) * 5


# -- (c) the share arithmetic -------------------------------------------------

class _Rows:
    """The least a block asks of its cache, for a bare call of a layer."""

    step = "chunk"
    stats = None

    def __init__(self, n):
        import contextlib

        self.valid = jnp.ones(n, bool)
        self.eng = type("E", (), {"_mesh_scope": contextlib.nullcontext})()

    def count_experts(self, li, stats):
        self.stats = stats


def test_the_four_shares_routed_parts_and_the_shared_expert_once_are_the_whole_layer():
    """Four chips hold four experts each of a layer's sixteen: their routed
    parts, up-projected, with the shared expert counted once, add up to the
    uncut reference layer; each share's pairs partition the routed pairs."""
    s = ref.sizes(dict(CFG, experts_held=None))
    pr = ref._programs(s, "float32")
    from chipbench.lib import seeded

    key = seeded.key_of(7)
    li = 1
    x = jnp.asarray(onp.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)
    p = pr["E"](key, jnp.int32(li))
    ids, w, v, shared = pr["route_step"](p, x)
    acc = jnp.zeros_like(v)
    for e in range(16):
        acc = pr["expert_step"](pr["expert"](key, jnp.int32(
            ref.expert_code(li, e))), v, ids, w, acc, jnp.int32(e))
    whole = onp.asarray(pr["finish"](p, x, acc, shared) - x)

    parts, pairs = [], 0
    for first in (0, 4, 8, 12):
        cfg = dict(CFG, experts_held=[first, 4])
        d = runner.build_decoder(cfg, 7, ref, "float32")
        lp = d._params["layers"][li]
        u = nemotron_h.rms_gain(x, lp["n"], 1e-5)
        cache = _Rows(24)
        out = d.experts(li, lp, u, cache)
        shared_part = d._mm(jnp.square(jnp.maximum(d._mm(u, lp["ws_1"]), 0)),
                            lp["ws_2"])
        parts.append(onp.asarray(out - shared_part))
        pairs += int(cache.stats[0])
    assert pairs == 24 * 4
    onp.testing.assert_allclose(sum(parts) + onp.asarray(shared_part), whole,
                                atol=2e-5)
    assert onp.abs(parts[0]).max() > 1e-3


# -- (d) the refusals ---------------------------------------------------------

def test_the_family_table_names_the_family(dec):
    assert slots_class(dec) is HybridSlotDecoder

    class Unknown:
        family = "rwkv"

    with pytest.raises(ValueError, match="evabyte.*nemotron_h.*pangu_moe"):
        slots_class(Unknown())


@pytest.mark.parametrize("kwargs,what", [
    (dict(prefix_reuse=True), "prefix_reuse"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(spec_k=2), "speculative"), (dict(draft="ngram"), "speculative"),
], ids=["prefix_reuse", "int8", "spec_k", "draft"])
def test_refused_settings_name_the_family_and_the_reason(dec, kwargs, what):
    with pytest.raises(NotImplementedError,
                       match=f"nemotron_h family.*{what}"):
        mx.serve.ServeEngine(dec, **ENGINE, **kwargs)


def test_handoff_adoption_preemption_and_sharding_are_refused(dec):
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    assert eng._sched.slots.prefix_cache.enabled is False
    with pytest.raises(NotImplementedError, match="handoff"):
        eng._sched.submit(onp.arange(10, dtype=onp.int32), 4,
                          prefill_only=True)
    with pytest.raises(NotImplementedError, match="adoption"):
        eng._sched.adopt_page_plan(10, 4)
    h = eng.submit(onp.arange(10, dtype=onp.int32), 30)
    for _ in range(4):
        eng.step()
    with pytest.raises(NotImplementedError,
                       match="nemotron_h family.*preemption.*state"):
        eng._sched.preempt(h.slot)
    drive(eng, [h])                     # ... and the request is unharmed
    assert gaps_to_reference([onp.arange(10, dtype=onp.int32)],
                             [h.result()]).max() <= 1e-4
    eng.shutdown(drain=False)
    with pytest.raises(NotImplementedError, match="nemotron_h.*sharded"):
        ShardedSlotDecoder(dec, 2)
    with pytest.raises(TypeError):
        SlotDecoder(dec)
    with pytest.raises(TypeError):
        HybridSlotDecoder(object())


def test_a_decoder_is_held_to_its_configurations_shapes(dec):
    cfg = nemotron_h.NemotronHConfig.from_dict(CFG)
    params = dict(dec._params, layers=list(dec._params["layers"]))
    with pytest.raises(ValueError, match="layers given"):
        nemotron_h.NemotronHDecoder(
            cfg, dict(params, layers=params["layers"][:2]))
    bad = dict(params["layers"][1], we_1=params["layers"][1]["we_1"][:5])
    with pytest.raises(ValueError, match="layers.1.we_1"):
        nemotron_h.NemotronHDecoder(cfg, dict(params, layers=[
            params["layers"][0], bad] + params["layers"][2:]))
    with pytest.raises(ValueError, match="experts_held"):
        nemotron_h.NemotronHDecoder(
            nemotron_h.NemotronHConfig.from_dict(
                dict(CFG, experts_held=[12, 6])), params)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h.NemotronHConfig.from_dict(
            dict(CFG, hybrid_override_pattern="MEMX"))
    with pytest.raises(ValueError, match="chunk_size"):
        HybridSlotDecoder(dec, **dict(ENGINE, prefill_chunk=48))
